"""Share of its roofline the attention of a model with full and sliding layers
reaches: the least time to read the keys its decode queries had to read, by
kind, and to do the counted (query, key) pairs' operations
(opsbytes_sliding.attention_least_seconds: the larger of bytes at peak HBM
bandwidth and operations at peak bf16), over the device time of the
``attention`` scope in the trace (both kinds' kernels and the per-head gate).

The counts are the program's, cumulative under /health.sliding_attention; the
growth between the probe before the ramp and the probe after the tail is the
run's, and the capture's part of it is taken by forward passes, as
readers/latent_attention_roofline.py does. A lower bound by construction: a
window's row reads, the rows a tile reads again for each of its query tiles, the
pages a span starts or ends inside and masked halves of diagonal blocks are not
counted. A program without the counters gives ``None``."""
from arith import at_path
from opsbytes_sliding import attention_least_seconds

AT = "sliding_attention"


def growth(ctx, key):
    before, after = ctx.get("health_before") or {}, ctx.get("health_after") or {}
    return at_path(after, [AT, key]) - at_path(before, [AT, key])


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or not ctx["fields"].get("sliding_window"):
        return None
    if not (ctx.get("health_after") or {}).get(AT):
        return None
    seconds = tr["category_s"].get("attention", 0.0)
    passes = growth(ctx, "forward_passes")
    if seconds <= 0 or passes <= 0:
        return None
    least = attention_least_seconds(
        ctx["fields"], growth(ctx, "sliding_keys_read"), growth(ctx, "full_keys_read"),
        growth(ctx, "window_pairs_sliding"), growth(ctx, "window_pairs_full"), ctx["peaks"])
    return 100.0 * least * tr["forward_passes"] / passes / seconds
