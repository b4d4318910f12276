"""Share of its roofline the latent attention reaches: the least time to read
the cached rows its decode queries had before them and to do the counted
(query, key) pairs' operations (opsbytes_mla.attention_least_seconds: the larger
of bytes at peak HBM bandwidth and operations at peak bf16), over the device
time of the ``attention`` scope in the trace.

The counts are the program's, cumulative under /health.latent_attention; the
growth between the probe before the ramp and the probe after the tail is the
run's, and the capture's part of it is taken by forward passes: work x (forward
passes in the trace) / (growth of ``forward_passes`` there, every pool pass the
scheduler dispatched). A lower bound by construction: a window's row reads, the
rows a tile reads again for each of its query tiles, and masked halves of
diagonal blocks are not counted. A program without the counters gives ``None``."""
from arith import at_path
from opsbytes_mla import attention_least_seconds

AT = "latent_attention"


def growth(ctx, key):
    before, after = ctx.get("health_before") or {}, ctx.get("health_after") or {}
    return at_path(after, [AT, key]) - at_path(before, [AT, key])


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or "kv_lora_rank" not in ctx["fields"]:
        return None
    if AT not in (ctx.get("health_after") or {}) or not ctx["health_after"][AT]:
        return None
    seconds = tr["category_s"].get("attention", 0.0)
    passes = growth(ctx, "forward_passes")
    if seconds <= 0 or passes <= 0:
        return None
    least = attention_least_seconds(ctx["fields"], growth(ctx, "latent_rows_read"),
                                    growth(ctx, "window_pairs"), ctx["peaks"])
    return 100.0 * least * tr["forward_passes"] / passes / seconds
