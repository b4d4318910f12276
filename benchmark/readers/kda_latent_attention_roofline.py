"""Share of its roofline the latent attention reaches where only a pattern's
``*`` layers are latent: readers/latent_attention_roofline.py's rule with the
work counted over the LATENT layers (opsbytes_kda.latent_least_seconds: two of
the twelve, rows of 576 values), over the device time of the ``attention``
scope in the trace. The counts are the program's, cumulative under
/health.latent_attention, matched to the capture by forward passes. A
configuration without a decay a key channel beside its latent rows, or a
program without the counters, gives ``None``."""
from arith import at_path
from opsbytes_kda import latent_least_seconds

AT = "latent_attention"


def growth(ctx, key):
    before, after = ctx.get("health_before") or {}, ctx.get("health_after") or {}
    return at_path(after, [AT, key]) - at_path(before, [AT, key])


def read(ctx, params):
    tr = ctx.get("trace")
    f = ctx["fields"]
    if (not tr or not tr.get("forward_passes") or "kv_lora_rank" not in f
            or not f.get("lin_channel_decay")):
        return None
    if not (ctx.get("health_after") or {}).get(AT):
        return None
    seconds = tr["category_s"].get("attention", 0.0)
    passes = growth(ctx, "forward_passes")
    if seconds <= 0 or passes <= 0:
        return None
    least = latent_least_seconds(f, growth(ctx, "latent_rows_read"),
                                 growth(ctx, "window_pairs"), ctx["peaks"])
    return 100.0 * least * tr["forward_passes"] / passes / seconds
