"""Share of the HBM-bandwidth roofline the gated attention layers reach: the
least time to read K and V of the keys their decode queries had before them
(/health.linear_attention.full_keys_read, summed over the attention layers on
the device, x opsbytes_gdn_moe.kv_bytes_per_key: 2,048 B a key a layer, 6,144 B
a live key a pass of three layers), over the device time of the ``attention``
scope in the trace (the ragged kernel and the elementwise gate under it). The
growth between the probes is the run's; the capture's part of it is taken by
forward passes. A lower bound by construction: a window's prompt rows' reads and
their (query, key) products are not counted. Another family, or a program
without the counters, gives ``None``."""
from opsbytes_gdn_moe import kv_bytes_per_key, of_family
from readers.gdn_moe_mixer_roofline import AT, growth


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or not of_family(ctx["fields"]):
        return None
    if "full_keys_read" not in ((ctx.get("health_after") or {}).get(AT) or {}):
        return None
    seconds = tr["category_s"].get("attention", 0.0)
    passes = growth(ctx, [AT, "forward_passes"])
    if seconds <= 0 or passes <= 0:
        return None
    keys = growth(ctx, [AT, "full_keys_read"]) * tr["forward_passes"] / passes
    least = keys * kv_bytes_per_key(ctx["fields"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
