"""Share of the HBM-bandwidth roofline the selecting attention reaches: the
least time to move the index keys its queries scanned and the K/V rows its
decode queries selected (opsbytes_sparse.selection_bytes), over the device time
of the ``attention`` scope in the trace.

The counts are the program's, cumulative under /health.sparse_attention; the
growth between the probe before the ramp and the probe after the tail is the
run's, and the capture's part of it is taken by forward passes: bytes x (forward
passes in the trace) / (growth of ``forward_passes`` there, every pool pass the
scheduler dispatched). A lower bound by construction: window rows' own K/V reads
and the round trips of the gathers are not counted. A program without the
counters gives ``None``."""
from arith import at_path
from opsbytes_sparse import selection_bytes

AT = "sparse_attention"


def growth(ctx, key):
    before, after = ctx.get("health_before") or {}, ctx.get("health_after") or {}
    return at_path(after, [AT, key]) - at_path(before, [AT, key])


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes"):
        return None
    seconds = tr["category_s"].get("attention", 0.0)
    passes = growth(ctx, "forward_passes")
    if seconds <= 0 or passes <= 0 or "index_head_dim" not in ctx["fields"]:
        return None
    run_bytes = selection_bytes(ctx["fields"], growth(ctx, "index_rows_scanned"),
                                growth(ctx, "decode_rows_selected"))
    least = run_bytes * tr["forward_passes"] / passes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
