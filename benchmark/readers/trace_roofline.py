"""Share of the HBM-bandwidth roofline the weight GEMMs reach: the least time
the chip could take to stream the int8 weights of the forward passes in the
trace, over the device time their scopes took."""
from opsbytes import weight_stream_bytes


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes"):
        return None
    cats = ctx["trace_rules"]["weight_gemm_categories"]
    seconds = sum(tr["category_s"].get(c, 0.0) for c in cats)
    if seconds <= 0:
        return None
    least = weight_stream_bytes(ctx["sizes"]) * tr["forward_passes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
