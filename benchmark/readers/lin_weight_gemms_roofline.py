"""``weight_gemms_roofline`` for a model of linear-attention and full-attention
layers: the least time the chip could take to stream the int8 weights its
forward passes read in the trace's weight-GEMM categories
(opsbytes_linear.gemm_stream_bytes: the dense MLPs, the full-attention layers'
projections, the head), over the device time of those categories. A
configuration without linear layers gives ``None``."""
from opsbytes_linear import gemm_stream_bytes


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or "lin_value_heads" not in ctx["fields"]:
        return None
    seconds = sum(tr["category_s"].get(c, 0.0)
                  for c in ctx["trace_rules"]["weight_gemm_categories"])
    if seconds <= 0:
        return None
    least = (gemm_stream_bytes(ctx["fields"]) * tr["forward_passes"]
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
