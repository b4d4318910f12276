"""``weight_gemms_roofline`` for a model of Mamba-2 and attention layers with a
dense MLP behind each: the least time the chip could take to stream the int8
weights its forward passes read in the trace's weight-GEMM categories
(opsbytes_ssm_dense.gemm_stream_bytes: the dense MLPs, the attention layers'
projections, the tied head once), over the device time of those categories. A
configuration without state-space layers and dense MLP mixers gives ``None``."""
from opsbytes_ssm_dense import gemm_stream_bytes
from readers.ssd_step_roofline import of_family


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or not of_family(ctx):
        return None
    seconds = sum(tr["category_s"].get(c, 0.0)
                  for c in ctx["trace_rules"]["weight_gemm_categories"])
    if seconds <= 0:
        return None
    least = (gemm_stream_bytes(ctx["fields"]) * tr["forward_passes"]
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
