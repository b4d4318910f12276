"""``weight_gemms_roofline`` for a model of gated-delta-rule and gated attention
layers with a chip's share of its experts beside a gated shared expert: the
least time the chip could take to stream the weights its forward passes read in
the trace's weight-GEMM categories (opsbytes_gdn_moe.gemm_stream_bytes: the held
experts read x 3 matrices with the shared expert, its gate and the router, the
attention layers' projections, the head), over the device time of those
categories. How many experts a layer's pass read is the configuration file's
``experts_streamed`` counter pair, as readers/hybrid_weight_gemms_roofline.py
reads it. Another family, or a program without the counters, gives ``None``."""
from opsbytes_gdn_moe import gemm_stream_bytes, of_family
from readers.hybrid_weight_gemms_roofline import experts_streamed


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or not of_family(ctx["fields"]):
        return None
    seconds = sum(tr["category_s"].get(c, 0.0)
                  for c in ctx["trace_rules"]["weight_gemm_categories"])
    read_a_pass = experts_streamed(ctx)
    counted = (ctx.get("config") or {}).get("experts_streamed") is not None
    if seconds <= 0 or (counted and read_a_pass is None):
        return None
    least = (gemm_stream_bytes(ctx["fields"], read_a_pass) * tr["forward_passes"]
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
