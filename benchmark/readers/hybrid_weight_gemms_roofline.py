"""``weight_gemms_roofline`` for a model of one mixer a layer: the least time the
chip could take to stream the int8 weights its forward passes read in the
trace's weight-GEMM categories (opsbytes_hybrid.gemm_stream_bytes: experts read
x 2 matrices + the shared expert in expert layers, attention projections in
attention layers, the head), over the device time of those categories.

How many experts a layer's pass read is the configuration file's
``experts_streamed`` counter pair, the growth of one /health count over the
growth of another between the probes, as readers/trace_roofline.py reads it. A
program without the counters, or a file without the pattern, gives ``None``."""
from arith import at_path
from opsbytes_hybrid import gemm_stream_bytes


def experts_streamed(ctx):
    spec = (ctx.get("config") or {}).get("experts_streamed")
    if spec is None or isinstance(spec, (int, float)):
        return spec
    before, after = ctx.get("health_before") or {}, ctx.get("health_after") or {}
    passes = at_path(after, spec["per"]) - at_path(before, spec["per"])
    if passes <= 0:
        return None
    return (at_path(after, spec["counter"]) - at_path(before, spec["counter"])) / passes


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or "layer_pattern" not in ctx["fields"]:
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
