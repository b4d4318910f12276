"""Share of the HBM-bandwidth roofline the weight GEMMs reach: the least time
ONE chip could take to stream its shard of the int8 weights of the forward
passes in the trace, over the device time their scopes took on a chip (the
trace's seconds are averaged per device plane, so the bytes are per chip too).

A configuration whose program streams only the experts its tokens picked says
how many in its file: ``"experts_streamed"``, a number, or
``{"counter": [path under /health], "per": [path under /health]}``, the
growth of a count of experts read over the growth of a count of layer passes
between the probe before the ramp and the probe after the tail. Absent: all
experts (opsbytes.weight_stream_bytes). No program counter of that kind
exists yet."""
from arith import at_path as _at
from opsbytes import weight_stream_bytes


def experts_streamed(ctx):
    spec = (ctx.get("config") or {}).get("experts_streamed")
    if spec is None or isinstance(spec, (int, float)):
        return spec
    before, after = ctx.get("health_before") or {}, ctx.get("health_after") or {}
    passes = _at(after, spec["per"]) - _at(before, spec["per"])
    if passes <= 0:
        raise LookupError(f"experts_streamed: /health counts nothing under {spec['per']}")
    return (_at(after, spec["counter"]) - _at(before, spec["counter"])) / passes


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes"):
        return None
    cats = ctx["trace_rules"]["weight_gemm_categories"]
    seconds = sum(tr["category_s"].get(c, 0.0) for c in cats)
    if seconds <= 0:
        return None
    mesh = ctx.get("mesh") or {}
    least = weight_stream_bytes(
        ctx["fields"], shards=mesh.get("model", 1), expert_shards=mesh.get("expert", 1),
        experts_streamed=experts_streamed(ctx)) * tr["forward_passes"] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
