"""Share of the HBM-bandwidth roofline the state-space mixers reach: the least
time to move what their passes must (opsbytes_hybrid.ssm_pass_bytes: both
projections a pass, and every row's recurrent state read and written), over the
device time of the trace's ``other_device`` category, where the program's
``ssm/*`` scopes land because no rule of trace_categories.json names them.

The passes are the program's counts under /health.ssm, growth between the
probes: ``forward_passes`` (every pass the scheduler dispatched), of which
``eager_prefill_passes`` held one sequence and the rest, the chunk programs'
steps, ``live_rows`` (the decode batch: a dead row's state is read and written
like a live one's). The capture's part is taken by forward passes, as
readers/sparse_attention_roofline.py does. NOT a bound on either side until
the ``ssm/*`` scopes have a rule of their own: ``other_device`` also holds the
1-2% of the device it holds in every cell and a window pass is bound by its
arithmetic, not its bytes (both read the share low), while the copies that
are the mixers' are billed to ``kv_pool_copy`` by the HLO rule and missing
from the seconds (which reads it high): the convolution tail's update, and the
chunk loop's scope-less copies of the whole live state, two a step (PERF.md
section 5: 41.96% as divided here, 24.7% with those counted). A reader is
handed seconds by category only. A program without the counters gives
``None``."""
from arith import at_path
from opsbytes_hybrid import ssm_pass_bytes

AT = "ssm"


def growth(ctx, key):
    before, after = ctx.get("health_before") or {}, ctx.get("health_after") or {}
    return at_path(after, [AT, key]) - at_path(before, [AT, key])


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or "layer_pattern" not in ctx["fields"]:
        return None
    seconds = tr["category_s"].get("other_device", 0.0)
    passes, eager = growth(ctx, "forward_passes"), growth(ctx, "eager_prefill_passes")
    rows = at_path(ctx.get("health_after") or {}, [AT, "live_rows"])
    if seconds <= 0 or passes <= 0 or rows <= 0:
        return None
    run_bytes = (ssm_pass_bytes(ctx["fields"], 1) * eager
                 + ssm_pass_bytes(ctx["fields"], rows) * (passes - eager))
    least = run_bytes * tr["forward_passes"] / passes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
