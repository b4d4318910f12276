"""Share of the HBM-bandwidth roofline the Mamba-2 mixers reach in a model whose
layers each have a dense MLP behind them: the least time to stream the mixers'
two projections once a forward pass of the trace (opsbytes_ssm_dense.
ssm_pass_bytes) and to read and write once the matrix state of every row a decode
pass moved (step_kernel_bytes over readers/ssd_step_roofline.py's count), over
the device time of the trace's ``other_device`` category, where the program's
``ssm/*`` scopes land because no rule of trace_categories.json names them. The
state a WINDOW pass reads and writes is left out: its in-place write is billed to
``kv_pool_copy`` by the HLO rule and is not in these seconds. A program without
the counters gives ``None``."""
from opsbytes_ssm_dense import ssm_pass_bytes, step_kernel_bytes
from readers.ssd_step_roofline import moved, of_family


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or not of_family(ctx):
        return None
    seconds, got = tr["category_s"].get("other_device", 0.0), moved(ctx)
    if seconds <= 0 or got is None:
        return None
    run_bytes = (ssm_pass_bytes(ctx["fields"]) * got["passes"]
                 + step_kernel_bytes(ctx["fields"], got["moving_row_layers"]))
    least = (run_bytes * tr["forward_passes"] / got["passes"]
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
