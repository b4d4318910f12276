"""Share of the HBM-bandwidth roofline the Mamba-2 decode step's kernel reaches,
the kernel ALONE: the least time to read once and write once the matrix state of
every row that moved (opsbytes_ssm_dense.step_kernel_bytes over ``moved``'s
count, the capture's part taken by forward passes), over the seconds of the op
named ``ssd_step`` among the trace's ``breakdown.device_ops``. ``None`` where the
trace does not list the op (a program without the kernel, or one in which it is
not among the ten longest), or the program lacks the counters. A reading over
100% is a wrong count, not a fast kernel.

``moved``: the (row, Mamba layer) pairs whose state a DECODE pass's kernel read
and wrote between the probes. /health.ssm counts forward passes dispatched
(``forward_passes``), the one-sequence eager pieces among them
(``eager_prefill_passes``) and the rows of decode passes the kernel passed over
(``decode_rows_still``, summed over the layers on the device); /health.ragged.
window.windows counts the chunks that carried a window, whose first pass runs
the windowed scan and not the kernel (the plain scan shortens by one step:
engine/batcher.py::make_termination_chunk_fn). Every other pass is a decode pass
of ``live_rows`` rows in each Mamba layer."""
from arith import at_path
from opsbytes_ssm_dense import kinds, step_kernel_bytes


def of_family(ctx) -> bool:
    """A configuration whose pattern holds Mamba-2 layers and dense MLP mixers."""
    f = ctx["fields"]
    return "layer_pattern" in f and {"M", "D"} <= set(kinds(f))


def growth(ctx, path):
    before, after = ctx.get("health_before") or {}, ctx.get("health_after") or {}
    return at_path(after, path) - at_path(before, path)


def moved(ctx):
    """{passes, moving_row_layers} between the probes, or None where the
    program lacks a counter or nothing ran."""
    after = ctx.get("health_after") or {}
    ssm = after.get("ssm")
    if not isinstance(ssm, dict) or "decode_rows_still" not in ssm:
        return None
    passes = growth(ctx, ["ssm", "forward_passes"])
    decode = (passes - growth(ctx, ["ssm", "eager_prefill_passes"])
              - growth(ctx, ["ragged", "window", "windows"]))
    rows = at_path(after, ["ssm", "live_rows"])
    if passes <= 0 or decode <= 0 or rows <= 0:
        return None
    pairs = (decode * rows * kinds(ctx["fields"]).count("M")
             - growth(ctx, ["ssm", "decode_rows_still"]))
    return {"passes": passes, "moving_row_layers": max(pairs, 0.0)}


def kernel_seconds(tr, name="ssd_step"):
    ops = (tr.get("breakdown") or {}).get("device_ops") or []
    return sum(s for op, s in ops if op.split(":", 1)[-1] == name)


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or not of_family(ctx):
        return None
    seconds, got = kernel_seconds(tr), moved(ctx)
    if seconds <= 0 or got is None:
        return None
    least = (step_kernel_bytes(ctx["fields"], got["moving_row_layers"])
             * tr["forward_passes"] / got["passes"]
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
