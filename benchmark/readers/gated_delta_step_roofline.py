"""Share of the HBM-bandwidth roofline the gated delta rule's decode-step kernel
reaches, the kernel ALONE: the least time to read once and write once the matrix
state of every row that moved (opsbytes_gdn_moe.step_kernel_bytes over
``moved``'s count, the capture's part taken by forward passes), over the seconds
of the op named ``gated_delta_step`` among the trace's ``breakdown.device_ops``.
``None`` where the trace does not list the op (a program without the kernel, or
one in which it is not among the ten longest), for another family, or where the
program lacks the counters. A reading over 100% is a wrong count, not a fast
kernel.

``moved``: the (row, delta-rule layer) pairs whose state a DECODE pass's kernel
read and wrote between the probes, counted as readers/ssd_step_roofline.py counts
Mamba-2's. /health.ssm counts forward passes dispatched (``forward_passes``) and
the one-sequence eager pieces among them (``eager_prefill_passes``);
/health.ragged.window.windows the chunks that carried a window, whose first pass
runs the chunked scan and not the kernel; every other pass is a decode pass of
``live_rows`` rows in each delta-rule layer, less the rows the kernel passed over
(/health.linear_attention.decode_rows_still, summed over the layers on the
device). NOT /health.linear_attention.decode_rows_linear: that holds the decode
rows that rode a window's scan too."""
from arith import at_path
from opsbytes_gdn_moe import kinds, of_family, step_kernel_bytes
from readers.gdn_moe_mixer_roofline import AT, growth
from readers.ssd_step_roofline import kernel_seconds


def moved(ctx):
    """{passes, moving_row_layers} between the probes, or None where the
    program lacks a counter or nothing ran."""
    after = ctx.get("health_after") or {}
    if "decode_rows_still" not in (after.get(AT) or {}) or not isinstance(after.get("ssm"), dict):
        return None
    passes = growth(ctx, ["ssm", "forward_passes"])
    decode = (passes - growth(ctx, ["ssm", "eager_prefill_passes"])
              - growth(ctx, ["ragged", "window", "windows"]))
    rows = at_path(after, ["ssm", "live_rows"])
    if passes <= 0 or decode <= 0 or rows <= 0:
        return None
    pairs = (decode * rows * kinds(ctx["fields"]).count("L")
             - growth(ctx, [AT, "decode_rows_still"]))
    return {"passes": passes, "moving_row_layers": max(pairs, 0.0)}


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or not of_family(ctx["fields"]):
        return None
    seconds, got = kernel_seconds(tr, "gated_delta_step"), moved(ctx)
    if seconds <= 0 or got is None:
        return None
    least = (step_kernel_bytes(ctx["fields"], got["moving_row_layers"])
             * tr["forward_passes"] / got["passes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
