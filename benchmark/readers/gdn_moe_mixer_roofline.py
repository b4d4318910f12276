"""Share of the HBM-bandwidth roofline the gated-delta-rule mixers reach where a
key head serves several value heads: the least time to stream their projections
once a forward pass of the trace and to read and write the matrix state of the
decode rows those passes moved (opsbytes_gdn_moe.lin_pass_bytes, lin_matrix_bytes),
over the device time of the trace's ``other_device`` category, where the
program's ``lin/*`` scopes land (the chunked scan, the step kernel
``gated_delta_step``, the convolution, the gated norm) because no rule of
trace_categories.json names them.

The rows moved are the program's count, /health.linear_attention.
decode_rows_linear (rows x delta-rule layers), its growth between the probes
matched to the capture by forward passes as readers/kda_mixer_roofline.py does.
Another family, or a program without the counter, gives ``None``."""
from arith import at_path
from opsbytes_gdn_moe import lin_matrix_bytes, lin_pass_bytes, of_family

AT = "linear_attention"


def growth(ctx, path):
    before, after = ctx.get("health_before") or {}, ctx.get("health_after") or {}
    return at_path(after, path) - at_path(before, path)


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or not of_family(ctx["fields"]):
        return None
    if not (ctx.get("health_after") or {}).get(AT):
        return None
    seconds = tr["category_s"].get("other_device", 0.0)
    passes = growth(ctx, [AT, "forward_passes"])
    if seconds <= 0 or passes <= 0:
        return None
    # rows x layers the run's decode passes moved, the capture's share of them
    moved = growth(ctx, [AT, "decode_rows_linear"]) * tr["forward_passes"] / passes
    least = (lin_pass_bytes(ctx["fields"]) * tr["forward_passes"]
             + moved * 2 * lin_matrix_bytes(ctx["fields"])) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
