"""Share of the HBM-bandwidth roofline the Kimi-delta-attention mixers reach:
the least time to stream their projections once a forward pass of the trace and
to read and write the matrix state of the decode rows those passes moved
(opsbytes_kda.kda_pass_bytes), over the device time of the trace's
``other_device`` category, where the program's ``lin/*`` scopes land (the chunked
scan, the step kernel ``gated_delta_step``, the convolution, the gated norm)
because no rule of trace_categories.json names them.

The rows moved are the program's count, /health.linear_attention.
decode_rows_linear (rows x KDA layers), its growth between the probes matched to
the capture by forward passes as readers/latent_attention_roofline.py does. A
configuration without a decay a key channel, or a program without the counter,
gives ``None``."""
from arith import at_path
from opsbytes_kda import kda_matrix_bytes, kda_pass_bytes

AT = "linear_attention"


def growth(ctx, section, key):
    before, after = ctx.get("health_before") or {}, ctx.get("health_after") or {}
    return at_path(after, [section, key]) - at_path(before, [section, key])


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or not ctx["fields"].get("lin_channel_decay"):
        return None
    if not (ctx.get("health_after") or {}).get(AT):
        return None
    seconds = tr["category_s"].get("other_device", 0.0)
    passes = growth(ctx, AT, "forward_passes")
    if seconds <= 0 or passes <= 0:
        return None
    # rows x layers the run's decode passes moved, the capture's share of them
    moved = growth(ctx, AT, "decode_rows_linear") * tr["forward_passes"] / passes
    least = (kda_pass_bytes(ctx["fields"]) * tr["forward_passes"]
             + moved * 2 * kda_matrix_bytes(ctx["fields"])) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
