"""Share of the HBM-bandwidth roofline the linear-attention mixers reach: the
least time to stream their projections once a forward pass of the trace
(opsbytes_linear.lin_pass_bytes), over the device time of the trace's
``other_device`` category, where the program's ``lin/*`` scopes land because no
rule of trace_categories.json names them. A configuration without linear layers
gives ``None``."""
from opsbytes_linear import lin_pass_bytes


def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("forward_passes") or "lin_value_heads" not in ctx["fields"]:
        return None
    seconds = tr["category_s"].get("other_device", 0.0)
    if seconds <= 0:
        return None
    least = (lin_pass_bytes(ctx["fields"]) * tr["forward_passes"]
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
