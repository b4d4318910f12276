"""The engine's own record of a request, from /debug/requests/{id}: the
trace events the scheduler stamps (submitted, admitted, first token,
finished) on the server's monotonic clock."""
from arith import percentile


def read(ctx, params):
    vals = [e[params["quantity"]] for e in ctx.get("engine", {}).values()
            if e.get(params["quantity"]) is not None]
    return percentile(vals, params["percentile"])
