"""Client-side time from the instant a request was due to an event."""
from arith import percentile


def read(ctx, params):
    vals = [(getattr(r, params["to"]) - r.due) * 1000.0 for r in ctx["records"]
            if getattr(r, params["to"]) is not None]
    return percentile(vals, params["percentile"])
