from arith import percentile


def read(ctx, params):
    return percentile([(r.sent - r.due) * 1000.0 for r in ctx["records"]],
                      params["percentile"])
