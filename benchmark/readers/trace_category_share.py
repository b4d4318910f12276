def read(ctx, params):
    tr = ctx.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    return 100.0 * tr["category_s"].get(params["category"], 0.0) / tr["busy_s"]
