def read(ctx, params):
    return ctx["setup_s"]
