"""Growth of one /health count over the growth of another, between the probe
before the ramp and the probe after the tail: ``params["num"]`` and
``params["den"]`` are paths (lists of keys) under /health, ``params["scale"]``
multiplies the ratio (100 for a share). A program without the counters (the
parent of the PR that adds them), or one whose denominator did not grow, gives
``None`` and the metric is left out of the line."""
from arith import at_path


def growth(ctx, path):
    before, after = ctx.get("health_before") or {}, ctx.get("health_after") or {}
    return at_path(after, path) - at_path(before, path)


def read(ctx, params):
    den = growth(ctx, params["den"])
    if den <= 0:
        return None
    return params.get("scale", 1.0) * growth(ctx, params["num"]) / den
