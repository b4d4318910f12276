"""A number the program reports under /health, read from the probe after the
tail (a value that is set once at start, such as how long the weights took to
make, or a gauge). ``params["path"]`` is the list of keys down to it. With
``"reduce": "max_over_mean"`` the value there is a list with an entry a device
and the result is its largest entry over its mean: 1.0 when every device holds
the same. A program without the key (or with a null there) gives ``None`` and
the metric is left out of the line."""


def read(ctx, params):
    node = ctx.get("health_after") or {}
    for key in params["path"]:
        node = node.get(key) if isinstance(node, dict) else None
    if params.get("reduce") == "max_over_mean":
        if not isinstance(node, list) or not node or not sum(node):
            return None
        return max(node) * len(node) / sum(node)
    if isinstance(node, bool) or not isinstance(node, (int, float)):
        return None
    return float(node)
