"""Means and shares from /health.spans (obs/trace.py SpanStats and the
scheduler thread's wall-time partition): the difference between the probe
before the ramp and the probe after the tail, so ramp, window and tail all
count (the same traffic throughout), as prefix_hit_share does.

``params``: ``plus`` and optional ``minus`` list ``[span, key]`` paths under
``spans`` whose differences make the numerator; ``over`` and optional
``over_minus`` make the denominator; ``scale`` multiplies the quotient (1000
for seconds -> ms, 100 for a share in %). Means add: every part of one parent
is divided by that parent's count. A span that never closed counts 0 (no
admission waited staged: 0 ms each); a program without the section, or a
denominator of 0, gives ``None`` and the metric is left out."""


from arith import at_path as _at


def read(ctx, params):
    before = (ctx.get("health_before") or {}).get("spans") or {}
    after = (ctx.get("health_after") or {}).get("spans")
    if not after:
        return None

    def total(plus, minus):
        return (sum(_at(after, p) - _at(before, p) for p in params[plus])
                - sum(_at(after, p) - _at(before, p) for p in params.get(minus, [])))

    den = total("over", "over_minus")
    if den <= 0:
        return None
    return params.get("scale", 1.0) * total("plus", "minus") / den
