"""The benchmark's arithmetic: percentiles and the token rate. Kept with the
benchmark so that every PR computes the same number in the same way."""

from __future__ import annotations

from typing import Iterable, Optional, Sequence


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """The p-th percentile (0-100) with linear interpolation between ranks
    (numpy's default); None for no values."""
    if not values:
        return None
    vs = sorted(values)
    k = (len(vs) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(vs) - 1)
    return vs[lo] + (vs[hi] - vs[lo]) * (k - lo)


def token_rate(requests: Iterable[tuple], t0: float, seconds: float) -> float:
    """Tokens that ARRIVED in [t0, t0 + seconds), per second, whoever sent the
    request and whenever it ends. ``requests`` yields ``(frame_times,
    tokens)``: the arrival time of each token frame of a request and the
    number of tokens in its whole answer. The server joins the tokens of one
    decode chunk into one frame, so a frame stands for tokens / len(frames)
    tokens of its request."""
    total = 0.0
    for frames, tokens in requests:
        if frames and tokens:
            inside = sum(1 for t in frames if t0 <= t < t0 + seconds)
            total += tokens * inside / len(frames)
    return total / seconds


def at_path(tree, path):
    """The number at ``path`` (a list of keys) in nested dicts such as a
    /health body; a missing key, or a count that never started, is 0."""
    for key in path:
        tree = tree.get(key) if isinstance(tree, dict) else None
    return tree or 0.0
