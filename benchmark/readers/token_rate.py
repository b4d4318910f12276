"""Tokens by the arrival time of their frames inside the window, of measured
and unmeasured requests alike."""
from arith import token_rate


def read(ctx, params):
    t0, seconds = ctx["window"]
    return token_rate(((r.frames, r.tokens) for r in ctx["all_records"]), t0, seconds)
