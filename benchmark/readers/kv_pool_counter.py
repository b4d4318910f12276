"""Counts from /health.kv_pool."""


def read(ctx, params):
    if params["quantity"] == "live_peak":
        live = [h["kv_pool"]["live"] for h in ctx.get("health_samples", [])
                if h.get("kv_pool")]
        return float(max(live)) if live else None
    a, b = ctx.get("health_before"), ctx.get("health_after")
    if not a or not b or not (a.get("kv_pool") or {}).get("radix"):
        return None
    ra, rb = a["kv_pool"]["radix"], b["kv_pool"]["radix"]
    hit = rb["hit_tokens"] - ra["hit_tokens"]
    miss = rb["miss_tokens"] - ra["miss_tokens"]
    return 100.0 * hit / (hit + miss) if hit + miss else None
