"""Prompt tokens served from cached pages over prompt tokens admitted in
the window, from ``summary()``'s cache-lookup counters."""


def read(ctx):
    hit = (ctx["c1"].get("cache_hit_tokens") or 0) \
        - (ctx["c0"].get("cache_hit_tokens") or 0)
    miss = (ctx["c1"].get("cache_miss_tokens") or 0) \
        - (ctx["c0"].get("cache_miss_tokens") or 0)
    if hit + miss <= 0 or hit <= 0:
        return None
    return 100.0 * hit / (hit + miss)
