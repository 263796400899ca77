"""Time in the ragged paged attention kernel over device busy time, from
the device trace: custom calls whose result is [tokens, kv_heads, group,
head_dim]."""
from harness import xplane as X


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    ns = X.attention_kernel_ns(tr["events"], ctx["cfg"])
    return 100.0 * ns / 1e9 / tr["busy_s"] if ns > 0 else None
