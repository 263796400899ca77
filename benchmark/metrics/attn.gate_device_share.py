"""Time under the attention output gate's scope (``attn_gate``: the
product ``h W_g``, its sigmoid and the multiply into the heads' output;
the architecture's shapes file lists it as ``GATE_SCOPES``) over device
busy time.  Nothing to read where the architecture has no gate or the
program names no such scope."""
from harness import scopes


def read(ctx):
    names = getattr(ctx["arch"], "GATE_SCOPES", ())
    evs = scopes.scoped_events(ctx)
    if not names or not evs or ctx["trace"]["busy_s"] <= 0:
        return None
    by = scopes.by_class(evs, ctx["cfg"], ctx["arch"])
    ns = sum(by.get(k, 0) for k in names)
    return 100.0 * ns / (ctx["trace"]["busy_s"] * 1e9) if ns > 0 else None
