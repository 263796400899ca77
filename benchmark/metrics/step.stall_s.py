"""Seconds the window lost to launches that took more than twice what
their like takes.  A launch's period runs from the end of the
``engine.block_on_result`` of the launch before it to the end of its
own: what a client sees as one gap, every host and device nanosecond
counted once.  Launches are classed by bucket and by whether a prefill
chunk rode; the sum is over max(0, period - 2 x the class's median).  0
in a sound run.  Each launch over the line is printed, with where its
period went."""
import json

from harness import scopes


def read(ctx):
    periods = scopes.launch_periods(ctx["spans"], ctx["t_open"],
                                    ctx["t_close"])
    if not periods:
        return None
    total, over = scopes.stalls(periods)
    for p in over:
        line = scopes.explain_period(p, ctx["spans"], ctx.get("trace"))
        print("[bench] stall", json.dumps(line), flush=True)
    return total / 1e9
