"""The least time the chip needs for the matrix products of the steps
that ran whole inside the traced window, over the time those steps spent
in matrix products (as ``matmul.device_share`` counts it: every
operation that holds a dot and every move of a weight toward one, so
the share cannot pass 100 by leaving part of the work out).  Operations come from each step's real tokens and
used logit rows (``engine.device_launch``), bytes from the weights read
once a step plus activations (``step_matmuls`` of the architecture's
shapes file), the peaks
from ``harness/peaks.py``; steps are joined to device operations by the
``engine.launch`` annotation that carries the step's id."""
from harness import costs, peaks, scopes


def read(ctx):
    evs = scopes.scoped_events(ctx)
    if not evs:
        return None
    launched = scopes.launch_args(ctx["spans"])
    whole = scopes.whole_steps(scopes.launch_annotations(ctx),
                               ctx["trace"]["window"]) & set(launched)
    ns = scopes.matmul_ns([e for e in evs if e["step"] in whole],
                          ctx["cfg"], ctx["arch"])
    if not whole or ns <= 0:
        return None
    steps = [(int(launched[s]["tokens"]), int(launched[s]["logit_rows"]))
             for s in whole]
    least = costs.matmul_least_seconds(ctx["arch"], ctx["cfg"], steps,
                                       peaks.peaks(ctx["device_kind"]))
    return 100.0 * least / (ns / 1e9)
