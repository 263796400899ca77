"""The least time the chip needs for the selective scans of the steps
that ran whole inside the traced window, over the traced time of their
launches (``SSM_KERNELS`` of the architecture's shapes file).  A step's
scan is counted from what its launch says of it on
``engine.device_launch``: ``rows`` segments holding ``state_rows``
tokens, ``state_starts`` of them a sequence's first (no state to read),
through the shapes file's ``scan_step``; steps are joined to device
operations by the ``engine.launch`` annotation that carries the step's
id, and each step's own bound is summed (a step cannot borrow another's
slack).  Nothing to read where the architecture has no such kernel or
the program counts no state rows."""
from types import SimpleNamespace

from harness import costs, peaks, scopes


def read(ctx):
    arch = ctx["arch"]
    names = getattr(arch, "SSM_KERNELS", ())
    evs = scopes.scoped_events(ctx)
    if not names or not evs or not hasattr(arch, "scan_step"):
        return None
    launched = {s: a for s, a in scopes.launch_args(ctx["spans"]).items()
                if "state_rows" in a}
    whole = scopes.whole_steps(scopes.launch_annotations(ctx),
                               ctx["trace"]["window"]) & set(launched)
    ns = scopes.kernel_ns([e for e in evs if e["step"] in whole],
                          SimpleNamespace(KERNELS=names))
    if not whole or ns <= 0:
        return None
    peak = peaks.peaks(ctx["device_kind"])
    least = sum(costs.least_seconds(
        *arch.scan_step(ctx["cfg"], int(launched[s]["state_rows"]),
                        int(launched[s]["rows"]),
                        int(launched[s]["state_starts"])), peak)[0]
        for s in whole)
    return 100.0 * least / (ns / 1e9)
