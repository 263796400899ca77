"""Launches the engine dispatched before the launch in front of them was
committed (the host's turn then ran under a step, not after it), over all
the launches it made inside the window, in percent: the ``ahead``
argument of ``engine.device_launch``.  Where it is false the span's
``reason`` says why (``idle``: nothing was in flight).  A program whose
launches do not say gives nothing to read."""
from harness import spans as S


def read(ctx):
    said = [bool(s["args"]["ahead"]) for s in S.in_window(
        S.named(ctx["spans"], "engine.device_launch", "X"),
        ctx["t_open"], ctx["t_close"]) if "ahead" in s["args"]]
    return 100.0 * sum(said) / len(said) if said else None
