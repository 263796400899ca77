"""The host's turn: the engine thread's own work of a ``step()`` call
(its wall time less the time inside the blocking read of the launch in
front), per launch over the window, from ``summary()``'s always-on
``turn_time_s`` and ``launches`` (``c1`` less ``c0``).  A period is the
longer of this and the device's step (``step.device_*_ms``).  A program
without the counter gives nothing to read."""


def read(ctx):
    c0, c1 = ctx["c0"], ctx["c1"]
    n = (c1.get("launches") or 0) - (c0.get("launches") or 0)
    if "turn_time_s" not in c1 or n <= 0:
        return None
    return 1e3 * (c1["turn_time_s"] - (c0.get("turn_time_s") or 0.0)) / n
