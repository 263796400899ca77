"""Time inside the jitted call of a step launch alone (the transfer of
its host arrays to the device and the jit dispatch), per launch over the
window: ``summary()``'s ``launch_call_time_s`` over ``launches`` (``c1``
less ``c0``).  A program without the counter gives nothing to read."""


def read(ctx):
    c0, c1 = ctx["c0"], ctx["c1"]
    n = (c1.get("launches") or 0) - (c0.get("launches") or 0)
    if "launch_call_time_s" not in c1 or n <= 0:
        return None
    return 1e3 * (c1["launch_call_time_s"]
                  - (c0.get("launch_call_time_s") or 0.0)) / n
