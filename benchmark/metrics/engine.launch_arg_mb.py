"""Megabytes (1e6 bytes) of host arrays handed to the jitted call of a
step launch, per launch over the window: ``summary()``'s
``launch_arg_bytes`` over ``launches`` (``c1`` less ``c0``).  What
``engine.launch_call_ms`` has to move.  A program without the counter
gives nothing to read."""


def read(ctx):
    c0, c1 = ctx["c0"], ctx["c1"]
    n = (c1.get("launches") or 0) - (c0.get("launches") or 0)
    if "launch_arg_bytes" not in c1 or n <= 0:
        return None
    return (c1["launch_arg_bytes"]
            - (c0.get("launch_arg_bytes") or 0)) / n / 1e6
