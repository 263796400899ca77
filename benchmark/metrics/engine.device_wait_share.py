"""The share of the window the engine thread spent waiting on the chip:
``summary()``'s ``block_time_s`` (inside the blocking read of a launch's
result, ``c1`` less ``c0``) over the window's seconds, in percent.  Near
0 where the host is the pace (the result is there when the turn gets to
it); high where the device is."""


def read(ctx):
    c0, c1 = ctx["c0"], ctx["c1"]
    seconds = (ctx["t_close"] - ctx["t_open"]) / 1e9
    if "block_time_s" not in c1 or seconds <= 0:
        return None
    return 100.0 * (c1["block_time_s"]
                    - (c0.get("block_time_s") or 0.0)) / seconds
