"""99th percentile of every gap between consecutive streamed tokens in
the window, on the client's clock.  Decides nothing: a window holds one
to three hundred steps, so this is the three or four worst steps."""


def read(ctx):
    return ctx["e2e"].get("gap_p99_ms")
