"""Query tokens a launch computed that no request asked for: 100 x (1 -
real / padded) over the window, from ``summary()``'s ``tokens_real`` and
``tokens_padded`` (counted where a launch is made; a launch pads its
tokens up to its bucket: 32, 64, 128, 192)."""


def read(ctx):
    real = (ctx["c1"].get("tokens_real") or 0) \
        - (ctx["c0"].get("tokens_real") or 0)
    padded = (ctx["c1"].get("tokens_padded") or 0) \
        - (ctx["c0"].get("tokens_padded") or 0)
    if padded <= 0 or real <= 0:
        return None
    return 100.0 * (1.0 - real / padded)
