"""Mean time, launch to materialised result, of the window's decode-only
steps (no prefill chunk aboard)."""
from harness import spans as S


def read(ctx):
    ls = [l["ms"] for l in S.launches(ctx["spans"])
          if l["chunks"] == 0 and ctx["t_open"] <= l["end"] < ctx["t_close"]]
    return sum(ls) / len(ls) if ls else None
