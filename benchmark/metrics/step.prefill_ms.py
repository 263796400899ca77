"""Mean time, launch to materialised result, of the window's steps that
carried a prefill chunk (Tracer span ``engine.device_inflight``, which
ends when ``np.asarray`` of the sampled tokens returns)."""
from harness import spans as S


def read(ctx):
    ls = [l["ms"] for l in S.launches(ctx["spans"])
          if l["chunks"] > 0 and ctx["t_open"] <= l["end"] < ctx["t_close"]]
    return sum(ls) / len(ls) if ls else None
