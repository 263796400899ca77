"""The share of the held experts that a step had to read: a launch's
``moe_experts_touched`` ((layer, expert) pairs that got a token, counted
by the step program and recorded with the step's id on
``engine.sample_commit``) over ``moe_experts_held`` (experts held x
sparse layers, from ``summary()``), in percent, averaged over the
launches committed inside the window.  100 says every step reads every
expert's three matrices: a step that carries a prefill chunk does; a
decode-only step of 32 rows, 8 experts each, over 256 experts reads
about 63.  A program without either count gives nothing to read."""
from harness import spans as S


def read(ctx):
    held = ctx["c1"].get("moe_experts_held") or 0
    if held <= 0:
        return None
    shares = [100.0 * s["args"]["moe_experts_touched"] / held
              for s in S.in_window(
                  S.named(ctx["spans"], "engine.sample_commit", "X"),
                  ctx["t_open"], ctx["t_close"])
              if "moe_experts_touched" in s["args"]]
    return sum(shares) / len(shares) if shares else None
