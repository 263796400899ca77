"""Self time of the engine's host phases (admit, schedule, pack,
block-table stage, prestage, sample-commit, retire) over the steps
launched in the window."""
from harness import spans as S

PHASES = ("engine.admit", "engine.schedule", "engine.pack",
          "engine.block_table_stage", "engine.prestage",
          "engine.sample_commit", "engine.retire")


def read(ctx):
    sp = S.in_window(ctx["spans"], ctx["t_open"], ctx["t_close"])
    steps = len(S.named(sp, "engine.step", "X"))
    if not steps:
        return None
    sp = [s for s in sp if s["name"] in PHASES]
    return S.self_time_ns(sp, PHASES) / 1e6 / steps
