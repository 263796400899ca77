"""How unevenly the router loads the experts held here: over the window's
steps, the most tokens one held expert got in one layer
(``moe_load_max``) over the mean load of that step (``moe_pairs_here``
spread over every held expert of every expert layer), averaged over the
steps.  1 is an even router; the grouped product's time follows the
fullest expert's tile count, not the mean.  Counted by the step program
and recorded with the step's id on ``engine.sample_commit``."""
from harness import spans as S


def read(ctx):
    arch = ctx["arch"]
    if not hasattr(arch, "expert_products"):
        return None
    m = arch.dims(ctx["cfg"])
    slots = m["held"] * (m["L"] - m["dense"])
    ratios = []
    for s in S.in_window(S.named(ctx["spans"], "engine.sample_commit", "X"),
                         ctx["t_open"], ctx["t_close"]):
        a = s["args"]
        if a.get("moe_pairs_here", 0) > 0:
            ratios.append(a["moe_load_max"] / (a["moe_pairs_here"] / slots))
    return sum(ratios) / len(ratios) if ratios else None
