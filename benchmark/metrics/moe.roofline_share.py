"""The least time the chip needs for the routed experts' grouped products
of the steps that ran whole inside the traced window, over the time
those steps spent under the scope of those products (``moe_experts``:
``EXPERT_SCOPES`` of the architecture's shapes file).  Operations and
bytes come from what each step's expert layers counted, which the engine
records with the step's id on ``engine.sample_commit``
(``moe_pairs_here``: token-expert pairs the held experts computed;
``moe_experts_touched``: (layer, expert) pairs that got a token, whose
three matrices are read once), through ``expert_products`` of the shapes
file; each step's own bound, summed.  Steps are joined to device
operations by the ``engine.launch`` annotation."""
from harness import costs, peaks, scopes, spans as S


def read(ctx):
    arch = ctx["arch"]
    names = getattr(arch, "EXPERT_SCOPES", ())
    evs = scopes.scoped_events(ctx)
    if not names or not evs or not hasattr(arch, "expert_products"):
        return None
    counted = {int(s["args"]["step"]): s["args"]
               for s in S.named(ctx["spans"], "engine.sample_commit", "X")
               if "moe_pairs_here" in s["args"]}
    whole = scopes.whole_steps(scopes.launch_annotations(ctx),
                               ctx["trace"]["window"]) & set(counted)
    ns = sum(e["self_ns"] for e in evs
             if e["step"] in whole and e["scope"] in names)
    if not whole or ns <= 0:
        return None
    peak = peaks.peaks(ctx["device_kind"])
    least = sum(costs.least_seconds(*arch.expert_products(
        ctx["cfg"], int(counted[s]["moe_pairs_here"]),
        int(counted[s]["moe_experts_touched"])), peak)[0] for s in whole)
    return 100.0 * least / (ns / 1e9)
