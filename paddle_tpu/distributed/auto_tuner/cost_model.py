"""Analytic cost + memory models for hybrid-parallel candidate ranking.

Reference counterparts: ``python/paddle/distributed/auto_tuner/cost_model.py``
(step-time estimation used by the dp_estimation search) and
``memory_cost_model.py`` (``get_model_memory_usage``).  Those models are
GPU-shaped (per-op benchmark table + NVLink constants); these are TPU-shaped:
MXU peak FLOP/s, HBM capacity, and ICI bandwidth per v5e-class chip, with the
parallelism terms (pipeline bubble, TP collective volume, ZeRO sharding
factors) expressed over the mesh axes.

All model-size inputs come from a plain dict so the tuner works for any
decoder-like config, not just the in-tree LLaMA::

    model_cfg = {
        "hidden_size": 1024, "intermediate_size": 2816,
        "num_hidden_layers": 24, "num_attention_heads": 16,
        "num_key_value_heads": 4, "vocab_size": 32000,
    }
"""
from __future__ import annotations

from ...core.runtime import MODELED_DEVICE, device_peaks

# The chip the tuner plans for, by name, from the published table (a
# caller planning for another names it per call; a device that is not in
# the table is an error there).
DEFAULT_HBM_BYTES = device_peaks(MODELED_DEVICE)["hbm_bytes"]
DEFAULT_PEAK_FLOPS = device_peaks(MODELED_DEVICE)["bf16_flops"]
DEFAULT_ICI_BYTES_PER_S = 4.5e10  # v5e: ~45 GB/s per ICI link direction


def _param_count(m: dict) -> tuple[int, int]:
    """(total params, per-layer params) for a LLaMA-shaped decoder."""
    h = m["hidden_size"]
    ffn = m["intermediate_size"]
    kv = m.get("num_key_value_heads", m["num_attention_heads"])
    head_dim = h // m["num_attention_heads"]
    per_layer = (
        h * h + 2 * h * kv * head_dim + h * h   # wq, wk, wv, wo
        + 3 * h * ffn                            # gate, up, down
        + 2 * h                                  # rms norms
    )
    total = (m["num_hidden_layers"] * per_layer
             + 2 * m["vocab_size"] * h           # embed + lm head
             + h)                                # final norm
    return total, per_layer


def estimate_memory_bytes(model_cfg: dict, cfg: dict, *,
                          param_bytes: int = 2,
                          grad_bytes: int = 2,
                          opt_bytes_per_param: int = 12) -> float:
    """Per-chip HBM footprint estimate for one hybrid-parallel candidate.

    cfg keys: dp, tp, pp, cp (defaults 1), zero_stage (0/1/2),
    micro_batch_size, seq_len, recompute (bool), num_microbatches.

    Accounting mirrors ``memory_cost_model.py:get_model_memory_usage``:
    params + grads + optimizer states (f32 master + Adam m/v = 12 B/param)
    + activations, each divided by the axes that shard it.
    """
    dp = cfg.get("dp", 1)
    tp = cfg.get("tp", 1)
    pp = cfg.get("pp", 1)
    cp = cfg.get("cp", 1)
    zero = cfg.get("zero_stage", 0)
    mbs = cfg.get("micro_batch_size", 1)
    seq = cfg.get("seq_len", 2048)
    m = cfg.get("num_microbatches", 1)
    recompute = cfg.get("recompute", True)

    n_total, _ = _param_count(model_cfg)
    n_local = n_total / (tp * pp)           # TP/PP shard params

    params = n_local * param_bytes
    grads = n_local * grad_bytes
    opt = n_local * opt_bytes_per_param
    if zero >= 1:
        opt /= dp                            # ZeRO-1: shard m/v over dp
    if zero >= 2:
        grads /= dp                          # ZeRO-2: reduce-scatter grads

    # Activations per microbatch-layer (bf16): the classic
    # ~s*b*h*(34 + 5*a*s/h) estimate collapses to ~2*s*b*h*L stored
    # boundaries under full recompute.
    h = model_cfg["hidden_size"]
    layers_local = model_cfg["num_hidden_layers"] / pp
    tok = mbs * seq / cp
    if recompute:
        act_per_layer = 2 * tok * h            # layer-boundary residual only
    else:
        act_per_layer = tok * h * (34 / tp) + 5 * tok * seq * \
            model_cfg["num_attention_heads"] / (tp * cp)
    # 1F1B keeps <= pp in-flight microbatches of activations per stage.
    in_flight = min(m, pp)
    acts = act_per_layer * layers_local * in_flight

    return params + grads + opt + acts


def estimate_step_time(model_cfg: dict, cfg: dict, *,
                       peak_flops: float = DEFAULT_PEAK_FLOPS,
                       ici_bytes_per_s: float = DEFAULT_ICI_BYTES_PER_S,
                       mfu: float = 0.4) -> float:
    """Estimated seconds per global step for one candidate.

    compute term: 6*N*tokens/(chips*peak*mfu) (+recompute adds 1 fwd pass
    -> factor 8/6); pipeline bubble: (pp-1)/(m*vpp + pp - 1)
    (reference 1F1B bubble, ``pipeline_parallel.py:684``); comm terms: TP
    allreduce volume per layer + dp grad sync, both at ICI bandwidth.
    """
    dp = cfg.get("dp", 1)
    tp = cfg.get("tp", 1)
    pp = cfg.get("pp", 1)
    cp = cfg.get("cp", 1)
    m = cfg.get("num_microbatches", 1)
    vpp = cfg.get("vpp", 1)
    mbs = cfg.get("micro_batch_size", 1)
    seq = cfg.get("seq_len", 2048)
    recompute = cfg.get("recompute", True)
    zero = cfg.get("zero_stage", 0)

    n_total, _ = _param_count(model_cfg)
    chips = dp * tp * pp * cp
    global_tokens = dp * mbs * m * seq

    flops_per_token = (8.0 if recompute else 6.0) * n_total
    compute = flops_per_token * global_tokens / (chips * peak_flops * mfu)

    # Pipeline bubble stretches compute; interleaving (vpp) shrinks it.
    if pp > 1:
        bubble = (pp - 1) / max(m * vpp, 1)
        compute *= 1.0 + bubble

    comm = 0.0
    h = model_cfg["hidden_size"]
    L = model_cfg["num_hidden_layers"]
    if tp > 1:
        # 2 allreduces/layer fwd + 2 bwd, ring cost 2*(tp-1)/tp * bytes.
        vol = 4 * L * (2 * (tp - 1) / tp) * (mbs * m * seq / cp) * h * 2
        comm += vol / ici_bytes_per_s
    if dp > 1:
        # grad sync: allreduce (2x volume) or reduce-scatter+allgather under
        # ZeRO (same ring volume), bf16 grads, overlappable ~50%.
        vol = 2 * (dp - 1) / dp * (n_total / (tp * pp)) * 2
        overlap = 0.5 if zero < 2 else 0.35
        comm += vol * (1 - overlap) / ici_bytes_per_s
    if cp > 1:
        # ring attention ppermute of K/V per layer, largely overlapped.
        kv = model_cfg.get("num_key_value_heads",
                           model_cfg["num_attention_heads"])
        head_dim = h // model_cfg["num_attention_heads"]
        vol = 2 * L * (cp - 1) * (mbs * m * seq / cp) * kv * head_dim * 2
        comm += 0.2 * vol / ici_bytes_per_s

    return compute + comm
