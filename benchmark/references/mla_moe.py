"""Plain reference of a latent-attention (MLA) decoder with sparse expert
layers, as ONE chip of an expert-parallel deployment computes it:
straight ``jax.numpy`` in float32 at ``highest`` matmul precision, the
EXPANDED form of the attention (per-head keys and values made from the
latents), no kernels, no cache, no batching across requests (each
sequence is one whole causal forward pass).  It imports nothing of the
program and takes nothing the program made: the weights come from
``harness/weights.py`` by the seed (the leaves are those
``shapes/mla_moe.py`` lists), one layer at a time.

The equations (DeepSeek-V2/V3 conventions, which ``deepseek_yarn`` and
the MLA keys of the configuration name).  Per layer ``h = RMSNorm(x)``.
Attention: ``q = W_q h`` as heads of ``[q_nope | q_rope]``; ``[c_kv |
k_rope] = W_kva h``; ``c = RMSNorm_w(c_kv)``; rotary on ``q_rope`` and
``k_rope`` (which all heads share) with YaRN frequencies; ``[k_nope |
v]_h = W_kvb,h c``; ``score_h = (q_nope_h . k_nope_h + q_rope_h . k_rope)
s`` with ``s = q_head_dim^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor)
+ 1``; causal softmax; ``x += W_o concat_h(sum p v_h)``.  The first
``first_k_dense_replace`` layers: SwiGLU.  The others: ``s = sigmoid(W_r
h2)`` over all experts of the model; the ``num_experts_per_tok`` largest
of ``s + b``; ``g = routed_scaling_factor s_idx / sum s_idx``; ``x +=
shared(h2) + sum over the chosen experts HELD HERE of g_i E_i(h2)``.

Departures and readings, noted:
- the share: this chip holds ``num_experts`` consecutive experts of the
  router's ``router_width`` from ``ep_rank * num_experts`` and a slice
  of the vocabulary; what the absent experts would add is left out and
  that partial result goes on to the next layer (model-configs guide,
  section 4); the program is given the same share;
- rotary pairs are the interleaved (2i, 2i+1) pairs, which is what the
  program computes; the Hugging Face code's de-interleaving is the same
  function under a fixed permutation of rows of ``W_q`` and ``W_kva``;
- ``use_qk_norm`` is read as a learned RMSNorm over each head's whole
  query before its rope part is rotated (``q_norm``), beside the
  latent's own (``kv_norm``); no norm on the expanded per-head keys;
- the sequence is processed at its own length rounded up to
  ``_BUCKET`` tokens (causal, so padding after the end changes nothing
  a real position sees), attention a block of queries at a time so that
  a 12,800-token pass fits.

``lower="int8"`` is the control's precision: every matrix (each expert's
own) rounded to int8 with one float32 scale per output channel (the
embedding: per row) before use.
"""
from __future__ import annotations

import math

import numpy as np

_BUCKET = 2048          # sequence lengths are rounded up to this
_Q_BLOCK = 512          # queries a block of the attention


def _int8_round(w, axis):
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _prep(w: dict, lower: str | None) -> dict:
    import jax.numpy as jnp
    out = {}
    for name, a in w.items():
        if lower == "int8" and a.ndim >= 2:
            out[name] = _int8_round(a, axis=-1 if name == "embed" else -2)
        elif lower is None or a.ndim == 1:
            out[name] = a.astype(jnp.float32)
        else:
            raise ValueError(f"no such lower precision: {lower!r}")
    return out


def _rms(x, w, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """Frequencies of the rope pairs under ``deepseek_yarn``: a pair that
    turns more than ``beta_fast`` times over the original context keeps
    its frequency, one that turns fewer than ``beta_slow`` times has it
    divided by ``factor``, and those between are blended linearly."""
    rs, d = cfg["rope_scaling"], int(cfg["qk_rope_head_dim"])
    base, factor = float(cfg["rope_theta"]), float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])
    plain = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def pair_of(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_of(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(rs["beta_slow"]))), d - 1)
    if low == high:
        high += 0.001
    scaled = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (plain * (1.0 - scaled) + plain / factor * scaled
            ).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = 0.1 * float(rs["mscale_all_dim"]) * math.log(float(rs["factor"])) + 1
    dq = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    return dq ** -0.5 * m * m


def _rope(x, inv_freq):
    """x [T, heads, d] at positions 0..T-1, interleaved pairs."""
    import jax.numpy as jnp
    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(x, w, m, eps, inv_freq, scale):
    """The attention block's addition to x [T, H], expanded form."""
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    nh, dn, dr, dv, dc = m["nh"], m["dn"], m["dr"], m["dv"], m["dc"]
    h = _rms(x, w["ln1"], eps)
    q = _rms((h @ w["wq"]).reshape(T, nh, dn + dr), w["q_norm"], eps)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], inv_freq)
    ckv = h @ w["wkva"]
    c = _rms(ckv[:, :dc], w["kv_norm"], eps)
    k_rope = _rope(ckv[:, None, dc:], inv_freq)[:, 0]
    kv = (c @ w["wkvb"]).reshape(T, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    key = jnp.arange(T)

    def block(i):
        """Queries i*B .. i*B+B-1 against every key up to their own."""
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * _Q_BLOCK, _Q_BLOCK)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i * _Q_BLOCK, _Q_BLOCK)
        s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
             + jnp.einsum("qhr,kr->hqk", qr, k_rope)) * scale
        pos = i * _Q_BLOCK + jnp.arange(_Q_BLOCK)
        s = jnp.where((key[None, :] <= pos[:, None])[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    att = jax.lax.map(block, jnp.arange(T // _Q_BLOCK))
    return att.reshape(T, nh * dv) @ w["wo"]


def _swiglu(h, gate, up, down):
    import jax
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _experts(h2, w, m, scaling):
    """Shared experts, plus the chosen experts held here, for every token
    of h2 [T, H]: each held expert multiplies all tokens and counts for
    those that chose it, with their gate."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(h2 @ w["router"])
    _, idx = jax.lax.top_k(s + w["router_bias"], m["k"])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    g = scaling * picked / jnp.sum(picked, -1, keepdims=True)

    def one(acc, inp):
        e, gate, up, down = inp
        g_e = jnp.sum(jnp.where(idx == e + m["first"], g, 0.0), axis=-1)
        return acc + g_e[:, None] * _swiglu(h2, gate, up, down), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(h2),
        (jnp.arange(m["held"]), w["e_gate"], w["e_up"], w["e_down"]))
    return _swiglu(h2, w["s_gate"], w["s_up"], w["s_down"]) + routed


def _layer(x, w, *, m, eps, inv_freq, scale, scaling, dense):
    x = x + _attention(x, w, m, eps, inv_freq, scale)
    h2 = _rms(x, w["ln2"], eps)
    if dense:
        return x + _swiglu(h2, w["gate"], w["up"], w["down"])
    return x + _experts(h2, w, m, scaling)


def logits_at(cfg: dict, seed: int, seqs: list, score_from: list,
              n_score: int, pad_to: int, lower: str | None = None):
    """Logits of whole forward passes.

    seqs: token-id lists (prompt then served tokens).  For sequence s the
    rows scored are positions score_from[s] .. score_from[s]+n_score-1
    (clipped to the sequence; rows past its end are padding the caller
    ignores).  Returns float32 [len(seqs), n_score, V] as numpy.
    ``pad_to`` bounds a sequence's length."""
    import functools

    import jax
    import jax.numpy as jnp

    from harness import spec, weights as W

    shapes = spec.load_shapes("mla_moe")
    m, leaves = shapes.dims(cfg), shapes.leaves(cfg)
    eps = float(cfg["rms_norm_eps"])
    dtype = jnp.dtype(cfg.get("dtype", "bfloat16"))
    kw = dict(m=m, eps=eps, inv_freq=yarn_inv_freq(cfg),
              scale=softmax_scale(cfg),
              scaling=float(cfg["routed_scaling_factor"]))
    lens = []
    for s in seqs:
        if len(s) > pad_to:
            raise ValueError(f"sequence of {len(s)} tokens over {pad_to}")
        lens.append(-(-len(s) // _BUCKET) * _BUCKET)

    with jax.default_matmul_precision("highest"):
        top = _prep(W.make_top(leaves, seed, dtype), lower)
        embed = jax.jit(lambda e, t: e[t])
        xs = []
        for s, n in zip(seqs, lens):
            toks = np.zeros((n,), np.int32)
            toks[:len(s)] = s
            xs.append(embed(top["embed"], jnp.asarray(toks)))
        layers = {d: jax.jit(functools.partial(_layer, dense=d, **kw))
                  for d in (True, False)}
        for i in range(m["L"]):
            w = _prep(W.make_layer(leaves, seed, i, dtype), lower)
            xs = [layers[i < m["dense"]](x, w) for x in xs]
            del w

        def head(x, rows, norm_f, head_w):
            return _rms(x[rows], norm_f, eps) @ head_w

        head = jax.jit(head)
        out = np.zeros((len(seqs), n_score, m["V"]), np.float32)
        for j, (x, f, n) in enumerate(zip(xs, score_from, lens)):
            rows = np.minimum(np.arange(n_score) + f, n - 1).astype(np.int32)
            out[j] = np.asarray(head(x, jnp.asarray(rows), top["norm_f"],
                                     top["head"]))
        return out
