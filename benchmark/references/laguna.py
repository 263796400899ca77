"""Plain reference of a decoder whose full-attention and sliding-window
layers differ in their query heads and their rotary, with a gated
attention output, a leading dense layer and sparse layers of many small
experts beside a shared one: straight ``jax.numpy`` in float32 at
``highest`` matmul precision, no kernels, no cache, no batching across
requests (each sequence is one whole forward pass), the window as a MASK
on the score matrix.  It imports nothing of the program and takes
nothing the program made: the weights come from ``harness/weights.py``
by the seed (the leaves are those ``shapes/laguna.py`` lists), one layer
at a time.

Layer i, n_i = num_attention_heads_per_layer[i] query heads, kvh K/V
heads of d; its kind is layer_types[i] (full_attention /
sliding_attention), its rotary rope_parameters[kind]:

    h   = RMSNorm(x; ln1)
    q,k,v = h W_q, h W_k, h W_v        # [T, n_i, d], [T, kvh, d]; no bias, no QK-norm
    r   = d * partial_rotary_factor    # 64 of 128 on a full layer, all 128 on a sliding one
    q[..., :r], k[..., :r] = rot(.; pos, inv_freq, a)   # the rest passes through
        inv_freq_j = theta^(-2j/r)                             (default)
        inv_freq_j = blend of theta^(-2j/r) and that / factor  (yarn), a = attention_factor
        rot: pairs (2j, 2j+1) turned by pos * inv_freq_j, cos and sin times a
    a_i = softmax_j(q_i k_j / sqrt(d)) v_j   over j <= i             (full)
                                              over i - W < j <= i     (sliding)
    a   = a * sigmoid(h W_g)           # gating: elementwise, W_g [H, n_i d]
    x   = x + a W_o
    h2  = RMSNorm(x; ln2)
    dense layer (mlp_layer_types[i]):  x = x + (silu(h2 G) * (h2 U)) D
    sparse layer:
        s   = sigmoid(W_r h2)                        # all E experts, float32
        idx = the k largest of s;  g = scaling * s_idx / sum(s_idx)
        x   = x + shared(h2) + sum_{e in idx} g_e (silu(h2 G_e) * (h2 U_e)) D_e

Readings, noted (the configuration's ``assumed`` says the same):
- ``gating`` true says that there is a gate and not its form: the output
  gate of the gated-attention family, elementwise on the heads' output,
  from the layer's normed input, before ``W_o``;
- the router scores with a sigmoid, normalises the taken scores and
  scales them by ``moe_routed_scaling_factor`` (that factor is this
  router's); no expert bias; the gate weighs the expert's OUTPUT
  (``moe_apply_router_weight_on_input`` false);
- no QK-norm (the configuration has no key for one);
- rotary pairs are the interleaved (2j, 2j+1) pairs, which is what the
  program computes; with seeded weights another pairing is a relabelling
  of the columns of ``W_q`` and ``W_k``;
- a window of W holds the query's own position: W keys at most;
- the sequence is processed at its own length rounded up to ``_BUCKET``
  tokens (causal, so padding after the end changes nothing a real
  position sees), attention a block of queries at a time so that a
  13k-token pass fits; every expert multiplies all tokens and counts
  for those that chose it.

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


def inv_freq(rp: dict, d: int) -> tuple:
    """(float32 [r / 2] frequencies, the factor on cos and sin) of one
    kind of layer's ``rope_parameters`` over heads of ``d``."""
    r = int(d * float(rp.get("partial_rotary_factor", 1)))
    theta = float(rp["rope_theta"])
    plain = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    if rp.get("rope_type", "default") == "default":
        return plain.astype(np.float32), 1.0
    if rp["rope_type"] != "yarn":
        raise ValueError(f"no such rope_type: {rp['rope_type']!r}")
    orig = float(rp["original_max_position_embeddings"])

    def dimension(turns):
        """The pair that turns ``turns`` times over ``orig`` positions."""
        return r * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dimension(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(dimension(float(rp["beta_slow"]))), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    # pairs below ``low`` keep their frequency, pairs above ``high``
    # turn ``factor`` times slower, a linear blend between
    blended = plain * (1.0 - ramp) + plain / float(rp["factor"]) * ramp
    return blended.astype(np.float32), float(rp["attention_factor"])


def _rope(x, inv, scale):
    """x [T, heads, d] at positions 0..T-1: its first 2 * len(inv)
    numbers rotated in interleaved pairs, the rest untouched."""
    import jax.numpy as jnp
    T, r = x.shape[0], 2 * len(inv)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = x[..., 0:r:2], x[..., 1:r:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                       -1).reshape(x.shape[:-1] + (r,))
    return jnp.concatenate([turned, x[..., r:]], -1)


def _attention(h, w, m, nh: int, window: bool):
    """The attention block's addition to x from h [T, H]."""
    import jax
    import jax.numpy as jnp
    T = h.shape[0]
    kvh, d = m["kvh"], m["d"]
    inv, scale = inv_freq(m["rope"][window], d)
    q = _rope((h @ w["wq"]).reshape(T, nh, d), inv, scale)
    k = _rope((h @ w["wk"]).reshape(T, kvh, d), inv, scale)
    v = (h @ w["wv"]).reshape(T, kvh, d)
    q = q.reshape(T, kvh, nh // kvh, d)
    key = jnp.arange(T)

    def block(i):
        """Queries i*B .. i*B+B-1 against every key, masked."""
        qb = jax.lax.dynamic_slice_in_dim(q, i * _Q_BLOCK, _Q_BLOCK)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k) / np.sqrt(d)
        pos = i * _Q_BLOCK + jnp.arange(_Q_BLOCK)
        see = key[None, :] <= pos[:, None]
        if window:
            see &= key[None, :] > pos[:, None] - m["W"]
        s = jnp.where(see[None, None], s, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, axis=-1), v)

    att = jax.lax.map(block, jnp.arange(T // _Q_BLOCK)).reshape(T, nh * d)
    return (att * jax.nn.sigmoid(h @ w["wg"])) @ w["wo"]


def _swiglu(h, gate, up, down):
    import jax
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _experts(h2, w, m):
    """The sparse layer's addition to x: the shared expert and the
    chosen experts, each of which multiplies all tokens and counts for
    those that chose it, with their gate."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(h2 @ w["router"])
    top, idx = jax.lax.top_k(s, m["k"])
    g = m["scaling"] * top / jnp.sum(top, axis=-1, keepdims=True)

    def one(acc, inp):
        e, gate, up, down = inp
        g_e = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)
        return acc + g_e[:, None] * _swiglu(h2, gate, up, down), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(h2),
        (jnp.arange(m["E"]), w["e_gate"], w["e_up"], w["e_down"]))
    return routed + _swiglu(h2, w["s_gate"], w["s_up"], w["s_down"])


def _layer(x, w, *, m, eps, nh, window, sparse):
    x = x + _attention(_rms(x, w["ln1"], eps), w, m, nh, window)
    h2 = _rms(x, w["ln2"], eps)
    if sparse:
        return x + _experts(h2, w, m)
    return x + _swiglu(h2, w["gate"], w["up"], w["down"])


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

    shapes = spec.load_shapes("laguna")
    m, leaves = shapes.dims(cfg), shapes.leaves(cfg)
    eps = float(cfg["rms_norm_eps"])
    dtype = jnp.dtype(cfg.get("dtype", "bfloat16"))
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

        @functools.cache
        def layer_of(nh, window, sparse):
            return jax.jit(functools.partial(
                _layer, m=m, eps=eps, nh=nh, window=window, sparse=sparse))

        for i in range(m["L"]):
            w = _prep(W.make_layer(leaves, seed, i, dtype), lower)
            layer = layer_of(m["heads"][i], m["window"][i], m["sparse"][i])
            xs = [layer(x, w) for x in xs]
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
