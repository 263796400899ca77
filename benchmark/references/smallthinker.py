"""Plain reference of a decoder that mixes global attention without
positions and sliding-window attention with rotary positions, every
layer with sparse ReGLU experts routed from the pre-attention norm:
straight ``jax.numpy`` in float32 at ``highest`` matmul precision, no
kernels, no cache, no batching across requests (each sequence is one
whole forward pass), the window as a MASK on the full score matrix.  It
imports nothing of the program and takes nothing the program made: the
weights come from ``harness/weights.py`` by the seed (the leaves are
those ``shapes/smallthinker.py`` lists), one layer at a time.

One layer (``sliding_window_layout[i]`` and ``rope_layout[i]`` are both 0
for a global layer and both 1 for a window layer):

    h   = RMSNorm(x; ln1)
    s   = W_r h                       # router logits, from the PRE-attention norm
    q,k,v = h W_q, h W_k, h W_v       # nh / kvh / kvh heads of d, no bias, no QK-norm
    q,k = rope(q,k; pos, theta) in a window layer; untouched in a global one (NoPE)
    a_i = softmax_j(q_i k_j / sqrt(d)) v_j   over j <= i             (global)
                                              over i - W < j <= i     (window)
    x   = x + a W_o
    h2  = RMSNorm(x; ln2)
    idx, t = top_k(s);  g = softmax(t)        # over the taken logits alone
    x   = x + sum_{e in idx} g_e (relu(h2 G_e) * (h2 U_e)) D_e

Readings, noted (the configuration's ``assumed`` says the same):
- "router placed before attention": the router's input is ``h``, the
  attention block's input, and its gates weigh experts that compute on
  ``h2``;
- a window of W holds the query's own position: W keys at most;
- rotary pairs are the interleaved (2i, 2i+1) pairs, which is what the
  program computes; with seeded weights another pairing is a relabelling
  of the columns of ``W_q`` and ``W_k``;
- ``moe_primary_router_apply_softmax``: a softmax over the taken logits;
  ``norm_topk_prob`` then changes nothing; no secondary experts (the
  configuration has keys for primary ones only); no bias anywhere;
- the sequence is processed at its own length rounded up to ``_BUCKET``
  tokens (causal, so padding after the end changes nothing a real
  position sees), attention a block of queries at a time so that a
  15,360-token pass fits; every expert multiplies all tokens and counts
  for those that chose it.

``lower="int8"`` is the control's precision: every matrix (each expert's
own) rounded to int8 with one float32 scale per output channel (the
embedding: per row) before use.
"""
from __future__ import annotations

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


def _rope(x, theta):
    """x [T, heads, d] at positions 0..T-1, interleaved pairs."""
    import jax.numpy as jnp
    T, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(h, w, m, window: bool):
    """The attention block's addition to x from h [T, H]."""
    import jax
    import jax.numpy as jnp
    T = h.shape[0]
    nh, kvh, d = m["nh"], m["kvh"], m["d"]
    q = (h @ w["wq"]).reshape(T, nh, d)
    k = (h @ w["wk"]).reshape(T, kvh, d)
    v = (h @ w["wv"]).reshape(T, kvh, d)
    if window:
        q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
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

    att = jax.lax.map(block, jnp.arange(T // _Q_BLOCK))
    return att.reshape(T, nh * d) @ w["wo"]


def _experts(h, h2, w, m):
    """The chosen experts' addition to x: chosen by h, computed on h2;
    each expert multiplies all tokens and counts for those that chose
    it, with their gate."""
    import jax
    import jax.numpy as jnp
    top, idx = jax.lax.top_k(h @ w["router"], m["k"])
    g = jax.nn.softmax(top, axis=-1)

    def one(acc, inp):
        e, gate, up, down = inp
        g_e = jnp.sum(jnp.where(idx == e, g, 0.0), axis=-1)
        y = (jax.nn.relu(h2 @ gate) * (h2 @ up)) @ down
        return acc + g_e[:, None] * y, None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(h2),
        (jnp.arange(m["E"]), w["e_gate"], w["e_up"], w["e_down"]))
    return routed


def _layer(x, w, *, m, eps, window):
    h = _rms(x, w["ln1"], eps)
    x = x + _attention(h, w, m, window)
    return x + _experts(h, _rms(x, w["ln2"], eps), w, m)


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

    shapes = spec.load_shapes("smallthinker")
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
        layers = {win: jax.jit(functools.partial(_layer, m=m, eps=eps,
                                                 window=win))
                  for win in (True, False)}
        for i in range(m["L"]):
            w = _prep(W.make_layer(leaves, seed, i, dtype), lower)
            xs = [layers[m["window"][i]](x, w) for x in xs]
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
