"""Plain reference of the LLaMA-style dense decoder: RMSNorm before each
block, rotary positions, grouped-query attention, SwiGLU.  Straight
``jax.numpy`` in float32 at ``highest`` matmul precision: no kernels, no
cache, no batching across requests (each sequence is one whole causal
forward pass).  It imports nothing of the program and takes nothing the
program made: the weights come from ``harness/weights.py`` by the seed
(the leaves are those ``shapes/llama_dense.py`` lists), one layer at a
time so that it fits beside nothing else.

Departure from the Hugging Face code, noted: rotary pairs are the
interleaved (2i, 2i+1) pairs of the RoFormer paper and of Meta's LLaMA
release, which is what the program computes; the Hugging Face layout
(i, i + d/2) is the same function under a fixed permutation of the rows
of wq and wk, and with weights from a seed there is nothing to permute.

``lower`` names the control's precision: ``"int8"`` rounds every matrix
to int8 with one float32 scale per output channel (the embedding: per
row) before use, which is the step below bfloat16 that the program offers
as ``weight_dtype="int8"``.
"""
from __future__ import annotations

import numpy as np


def _int8_round(w, axis):
    """Symmetric int8 with one scale per channel along ``axis``'s
    complement, returned as float32 values (fake quantization)."""
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _prep(w: dict, lower: str | None) -> dict:
    import jax.numpy as jnp
    out = {}
    for name, a in w.items():
        if lower == "int8" and a.ndim == 2:
            out[name] = _int8_round(a, axis=1 if name == "embed" else 0)
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


def _block(x, w, m, eps, theta):
    """One decoder layer on one sequence x [T, H], causal."""
    import jax
    import jax.numpy as jnp
    T = x.shape[0]
    nh, kvh, d = m["nh"], m["kvh"], m["d"]
    h = _rms(x, w["ln1"], eps)
    q = _rope((h @ w["wq"]).reshape(T, nh, d), theta)
    k = _rope((h @ w["wk"]).reshape(T, kvh, d), theta)
    v = (h @ w["wv"]).reshape(T, kvh, d)
    k = jnp.repeat(k, nh // kvh, axis=1)
    v = jnp.repeat(v, nh // kvh, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    att = jnp.einsum("hqk,khd->qhd", p, v).reshape(T, nh * d)
    x = x + att @ w["wo"]
    h2 = _rms(x, w["ln2"], eps)
    return x + (jax.nn.silu(h2 @ w["gate"]) * (h2 @ w["up"])) @ w["down"]


def logits_at(cfg: dict, seed: int, seqs: list, score_from: list,
              n_score: int, pad_to: int, lower: str | None = None):
    """Logits of whole forward passes.

    seqs: token-id lists (prompt then served tokens).  For sequence s the
    rows scored are positions score_from[s] .. score_from[s]+n_score-1
    (clipped to the sequence; rows past its end are padding the caller
    ignores).  Returns float32 [len(seqs), n_score, V] as numpy.

    Padding sits after every real token and the pass is causal, so it
    changes nothing a real position sees."""
    import jax
    import jax.numpy as jnp

    from harness import spec, weights as W

    shapes = spec.load_shapes("llama_dense")
    m, leaves = shapes.dims(cfg), shapes.leaves(cfg)
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    dtype = jnp.dtype(cfg.get("dtype", "bfloat16"))
    toks = np.zeros((len(seqs), pad_to), np.int32)
    for i, s in enumerate(seqs):
        if len(s) > pad_to:
            raise ValueError(f"sequence of {len(s)} tokens over {pad_to}")
        toks[i, :len(s)] = s
    rows = np.stack([np.minimum(np.arange(n_score) + f, pad_to - 1)
                     for f in score_from]).astype(np.int32)

    with jax.default_matmul_precision("highest"):
        top = _prep(W.make_top(leaves, seed, dtype), lower)
        x = jax.jit(lambda e, t: e[t])(top["embed"], jnp.asarray(toks))
        layer = jax.jit(lambda x, w: jax.lax.map(
            lambda xs: _block(xs, w, m, eps, theta), x))
        for i in range(m["L"]):
            w = _prep(W.make_layer(leaves, seed, i, dtype), lower)
            x = layer(x, w)
            del w

        def head(x, rows, norm_f, head_w):
            hs = jnp.take_along_axis(x, rows[:, :, None], axis=1)
            return _rms(hs, norm_f, eps) @ head_w

        out = jax.jit(head)(x, jnp.asarray(rows), top["norm_f"], top["head"])
        return np.asarray(out)
