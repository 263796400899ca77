"""Plain reference of the dense decoder with tied embedding and head:
the blocks of ``references/llama_dense.py``, the logits against the
embedding's own rows.  A test's architecture."""
import numpy as np

from harness import spec, weights as W


def logits_at(cfg, seed, seqs, score_from, n_score, pad_to, lower=None):
    import jax
    import jax.numpy as jnp

    dense = spec.load_reference("llama_dense")   # _block, _rms, _prep
    shapes = spec.load_shapes("tied_dense")
    m, leaves = shapes.dims(cfg), shapes.leaves(cfg)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    dtype = jnp.dtype(cfg.get("dtype", "bfloat16"))
    toks = np.zeros((len(seqs), pad_to), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    rows = np.stack([np.minimum(np.arange(n_score) + f, pad_to - 1)
                     for f in score_from]).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        top = dense._prep(W.make_top(leaves, seed, dtype), lower)
        assert sorted(top) == ["embed", "norm_f"]
        x = top["embed"][jnp.asarray(toks)]
        for i in range(m["L"]):
            w = dense._prep(W.make_layer(leaves, seed, i, dtype), lower)
            x = jax.lax.map(lambda xs: dense._block(xs, w, m, eps, theta), x)
        hs = jnp.take_along_axis(x, jnp.asarray(rows)[:, :, None], axis=1)
        return np.asarray(dense._rms(hs, top["norm_f"], eps)
                          @ top["embed"].T)
