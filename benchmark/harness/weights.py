"""Weights from the seed, made by the benchmark: the program is handed
them, the reference makes them again by itself.  Every leaf is a function
of (seed, leaf index) alone, so that one jitted call can make them all
for the program and another can make one layer for the reference."""
from __future__ import annotations

import math

LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "gate", "up", "down")
TOP_LEAVES = ("embed", "norm_f", "head")


def dims(cfg: dict) -> dict:
    h = int(cfg["hidden_size"])
    nh = int(cfg["num_attention_heads"])
    return {"H": h, "nh": nh, "kvh": int(cfg["num_key_value_heads"]),
            "d": h // nh, "F": int(cfg["intermediate_size"]),
            "V": int(cfg["vocab_size"]), "L": int(cfg["num_hidden_layers"])}


def leaf_shape(cfg: dict, name: str) -> tuple:
    m = dims(cfg)
    H, nh, kvh, d, F, V = m["H"], m["nh"], m["kvh"], m["d"], m["F"], m["V"]
    return {"ln1": (H,), "ln2": (H,), "norm_f": (H,),
            "wq": (H, nh * d), "wk": (H, kvh * d), "wv": (H, kvh * d),
            "wo": (nh * d, H), "gate": (H, F), "up": (H, F),
            "down": (F, H), "embed": (V, H), "head": (H, V)}[name]


def leaf_index(name: str, layer: int | None) -> int:
    if layer is None:
        return TOP_LEAVES.index(name)
    return len(TOP_LEAVES) + layer * len(LAYER_LEAVES) \
        + LAYER_LEAVES.index(name)


def seed_key(seed: int):
    """A key from any whole number, 2**31 and over included."""
    import jax
    seed = int(seed)
    k = jax.random.PRNGKey(seed % 2147483629)
    return jax.random.fold_in(k, seed // 2147483629)


def _leaf(key, cfg: dict, name: str, layer: int | None, dtype):
    """Norm scales near 1; matrices normal with the Xavier deviation;
    the embedding at unit deviation.  Drawn and scaled in float32, then
    cast: elementwise, so the same bits in any program."""
    import jax
    import jax.numpy as jnp
    shape = leaf_shape(cfg, name)
    k = jax.random.fold_in(key, leaf_index(name, layer))
    x = jax.random.normal(k, shape, jnp.float32)
    if len(shape) == 1:
        w = 1.0 + 0.1 * x
    elif name == "embed":
        w = x
    else:
        w = x * jnp.float32(math.sqrt(2.0 / (shape[0] + shape[1])))
    return w.astype(dtype)


def make_all(cfg: dict, seed: int, dtype):
    """Every leaf in one jitted call, on the default device, in the type
    served: ``{"top": {name: array}, "layers": [{name: array}, ...]}``."""
    import jax

    def build(key):
        top = {n: _leaf(key, cfg, n, None, dtype) for n in TOP_LEAVES}
        layers = [{n: _leaf(key, cfg, n, i, dtype) for n in LAYER_LEAVES}
                  for i in range(dims(cfg)["L"])]
        return {"top": top, "layers": layers}

    return jax.jit(build)(seed_key(seed))


def make_layer(cfg: dict, seed: int, layer: int, dtype):
    import jax
    return jax.jit(lambda key: {n: _leaf(key, cfg, n, layer, dtype)
                                for n in LAYER_LEAVES})(seed_key(seed))


def make_top(cfg: dict, seed: int, dtype):
    import jax
    return jax.jit(lambda key: {n: _leaf(key, cfg, n, None, dtype)
                                for n in TOP_LEAVES})(seed_key(seed))
