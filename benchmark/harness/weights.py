"""Weights from the seed, made by the benchmark: the program is handed
them, the reference makes them again by itself.  Every leaf is a function
of (seed, leaf index) alone, so that one jitted call can make them all
for the program and another can make one layer for the reference.

Which leaves there are is the architecture's to say: ``leaves`` below is
the list its shapes file gives (``shapes/<name>.py`` ``leaves(cfg)``),
``(name, layer or None, shape, kind)`` in index order.  Layers may hold
different leaves and a leaf may have any rank."""
from __future__ import annotations

import math


def seed_key(seed: int):
    """A key from any whole number, 2**31 and over included."""
    import jax
    seed = int(seed)
    k = jax.random.PRNGKey(seed % 2147483629)
    return jax.random.fold_in(k, seed // 2147483629)


def draw(key, index: int, shape: tuple, kind: str, dtype):
    """One leaf by its kind: ``norm`` a scale near 1; ``embedding`` at
    unit deviation; ``matrix`` normal with the Xavier deviation over its
    last two dimensions (``[experts, in, out]`` is a stack of matrices);
    ``zero`` nought (a bias that starts there).  Drawn and scaled in
    float32, then cast: elementwise, so the same bits in any program."""
    import jax
    import jax.numpy as jnp
    if kind == "zero":
        return jnp.zeros(shape, dtype)
    x = jax.random.normal(jax.random.fold_in(key, index), shape, jnp.float32)
    if kind == "norm":
        w = 1.0 + 0.1 * x
    elif kind == "embedding":
        w = x
    elif kind == "matrix":
        w = x * jnp.float32(math.sqrt(2.0 / (shape[-2] + shape[-1])))
    else:
        raise ValueError(f"no such kind of leaf: {kind!r}")
    return w.astype(dtype)


def _draw_some(leaves: list, seed: int, dtype, keep) -> list:
    """[(leaf, array)] of the leaves whose layer ``keep`` takes, drawn
    in one jitted call on the default device."""
    import jax
    want = [(i, leaf) for i, leaf in enumerate(leaves) if keep(leaf[1])]
    arrays = jax.jit(lambda key: [
        draw(key, i, shape, kind, dtype)
        for i, (_name, _layer, shape, kind) in want])(seed_key(seed))
    return [(leaf, a) for (_i, leaf), a in zip(want, arrays)]


def make_all(leaves: list, seed: int, dtype) -> dict:
    """Every leaf in one jitted call, in the type served:
    ``{"top": {name: array}, "layers": [{name: array}, ...]}``."""
    n_layers = 1 + max((leaf[1] for leaf in leaves if leaf[1] is not None),
                       default=-1)
    out = {"top": {}, "layers": [{} for _ in range(n_layers)]}
    for (name, layer, _shape, _kind), a in _draw_some(
            leaves, seed, dtype, lambda at: True):
        (out["top"] if layer is None else out["layers"][layer])[name] = a
    return out


def make_layer(leaves: list, seed: int, layer: int, dtype) -> dict:
    return {leaf[0]: a for leaf, a in _draw_some(
        leaves, seed, dtype, lambda at: at == layer)}


def make_top(leaves: list, seed: int, dtype) -> dict:
    return {leaf[0]: a for leaf, a in _draw_some(
        leaves, seed, dtype, lambda at: at is None)}
