"""``sample_tokens`` runs its sampled chain in a branch, and only there.

The branch is taken on the device when a row of the launch is sampled;
an all-greedy launch returns the argmax.  Held here: the function equals
a frozen copy of the one it replaced, token for token, whichever branch
a launch takes, and a greedy row does not see what its neighbours do.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.inference.sampling import (DEFAULT_CHAIN, make_samp,
                                           sample_tokens)

B = 8


def _frozen_sample_tokens(logits, samp, chain=DEFAULT_CHAIN):
    """The function as it stood before the branch (PR 28): every stage
    over every row, then a select."""
    lg = logits.astype(jnp.float32)
    for proc in chain:
        if proc.greedy_visible:
            lg = proc(lg, samp, jnp)
    greedy_tok = jnp.argmax(lg, -1).astype(jnp.int32)
    for proc in chain:
        if not proc.greedy_visible:
            lg = proc(lg, samp, jnp)

    def one(key, row):
        return jax.random.categorical(key, row)

    sampled = jax.vmap(one)(samp["keys"], lg).astype(jnp.int32)
    return jnp.where(samp["temps"] <= 0.0, greedy_tok, sampled)


def _keys(samp, rows, seed):
    for s in rows:
        samp["keys"][s] = np.asarray(
            jax.random.fold_in(jax.random.PRNGKey(seed), s), np.uint32)


def _samp(kind, V, rng):
    samp = make_samp(B, V)
    if kind == "greedy":
        pass
    elif kind == "greedy_penalty":
        samp["penalty"][:] = rng.choice([1.0, 1.3, 0.8], B)
        samp["seen"][:] = rng.random((B, V)) < 0.3
    elif kind == "one_sampled":
        samp["temps"][3] = 0.8
        samp["penalty"][1] = 1.2
        samp["seen"][1] = rng.random(V) < 0.3
        _keys(samp, [3], 11)
    elif kind == "all_sampled":
        samp["temps"][:] = rng.uniform(0.5, 1.5, B)
        samp["top_k"][:] = rng.choice([0, 1, 5, 40], B)
        samp["top_p"][:] = rng.choice([1.0, 0.9, 0.5], B)
        samp["penalty"][:] = rng.choice([1.0, 1.1], B)
        samp["seen"][:] = rng.random((B, V)) < 0.1
        _keys(samp, range(B), 5)
    else:
        raise AssertionError(kind)
    return samp


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("V", [1000, 32768])
@pytest.mark.parametrize("kind", ["greedy", "greedy_penalty",
                                  "one_sampled", "all_sampled"])
def test_equals_the_function_it_replaced(kind, V, dtype):
    rng = np.random.default_rng(len(kind) * 100003 + V)
    logits = jnp.asarray(rng.normal(0.0, 3.0, (B, V)), jnp.float32) \
        .astype(dtype)
    samp = _samp(kind, V, rng)
    got = np.asarray(jax.jit(sample_tokens)(logits, samp))
    want = np.asarray(jax.jit(_frozen_sample_tokens)(logits, samp))
    assert got.dtype == np.int32 and got.shape == (B,)
    np.testing.assert_array_equal(got, want)
    if kind.startswith("greedy"):
        # and the argmax is what the rows' processed logits say
        lg = np.asarray(logits.astype(jnp.float32))
        pen = samp["penalty"][:, None]
        lg = np.where(samp["seen"] & (pen != 1.0),
                      np.where(lg > 0, lg / pen, lg * pen), lg)
        np.testing.assert_array_equal(got, lg.argmax(-1))


def test_a_greedy_row_does_not_see_its_neighbours():
    V = 4096
    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(0.0, 3.0, (B, V)), jnp.bfloat16)
    alone = _samp("greedy_penalty", V, np.random.default_rng(4))
    mixed = {k: v.copy() for k, v in alone.items()}
    mixed["temps"][5] = 1.0
    mixed["top_k"][5] = 20
    mixed["top_p"][5] = 0.8
    _keys(mixed, [5], 9)
    fn = jax.jit(sample_tokens)
    a, m = np.asarray(fn(logits, alone)), np.asarray(fn(logits, mixed))
    greedy = np.arange(B) != 5
    np.testing.assert_array_equal(a[greedy], m[greedy])


def _primitives(jaxpr):
    """Names of every primitive in a jaxpr, sub-jaxprs included."""
    out = []
    for e in jaxpr.eqns:
        out.append(e.primitive.name)
        for sub in jax.core.jaxprs_in_params(e.params):
            out += _primitives(sub)
    return out


def test_the_sampled_chain_is_behind_one_conditional():
    """One ``cond``, every sort inside its sampled branch, and a greedy
    branch that does no work: what an all-greedy launch executes is the
    argmax outside."""
    V = 1000
    jaxpr = jax.make_jaxpr(sample_tokens)(
        jnp.zeros((B, V), jnp.bfloat16), make_samp(B, V)).jaxpr
    top = [e.primitive.name for e in jaxpr.eqns]
    assert top.count("cond") == 1 and _primitives(jaxpr).count("cond") == 1
    greedy, sampled = jaxpr.eqns[top.index("cond")].params["branches"]
    assert not greedy.jaxpr.eqns
    n_sorts = _primitives(jaxpr).count("sort")
    assert n_sorts and _primitives(sampled.jaxpr).count("sort") == n_sorts
