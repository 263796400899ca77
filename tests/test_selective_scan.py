"""The ragged selective scan (``ops/pallas/selective_scan.py``): the XLA
form against a plain loop a sequence, and the kernel (interpret mode)
against the XLA form, over launches that mix a chunk, decode rows, rows
that begin their sequence, rows of no tokens and padding."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import selective_scan as ss


def _launch(rng, lens, starts, Tq, di=256, n=8, layers=3, slots=6):
    """Inputs of one launch: rows of ``lens`` tokens (0: a row of no
    tokens, which names the last slot), padded to ``Tq``."""
    R = len(lens)
    cu = np.zeros((R + 1,), np.int32)
    cu[1:] = np.cumsum(lens)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    real = rng.permutation(slots - 1)[:R]
    args = dict(
        u=f(Tq, di), delta=jax.nn.softplus(f(Tq, di) - 2.0),
        A=-jnp.exp(f(n, di) * 0.3), Bm=f(Tq, n), Cm=f(Tq, n), D=f(di),
        state=f(layers, slots, n, di), layer=1,
        slots=jnp.asarray(np.where(np.asarray(lens) > 0, real, slots - 1),
                          jnp.int32),
        cu=jnp.asarray(cu), start=jnp.asarray(starts))
    return args


def _by_sequence(a):
    """A Python loop a row, a token at a time, in numpy float64."""
    u, dl, A, Bm, Cm, D = (np.asarray(a[k], np.float64) for k in
                           ("u", "delta", "A", "Bm", "Cm", "D"))
    state = np.asarray(a["state"], np.float64).copy()
    y = np.zeros_like(u)
    cu = np.asarray(a["cu"])
    for r, slot in enumerate(np.asarray(a["slots"])):
        if cu[r + 1] == cu[r]:
            continue
        s = np.zeros_like(state[0, 0]) if bool(a["start"][r]) \
            else state[a["layer"], slot].copy()
        for t in range(cu[r], cu[r + 1]):
            s = np.exp(dl[t][None] * A) * s \
                + (dl[t] * u[t])[None] * Bm[t][:, None]
            y[t] = (s * Cm[t][:, None]).sum(0) + D * u[t]
        state[a["layer"], slot] = s
    return y, state


CASES = {
    "chunk_and_decode_rows": ([19, 1, 1, 0, 1], [True, False, False, True,
                                                 False], 32),
    "all_decode": ([1, 1, 1, 1], [False] * 4, 8),
    "continued_chunk": ([0, 13, 0, 5], [True, False, True, True], 24),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_xla_form_against_a_loop_a_sequence(case):
    lens, starts, Tq = CASES[case]
    a = _launch(np.random.default_rng(3), lens, starts, Tq)
    y, state = jax.jit(ss.selective_scan_reference,
                       static_argnames=())(**a)
    y0, state0 = _by_sequence(a)
    np.testing.assert_allclose(np.asarray(y), y0, rtol=2e-5, atol=2e-5)
    live = sorted(set(range(a["state"].shape[1])) - {a["state"].shape[1] - 1})
    np.testing.assert_allclose(np.asarray(state)[:, live], state0[:, live],
                               rtol=2e-5, atol=2e-5)
    # the other layers' states are not touched
    np.testing.assert_array_equal(np.asarray(state)[[0, 2]],
                                  np.asarray(a["state"])[[0, 2]])


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_against_xla_form(case):
    lens, starts, Tq = CASES[case]
    a = _launch(np.random.default_rng(5), lens, starts, Tq)
    old, pa.INTERPRET = pa.INTERPRET, True
    try:
        y, state = ss.selective_scan(**a, use_kernel=True)
    finally:
        pa.INTERPRET = old
    y0, state0 = ss.selective_scan_reference(**a)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), rtol=1e-5,
                               atol=1e-5)
    live = list(range(a["state"].shape[1] - 1))
    np.testing.assert_allclose(np.asarray(state)[:, live],
                               np.asarray(state0)[:, live], rtol=1e-5,
                               atol=1e-5)
    # padding tokens read zero
    assert not np.asarray(y)[sum(lens):].any()


def test_a_state_neither_dies_nor_blows_up_at_the_published_scales():
    """At the published initialisation (``models/phi4flash.published``)
    a unit-scale input keeps a state of order one over thousands of
    rows."""
    from paddle_tpu.models.phi4flash import published
    rng = np.random.default_rng(0)
    di, n, T = 128, 16, 6752
    drawn = lambda *s: jnp.asarray(1.0 + 0.1 * rng.standard_normal(s),
                                   jnp.float32)
    A = -jnp.exp(published("A_log", drawn(n, di)))
    b_dt = published("b_dt", drawn(di))
    delta = jnp.broadcast_to(jax.nn.softplus(b_dt), (T, di))
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    state = jnp.zeros((1, 2, n, di), jnp.float32)
    y, state = jax.jit(ss.selective_scan_reference)(
        f(T, di), delta, A, f(T, n), f(T, n), jnp.ones((di,)), state, 0,
        jnp.asarray([0], jnp.int32), jnp.asarray([0, T], jnp.int32),
        jnp.asarray([True]))
    s = np.abs(np.asarray(state)[0, 0])
    assert np.isfinite(s).all() and 1e-3 < np.median(s) < 10.0
    assert np.abs(np.asarray(y)).max() < 100.0
