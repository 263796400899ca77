"""Logit-processor chain shared by every serving sampling site.

``LLMEngine`` used to own a private ``_sample_tokens`` (argmax vs plain
temperature categorical).  Speculative decoding needs the SAME
distribution math in two places — on device inside the decode/prefill
programs, and on host when the verify step turns draft logits into
accept/reject decisions — so the chain lives here, written against an
``xp`` array namespace that is ``jax.numpy`` inside compiled programs
and ``numpy`` on the host.  One implementation, byte-identical greedy
behaviour on both paths.

The chain order mirrors ``LlamaForCausalLM.generate``:

    repetition penalty (CTRL rule) -> [greedy rows: argmax here]
    -> temperature -> top-k -> top-p -> categorical

Per-sequence parameters ride in a ``samp`` dict of batch-wide arrays
(``make_samp``) so one compiled program serves any mix of greedy and
sampled requests:

    temps   [B] f32   (<= 0 -> greedy argmax, generate()-compatible)
    top_k   [B] i32   (0 -> off)
    top_p   [B] f32   (1.0 -> off; top token always kept)
    penalty [B] f32   (1.0 -> off)
    seen    [B,V] bool (prompt + generated token mask for the penalty)
    keys    [B,2] u32  (per-sequence PRNG keys; unused by greedy rows)
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "LogitProcessor", "RepetitionPenaltyProcessor", "TemperatureProcessor",
    "TopKProcessor", "TopPProcessor", "DEFAULT_CHAIN", "advance_keys",
    "greedy_tokens", "make_samp", "samp_structs", "sample_tokens",
    "target_dist",
]

_NEG_INF = float("-inf")


def _softmax(lg, xp):
    m = xp.max(lg, axis=-1, keepdims=True)
    e = xp.exp(lg - m)
    return e / xp.sum(e, axis=-1, keepdims=True)


class LogitProcessor:
    """One stage of the chain: ``(lg [B,V] f32, samp, xp) -> lg``.

    ``greedy_visible`` stages apply before the greedy/sampled split —
    greedy rows argmax their output; the rest only shape the sampled
    distribution (temperature scaling and truncation never change an
    argmax, matching generate()'s temperature==0 branch).
    """

    greedy_visible = False

    def __call__(self, lg, samp, xp):  # pragma: no cover - interface
        raise NotImplementedError


class RepetitionPenaltyProcessor(LogitProcessor):
    """CTRL rule: logits of seen tokens divide by the penalty when
    positive, multiply when negative.  penalty == 1.0 is the identity."""

    greedy_visible = True

    def __call__(self, lg, samp, xp):
        pen = samp["penalty"][:, None]
        pl = xp.where(lg > 0, lg / pen, lg * pen)
        return xp.where(samp["seen"] & (pen != 1.0), pl, lg)


class TemperatureProcessor(LogitProcessor):
    def __call__(self, lg, samp, xp):
        return lg / xp.maximum(samp["temps"], 1e-6)[:, None]


class TopKProcessor(LogitProcessor):
    """Keep each row's top_k logits (ties at the k-th value survive,
    generate()-compatible); top_k == 0 disables the stage for the row."""

    def __call__(self, lg, samp, xp):
        k = samp["top_k"]
        V = lg.shape[-1]
        srt = -xp.sort(-lg, axis=-1)                       # descending
        idx = xp.clip(k - 1, 0, V - 1).astype(xp.int32)
        kth = xp.take_along_axis(srt, idx[:, None], axis=-1)
        return xp.where((k > 0)[:, None] & (lg < kth), _NEG_INF, lg)


class TopPProcessor(LogitProcessor):
    """Nucleus sampling: smallest prefix of the sorted distribution with
    mass >= top_p (the top token is always kept); top_p >= 1.0 keeps
    every token, disabling the stage for the row."""

    def __call__(self, lg, samp, xp):
        p = samp["top_p"]
        order = xp.argsort(-lg, axis=-1, kind="stable") \
            if xp is np else xp.argsort(-lg, axis=-1)
        srt = xp.take_along_axis(lg, order, axis=-1)
        sp = _softmax(srt, xp)
        cum = xp.cumsum(sp, axis=-1)
        keep_sorted = cum - sp <= p[:, None]               # top always kept
        inv = xp.argsort(order, axis=-1, kind="stable") \
            if xp is np else xp.argsort(order, axis=-1)
        keep = xp.take_along_axis(keep_sorted, inv, axis=-1)
        return xp.where(keep, lg, _NEG_INF)


DEFAULT_CHAIN = (RepetitionPenaltyProcessor(), TemperatureProcessor(),
                 TopKProcessor(), TopPProcessor())


def make_samp(B: int, V: int) -> dict:
    """Host-side samp arrays at their 'off' defaults (greedy, no
    penalty/truncation) — the engine mutates rows in place per slot."""
    return {
        "temps": np.zeros((B,), np.float32),
        "top_k": np.zeros((B,), np.int32),
        "top_p": np.ones((B,), np.float32),
        "penalty": np.ones((B,), np.float32),
        "seen": np.zeros((B, V), bool),
        "keys": np.zeros((B, 2), np.uint32),
    }


def samp_structs(B: int, V: int) -> dict:
    """ShapeDtypeStruct mirror of ``make_samp`` for program_specs."""
    sds = jax.ShapeDtypeStruct
    return {
        "temps": sds((B,), jnp.float32),
        "top_k": sds((B,), jnp.int32),
        "top_p": sds((B,), jnp.float32),
        "penalty": sds((B,), jnp.float32),
        "seen": sds((B, V), jnp.bool_),
        "keys": sds((B, 2), jnp.uint32),
    }


def advance_keys(base_keys, offsets):
    """Scan-carried sampler keys for the device-resident decode window.

    The per-step host path derives each row's key as
    ``fold_in(PRNGKey(seed), len(generated))`` immediately before launch;
    inside a multi-step window the host is absent, so the loop carries
    each row's base key (``PRNGKey(seed)``, [B,2] u32) plus a generated-
    token counter and re-derives ``fold_in(base, counter)`` per iteration
    — the identical threefry derivation, so any K-window slicing of the
    decode stream samples from byte-identical keys.
    """
    return jax.vmap(jax.random.fold_in)(base_keys, offsets)


def greedy_tokens(logits, samp, chain=DEFAULT_CHAIN):
    """What every row's token is unless the row is sampled: the argmax
    after the greedy-visible stages, and the logits those stages left
    (``sample_tokens``' first half; a step program whose sampled tail
    is compiled apart ends here)."""
    lg = logits.astype(jnp.float32)
    for proc in chain:
        if proc.greedy_visible:
            lg = proc(lg, samp, jnp)
    return lg, jnp.argmax(lg, -1).astype(jnp.int32)


def sample_tokens(logits, samp, chain=DEFAULT_CHAIN):
    """Device-side per-sequence sampling over [B, V] logits.

    Greedy rows (temps <= 0) argmax after the greedy-visible stages —
    byte-compatible with generate()'s greedy branch — while sampled rows
    run the full chain into a per-row categorical draw.  The sampled
    tail (sorts and gathers over the whole [B, V]) sits in one branch of
    a ``lax.cond`` on "some row of this launch is sampled": a scalar
    predicate is a real conditional on the device, so an all-greedy
    launch executes the argmax and nothing after it.
    """
    lg, greedy_tok = greedy_tokens(logits, samp, chain)

    def sampled_tail(lg):
        for proc in chain:
            if not proc.greedy_visible:
                lg = proc(lg, samp, jnp)
        sampled = jax.vmap(jax.random.categorical)(
            samp["keys"], lg).astype(jnp.int32)
        return jnp.where(samp["temps"] <= 0.0, greedy_tok, sampled)

    return lax.cond(jnp.any(samp["temps"] > 0.0), sampled_tail,
                    lambda _: greedy_tok, lg)


def target_dist(logits_row, *, temperature=0.0, top_k=0, top_p=1.0,
                penalty=1.0, seen=None, chain=DEFAULT_CHAIN):
    """Host-side target distribution for ONE position: the probabilities
    the device sampler would draw from (one-hot argmax for greedy rows).
    The verify step's rejection sampling is exact only because this runs
    the very same chain the compiled programs do.
    """
    lg = np.asarray(logits_row, np.float32)[None]
    V = lg.shape[-1]
    samp = {
        "temps": np.asarray([temperature], np.float32),
        "top_k": np.asarray([top_k], np.int32),
        "top_p": np.asarray([top_p], np.float32),
        "penalty": np.asarray([penalty], np.float32),
        "seen": (np.zeros((1, V), bool) if seen is None
                 else np.asarray(seen, bool).reshape(1, V)),
    }
    with np.errstate(invalid="ignore", over="ignore"):
        for proc in chain:
            if proc.greedy_visible:
                lg = proc(lg, samp, np)
        if temperature <= 0.0:
            out = np.zeros((V,), np.float32)
            out[int(np.argmax(lg[0]))] = 1.0
            return out
        for proc in chain:
            if not proc.greedy_visible:
                lg = proc(lg, samp, np)
        return _softmax(lg, np)[0]
