"""(g) The dense decoder's programs compute what the parent's computed.
The frozen side is the parent's programs: ``_parent_ragged_fn`` (PR 27's
hand-written ``LLMEngine._make_ragged_fn``, ``self`` spelled ``eng``,
comments dropped), PR 29's hand-written block with either page type's
commit and attend (``_frozen_block``), the int8 step round it
(``_frozen_ragged_fn``), the window's loop round it
(``_frozen_window_fn``) and the copy-on-write program.  The frozen side
calls nothing of ``layer_stack``: ``_scan_layers`` below is PR 30's
``scan_layers``, which sliced a layer's pools out, handed them to the
block and wrote them back.

Until PR 31 the live programs were held to these jaxpr for jaxpr.  PR 31
changes the dense jaxprs on purpose (``layer_stack``: the pools of all
layers ride whole through the scan, ``kv_write`` scatters rows at
(layer, page, head, slot) and the kernel reads the pools at a layer
index), so the assertion is now equality of RESULTS on seeded inputs:
sampled tokens, finiteness flags, logits where returned and every pool,
bit for bit, after the step (the same values land in the same pages and
no other page is touched).  The copy-on-write program and the two
frozen spellings of the float step are still compared as jaxprs.

PR 34 gives the live step two more arguments, ``prev`` and ``src`` (a
token the host does not have yet is taken on the device from the
sampled tokens of the launch in front).  The frozen programs know
neither: the live program is handed part of the same tokens through
``prev`` (``_with_prev``) and must give what the frozen one gives on
the tokens spelled out.

PR 40 holds the engine's stacked ``wq``, ``wk`` and ``wv`` as
[L, heads, d, in] and contracts them on the weight's last axis: the same
sum, not at every shape the same bits as ``h @ w``.  Both sides are handed
``eng.params`` and take ``mm`` from ``eng._weight_ops()``, so the frozen
q/k/v products are respelled with the same ``dot_general`` over the same
leaves, and everything else stays pinned bit for bit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from paddle_tpu.inference import LLMEngine, serving
from paddle_tpu.inference.sampling import advance_keys, sample_tokens
from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                     _rms_weight, _rope_positions)
from paddle_tpu.ops.pallas import paged_attention as _pa


def _scan_layers(body, x, layers, pools):
    """PR 30's ``layer_stack.scan_layers``: the stacked pools in the
    carry, a layer's slice of each taken out for ``body(x, (p, *pools))
    -> (x, pools)`` and written back."""
    n = jax.tree_util.tree_leaves(layers)[0].shape[0]

    def turn(carry, inp):
        x, pools = carry
        p, l = inp
        x, new = body(x, (p,) + tuple(
            lax.dynamic_index_in_dim(c, l, keepdims=False) for c in pools))
        return (x, tuple(lax.dynamic_update_index_in_dim(c, v, l, 0)
                         for c, v in zip(pools, new))), None

    (x, pools), _ = lax.scan(turn, (x, tuple(pools)),
                             (layers, jnp.arange(n, dtype=jnp.int32)))
    return x, pools


def _parent_ragged_fn(eng, Tq):
    nh, kvh, d = eng._nh, eng._kvh, eng._hd
    bs = eng.block_size
    B = eng.max_num_seqs
    with_logits = eng._with_logits
    eps = eng.config.rms_norm_eps
    theta = eng.config.rope_theta
    if eng.kv_dtype == "int8":
        return eng._make_ragged_fn_q8(Tq)
    tp = eng.tp
    nh, kvh = nh // tp, kvh // tp
    shard_head = eng._shard_head
    mm, embed, head_logits = eng._weight_ops()
    use_pallas = eng.attention_path.startswith("pallas")

    def run(params, kc, vc, toks, cu, kvl, bt, lidx, samp):
        seg, rel = _pa.ragged_segments(cu, kvl, Tq)
        with jax.named_scope("embed"):
            x = embed(params, toks)                       # [Tq, H]

        def body(x, inp):
            p, kcl, vcl = inp
            with jax.named_scope("norm"):
                h = _rms_weight(x, p["ln1"], eps)
            with jax.named_scope("qkv"):
                q = mm(h, p, "wq").reshape(Tq, nh, d)
                k = mm(h, p, "wk").reshape(Tq, kvh, d)
                v = mm(h, p, "wv").reshape(Tq, kvh, d)
            with jax.named_scope("rope"):
                q = _rope_positions(q, rel, theta)
                k = _rope_positions(k, rel, theta)
            with jax.named_scope("kv_write"):
                blk = bt[seg, rel // bs]                  # [Tq]
                slot = rel % bs
                kcl = kcl.at[blk, :, slot, :].set(k.astype(kcl.dtype))
                vcl = vcl.at[blk, :, slot, :].set(v.astype(vcl.dtype))
            with jax.named_scope("attn"):
                if use_pallas:
                    att = _pa.ragged_paged_attention_packed(
                        q, kcl, vcl, bt, cu, kvl)
                else:
                    att = _pa.ragged_paged_reference_segrel(
                        q, kcl, vcl, bt, seg, rel)
                if tp > 1:
                    att = lax.all_gather(att, "tp", axis=1,
                                         tiled=True)
            with jax.named_scope("o_proj"):
                x = x + mm(att.reshape(Tq, tp * nh * d), p, "wo")
            with jax.named_scope("norm"):
                h2 = _rms_weight(x, p["ln2"], eps)
            with jax.named_scope("mlp"):
                a = jax.nn.silu(mm(h2, p, "gate").astype(jnp.float32)
                                ).astype(h2.dtype) * mm(h2, p, "up")
                x = x + mm(a, p, "down")
            return x, (kcl, vcl)

        with jax.named_scope("layers"):
            x, (kc, vc) = _scan_layers(body, x, params["layers"],
                                       (kc, vc))
        with jax.named_scope("norm"):
            h = _rms_weight(x, params["norm_f"], eps)
        with jax.named_scope("head"):
            hsel = h[lidx]                                # [Lq, H]
            logits = head_logits(params, hsel)            # [Lq, V]
            if shard_head:
                logits = lax.all_gather(logits, "tp", axis=1,
                                        tiled=True)
        with jax.named_scope("sample"):
            sampled = sample_tokens(logits, samp)
            fin = jnp.all(jnp.isfinite(logits), axis=-1)  # [Lq]
        if with_logits:
            return sampled, fin, logits, kc, vc
        return sampled, fin, kc, vc

    return eng._wrap_tp(run, 6), (1, 2)


def _frozen_block(eng, Tq, seg, rel, bt, cu, kvl, fresh):
    """PR 29's decoder block as ``_scan_layers`` takes it: float pages
    commit with two scatters, int8 pages quantize at commit (``fresh``
    None: the caller reset the scales already)."""
    nh, kvh, d = eng._nh // eng.tp, eng._kvh // eng.tp, eng._hd
    bs, tp = eng.block_size, eng.tp
    eps, theta = eng.config.rms_norm_eps, eng.config.rope_theta
    mm, _, _ = eng._weight_ops()
    use_pallas = eng.attention_path.startswith("pallas")
    q8 = eng.kv_dtype == "int8"

    def body(x, inp):
        p, pools = inp[0], inp[1:]
        with jax.named_scope("norm"):
            h = _rms_weight(x, p["ln1"], eps)
        with jax.named_scope("qkv"):
            q = mm(h, p, "wq").reshape(Tq, nh, d)
            k = mm(h, p, "wk").reshape(Tq, kvh, d)
            v = mm(h, p, "wv").reshape(Tq, kvh, d)
        with jax.named_scope("rope"):
            q = _rope_positions(q, rel, theta)
            k = _rope_positions(k, rel, theta)
        with jax.named_scope("kv_write"):
            blk = bt[seg, rel // bs]
            slot = rel % bs
            if q8:
                kcl, vcl, ksl, vsl = pools
                kf = k.astype(jnp.float32)
                vf = v.astype(jnp.float32)
                if fresh is not None:
                    ksl = jnp.where(fresh[:, None], 0.0, ksl)
                    vsl = jnp.where(fresh[:, None], 0.0, vsl)
                ks_old = ksl[blk]
                vs_old = vsl[blk]
                ksl = ksl.at[blk].max(jnp.max(jnp.abs(kf), axis=-1)
                                      / 127.0)
                vsl = vsl.at[blk].max(jnp.max(jnp.abs(vf), axis=-1)
                                      / 127.0)
                ks_new = ksl[blk]
                vs_new = vsl[blk]
                rk = jnp.where(ks_new > 0.0,
                               ks_old / jnp.maximum(ks_new, 1e-30), 0.0)
                rv = jnp.where(vs_new > 0.0,
                               vs_old / jnp.maximum(vs_new, 1e-30), 0.0)
                kp = jnp.round(kcl[blk].astype(jnp.float32)
                               * rk[:, :, None, None])
                vp = jnp.round(vcl[blk].astype(jnp.float32)
                               * rv[:, :, None, None])
                kcl = kcl.at[blk].set(
                    jnp.clip(kp, -127, 127).astype(jnp.int8))
                vcl = vcl.at[blk].set(
                    jnp.clip(vp, -127, 127).astype(jnp.int8))
                kq = jnp.round(kf / jnp.maximum(ks_new,
                                                1e-30)[:, :, None])
                vq = jnp.round(vf / jnp.maximum(vs_new,
                                                1e-30)[:, :, None])
                kcl = kcl.at[blk, :, slot, :].set(
                    jnp.clip(kq, -127, 127).astype(jnp.int8))
                vcl = vcl.at[blk, :, slot, :].set(
                    jnp.clip(vq, -127, 127).astype(jnp.int8))
                pools = (kcl, vcl, ksl, vsl)
            else:
                kcl, vcl = pools
                kcl = kcl.at[blk, :, slot, :].set(k.astype(kcl.dtype))
                vcl = vcl.at[blk, :, slot, :].set(v.astype(vcl.dtype))
                pools = (kcl, vcl)
        with jax.named_scope("attn"):
            if q8 and use_pallas:
                att = _pa.ragged_paged_attention_quant_packed(
                    q, *pools, bt, cu, kvl)
            elif q8:
                att = _pa.ragged_paged_reference_quant_segrel(
                    q, *pools, bt, seg, rel)
            elif use_pallas:
                att = _pa.ragged_paged_attention_packed(
                    q, *pools, bt, cu, kvl)
            else:
                att = _pa.ragged_paged_reference_segrel(
                    q, *pools, bt, seg, rel)
            if q8:
                att = att.astype(x.dtype)
            if tp > 1:
                att = lax.all_gather(att, "tp", axis=1, tiled=True)
        with jax.named_scope("o_proj"):
            x = x + mm(att.reshape(Tq, tp * nh * d), p, "wo")
        with jax.named_scope("norm"):
            h2 = _rms_weight(x, p["ln2"], eps)
        with jax.named_scope("mlp"):
            a = jax.nn.silu(mm(h2, p, "gate").astype(jnp.float32)
                            ).astype(h2.dtype) * mm(h2, p, "up")
            x = x + mm(a, p, "down")
        return x, pools

    return body


def _frozen_logits(eng, params, toks, pools, body, lidx):
    """Embed, the scanned block, norm and head round ``body``."""
    _, embed, head_logits = eng._weight_ops()
    with jax.named_scope("embed"):
        x = embed(params, toks)
    with jax.named_scope("layers"):
        x, pools = _scan_layers(body, x, params["layers"], pools)
    with jax.named_scope("norm"):
        h = _rms_weight(x, params["norm_f"], eng.config.rms_norm_eps)
    with jax.named_scope("head"):
        if lidx is not None:
            h = h[lidx]
        logits = head_logits(params, h)
        if eng._shard_head:
            logits = lax.all_gather(logits, "tp", axis=1, tiled=True)
    return logits, pools


def _frozen_ragged_fn(eng, Tq):
    """PR 29's step over either page type (``_make_ragged_fn_q8`` over
    int8 pages)."""
    q8 = eng.kv_dtype == "int8"
    n = 4 if q8 else 2
    with_logits = eng._with_logits

    def run(params, *rest):
        pools, host = rest[:n], rest[n:]
        fresh = host[0] if q8 else None
        toks, cu, kvl, bt, lidx, samp = host[-6:]
        seg, rel = _pa.ragged_segments(cu, kvl, Tq)
        body = _frozen_block(eng, Tq, seg, rel, bt, cu, kvl, fresh)
        logits, pools = _frozen_logits(eng, params, toks, pools, body,
                                       lidx)
        with jax.named_scope("sample"):
            sampled = sample_tokens(logits, samp)
            fin = jnp.all(jnp.isfinite(logits), axis=-1)
        if with_logits:
            return (sampled, fin, logits) + pools
        return (sampled, fin) + pools

    return eng._wrap_tp(run, 6 + q8), tuple(range(1, 1 + n))


def _frozen_window_fn(eng):
    """PR 29's ``_make_window_fn`` / ``_make_window_fn_q8``: the loop
    round the block at Tq = B, the int8 scales reset once before it."""
    B, K = eng.max_num_seqs, eng.decode_window
    q8 = eng.kv_dtype == "int8"
    n = 4 if q8 else 2

    def run(params, *rest):
        pools, host = rest[:n], rest[n:]
        (toks, kvl, active, gen, budgets, eos_ids, base_keys, bt,
         samp) = host[-9:]
        rows = jnp.arange(B, dtype=jnp.int32)
        if q8:
            kc, vc, ks, vs = pools
            ks = jnp.where(host[0][None, :, None], 0.0, ks)
            vs = jnp.where(host[0][None, :, None], 0.0, vs)
            pools = (kc, vc, ks, vs)

        def step(carry):
            i, tok, kvl, active, gen, seen = carry[:6]
            pools, (touts, fouts) = carry[6:6 + n], carry[6 + n:]
            seg, rel = _pa.decode_window_segments(active, kvl)
            cu_w, kvl_w = _pa.decode_window_rows(active, kvl)
            body = _frozen_block(eng, B, seg, rel, bt, cu_w, kvl_w, None)
            logits, pools = _frozen_logits(eng, params, tok, pools, body,
                                           None)
            with jax.named_scope("sample"):
                keys = advance_keys(base_keys, gen)
                sampled = sample_tokens(
                    logits, {"temps": samp["temps"],
                             "top_k": samp["top_k"],
                             "top_p": samp["top_p"],
                             "penalty": samp["penalty"],
                             "seen": seen, "keys": keys})
                fin = jnp.all(jnp.isfinite(logits), axis=-1)
            sampled = jnp.where(active, sampled, tok)
            touts = touts.at[i].set(sampled)
            fouts = fouts.at[i].set(fin | ~active)
            seen = seen.at[rows, sampled].set(seen[rows, sampled] | active)
            nxt = active & (sampled != eos_ids) & (gen + 1 < budgets)
            adv = active.astype(jnp.int32)
            return (i + 1, sampled, kvl + adv, nxt, gen + adv, seen,
                    *pools, touts, fouts)

        def cond(carry):
            return (carry[0] < K) & jnp.any(carry[3])

        carry = (jnp.int32(0), toks, kvl, active, gen, samp["seen"],
                 *pools, jnp.zeros((K, B), jnp.int32),
                 jnp.ones((K, B), jnp.bool_))
        carry = lax.while_loop(cond, step, carry)
        return carry[6 + n:] + carry[6:6 + n]

    return eng._wrap_tp(run, 9 + q8, 2), tuple(range(1, 1 + n))


def _parent_cow_fn(eng):
    def run(kc, vc, s, d):
        kc = kc.at[:, d].set(kc[:, s])
        vc = vc.at[:, d].set(vc[:, s])
        return kc, vc

    return run, (0, 1)


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(LlamaConfig.tiny(vocab=97, hidden=32, layers=3,
                                             heads=4, ffn=64, seq=64))


def _engine(model, **kw):
    return LLMEngine(model, max_num_seqs=4, block_size=8, max_model_len=64,
                     max_prefill_tokens=32, prefill_token_bucket=16, **kw)


def _filled(eng, structs, rng):
    """The leading arguments of a step program (parameters, pools, the
    fresh mask over int8 pages) with every pool filled from ``rng``:
    a page the step must not touch holds something to compare."""
    n = len(eng._pools())
    pools = []
    for s in structs[1:1 + n]:
        if s.dtype == jnp.int8:
            pools.append(rng.integers(-127, 128, s.shape, dtype=np.int8))
        elif len(s.shape) == 3:                       # scales: positive
            pools.append(rng.uniform(0.01, 0.05, s.shape).astype(s.dtype))
        else:
            pools.append(rng.standard_normal(s.shape).astype(s.dtype))
    head = (eng.params,) + tuple(pools)
    if eng.kv_dtype == "int8":
        head += (rng.random(structs[1 + n].shape) < 0.25,)
    return head


def _table(eng, rng):
    """[B + 1, nblk]: every row its own pages, the null row last."""
    B, nblk = eng.max_num_seqs, eng.nblk
    bt = np.zeros((B + 1, nblk), np.int32)
    bt[:B] = rng.permutation(np.arange(1, B * nblk + 1)).reshape(B, nblk)
    assert bt.max() < eng._kc.shape[1]
    return bt


def _samp(rng, rows, vocab):
    """Greedy rows beside sampled ones with a penalty and truncation."""
    return {"temps": np.where(np.arange(rows) % 2, 0.8, 0.0).astype(
                np.float32),
            "top_k": (np.arange(rows) % 3 * 5).astype(np.int32),
            "top_p": np.where(np.arange(rows) % 4 == 3, 0.9, 1.0).astype(
                np.float32),
            "penalty": np.where(np.arange(rows) % 2, 1.0, 1.3).astype(
                np.float32),
            "seen": rng.random((rows, vocab)) < 0.1,
            "keys": rng.integers(0, 2 ** 32, (rows, 2), dtype=np.uint32)}


def _step_args(eng, Tq, seed=0):
    """One seeded launch of the ragged step: in the 32-token bucket a
    resumed chunk, a decode row, a whole prompt and a verify row with
    tail padding; in a smaller one three decode rows, a row of no
    queries and padding."""
    rng = np.random.default_rng(seed)
    B, V = eng.max_num_seqs, eng.config.vocab_size
    structs = eng._ragged_arg_structs(Tq)[:-2]       # less prev, src
    qlen, kvl = ([13, 1, 10, 3], [20, 9, 10, 17]) if Tq >= 32 else \
        ([1, 1, 0, 1], [5, 16, 0, 30])
    cu = np.concatenate([[0], np.cumsum(qlen)]).astype(np.int32)
    toks = np.zeros((Tq,), np.int32)
    toks[:cu[-1]] = rng.integers(1, V, cu[-1])
    Lq = structs[-2].shape[0]
    lidx = np.zeros((Lq,), np.int32)
    lidx[:B] = np.maximum(cu[1:] - 1, 0)
    return _filled(eng, structs, rng) + (
        toks, cu, np.asarray(kvl, np.int32), _table(eng, rng), lidx,
        _samp(rng, Lq, V))


def _window_args(eng, seed=0):
    """One seeded launch of the decode window: a frozen row, a row whose
    budget ends inside the window, two that run it through."""
    rng = np.random.default_rng(seed)
    B, V = eng.max_num_seqs, eng.config.vocab_size
    i32 = np.int32
    return _filled(eng, eng._window_arg_structs(), rng) + (
        rng.integers(1, V, B).astype(i32), np.asarray([6, 17, 3, 40], i32),
        np.asarray([True, True, False, True]), np.asarray([2, 0, 5, 9], i32),
        np.asarray([12, 2, 8, 30], i32), np.full((B,), -1, i32),
        rng.integers(0, 2 ** 32, (B, 2), dtype=np.uint32), _table(eng, rng),
        _samp(rng, B, V))


def _with_prev(args, seed=1):
    """The live step's arguments for the launch ``args`` spells out: the
    same tokens, but some of them zeroed in ``toks`` and handed over in
    ``prev`` with ``src`` naming the row (as a launch dispatched ahead
    of the commit in front of it gets that launch's samples); the rows
    of ``prev`` nothing names hold other tokens."""
    rng = np.random.default_rng(seed)
    toks, lidx = args[-6], args[-2]
    Tq, Lq = toks.shape[0], lidx.shape[0]
    moved = rng.permutation(Tq)[:min(Lq, Tq) // 2 + 1]
    rows = rng.permutation(Lq)[:len(moved)]
    prev = rng.integers(1, 90, Lq).astype(np.int32)
    src = np.full((Tq,), -1, np.int32)
    staged = toks.copy()
    prev[rows], src[moved], staged[moved] = toks[moved], rows, 0
    assert np.any(staged != toks)
    return args[:-6] + (staged,) + args[-5:] + (prev, src)


def _as_frozen(out, n_pools, step):
    """The live program's outputs in the frozen program's layout.  Since
    PR 38 tokens and finiteness flags reach the host in one int32 vector
    (``serving._pack_results``): a step returns it beside ``sampled``
    (which it must repeat), the decode window returns it alone."""
    from paddle_tpu.inference.serving import _unpack_results
    front, pools = out[:-n_pools], out[-n_pools:]
    if step:
        sampled, packed, *logits = front
        toks, fin, counts = _unpack_results(np.asarray(packed),
                                            sampled.shape)
        np.testing.assert_array_equal(toks, np.asarray(sampled))
    else:
        (packed,), logits = front, []
        B = 4                                   # _engine's max_num_seqs
        toks, fin, counts = _unpack_results(
            np.asarray(packed), (packed.shape[0] // (2 * B), B))
    assert packed.dtype == np.int32 and toks.dtype == np.int32
    assert not counts.size          # a dense model counts nothing
    return (toks, fin, *logits, *pools)


def _same_results(new, old, args, live_args=None):
    """Both programs on the same launch (``live_args``: the live
    program's spelling of it, where it takes more than the frozen one):
    every output (tokens, finiteness, logits where returned, then each
    pool) bit for bit."""
    (new, donate), (old, old_donate) = new, old
    assert tuple(donate) == tuple(old_donate)
    got = _as_frozen(jax.jit(new)(*(live_args or args)), len(donate),
                     live_args is not None)
    want = jax.jit(old)(*args)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the step wrote something: a pool that came back as it went in
    # would pass the comparison above whatever the scatter did
    n = len(donate)
    assert all(np.any(np.asarray(g) != a)
               for g, a in zip(got[-n:], args[1:1 + n]))
    return tuple(donate)


_STEP_KW = [{}, {"drafter": "ngram", "spec_k": 2}, {"tp": 2}]
_STEP_IDS = ["plain", "with_logits", "tp2"]


@pytest.mark.parametrize("kw", _STEP_KW, ids=_STEP_IDS)
@pytest.mark.parametrize("Tq", [4, 32])
def test_dense_step_program_is_the_parents(model, kw, Tq):
    eng = _engine(model, **kw)
    args = _step_args(eng, Tq)
    assert _same_results(eng._make_ragged_fn(Tq), _parent_ragged_fn(eng, Tq),
                         args, _with_prev(args)) == (1, 2)


def _same_program(new, old, args):
    (new, donate), (old, old_donate) = new, old
    assert tuple(donate) == tuple(old_donate)
    assert str(jax.make_jaxpr(new)(*args)) == str(jax.make_jaxpr(old)(*args))
    return tuple(donate)


@pytest.mark.parametrize("kw", _STEP_KW, ids=_STEP_IDS)
@pytest.mark.parametrize("Tq", [4, 32])
def test_int8_page_step_program_is_the_parents(model, kw, Tq):
    eng = _engine(model, kv_dtype="int8", **kw)
    args = _step_args(eng, Tq)
    assert _same_results(eng._make_ragged_fn(Tq), _frozen_ragged_fn(eng, Tq),
                         args, _with_prev(args)) == (1, 2, 3, 4)


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_decode_window_program_is_the_parents(model, kv_dtype, tp):
    eng = _engine(model, kv_dtype=kv_dtype, tp=tp, decode_window=4)
    assert _same_results(eng._make_window_fn(), _frozen_window_fn(eng),
                         _window_args(eng)) \
        == ((1, 2, 3, 4) if kv_dtype == "int8" else (1, 2))


def test_programs_over_int8_weights_and_pages_are_the_parents(model):
    eng = _engine(model, kv_dtype="int8", weight_dtype="int8",
                  decode_window=4)
    specs = {s.name: s for s in eng.program_specs()}
    assert sorted(specs) == ["serving.cow_copy_q8_w8",
                             "serving.decode_window_q8_w8",
                             "serving.ragged_step_q8_w8"]
    step, win = (specs["serving.ragged_step_q8_w8"],
                 specs["serving.decode_window_q8_w8"])
    args = _step_args(eng, 16)
    _same_results((step.fn, step.donate_argnums),
                  _frozen_ragged_fn(eng, 16), args, _with_prev(args))
    _same_results((win.fn, win.donate_argnums), _frozen_window_fn(eng),
                  _window_args(eng))


def test_frozen_block_is_the_frozen_float_step(model):
    """The block the new cases freeze is, over float pages, the step PR 27
    froze above: one composition, held to the older literal text."""
    eng = _engine(model)
    _same_program(_frozen_ragged_fn(eng, 32), _parent_ragged_fn(eng, 32),
                  eng._ragged_arg_structs(32)[:-2])


def test_dense_programs_of_program_specs_are_the_parents(model):
    eng = _engine(model)
    specs = {s.name: s for s in eng.program_specs()}
    assert sorted(specs) == ["serving.cow_copy", "serving.ragged_step"]
    step, cow = specs["serving.ragged_step"], specs["serving.cow_copy"]
    args = _step_args(eng, 16)
    assert _same_results((step.fn, step.donate_argnums),
                         _parent_ragged_fn(eng, 16),
                         args, _with_prev(args)) == (1, 2)
    old_cow, old_donate = _parent_cow_fn(eng)
    assert str(jax.make_jaxpr(cow.fn)(*cow.args)) \
        == str(jax.make_jaxpr(old_cow)(*cow.args))
    assert tuple(cow.donate_argnums) == old_donate


def test_layer_kinds_of_the_dense_decoder(model):
    eng = _engine(model)
    assert eng._layer_kinds == [("gqa", "swiglu")] * 3
    assert not eng._latent and len(eng._pools()) == 2
    assert eng.kv_page_bytes() == 2 * 3 * 4 * 8 * 8 * 4   # K, V: L Hkv bs D f32
    q8 = _engine(model, kv_dtype="int8")
    assert len(q8._pools()) == 4
    assert q8.kv_page_bytes() == 2 * 3 * 4 * 8 * 8 + 2 * 3 * 4 * 4
