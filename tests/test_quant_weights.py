"""Quantized weight streaming (CPU, Pallas kernel in interpret mode):
pool round-trip error bounds, fused-kernel parity against the XLA
fake-quant oracle, the serving engine's greedy fidelity / program-kind
pins across tp and decode-window variants, the resident-byte
compression the ISSUE gates on, and the roofline cost-model ordering
the autotuner rails quote."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.pallas import quant_matmul as qm

VOCAB = 97
CFG = LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4, ffn=64,
                       seq=64)


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(CFG)


def _engine(model, **kw):
    kw.setdefault("max_num_seqs", 8)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_prefill_tokens", 256)
    kw.setdefault("prefill_token_bucket", 64)
    return LLMEngine(model, **kw)


def _audit_stream(n=16):
    """The 16-request ragged stream the audit tests pin budgets on."""
    rng = np.random.RandomState(7)
    shapes = [(4, 8), (9, 8), (13, 6)]
    return [(rng.randint(0, VOCAB, shapes[i % 3][0]).tolist(),
             shapes[i % 3][1]) for i in range(n)]


def _drive(eng, reqs, **req_kw):
    rids = [eng.add_request(p, max_new_tokens=mx, **req_kw)
            for p, mx in reqs]
    outs = eng.run()
    return [outs[r] for r in rids]


# ---------------------------------------------------------------------------
# pool round trip: quantize -> dequantize error bounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wdt", ["int8", "int4"])
def test_quantize_round_trip_error_bounds(wdt):
    """Symmetric round-to-nearest: every element lands within half a
    quantization step of its source (per-channel step for int8,
    per-128-row-group step for int4)."""
    rng = np.random.RandomState(0)
    w = rng.randn(256, 128).astype(np.float32)
    q, s = qm.quantize_weight(w, wdt)
    deq = np.asarray(qm.dequantize_weight(q, s, wdt))
    if wdt == "int8":
        assert q.dtype == jnp.int8 and q.shape == w.shape
        step = np.asarray(s)[None, :]
    else:
        assert q.shape == (128, 128)        # nibble-packed along K
        step = np.repeat(np.asarray(s), qm.GROUP, axis=0)[:256]
    assert np.max(np.abs(deq - w) / step) <= 0.5 + 1e-6


def test_unpack_int4_is_exact():
    rng = np.random.RandomState(1)
    vals = rng.randint(-8, 8, size=(64, 32)).astype(np.int32)
    lo, hi = vals[0::2], vals[1::2]
    packed = ((hi << 4) | (lo & 0xF)) & 0xFF
    packed = packed.astype(np.uint8).view(np.int8)
    out = np.asarray(qm.unpack_int4(jnp.asarray(packed)))
    np.testing.assert_array_equal(out, vals)


@pytest.mark.parametrize("wdt", ["int8", "int4"])
def test_embedding_gather_dequant_matches_dense(wdt):
    """dequantize_rows on gathered rows == the dense fake-quant table
    at those rows — the gather axis carries the scales."""
    rng = np.random.RandomState(2)
    table = rng.randn(53, 64).astype(np.float32)
    q, s = qm.quantize_embedding(table, wdt)
    toks = jnp.asarray([0, 7, 51, 7], jnp.int32)
    got = np.asarray(qm.dequantize_rows(
        jnp.take(q, toks, axis=0), jnp.take(s, toks, axis=0), wdt))
    step = np.asarray(s) / 1.0
    ref = np.asarray(table)[np.asarray(toks)]
    bound = step[np.asarray(toks)][:, None]
    assert np.max(np.abs(got - ref) / bound) <= 0.5 + 1e-6


# ---------------------------------------------------------------------------
# fused kernel vs the XLA fake-quant oracle (interpret mode on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wdt", ["int8", "int4"])
def test_pallas_matmul_matches_reference_oracle(wdt):
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(8, 256), jnp.float32)
    w = rng.randn(256, 384).astype(np.float32)
    q, s = qm.quantize_weight(w, wdt)
    ref = np.asarray(qm.reference_matmul(x, q, s, wdt))
    prev = qm.INTERPRET
    qm.INTERPRET = True
    try:
        assert qm.ineligible(256, 384, wdt) is None
        got = np.asarray(qm.matmul(x, q, s, weight_dtype=wdt))
    finally:
        qm.INTERPRET = prev
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_supports_rejects_unaligned_lanes():
    # N off the 128-lane grid routes callers to the XLA oracle
    assert "128 lanes" in qm.ineligible(256, 100, "int8")


# ---------------------------------------------------------------------------
# serving engine: fidelity, program pins, variants
# ---------------------------------------------------------------------------

def test_engine_rejects_unknown_weight_dtype(model):
    with pytest.raises(ValueError):
        _engine(model, weight_dtype="int2")


def test_greedy_majority_byte_identical_f32_vs_int8(model):
    """int8 weights perturb logits by <=0.5 quant steps per channel; on
    the 16-request audit stream the greedy argmax stream must stay
    byte-identical for a clear majority of requests — and the quantized
    engine must run the SAME single ragged program kind (no compile
    regression, names suffixed _w8)."""
    reqs = _audit_stream(16)
    e32 = _engine(model)
    o32 = _drive(e32, reqs)
    e8 = _engine(model, weight_dtype="int8")
    o8 = _drive(e8, reqs)
    same = sum(a.generated == b.generated for a, b in zip(o32, o8))
    assert same >= 9, f"only {same}/16 greedy streams byte-identical"
    assert dict(e8.compile_counts) == dict(e32.compile_counts)
    names = {ps.name for ps in e8.program_specs()}
    assert any(n.endswith("_w8") for n in names), names
    assert e8.blocks.num_used == 0


def test_int8_deterministic_across_tp_and_window(model):
    """The quantized pools slice by the same column blocks tp shards
    already use, and the decode-window scan body routes through the
    same dequant path — int8 outputs are byte-identical across tp=2
    and decode_window=4 variants."""
    reqs = _audit_stream(8)
    base = _drive(_engine(model, weight_dtype="int8"), reqs)
    tp2 = _drive(_engine(model, weight_dtype="int8", tp=2), reqs)
    win = _drive(_engine(model, weight_dtype="int8", decode_window=4),
                 reqs)
    assert [o.generated for o in tp2] == [o.generated for o in base]
    assert [o.generated for o in win] == [o.generated for o in base]


def test_int4_engine_is_deterministic(model):
    reqs = _audit_stream(4)
    a = _drive(_engine(model, weight_dtype="int4"), reqs)
    b = _drive(_engine(model, weight_dtype="int4"), reqs)
    assert [o.generated for o in a] == [o.generated for o in b]
    assert all(o.finish_reason == "length" for o in a)


# ---------------------------------------------------------------------------
# resident bytes: the compression the ISSUE gates on
# ---------------------------------------------------------------------------

def test_weight_bytes_resident_compression_at_model_shape():
    """At the hidden=512 test config the f32 scale/norm floor is
    amortized: int8 must cut resident weight bytes >=3.9x, int4
    >=7.5x."""
    cfg = LlamaConfig.tiny(vocab=256, hidden=512, layers=2, heads=4,
                           ffn=1024, seq=64)
    model = LlamaForCausalLM(cfg)
    kw = dict(max_num_seqs=2, block_size=16, max_model_len=64,
              max_prefill_tokens=64, prefill_token_bucket=32)
    f32 = LLMEngine(model, **kw).weight_bytes_resident()
    i8 = LLMEngine(model, weight_dtype="int8",
                   **kw).weight_bytes_resident()
    i4 = LLMEngine(model, weight_dtype="int4",
                   **kw).weight_bytes_resident()
    assert f32 / i8 >= 3.9, (f32, i8)
    assert f32 / i4 >= 7.5, (f32, i4)


def test_stats_carry_weight_residency_surface(model):
    from paddle_tpu.profiler.serving import ServingStats
    e8 = _engine(model, weight_dtype="int8")
    _drive(e8, _audit_stream(2))
    snap = e8.stats.snapshot()
    assert snap["weight_dtype"] == "int8"
    assert snap["weight_bytes_resident"] == e8.weight_bytes_resident()
    assert snap["weight_bytes_resident"] > 0
    assert snap["weight_bytes_resident_per_shard"] > 0
    # summary() mirrors the gauges for the frontend /metrics surface
    summ = e8.summary()
    assert summ["weight_dtype"] == "int8"
    assert summ["weight_bytes_resident"] == snap["weight_bytes_resident"]
    # mesh-wide aggregation: equal dtypes pass through, mixed flags
    e32 = _engine(model)
    _drive(e32, _audit_stream(2))
    agg = ServingStats.aggregate([snap, e32.stats.snapshot()])
    assert agg["weight_dtype"] == "mixed"
    agg8 = ServingStats.aggregate([snap, snap])
    assert agg8["weight_dtype"] == "int8"
    assert agg8["weight_bytes_resident"] \
        == 2 * snap["weight_bytes_resident"]


# ---------------------------------------------------------------------------
# autotuner rails: cost-model ordering at llama-sm decode shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wdt", ["int8", "int4"])
def test_modeled_decode_layer_cheaper_than_f32(wdt):
    """The acceptance gate serve_bench quotes: over one llama-sm
    decoder layer's matmuls, the best tuned quant_matmul candidate
    models cheaper than the dense f32 XLA contraction."""
    from paddle_tpu.tune import cost
    from paddle_tpu.tune.registry import candidate_configs, get_kernel
    kern = get_kernel("quant_matmul")
    shapes = [(512, 512)] * 4 + [(512, 1408)] * 2 + [(1408, 512)]
    quant = sum(
        min(cost.estimate("quant_matmul",
                          {"m": 8, "k": k, "n": n, "dtype": wdt}, c)
            for c in candidate_configs(kern))
        for k, n in shapes)
    f32 = sum(cost.f32_matmul_estimate(8, k, n) for k, n in shapes)
    assert quant < f32, (quant, f32)
