"""Replica router: deterministic policy selection, prefix-affinity
landing, per-replica abort/drain lifecycle, outstanding-token
accounting, fleet stats aggregation, and pool hygiene after a
32-stream run with aborts."""
import threading

import numpy as np
import pytest

from paddle_tpu.inference import LLMEngine
from paddle_tpu.inference.frontend import ReplicaRouter, build_replicas
from paddle_tpu.inference.frontend.metrics import render_metrics
from paddle_tpu.inference.kv_cache import prefix_chain_hashes
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.profiler import ServingStats

VOCAB = 97
CFG = LlamaConfig.tiny(vocab=VOCAB, hidden=32, layers=2, heads=4, ffn=64,
                       seq=64)


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM(CFG)


def _engine(model, **kw):
    kw.setdefault("max_num_seqs", 4)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("max_prefill_tokens", 128)
    kw.setdefault("prefill_token_bucket", 32)
    return LLMEngine(model, **kw)


def _router(model, n=2, policy="affinity", start=True, max_pending=None,
            **ekw):
    def factory(replica=0):
        return _engine(model, **ekw)

    router = ReplicaRouter(build_replicas(factory(), factory, n,
                                          max_pending=max_pending),
                           policy=policy)
    return router.start() if start else router


class _Sink:
    """Collects one request's stream; .done fires on the terminal."""

    def __init__(self):
        self.done = threading.Event()
        self.out = None
        self.tokens = []

    def __call__(self, ev):
        if ev[0] == "token":
            self.tokens.append(ev[1])
        elif ev[0] == "finish":
            self.out = ev[1]
            self.done.set()


def _await(sinks, timeout=120.0):
    for s in sinks:
        assert s.done.wait(timeout), "request never finished"


# ---------------------------------------------------------------------------
# construction contracts
# ---------------------------------------------------------------------------

def test_build_replicas_requires_factory(model):
    with pytest.raises(ValueError, match="engine_factory"):
        build_replicas(_engine(model), None, 2)


def test_recovered_replica_returns_to_its_own_device(model):
    """build_replicas binds each runner's index into the factory, so a
    replica rebuilt after a crashed step lands on the device it had."""
    import jax

    from paddle_tpu.inference.faults import FaultPlan

    def factory(replica=0):
        return _engine(model, devices=[jax.devices()[replica]])

    runners = build_replicas(factory(), factory, 2)
    assert [r.engine.devices for r in runners] == [[jax.devices()[0]],
                                                   [jax.devices()[1]]]
    old = runners[1].engine
    old.set_fault_plan(FaultPlan(crash_steps=(2,)))
    runners[1].start()
    sink = _Sink()
    try:
        runners[1].submit([5, 6, 7], deliver=sink, max_new_tokens=6)
        _await([sink])
    finally:
        assert runners[1].drain(timeout_s=60.0)
    new = runners[1].engine
    assert new is not old and sink.out.finish_reason == "length"
    assert {d for x in jax.tree.leaves(new.params) + [new._kc]
            for d in x.devices()} == {jax.devices()[1]}


def test_router_validates_indexed_runner_names(model):
    def factory(replica=0):
        return _engine(model)

    runners = build_replicas(factory(), factory, 2)
    with pytest.raises(ValueError, match="must be named"):
        ReplicaRouter(list(reversed(runners)))


def test_router_rejects_unknown_policy(model):
    def factory(replica=0):
        return _engine(model)

    with pytest.raises(ValueError, match="policy"):
        ReplicaRouter(build_replicas(factory(), factory, 1),
                      policy="round-robin")


# ---------------------------------------------------------------------------
# policy selection (white-box: _pick under a held-open load picture)
# ---------------------------------------------------------------------------

def test_least_outstanding_ties_break_to_lowest_index(model):
    r = _router(model, n=3, policy="least", start=False)
    assert r._pick([]) == (0, False)              # idle fleet -> r0
    r._outstanding[0] = 10
    assert r._pick([])[0] == 1                    # r1/r2 tie -> r1
    r._outstanding[1] = 10
    assert r._pick([])[0] == 2
    r._outstanding[2] = 20
    assert r._pick([])[0] == 0                    # 10/10/20 tie -> r0


def test_affinity_prefers_longest_leading_run_then_load(model):
    r = _router(model, n=3, policy="affinity", start=False)
    hashes = prefix_chain_hashes(list(range(24)), r._block_size)
    assert len(hashes) == 3
    # r2 remembers the full chain, r0 only the first page
    r._registry[0][hashes[0]] = None
    for h in hashes:
        r._registry[2][h] = None
    assert r._pick(hashes) == (2, True)
    # equal runs: the less-loaded replica wins the tie
    for h in hashes:
        r._registry[1][h] = None
    r._outstanding[2] = 50
    assert r._pick(hashes) == (1, True)
    # no match anywhere: least-outstanding fallback, not a hit
    cold = prefix_chain_hashes([90, 91, 92, 93, 94, 95, 96, 90],
                               r._block_size)
    assert r._pick(cold) == (0, False)


# ---------------------------------------------------------------------------
# end-to-end routing
# ---------------------------------------------------------------------------

def test_shared_prefix_requests_land_on_one_replica(model):
    router = _router(model, n=2, policy="affinity")
    try:
        rng = np.random.RandomState(5)
        prefix = rng.randint(0, VOCAB, 16).tolist()   # 2 full pages
        sinks, rids = [], []
        for _ in range(4):
            s = _Sink()
            rids.append(router.submit(
                prefix + rng.randint(0, VOCAB, 3).tolist(),
                deliver=s, max_new_tokens=4))
            sinks.append(s)
        _await(sinks)
        owners = {rid.split("-", 1)[0] for rid in rids}
        assert len(owners) == 1                   # all on the same replica
        c = router.router_counters()
        # first request seeds the registry; the other three match it
        assert c["affinity_hit_total"] == 3
        assert c["routed_total"] == 4
        assert c["outstanding_tokens"] == [0, 0]  # settled on finish
        assert all(s.out.finish_reason in ("length", "eos") for s in sinks)
    finally:
        router.close()


def test_abort_routes_to_owning_replica(model):
    router = _router(model, n=2, policy="least")
    try:
        slow, fast = _Sink(), _Sink()
        rid = router.submit(list(range(8)), deliver=slow,
                            max_new_tokens=48)
        router.submit([3, 1, 4], deliver=fast, max_new_tokens=2)
        _await([fast])
        router.abort(rid, "client_disconnect")
        _await([slow])
        assert slow.out.finish_reason == "client_disconnect"
        assert router.router_counters()["outstanding_tokens"] == [0, 0]
        router.abort("bogus-id")                  # unknown owner: no-op
        router.abort("r9-req-0")                  # out-of-range: no-op
    finally:
        router.close()


def test_32_stream_run_with_aborts_leaves_pools_clean(model):
    """The chaos sweep: 32 concurrent streams over 2 replicas, every
    4th aborted mid-flight.  Afterwards every replica's page pool must
    hold zero used pages with intact free-list invariants, and the
    router's outstanding-token ledger must read all-zero."""
    # room for every stream on one replica: routing by tokens sends 17
    # of the 32 to replica 0, and the default bound of 16 held only when
    # an engine thread had retired one before the last submit
    router = _router(model, n=2, policy="affinity", max_pending=32)
    try:
        rng = np.random.RandomState(9)
        sinks = []
        for i in range(32):
            s = _Sink()
            n = int(rng.randint(4, 24))
            rid = router.submit(rng.randint(0, VOCAB, n).tolist(),
                                deliver=s, max_new_tokens=8)
            if i % 4 == 0:
                router.abort(rid, "chaos")
            sinks.append(s)
        _await(sinks)
        assert router.drain(timeout_s=60.0)
        c = router.router_counters()
        assert c["outstanding_tokens"] == [0, 0]
        assert sum(c["routed_requests"]) == 32
        assert all(n > 0 for n in c["routed_requests"])
        for eng in router.engines:
            eng.blocks.check_invariants()
            assert eng.blocks.num_used == 0
        snap = router.stats_snapshot()
        assert snap["replicas"] == 2
        # aborted streams terminate without retiring; a chaos abort
        # that raced a finished request is a benign no-op and retires
        aborted = sum(1 for s in sinks
                      if s.out.finish_reason == "chaos")
        assert snap["retired"] == 32 - aborted
        assert aborted > 0
    finally:
        router.close()


# ---------------------------------------------------------------------------
# fleet observability
# ---------------------------------------------------------------------------

def test_stats_aggregate_semantics(model):
    eng = _engine(model)
    eng.add_request(list(range(12)), max_new_tokens=4)
    eng.add_request(list(range(12)), max_new_tokens=4)  # prefix hit
    eng.run()
    s = eng.stats.snapshot()
    agg = ServingStats.aggregate([s, s])
    assert agg["replicas"] == 2
    assert agg["retired"] == 2 * s["retired"]                  # counters sum
    assert agg["p50_token_ms"] == s["p50_token_ms"]  # no samples: max
    assert agg["mean_batch_occupancy"] == \
        pytest.approx(s["mean_batch_occupancy"])               # means mean
    assert agg["decode_tokens_per_s"] == \
        pytest.approx(2 * s["decode_tokens_per_s"], rel=1e-6)  # rates sum
    assert agg["prefix_hit_rate"] == \
        pytest.approx(s["prefix_hit_rate"])       # recomputed from sums
    # histograms merge bucket-by-bucket: identical bounds, counts add
    assert agg["itl_hist_count"] == 2 * s["itl_hist_count"]
    assert all(agg["itl_hist_buckets"][le] == 2 * n
               for le, n in s["itl_hist_buckets"].items())
    with pytest.raises(ValueError):
        ServingStats.aggregate([])


def test_stats_aggregate_pools_reservoir_samples():
    """Honest fleet quantiles: snapshots carrying their reservoir
    samples aggregate to the percentile of the pooled UNION, not the
    max of per-replica percentiles.  Two disjoint latency populations
    make the two semantics differ visibly."""
    fast, slow = ServingStats(), ServingStats()
    for _ in range(150):
        fast.record_decode(0.001, n_tokens=1, occupancy=1.0)   # 1 ms
    for _ in range(50):
        slow.record_decode(0.101, n_tokens=1, occupancy=1.0)   # 101 ms
    snaps = [fast.snapshot(include_samples=True),
             slow.snapshot(include_samples=True)]
    agg = ServingStats.aggregate(snaps)
    # max-of-quantiles would say p50 == 101 ms; 3/4 of the pooled union
    # is the fast population, so the honest fleet p50 is 1 ms
    assert agg["p50_token_ms"] == pytest.approx(1.0, rel=1e-6)
    assert agg["itl_p50_ms"] == agg["p50_token_ms"]
    assert agg["p99_token_ms"] == pytest.approx(101.0, rel=1e-6)
    # the raw samples themselves never leak into the aggregate
    assert "_samples" not in agg
    # without samples the conservative max-of-quantiles fallback holds
    fallback = ServingStats.aggregate(
        [fast.snapshot(), slow.snapshot()])
    assert fallback["p50_token_ms"] == pytest.approx(101.0, rel=1e-6)


def test_metrics_render_true_histograms():
    """The /metrics exposition carries real Prometheus histograms for
    TTFT / ITL / step duration: ``# TYPE ... histogram``, cumulative
    ``_bucket{le=}`` samples monotone in le and ending at +Inf, and
    consistent ``_sum`` / ``_count``."""
    stats = ServingStats()
    for v in (0.0005, 0.003, 0.02, 0.02, 0.7, 30.0):
        stats.record_decode(v, n_tokens=1, occupancy=1.0)
    stats.record_ttft(0.004)
    stats.record_ttft(0.09)
    stats.record_step(0.002)
    text = render_metrics(stats.snapshot())
    for series in ("ttft_hist_seconds", "itl_hist_seconds",
                   "step_duration_seconds"):
        assert f"# TYPE paddle_tpu_{series} histogram" in text
        assert f'paddle_tpu_{series}_bucket{{le="+Inf"}}' in text
    # cumulative counts are non-decreasing across the le ladder and the
    # +Inf bucket equals _count; _sum matches the recorded observations
    lines = text.splitlines()
    itl = [ln for ln in lines
           if ln.startswith("paddle_tpu_itl_hist_seconds_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in itl]
    assert counts == sorted(counts)
    assert counts[-1] == 6
    assert "paddle_tpu_itl_hist_seconds_count 6" in text
    assert 'paddle_tpu_itl_hist_seconds_bucket{le="0.001"} 1' in text
    assert 'paddle_tpu_itl_hist_seconds_bucket{le="10"} 5' in text
    sum_ln = next(ln for ln in lines
                  if ln.startswith("paddle_tpu_itl_hist_seconds_sum"))
    assert float(sum_ln.rsplit(" ", 1)[1]) == \
        pytest.approx(0.0005 + 0.003 + 0.02 + 0.02 + 0.7 + 30.0)
    assert "paddle_tpu_ttft_hist_seconds_count 2" in text
    assert "paddle_tpu_step_duration_seconds_count 1" in text


def test_metrics_render_carries_per_replica_series(model):
    router = _router(model, n=2, policy="affinity")
    try:
        s = _Sink()
        router.submit(list(range(10)), deliver=s, max_new_tokens=4)
        _await([s])
        text = render_metrics(router.stats_snapshot(),
                              engines=router.engines,
                              router=router.router_counters())
        assert "paddle_tpu_replicas 2" in text
        for series in ("replica_outstanding_tokens",
                       "replica_routed_requests_total",
                       "replica_affinity_hits_total"):
            for i in (0, 1):
                assert f'paddle_tpu_{series}{{replica="{i}"}}' in text
    finally:
        router.close()
