"""Serving benchmark: continuous-batching decode throughput over paged KV.

Drives paddle_tpu.inference.LLMEngine with a deterministic ragged request
stream (step-indexed Poisson-ish arrivals) and prints ONE JSON line:

  {"metric": "serve_decode_tokens_per_s", "value": ..., "unit": "tok/s",
   "backend": ..., "p50_token_ms": ..., "p99_token_ms": ...,
   "batch_occupancy": ..., "decode_compiles": ..., "prefill_compiles": ...,
   "requests": ..., "preempted": ...}

With ``--prefix-share K`` the stream instead shares K system prompts
across the requests and the same workload runs twice — prefix caching OFF
(the PR-1 engine behavior) then ON — reporting end-to-end throughput for
both plus the cache's own surface:

  {"metric": "serve_prefix_tokens_per_s", "value": ..., "unit": "tok/s",
   "baseline_tokens_per_s": ..., "speedup": ..., "prefix_hit_rate": ...,
   "prefill_tokens_saved": ..., "ttft_p50_ms": ..., "ttft_p99_ms": ...,
   "baseline_ttft_p50_ms": ..., "baseline_ttft_p99_ms": ..., ...}

With ``--spec K`` the stream is repetitive text (the n-gram prompt-lookup
drafter's home turf) and the same workload runs with speculation OFF then
ON (spec_k=K), reporting wall-clock emitted tok/s for both plus per-phase
throughput — decode and verify each over their own wall time.  (The old
"speedup" ratio compared verify-folded decode numbers against plain
decode of a different token mix — a bookkeeping artifact, dropped):

  {"metric": "serve_spec_tokens_per_s", "value": ..., "unit": "tok/s",
   "baseline_tokens_per_s": ..., "decode_tokens_per_s": ...,
   "verify_tokens_per_s": ..., "accept_rate": ..., "draft_proposed": ...,
   "draft_accepted": ..., "rollback_tokens": ..., "verify_steps": ...,
   "spec_disables": ..., ...}

With ``--mixed`` the stream interleaves long prefills (chunk-resumed
across steps), short prompts, plain decodes and n-gram speculation
rounds — every row shape the ONE ragged step program serves — and
reports the padding-waste ratio (padded/real tokens) against what the
retired per-phase programs would have padded for the same launches:

  {"metric": "serve_mixed_tokens_per_s", "value": ..., "unit": "tok/s",
   "padding_waste_ratio": ..., "legacy_padding_waste_ratio": ...,
   "padding_waste_reduction": ..., "attention_compiles": ...,
   "attention_program_kinds": 1, "accept_rate": ..., ...}

``--mixed`` also A/Bs the async step pipeline: the identical stream
runs on an ``overlap=True`` engine and an ``overlap=False`` one
(``--overlap off`` flips which arm is the headline/traced one), and
the record carries both arms' decode wall-clock plus their
dispatch/block attribution and host-bubble fraction:

  {"overlap": "on", "overlap_on_wall_s": ..., "overlap_on_tokens_per_s":
   ..., "overlap_on_dispatch_time_s": ..., "overlap_on_block_time_s":
   ..., "overlap_on_host_bubble_frac": ..., "overlap_off_wall_s": ...,
   ...}

With ``--decode-window K`` one steady pure-decode workload runs twice —
per-step engine (decode_window=1) then the device-resident K-step
window engine — same prompts, greedy, so the outputs must match
byte-for-byte.  The headline value is the window arm's decode tok/s;
the hardware-independent win is the round-trip count (every mode's
record carries the same three keys at its own engine's values):

  {"metric": "serve_window_tokens_per_s", "value": ..., "unit": "tok/s",
   "outputs_match": true, "decode_window_k": K,
   "decode_window_tokens_per_s": ...,
   "decode_window_host_round_trips_per_token": ...,  # ~1.0 -> ~1/K
   "baseline_host_round_trips_per_token": ...,
   "tokens_per_launch": ..., "decode_window_fallbacks": ..., ...}

With ``--http`` the SAME ragged workload runs twice over the real HTTP
frontend (paddle_tpu.inference.frontend) on localhost — concurrent
streaming clients, SSE parsing, client-side TTFT/ITL — next to an
engine-direct run of the identical stream, so the line quantifies what
the HTTP tier costs:

  {"metric": "serve_http_tokens_per_s", "value": ..., "unit": "tok/s",
   "engine_tokens_per_s": ..., "http_overhead": ...,
   "ttft_p50_ms": ..., "ttft_p99_ms": ..., "itl_p50_ms": ...,
   "itl_p99_ms": ..., "requests": ..., "aborts": ..., "shed": ...}

With ``--slo`` the same stream rides the HTTP frontend with the SLO
observatory armed — windowed telemetry, per-request flight recorder,
anomaly spool — and the record is built from ``GET /slo`` and
``GET /debug/requests`` (so CI proves the observatory saw the traffic):

  {"metric": "serve_slo_tokens_per_s", "value": ..., "unit": "tok/s",
   "slo_state": "NORMAL", "ttft_p95_w60s": ..., "itl_p99_w60s": ...,
   "windowed_ttft_samples": ..., "flight_records": ...,
   "anomalies_captured": ...}

Every mode's record also carries ``ttft_p95_w60s`` / ``itl_p99_w60s`` /
``slo_state`` / ``anomalies_captured`` from the windowed layer.

With ``--memory-pressure`` the page pool is sized from a fixed HBM byte
budget (not a block count) and a burst of medium prompts runs once per
KV dtype — float32 baseline, then ``--kv-dtype`` — each through a
DegradationController, so the line proves what quantized pages buy on
the same silicon at matched traffic:

  {"metric": "serve_pressure_resident_seqs", "value": ..., "unit": "seqs",
   "resident_ratio": ..., "baseline_peak_resident_seqs": ...,
   "preempted": ..., "baseline_preempted": ...,
   "degradation_tier_entries": ..., "baseline_degradation_tier_entries": ...,
   "hbm_budget_bytes": ..., "num_blocks": ..., "baseline_num_blocks": ...}

With ``--weight-pressure`` the same burst workload A/Bs a float32
weight pool against a ``--weight-dtype`` quantized one (int8 if the
flag is left at float32) under the SAME per-chip HBM budget — the f32
weights plus a fixed page allowance — so the bytes the quantized pool
hands back buy extra KV pages.  The record shows the compression and
the residency headroom, plus the roofline-modeled decode matmul cost
of the tuned ``quant_matmul`` kernel vs the dense f32 XLA contraction
at a llama-sm projection shape:

  {"metric": "serve_weight_resident_seqs", "value": ..., "unit": "seqs",
   "weight_compression_ratio": ..., "weight_bytes_resident": ...,
   "baseline_weight_bytes_resident": ..., "resident_ratio": ...,
   "modeled_decode_layer_s": ..., "modeled_f32_layer_s": ...,
   "modeled_decode_cost_ratio": ..., "num_blocks": ...,
   "baseline_num_blocks": ..., "hbm_budget_bytes": ...}

With ``--http --replicas D`` the shared-prefix workload (``share_ways``
from ``--prefix-share``, default 4) runs over D data-parallel engine
replicas behind the prefix-affinity replica router — the SAME stream
once under random routing, once under affinity — so the line shows what
landing shared prompts on the replica that already holds their KV pages
buys:

  {"metric": "serve_router_tokens_per_s", "value": ..., "unit": "tok/s",
   "affinity_hit_rate": ..., "load_imbalance": ...,
   "random_tokens_per_s": ..., "ttft_p50_ms": ...,
   "random_ttft_p50_ms": ..., "routed_requests": [...], ...}

Every mode's record also carries the KV-residency surface — ``kv_dtype``,
``kv_bytes_resident``, ``peak_resident_seqs``,
``degradation_tier_entries`` — plus ``tp`` and ``replicas``;
``--kv-dtype int8`` threads quantized KV pages, ``--weight-dtype
int8|int4`` threads quantized weight pools (every record carries
``weight_dtype`` and ``weight_bytes_resident``), and ``--tp N`` threads
an N-way tensor-parallel mesh (host devices forced on CPU) through
every engine the bench builds.

The run computes on the device JAX resolves and names it in "backend" and
"device".  Finding no accelerator is an error; a CPU run happens only
under an explicit JAX_PLATFORMS=cpu.  A mode that raises ends the run with
its traceback and a non-zero exit code.

  python tools/perf/serve_bench.py [--smoke] [--requests N] [--seed S]
                                   [--prefix-share K]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _emit(record):
    print(json.dumps(record))
    sys.stdout.flush()


def _request_stream(rng, n_requests, vocab, max_len):
    """Deterministic ragged stream: (arrival_step, prompt, max_new)."""
    stream = []
    step = 0
    for _ in range(n_requests):
        step += int(rng.poisson(1.5))            # step-indexed arrivals
        n = int(rng.randint(4, max_len // 4))
        max_new = int(rng.randint(4, max_len // 2 - n + 5))
        prompt = rng.randint(0, vocab, n).tolist()
        stream.append((step, prompt, max(4, max_new)))
    return stream


def _prefix_stream(rng, n_requests, share_ways, vocab, max_len):
    """Shared-prefix stream: each request is one of ``share_ways`` system
    prompts (a few KV pages long) plus a short unique user suffix."""
    sys_len = max(3 * (max_len // 8), 8)
    sys_prompts = [rng.randint(0, vocab, sys_len).tolist()
                   for _ in range(share_ways)]
    stream, step = [], 0
    for i in range(n_requests):
        step += int(rng.poisson(1.0))
        prompt = sys_prompts[i % share_ways] \
            + rng.randint(0, vocab, int(rng.randint(2, 6))).tolist()
        stream.append((step, prompt, 8))
    return stream


def _drive(engine, stream):
    """Run the arrival-scheduled stream to completion; wall seconds."""
    import time

    t0 = time.perf_counter()
    step_no = 0
    pending = list(stream)
    while pending or engine.has_unfinished():
        while pending and pending[0][0] <= step_no:
            _, prompt, max_new = pending.pop(0)
            engine.add_request(prompt, max_new_tokens=max_new)
        engine.step()
        step_no += 1
    return time.perf_counter() - t0


def _mem_keys(engine):
    """Residency surface every mode reports, all dtypes: what the KV
    pages and the weight pools cost in bytes and how many sequences
    the pages held at peak."""
    return {
        "kv_dtype": engine.kv_dtype,
        "kv_bytes_resident": engine.kv_bytes_resident(),
        "weight_dtype": engine.weight_dtype,
        "weight_bytes_resident": engine.weight_bytes_resident(),
        "peak_resident_seqs": engine.peak_resident_seqs,
        "degradation_tier_entries": engine.degradation_tier_entries,
        "tuning_cache": engine.summary()["tuning_cache"],
    }


def _slo_keys(snap):
    """Windowed SLO surface every mode reports next to the lifetime
    stats: the rolling mid-window percentiles, the burn-rate state and
    the anomaly-capture count (0s if windows were never enabled)."""
    return {
        "ttft_p95_w60s": snap.get("ttft_p95_w60s", 0.0),
        "itl_p99_w60s": snap.get("itl_p99_w60s", 0.0),
        "slo_state": snap.get("slo_state_name", "NORMAL"),
        "anomalies_captured": snap.get("anomalies_captured", 0),
    }


def _window_keys(snap):
    """Device-resident decode-window surface every decode-bearing mode
    reports: the largest on-device window the engine ran, its decode
    throughput, and host round-trips per PER-ROW decode position — the
    sync count on one request's critical path, ~1.0 for the per-step
    engine regardless of batch width, falling toward 1/K with a K-step
    window engaged."""
    rounds = snap.get("decode_rounds", 0)
    trips = snap.get("host_round_trips", 0)
    return {
        "decode_window_k": snap.get("decode_window_k", 1),
        "decode_window_tokens_per_s": snap.get("decode_tokens_per_s",
                                               0.0),
        "decode_window_host_round_trips_per_token":
            round(trips / rounds, 4) if rounds else 0.0,
    }


def run_prefix_bench(smoke: bool, n_requests: int, share_ways: int,
                     seed: int, backend: str, kv_dtype: str = "float32",
                     tp: int = 1, weight_dtype: str = "float32"):
    """Same shared-prefix workload with prefix caching OFF then ON.  Each
    engine gets one untimed pass (compiles every program bucket and, for
    the cached engine, populates the pool) and one timed steady-state
    pass; value is emitted tokens per wall second of the timed pass."""
    import numpy as np

    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if smoke or backend == "cpu":
        # longer context than the plain bench: the shared system prompt is
        # most of the prompt, so the workload is prefill-heavy and the
        # cache's savings are visible in end-to-end throughput
        cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                               ffn=128, seq=512)
        engine_kw = dict(max_num_seqs=4, block_size=8, max_model_len=512,
                         max_prefill_tokens=256, prefill_token_bucket=64)
    else:
        cfg = LlamaConfig(vocab_size=8192, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=1024)
        engine_kw = dict(max_num_seqs=16, block_size=16, max_model_len=1024,
                         max_prefill_tokens=2048, prefill_token_bucket=256)

    model = LlamaForCausalLM(cfg)
    total_new = None
    runs = {}
    for caching in (False, True):
        engine = LLMEngine(model, enable_prefix_caching=caching,
                           kv_dtype=kv_dtype, weight_dtype=weight_dtype, tp=tp, **engine_kw)
        engine.stats.enable_windows()
        rng = np.random.RandomState(seed)
        stream = _prefix_stream(rng, n_requests, share_ways,
                                cfg.vocab_size, engine_kw["max_model_len"])
        total_new = sum(mn for _, _, mn in stream)
        _drive(engine, stream)           # warm pass: compile + populate
        engine.stats.reset()
        elapsed = _drive(engine, stream)  # timed steady-state pass
        s = engine.stats.summary()
        s["tokens_per_s"] = total_new / elapsed if elapsed else 0.0
        s["decode_compiles"] = engine.num_decode_programs
        s["prefill_compiles"] = engine.num_prefill_programs
        runs[caching] = s

    on, off = runs[True], runs[False]
    return {
        "metric": "serve_prefix_tokens_per_s",
        "value": round(on["tokens_per_s"], 2),
        "unit": "tok/s",
        "backend": backend,
        "share_ways": share_ways,
        "requests": n_requests,
        "new_tokens": total_new,
        "baseline_tokens_per_s": round(off["tokens_per_s"], 2),
        "speedup": round(on["tokens_per_s"] / off["tokens_per_s"], 3)
        if off["tokens_per_s"] else 0.0,
        "prefix_hit_rate": on["prefix_hit_rate"],
        "prefill_tokens_saved": on["prefill_tokens_saved"],
        "baseline_prefill_tokens": off["prefill_tokens"],
        "prefill_tokens": on["prefill_tokens"],
        "ttft_p50_ms": on["ttft_p50_ms"],
        "ttft_p99_ms": on["ttft_p99_ms"],
        "baseline_ttft_p50_ms": off["ttft_p50_ms"],
        "baseline_ttft_p99_ms": off["ttft_p99_ms"],
        "cow_copies": on["cow_copies"],
        "cache_evictions": on["cache_evictions"],
        "decode_compiles": on["decode_compiles"],
        "prefill_compiles": on["prefill_compiles"],
        "preempted": on["preemptions"],
        **_mem_keys(engine),
        **_slo_keys(engine.stats.snapshot()),
        **_window_keys(engine.stats.snapshot()),
    }


def _spec_text_stream(rng, n_requests, vocab, max_len):
    """Repetitive-text stream: each prompt is a short motif tiled to a
    few KV pages (structured / self-repeating output — prompt-lookup
    drafting's home turf), with a long decode budget so the run is
    decode-dominated and greedy continuations settle into cycles the
    n-gram drafter keeps predicting."""
    stream, step = [], 0
    plo, phi = max(4, max_len // 5), max(6, max_len // 4 + 1)
    for _ in range(n_requests):
        step += int(rng.poisson(1.0))
        motif = rng.randint(0, vocab, int(rng.randint(2, 5))).tolist()
        n = int(rng.randint(plo, phi))
        prompt = (motif * (n // len(motif) + 1))[:n]
        stream.append((step, prompt, max_len - phi - 8))
    return stream


def run_spec_bench(smoke: bool, n_requests: int, spec_k: int, seed: int,
                   backend: str, kv_dtype: str = "float32", tp: int = 1,
                   weight_dtype: str = "float32"):
    """Same repetitive-text workload with speculation OFF then ON.  Each
    engine gets one untimed pass (compiles every program bucket) and one
    timed pass; value is emitted tokens per wall second across the
    decode AND verify phases (each phase also reported over its own wall
    time).  The same emitted tokens ride fewer, heavier steps when
    speculation wins — the per-phase numbers make that legible instead
    of hiding verify time inside decode time."""
    import numpy as np

    import paddle_tpu
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle_tpu.seed(seed)        # acceptance depends on the model's own
    # greedy cycles, so pin the weights for run-to-run reproducibility

    if smoke or backend == "cpu":
        # deliberately launch-latency-bound: a tiny model with short
        # sequences, where decode pays per-launch dispatch far above its
        # per-row compute — the regime speculation is built for (on real
        # accelerators the same regime is HBM-bandwidth-bound decode)
        cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                               ffn=64, seq=64)
        engine_kw = dict(max_num_seqs=4, block_size=8, max_model_len=64,
                         max_prefill_tokens=128, prefill_token_bucket=32)
    else:
        cfg = LlamaConfig(vocab_size=8192, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=1024)
        engine_kw = dict(max_num_seqs=16, block_size=16, max_model_len=1024,
                         max_prefill_tokens=2048, prefill_token_bucket=256)

    model = LlamaForCausalLM(cfg)
    from paddle_tpu.inference import NGramDrafter

    runs = {}
    for spec in (False, True):
        kw = dict(engine_kw)
        if spec:
            # wide-window prompt lookup; the acceptance floor is a
            # production guard against hopeless workloads, and this
            # bench MEASURES the speculative path, so it never trips off
            kw.update(drafter=NGramDrafter(max_ngram=6, min_ngram=1),
                      spec_k=spec_k, max_spec_k=spec_k,
                      spec_accept_floor=0.0)
        engine = LLMEngine(model, kv_dtype=kv_dtype, weight_dtype=weight_dtype, tp=tp, **kw)
        engine.stats.enable_windows()
        rng = np.random.RandomState(seed)
        stream = _spec_text_stream(rng, n_requests, cfg.vocab_size,
                                   engine_kw["max_model_len"])
        _drive(engine, list(stream))      # warm pass: compile every bucket
        best = None
        for _ in range(2):                # best-of-2 timed passes: the
            engine.stats.reset()          # runs are short, wall noise is
            _drive(engine, list(stream))  # not
            s = engine.stats.summary()
            if best is None or s["emitted_tokens_per_s"] \
                    > best["emitted_tokens_per_s"]:
                best = s
        s = best
        s["attention_compiles"] = engine.compile_counts["ragged"]
        runs[spec] = s

    on, off = runs[True], runs[False]
    return {
        "metric": "serve_spec_tokens_per_s",
        "value": on["emitted_tokens_per_s"],
        "unit": "tok/s",
        "backend": backend,
        "spec_k": spec_k,
        "requests": n_requests,
        "baseline_tokens_per_s": off["emitted_tokens_per_s"],
        "decode_tokens_per_s": on["decode_tokens_per_s"],
        "verify_tokens_per_s": on["verify_tokens_per_s"],
        "prefill_tokens_per_s": on["prefill_tokens_per_s"],
        "baseline_decode_tokens_per_s": off["decode_tokens_per_s"],
        "accept_rate": on["accept_rate"],
        "draft_proposed": on["draft_proposed"],
        "draft_accepted": on["draft_accepted"],
        "spec_emitted_tokens": on["spec_emitted_tokens"],
        "rollback_tokens": on["rollback_tokens"],
        "rollback_pages": on["rollback_pages"],
        "verify_steps": on["verify_steps"],
        "spec_disables": on["spec_disables"],
        "decode_steps": on["decode_steps"],
        "baseline_decode_steps": off["decode_steps"],
        "decode_tokens": on["decode_tokens"],
        "verify_tokens": on["verify_tokens"],
        "attention_compiles": on["attention_compiles"],
        "p50_token_ms": on["p50_token_ms"],
        "p99_token_ms": on["p99_token_ms"],
        "preempted": on["preemptions"],
        **_mem_keys(engine),
        **_slo_keys(engine.stats.snapshot()),
        **_window_keys(engine.stats.snapshot()),
    }


def _http_drive(port, stream, *, step_delay_s: float = 0.002):
    """Drive the arrival-scheduled stream as concurrent HTTP streaming
    clients against a live frontend.  Returns (wall_s, per-request list
    of {tokens, ttft_s, itls_s, finish})."""
    import http.client
    import threading
    import time

    results = [None] * len(stream)

    def one(i, arrival, prompt, max_new):
        time.sleep(arrival * step_delay_s)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
        body = json.dumps({"prompt": prompt, "max_tokens": max_new,
                           "stream": True}).encode()
        t0 = time.perf_counter()
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        toks, itls, finish = [], [], None
        t_first = t_prev = None
        buf, done = b"", False
        while not done:
            chunk = resp.read(256)       # http.client de-chunks for us
            if not chunk:
                break
            buf += chunk
            while b"\n\n" in buf:
                frame, buf = buf.split(b"\n\n", 1)
                data = frame.partition(b"data: ")[2].decode()
                if data == "[DONE]":
                    done = True
                    continue
                ch = json.loads(data)["choices"][0]
                now = time.perf_counter()
                if ch["finish_reason"] is not None:
                    finish = ch["finish_reason"]
                    continue
                toks.append(ch["token"])
                if t_first is None:
                    t_first = now
                else:
                    itls.append(now - t_prev)
                t_prev = now
        conn.close()
        results[i] = {"tokens": toks, "finish": finish,
                      "ttft_s": (t_first - t0) if t_first else 0.0,
                      "itls_s": itls}

    threads = [threading.Thread(target=one, args=(i, a, p, mn))
               for i, (a, p, mn) in enumerate(stream)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0, results


def run_http_bench(smoke: bool, n_requests: int, seed: int, backend: str,
                   kv_dtype: str = "float32", tp: int = 1,
                   weight_dtype: str = "float32"):
    """The run_bench workload through the real HTTP frontend (SSE
    streaming clients over localhost) next to an engine-direct run of
    the identical stream.  Both engines get one untimed warm pass; value
    is emitted tokens per wall second of the timed HTTP pass, with the
    engine-direct number alongside so the HTTP tier's cost is explicit."""
    import numpy as np

    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.frontend import serve_background
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if smoke or backend == "cpu":
        cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                               ffn=128, seq=128)
        engine_kw = dict(max_num_seqs=4, block_size=8, max_model_len=128,
                         max_prefill_tokens=256, prefill_token_bucket=64)
    else:
        cfg = LlamaConfig(vocab_size=8192, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=1024)
        engine_kw = dict(max_num_seqs=16, block_size=16, max_model_len=1024,
                         max_prefill_tokens=2048, prefill_token_bucket=256)

    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(seed)
    stream = _request_stream(rng, n_requests, cfg.vocab_size,
                             engine_kw["max_model_len"])
    total_new = sum(mn for _, _, mn in stream)

    # engine-direct reference: TWO warm passes (the first compiles the
    # cold-cache prefill buckets, the second compiles the chunked-resume
    # buckets that only exist once the prefix cache is hot), then timed
    direct = LLMEngine(model, kv_dtype=kv_dtype, weight_dtype=weight_dtype, tp=tp, **engine_kw)
    direct.stats.enable_windows()
    _drive(direct, list(stream))
    _drive(direct, list(stream))
    direct.stats.reset()
    direct_wall = _drive(direct, list(stream))
    s_direct = direct.stats.summary()
    direct_tps = total_new / direct_wall if direct_wall else 0.0

    # same workload through the frontend (fresh engine, same weights).
    # Concurrent clients batch nondeterministically, so the timed pass
    # can still hit a never-seen (tokens, batch) bucket and pay a
    # compile; the record carries timed_new_compiles so an inflated
    # TTFT tail is attributable.
    served = LLMEngine(model, retain_outputs=False, kv_dtype=kv_dtype, weight_dtype=weight_dtype,
                       tp=tp, **engine_kw)
    srv = serve_background(served, model_name="bench",
                           max_pending=4 * len(stream))
    try:
        _http_drive(srv.port, stream)    # warm: cold-cache buckets
        _http_drive(srv.port, stream)    # warm: hot-cache chunked buckets
        best = None
        for _ in range(2):               # best-of-2: a pass that hit a
            compiles_before = sum(served.compile_counts.values())
            served.stats.reset()         # fresh (tokens, batch) bucket
            wall_i, results_i = _http_drive(srv.port, stream)  # pays a
            new_i = sum(served.compile_counts.values()) \
                - compiles_before        # compile; the warmer pass wins
            if best is None or wall_i < best[0]:
                best = (wall_i, results_i, new_i,
                        served.stats.summary())
        wall, results, new_compiles, s_http = best
    finally:
        drained = srv.stop()

    got_tokens = sum(len(r["tokens"]) for r in results if r)
    ttfts = sorted(r["ttft_s"] for r in results if r)
    itls = sorted(x for r in results if r for x in r["itls_s"])

    def _pct(vals, q):
        if not vals:
            return 0.0
        return 1e3 * vals[min(len(vals) - 1,
                              int(round(q / 100.0 * (len(vals) - 1))))]

    http_tps = got_tokens / wall if wall else 0.0
    return {
        "metric": "serve_http_tokens_per_s",
        "value": round(http_tps, 2),
        "unit": "tok/s",
        "backend": backend,
        "requests": n_requests,
        "new_tokens": total_new,
        "streamed_tokens": got_tokens,
        "engine_tokens_per_s": round(direct_tps, 2),
        "http_overhead": round(direct_tps / http_tps, 3) if http_tps else 0.0,
        "ttft_p50_ms": round(_pct(ttfts, 50), 3),
        "ttft_p99_ms": round(_pct(ttfts, 99), 3),
        "itl_p50_ms": round(_pct(itls, 50), 3),
        "itl_p99_ms": round(_pct(itls, 99), 3),
        "engine_ttft_p50_ms": s_direct["ttft_p50_ms"],
        "engine_itl_p50_ms": s_direct["itl_p50_ms"],
        "server_itl_p50_ms": s_http["itl_p50_ms"],
        "aborts": s_http["aborts"],
        "shed": 0,
        "timed_new_compiles": new_compiles,
        "drained": bool(drained),
        "finish_reasons": sorted({r["finish"] for r in results if r}),
        **_mem_keys(served),
        **_slo_keys(served.stats.snapshot()),
        **_window_keys(served.stats.snapshot()),
    }


def run_slo_bench(smoke: bool, n_requests: int, seed: int, backend: str,
                  kv_dtype: str = "float32", tp: int = 1,
                  weight_dtype: str = "float32"):
    """The SLO observatory exercised end to end: a mixed stream rides
    the real HTTP frontend while windowed telemetry, the flight
    recorder and an anomaly spool run, then the record is built FROM
    the observability surfaces themselves — ``GET /slo`` and
    ``GET /debug/requests`` — so CI proves the observatory saw the
    traffic, not just that the traffic ran."""
    import http.client
    import tempfile
    import time

    import numpy as np

    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.frontend import serve_background
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if smoke or backend == "cpu":
        cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                               ffn=128, seq=128)
        engine_kw = dict(max_num_seqs=4, block_size=8, max_model_len=128,
                         max_prefill_tokens=256, prefill_token_bucket=64)
    else:
        cfg = LlamaConfig(vocab_size=8192, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=1024)
        engine_kw = dict(max_num_seqs=16, block_size=16, max_model_len=1024,
                         max_prefill_tokens=2048, prefill_token_bucket=256)

    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(seed)
    stream = _request_stream(rng, n_requests, cfg.vocab_size,
                             engine_kw["max_model_len"])
    engine = LLMEngine(model, retain_outputs=False, kv_dtype=kv_dtype, weight_dtype=weight_dtype,
                       tp=tp, **engine_kw)
    spool_dir = tempfile.mkdtemp(prefix="serve-bench-anomaly-")
    srv = serve_background(engine, model_name="bench",
                           max_pending=4 * len(stream),
                           anomaly_spool=spool_dir)

    def _get_json(path):
        conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                          timeout=60)
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read()
        conn.close()
        return resp.status, json.loads(body)

    try:
        _http_drive(srv.port, stream)        # warm: compile every bucket
        t0 = time.perf_counter()
        wall, results = _http_drive(srv.port, stream)
        st_slo, slo = _get_json("/slo")
        st_dbg, dbg = _get_json("/debug/requests?finished=true&limit=8")
    finally:
        srv.stop()

    got = sum(len(r["tokens"]) for r in results if r)
    ws = slo.get("windows", {})
    labels = sorted((k for k in ws if k != "bounds"),
                    key=lambda k: float(k[:-1]))
    mid = ws[labels[min(1, len(labels) - 1)]] if labels else {}

    def _count(ch):
        return (mid.get(ch) or {}).get("count", 0)

    return {
        "metric": "serve_slo_tokens_per_s",
        "value": round(got / wall, 2) if wall else 0.0,
        "unit": "tok/s",
        "backend": backend,
        "requests": n_requests,
        "streamed_tokens": got,
        "wall_s": round(time.perf_counter() - t0, 3),
        "slo_http_status": st_slo,
        "debug_requests_http_status": st_dbg,
        "ttft_p95_w60s": slo.get("ttft_p95_w60s", 0.0),
        "itl_p99_w60s": slo.get("itl_p99_w60s", 0.0),
        "queue_wait_p95_w60s": slo.get("queue_wait_p95_w60s", 0.0),
        "slo_state": slo.get("slo_state_name", "NORMAL"),
        "windowed_ttft_samples": _count("ttft"),
        "windowed_itl_samples": _count("itl"),
        "windowed_request_samples": _count("request"),
        "availability_rate": (mid.get("availability") or {}).get("rate",
                                                                 0.0),
        "flight_records": dbg.get("count", 0),
        "flight_evicted": dbg.get("evicted", 0),
        "anomalies_detected": slo.get("anomalies_detected", 0),
        "anomalies_captured": slo.get("anomalies_captured", 0),
        "anomaly_spool_dropped": slo.get("anomaly_spool_dropped", 0),
        **_mem_keys(engine),
        **_window_keys(engine.stats.snapshot()),
    }


def run_router_bench(smoke: bool, n_requests: int, share_ways: int,
                     seed: int, backend: str, kv_dtype: str,
                     replicas: int, tp: int = 1,
                     weight_dtype: str = "float32"):
    """The shared-prefix workload over the HTTP frontend with
    ``replicas`` data-parallel engines behind the replica router.  The
    SAME stream runs once under random routing (the control: shared
    prompts scatter, every replica re-prefills every system prompt) and
    once under prefix-affinity (shared prompts land on the replica whose
    cache already holds their pages).  Value is streamed tokens per wall
    second of the affinity pass; the record carries both policies' TTFT,
    the affinity hit rate, and the per-replica load imbalance (max/mean
    outstanding tokens, sampled while the stream is in flight)."""
    import threading
    import time

    import numpy as np

    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.frontend import serve_background
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if smoke or backend == "cpu":
        cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                               ffn=128, seq=256)
        engine_kw = dict(max_num_seqs=4, block_size=8, max_model_len=256,
                         max_prefill_tokens=256, prefill_token_bucket=64)
    else:
        cfg = LlamaConfig(vocab_size=8192, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=1024)
        engine_kw = dict(max_num_seqs=16, block_size=16, max_model_len=1024,
                         max_prefill_tokens=2048, prefill_token_bucket=256)

    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(seed)
    stream = _prefix_stream(rng, n_requests, share_ways,
                            cfg.vocab_size, engine_kw["max_model_len"])
    # warm with DIFFERENT system prompts: compiles every program bucket
    # (cold prefill, hot chunked resume, decode) on every replica while
    # leaving the timed stream's prefixes uncached — otherwise two warm
    # passes of the real stream would park every prefix in every
    # replica's cache and random routing would measure as well as
    # affinity
    warm = _prefix_stream(np.random.RandomState(seed + 1), n_requests,
                          share_ways, cfg.vocab_size,
                          engine_kw["max_model_len"])

    import jax
    devs = jax.devices()
    if backend != "cpu" and len(devs) < replicas * tp:
        raise RuntimeError(
            f"{replicas} replicas x tp={tp} need {replicas * tp} devices, "
            f"JAX has {len(devs)}: replicas sharing a chip measure nothing")

    def make_engine(replica=0):
        # each replica on devices of its own (the CPU toy shares one)
        own = devs[replica * tp:(replica + 1) * tp]
        return LLMEngine(model, retain_outputs=False, kv_dtype=kv_dtype, weight_dtype=weight_dtype,
                         enable_prefix_caching=True, tp=tp,
                         devices=own if len(own) == tp else None,
                         **engine_kw)

    runs = {}
    for policy in ("random", "affinity"):
        srv = serve_background(make_engine(), model_name="bench",
                               max_pending=4 * len(stream),
                               engine_factory=make_engine,
                               replicas=replicas, router_policy=policy)
        router = srv.frontend.runner
        try:
            _http_drive(srv.port, warm)
            _http_drive(srv.port, warm)
            before = router.router_counters()
            imb, stop_ev = [], threading.Event()

            def sample(_r=router, _imb=imb, _ev=stop_ev):
                while not _ev.is_set():
                    vals = _r.router_counters()["outstanding_tokens"]
                    mean = sum(vals) / len(vals)
                    if mean > 0:
                        _imb.append(max(vals) / mean)
                    time.sleep(0.005)

            sampler = threading.Thread(target=sample, daemon=True)
            sampler.start()
            wall, results = _http_drive(srv.port, stream)
            stop_ev.set()
            sampler.join(timeout=5.0)
            counters = router.router_counters()
            runner_snap = router.stats_snapshot()
        finally:
            srv.stop()
        got = sum(len(r["tokens"]) for r in results if r)
        ttfts = sorted(r["ttft_s"] for r in results if r)
        # marginal counters: the timed pass only, not the warm passes
        hits = (counters["affinity_hit_total"]
                - before["affinity_hit_total"])
        routed_n = counters["routed_total"] - before["routed_total"]
        runs[policy] = {
            "tokens_per_s": got / wall if wall else 0.0,
            "ttfts": ttfts,
            "hit_rate": hits / routed_n if routed_n else 0.0,
            "imbalance": sum(imb) / len(imb) if imb else 0.0,
            "routed": [a - b for a, b in
                       zip(counters["routed_requests"],
                           before["routed_requests"])],
        }

    def _pct(vals, q):
        if not vals:
            return 0.0
        return 1e3 * vals[min(len(vals) - 1,
                              int(round(q / 100.0 * (len(vals) - 1))))]

    aff, rnd = runs["affinity"], runs["random"]
    return {
        "metric": "serve_router_tokens_per_s",
        "value": round(aff["tokens_per_s"], 2),
        "unit": "tok/s",
        "backend": backend,
        "requests": n_requests,
        "share_ways": share_ways,
        "router_policy": "affinity",
        "affinity_hit_rate": round(aff["hit_rate"], 4),
        "load_imbalance": round(aff["imbalance"], 3),
        "routed_requests": aff["routed"],
        "ttft_p50_ms": round(_pct(aff["ttfts"], 50), 3),
        "ttft_p99_ms": round(_pct(aff["ttfts"], 99), 3),
        "random_tokens_per_s": round(rnd["tokens_per_s"], 2),
        "random_ttft_p50_ms": round(_pct(rnd["ttfts"], 50), 3),
        "random_ttft_p99_ms": round(_pct(rnd["ttfts"], 99), 3),
        "random_load_imbalance": round(rnd["imbalance"], 3),
        "random_routed_requests": rnd["routed"],
        "speedup": round(aff["tokens_per_s"] / rnd["tokens_per_s"], 3)
        if rnd["tokens_per_s"] else 0.0,
        "kv_dtype": kv_dtype,
        # the loop ends on the affinity pass: its fleet-pooled snapshot
        **_slo_keys(runner_snap),
        **_window_keys(runner_snap),
    }


def _workload_fingerprint(payload: dict) -> str:
    """Stable id of (seed + workload-shaping config): sha1 over the
    canonical JSON of ``payload``.  The SAME fingerprint goes into the
    bench record and into ``--dump-workload``'s capture, so the fleet
    simulator's validation mode can prove it is replaying the exact
    stream that produced the record it scores against."""
    import hashlib

    blob = json.dumps(payload, sort_keys=True, separators=(",", ":"))
    return hashlib.sha1(blob.encode("utf-8")).hexdigest()[:16]


def _mixed_request_stream(rng, n_requests, vocab, max_len,
                          max_prefill_tokens):
    """The whole serving zoo in one arrival-scheduled stream: every 4th
    request is a LONG prompt (over the per-step prefill budget, so it
    resumes across chunked steps while other rows decode), the rest are
    short; prompts are motif-tiled so the n-gram drafter keeps proposing
    and verify rows interleave with plain decodes."""
    stream, step = [], 0
    for i in range(n_requests):
        step += int(rng.poisson(1.0))
        motif = rng.randint(0, vocab, int(rng.randint(2, 5))).tolist()
        if i % 4 == 0:
            n = int(rng.randint(max_prefill_tokens + 4,
                                max_prefill_tokens * 2))
        else:
            n = int(rng.randint(4, 17))
        prompt = (motif * (n // len(motif) + 1))[:n]
        max_new = int(rng.randint(12, min(41, max_len - n)))
        stream.append((step, prompt, max_new))
    return stream


def run_mixed_bench(smoke: bool, n_requests: int, seed: int, backend: str,
                    kv_dtype: str = "float32", tp: int = 1, tracer=None,
                    overlap: str = "on", weight_dtype: str = "float32",
                    dump_workload: str | None = None):
    """The ISSUE's headline workload: long prefills, chunked resumes,
    plain decodes, and speculative verify rounds all riding the ONE
    ragged step program.  Reports throughput, the exact attention
    program budget, and the padding-waste ratio (padded/real tokens)
    next to what the retired four-program engine would have padded for
    the same launches (``legacy_padding_waste_ratio``).

    Always runs BOTH async-pipeline arms over the same stream — the
    ``--overlap`` flag only picks which arm is the headline (and traced)
    one — so the record carries each arm's decode wall-clock plus its
    dispatch/block split and host-bubble fraction
    (``overlap_{on,off}_wall_s`` / ``_host_bubble_frac``)."""
    import numpy as np

    import paddle_tpu
    from paddle_tpu.inference import LLMEngine, NGramDrafter
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    paddle_tpu.seed(seed)

    if smoke or backend == "cpu":
        cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                               ffn=64, seq=256)
        engine_kw = dict(max_num_seqs=8, block_size=8, max_model_len=256,
                         max_prefill_tokens=64, prefill_token_bucket=32)
        spec_k = 3
    else:
        cfg = LlamaConfig(vocab_size=8192, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=1024)
        engine_kw = dict(max_num_seqs=16, block_size=16, max_model_len=1024,
                         max_prefill_tokens=256, prefill_token_bucket=128)
        spec_k = 4

    model = LlamaForCausalLM(cfg)

    def _mk_engine(ov: bool):
        return LLMEngine(model, enable_prefix_caching=True,
                         drafter=NGramDrafter(max_ngram=6, min_ngram=1),
                         spec_k=spec_k, max_spec_k=spec_k,
                         spec_accept_floor=0.0, kv_dtype=kv_dtype, weight_dtype=weight_dtype, tp=tp,
                         overlap=ov, **engine_kw)

    engine = _mk_engine(overlap != "off")
    engine.stats.enable_windows()
    rng = np.random.RandomState(seed)
    stream = _mixed_request_stream(rng, n_requests, cfg.vocab_size,
                                   engine_kw["max_model_len"],
                                   engine_kw["max_prefill_tokens"])
    total_new = sum(mn for _, _, mn in stream)

    fingerprint = _workload_fingerprint({
        "mode": "mixed", "seed": int(seed), "requests": int(n_requests),
        "smoke": bool(smoke or backend == "cpu"), "kv_dtype": kv_dtype,
        "weight_dtype": weight_dtype, "tp": int(tp),
        "engine_kw": engine_kw, "spec_k": spec_k,
        "vocab": cfg.vocab_size})
    if dump_workload:
        # everything the simulator needs to rebuild this run: the exact
        # stream plus the engine config that shaped its scheduling
        with open(dump_workload, "w", encoding="utf-8") as f:
            json.dump({
                "workload_fingerprint": fingerprint,
                "mode": "mixed",
                "seed": int(seed),
                "requests": int(n_requests),
                "engine_kw": engine_kw,
                "spec_k": spec_k,
                "vocab": cfg.vocab_size,
                "stream": [[step, list(map(int, prompt)), int(mn)]
                           for step, prompt, mn in stream],
            }, f, sort_keys=True)
            f.write("\n")

    _drive(engine, list(stream))         # warm pass: compile every bucket
    engine.stats.reset()
    for k in engine.pad_stats:           # ratio is for the timed pass only
        engine.pad_stats[k] = 0
    if tracer is not None:
        # trace the TIMED pass only: the warm pass's compiles would
        # drown the steady-state step phases the timeline is for
        engine.set_tracer(tracer)
    elapsed = _drive(engine, list(stream))
    s = engine.stats.summary()
    ps = dict(engine.pad_stats)

    # A/B arm: the same stream on an engine with the OPPOSITE overlap
    # setting (warm pass, then timed), so one record carries both the
    # async pipeline and the synchronous step for the same workload
    engine_b = _mk_engine(overlap == "off")
    _drive(engine_b, list(stream))
    engine_b.stats.reset()
    elapsed_b = _drive(engine_b, list(stream))
    s_b = engine_b.stats.summary()

    def _arm_keys(arm, wall, st):
        # host-bubble: the step wall time NOT spent blocked on the
        # device result (dispatch packing + apply/retire bookkeeping)
        step_s = st["step_time_s"]
        bubble = 1.0 - st["block_time_s"] / step_s if step_s else 0.0
        return {
            f"overlap_{arm}_wall_s": round(wall, 3),
            f"overlap_{arm}_tokens_per_s":
            round(total_new / wall, 2) if wall else 0.0,
            f"overlap_{arm}_dispatch_time_s": st["dispatch_time_s"],
            f"overlap_{arm}_block_time_s": st["block_time_s"],
            f"overlap_{arm}_host_bubble_frac": round(bubble, 4),
        }

    arm = "off" if overlap == "off" else "on"
    other = "on" if arm == "off" else "off"
    ab_keys = {"overlap": arm, **_arm_keys(arm, elapsed, s),
               **_arm_keys(other, elapsed_b, s_b)}

    if tracer is not None:
        # ride a handful of the same requests through the full serving
        # stack (HTTP SSE -> replica router -> runner -> engine) onto
        # the SAME ring, so one dumped trace shows request-correlated
        # spans from all four tiers next to the engine-direct timeline
        from paddle_tpu.inference.frontend import serve_background

        def _factory(replica=0):
            # same overlap arm as the headline engine, so the dumped
            # trace is internally consistent (an --overlap off artifact
            # carries zero engine.device_inflight windows anywhere)
            return LLMEngine(model, retain_outputs=False,
                             enable_prefix_caching=True,
                             kv_dtype=kv_dtype, weight_dtype=weight_dtype, tp=tp,
                             overlap=overlap != "off", **engine_kw)

        http_engine = _factory()
        http_engine.set_tracer(tracer)
        srv = serve_background(http_engine, model_name="bench",
                               replicas=2, engine_factory=_factory,
                               max_pending=4 * len(stream))
        try:
            _http_drive(srv.port,
                        [(i, prompt, max_new) for i, (_, prompt, max_new)
                         in enumerate(stream[:6])])
        finally:
            srv.stop()

    real = max(ps["real"], 1)
    waste = ps["padded"] / real
    legacy_waste = ps["legacy_padded"] / real
    return {
        "metric": "serve_mixed_tokens_per_s",
        "value": round(total_new / elapsed, 2) if elapsed else 0.0,
        "unit": "tok/s",
        "backend": backend,
        "requests": n_requests,
        "long_prompts": (n_requests + 3) // 4,
        "spec_k": spec_k,
        "new_tokens": total_new,
        "decode_tokens_per_s": s["decode_tokens_per_s"],
        "real_tokens": ps["real"],
        "padded_tokens": ps["padded"],
        "legacy_padded_tokens": ps["legacy_padded"],
        "padding_waste_ratio": round(waste, 3),
        "legacy_padding_waste_ratio": round(legacy_waste, 3),
        "padding_waste_reduction": round(
            1.0 - ps["padded"] / ps["legacy_padded"], 3)
        if ps["legacy_padded"] else 0.0,
        "attention_compiles": engine.compile_counts["ragged"],
        "attention_program_kinds": len(
            [k for k, v in engine.compile_counts.items()
             if v and k != "cow"]),
        "accept_rate": s["accept_rate"],
        "verify_steps": s["verify_steps"],
        "spec_rounds": s["spec_rounds"],
        "draft_proposed": s["draft_proposed"],
        "spec_emitted_tokens": s["spec_emitted_tokens"],
        "prefill_tokens": s["prefill_tokens"],
        "p50_token_ms": s["p50_token_ms"],
        "p99_token_ms": s["p99_token_ms"],
        "ttft_p50_ms": s["ttft_p50_ms"],
        "ttft_p95_ms": round(engine.stats.ttft_ms(95.0), 3),
        "ttft_p99_ms": s["ttft_p99_ms"],
        "preempted": s["preemptions"],
        "workload_fingerprint": fingerprint,
        **ab_keys,
        **_mem_keys(engine),
        **_slo_keys(engine.stats.snapshot()),
        **_window_keys(engine.stats.snapshot()),
    }


def run_chaos_bench(smoke: bool, n_requests: int, seed: int, backend: str,
                    kv_dtype: str = "float32", tp: int = 1,
                    weight_dtype: str = "float32"):
    """Goodput under injected faults: the ragged request stream runs
    through the supervised EngineRunner while a seeded FaultPlan crashes
    a step, hangs a step past the watchdog deadline, poisons a logit
    row, and fakes a pool-exhaustion window.  Value is tokens delivered
    to clients per wall second INCLUDING the recovery stalls — the
    self-healing tax, measured, not estimated."""
    import queue as queue_mod
    import time

    import numpy as np

    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.faults import FaultPlan
    from paddle_tpu.inference.frontend import EngineRunner
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if smoke or backend == "cpu":
        cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                               ffn=64, seq=64)
        engine_kw = dict(max_num_seqs=8, block_size=8, max_model_len=64,
                         max_prefill_tokens=64, prefill_token_bucket=128)
        step_deadline_s, slow_s = 12.0, 30.0
    else:
        cfg = LlamaConfig(vocab_size=8192, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=1024)
        engine_kw = dict(max_num_seqs=16, block_size=16, max_model_len=1024,
                         max_prefill_tokens=256, prefill_token_bucket=128)
        step_deadline_s, slow_s = 30.0, 75.0

    model = LlamaForCausalLM(cfg)

    def factory():
        return LLMEngine(model, retain_outputs=False, kv_dtype=kv_dtype, weight_dtype=weight_dtype,
                         tp=tp, **engine_kw)

    # the full schedule from one seed: one crash (in-thread recovery),
    # one hang past the watchdog deadline, one NaN row (quarantine), one
    # pool-exhaustion window (preempt + degradation pressure)
    plan = FaultPlan.seeded(seed, slow_s=slow_s, horizon=24)
    engine = factory()
    engine.stats.enable_windows()   # survives supervised rebuilds: the
    engine.set_fault_plan(plan)     # runner carries stats across engines
    runner = EngineRunner(engine, max_pending=4 * n_requests,
                          engine_factory=factory,
                          step_deadline_s=step_deadline_s).start()

    rng = np.random.RandomState(seed)
    queues = []
    t0 = time.perf_counter()
    for _ in range(n_requests):
        prompt = rng.randint(0, cfg.vocab_size,
                             int(rng.randint(4, 17))).tolist()
        q = queue_mod.Queue()
        queues.append(q)
        runner.submit(prompt, deliver=q.put_nowait,
                      max_new_tokens=int(rng.randint(8, 25)))
    outs = []
    for q in queues:
        while True:
            kind, val = q.get(timeout=600)
            if kind == "finish":
                outs.append(val)
                break
    wall = time.perf_counter() - t0
    drained = runner.drain(timeout_s=60.0)
    fin = runner.engine

    completed = [o for o in outs if o.finish_reason in ("eos", "length")]
    good_tokens = sum(len(o.generated) for o in completed)
    snap = fin.stats.snapshot()
    return {
        "metric": "serve_chaos_goodput_tokens_per_s",
        "value": round(good_tokens / wall, 2) if wall else 0.0,
        "unit": "tok/s",
        "backend": backend,
        "requests": n_requests,
        "completed": len(completed),
        "goodput_tokens": good_tokens,
        "wall_s": round(wall, 3),
        "engine_restarts": snap["engine_restarts"],
        "quarantined": snap["quarantined"],
        "fault_injections": snap["fault_injections"],
        "faults_exhausted": plan.exhausted(),
        "degradation_transitions": snap["degradation_transitions"],
        "preempted": snap["preemptions"],
        "attention_compiles": fin.compile_counts["ragged"],
        "leaked_pages": fin.blocks.num_used,
        "pool_clean": fin.blocks.num_used == 0,
        "drained": bool(drained),
        "finish_reasons": sorted({o.finish_reason for o in outs}),
        "step_deadline_s": step_deadline_s,
        **_mem_keys(fin),
        **_slo_keys(snap),
        **_window_keys(snap),
    }


def _pressure_stream(rng, n_requests, vocab):
    """Burst arrivals of medium prompts with modest decode budgets —
    sized so page residency, not compute, is the binding resource."""
    stream, step = [], 0
    for _ in range(n_requests):
        step += int(rng.poisson(0.3))
        prompt = rng.randint(0, vocab, 48).tolist()
        stream.append((step, prompt, 16))
    return stream


def _returning_stream(rng, n_requests, vocab, n_users=8):
    """Returning-user traffic for the spill-tier A/B: every prompt is
    one of ``n_users`` fixed 48-token prefixes, so a user whose parked
    pages were pressure-evicted comes BACK — which is the only traffic
    where a spill tier can matter.  Paced one arrival per two steps so
    revisits land after the evictions they need to profit from."""
    users = [rng.randint(0, vocab, 48).tolist() for _ in range(n_users)]
    stream, step = [], 0
    for _ in range(n_requests):
        step += 2
        stream.append((step, users[int(rng.randint(0, n_users))], 16))
    return stream


def _drive_outputs(engine, stream):
    """_drive, collecting every finished request's generated tokens in
    a deterministic (rid-sorted) order for byte-identity checks."""
    outs = {}
    step_no = 0
    pending = list(stream)
    while pending or engine.has_unfinished():
        while pending and pending[0][0] <= step_no:
            _, prompt, max_new = pending.pop(0)
            engine.add_request(prompt, max_new_tokens=max_new,
                               temperature=0.0)
        for fo in engine.step():
            outs[fo.rid] = tuple(fo.generated)
        step_no += 1
    return [outs[rid] for rid in sorted(outs)]


def _page_bytes(cfg, block_size, kv_dtype):
    """Per-page HBM cost for a dtype BEFORE building an engine — the
    pressure bench sizes pools from a byte budget, so both dtypes get
    the same silicon, not the same block count."""
    hd = cfg.hidden_size // cfg.num_attention_heads
    per = 2 * cfg.num_hidden_layers * cfg.num_key_value_heads \
        * block_size * hd * (1 if kv_dtype == "int8" else 4)
    if kv_dtype == "int8":
        # f32 scale rows ride in a parallel pool
        per += 2 * cfg.num_hidden_layers * cfg.num_key_value_heads * 4
    return per


def _drive_peak(engine, stream):
    """_drive plus per-step sampling of the KV-residency peak."""
    import time

    t0 = time.perf_counter()
    step_no, peak_bytes = 0, 0
    pending = list(stream)
    while pending or engine.has_unfinished():
        while pending and pending[0][0] <= step_no:
            _, prompt, max_new = pending.pop(0)
            engine.add_request(prompt, max_new_tokens=max_new)
        engine.step()
        peak_bytes = max(peak_bytes, engine.kv_bytes_resident())
        step_no += 1
    return time.perf_counter() - t0, peak_bytes


def run_pressure_bench(smoke: bool, n_requests: int, seed: int,
                       backend: str, kv_dtype: str, tp: int = 1,
                       weight_dtype: str = "float32",
                       host_kv_bytes: int = None):
    """Fixed-HBM A/B: the same burst stream runs on a float32 pool and
    a ``kv_dtype`` pool sized from the SAME byte budget, each with a
    DegradationController installed.  int8 pages are ~4x smaller, so
    the budget holds ~4x the blocks — the record shows how many more
    sequences stayed resident and how many preemptions / degradation
    tier entries that headroom avoided at matched traffic.

    A second matched-HBM A/B rides along: the same returning-user burst
    stream on the SAME pool with the host spill tier on vs off.  Both
    arms precompile the full bucket ladder, so the record's
    ``spill_compile_counts_equal`` verdict means the tier's restores
    introduced no programs, and ``spill_outputs_match`` pins restored
    bytes byte-identical to recomputed ones."""
    import numpy as np

    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.kv_tier import HostSpillPool
    from paddle_tpu.inference.pressure import DegradationController
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    # a residency proof, not a throughput race: one tiny config serves
    # every backend, sized so the float32 pool starves mid-stream
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                           ffn=64, seq=256)
    engine_kw = dict(max_num_seqs=16, block_size=8, max_model_len=256,
                     max_prefill_tokens=128, prefill_token_bucket=64)
    # the budget binds PER CHIP: under tp each shard holds 1/tp of every
    # page, so the same per-chip HBM affords tp x the page count
    budget = 52 * _page_bytes(cfg, engine_kw["block_size"], "float32") // tp

    model = LlamaForCausalLM(cfg)
    runs = {}
    for dt in ("float32", kv_dtype):
        nb = budget // (_page_bytes(cfg, engine_kw["block_size"], dt)
                        // tp)
        engine = LLMEngine(model, kv_dtype=dt, num_blocks=int(nb),
                           weight_dtype=weight_dtype,
                           pressure=DegradationController(), tp=tp,
                           **engine_kw)
        engine.stats.enable_windows()
        rng = np.random.RandomState(seed)
        stream = _pressure_stream(rng, n_requests, cfg.vocab_size)
        wall, peak_bytes = _drive_peak(engine, stream)
        s = engine.stats.summary()
        runs[dt] = {
            "num_blocks": int(nb),
            "kv_page_bytes": engine.kv_page_bytes(),
            "peak_resident_seqs": engine.peak_resident_seqs,
            "peak_kv_bytes_resident": int(peak_bytes),
            "kv_bytes_resident": engine.kv_bytes_resident(),
            "degradation_tier_entries": engine.degradation_tier_entries,
            "preempted": s["preemptions"],
            "retired": s["retired"],
            "wall_s": round(wall, 3),
        }
    dtype_snap = engine.stats.snapshot()  # the kv_dtype arm's windows

    # -- spill-tier A/B: same float32 pool, host tier on vs off --------
    # 2x the requests of the dtype A/B so each of the 8 users returns
    # often enough for pressure-evicted pages to be worth restoring
    tier_cap = int(host_kv_bytes) if host_kv_bytes else 4 * int(budget)
    spill = {}
    for cap in (0, tier_cap):
        tier = HostSpillPool(cap) if cap else None
        nb = budget // (_page_bytes(cfg, engine_kw["block_size"],
                                    "float32") // tp)
        engine = LLMEngine(model, kv_dtype="float32", num_blocks=int(nb),
                           weight_dtype=weight_dtype,
                           pressure=DegradationController(), tp=tp,
                           kv_tier=tier, **engine_kw)
        engine.precompile_buckets()
        compiles_pre = dict(engine.compile_counts)
        rng = np.random.RandomState(seed)
        stream = _returning_stream(rng, 2 * n_requests, cfg.vocab_size)
        outs = _drive_outputs(engine, stream)
        snap = engine.stats.snapshot()
        spill["on" if cap else "off"] = {
            "outs": outs,
            "compiles": dict(engine.compile_counts),
            "stream_compiled": engine.compile_counts != compiles_pre,
            "prefix_hit_rate": snap["prefix_hit_rate"],
            "re_prefill_tokens": snap["cache_miss_tokens"],
            "snap": snap,
        }
    on, off = spill["on"], spill["off"]
    q, base = runs[kv_dtype], runs["float32"]
    return {
        "metric": "serve_pressure_resident_seqs",
        "value": q["peak_resident_seqs"],
        "unit": "seqs",
        "backend": backend,
        "kv_dtype": kv_dtype,
        "requests": n_requests,
        "hbm_budget_bytes": int(budget),
        "num_blocks": q["num_blocks"],
        "baseline_num_blocks": base["num_blocks"],
        "kv_page_bytes": q["kv_page_bytes"],
        "baseline_kv_page_bytes": base["kv_page_bytes"],
        "peak_resident_seqs": q["peak_resident_seqs"],
        "baseline_peak_resident_seqs": base["peak_resident_seqs"],
        "resident_ratio": round(q["peak_resident_seqs"]
                                / base["peak_resident_seqs"], 3)
        if base["peak_resident_seqs"] else 0.0,
        "peak_kv_bytes_resident": q["peak_kv_bytes_resident"],
        "baseline_peak_kv_bytes_resident": base["peak_kv_bytes_resident"],
        "kv_bytes_resident": q["kv_bytes_resident"],
        "degradation_tier_entries": q["degradation_tier_entries"],
        "baseline_degradation_tier_entries":
            base["degradation_tier_entries"],
        "preempted": q["preempted"],
        "baseline_preempted": base["preempted"],
        "retired": q["retired"],
        "baseline_retired": base["retired"],
        # spill-tier A/B (host tier on vs off, same pool, same stream)
        "host_kv_bytes": tier_cap,
        "host_kv_bytes_resident": on["snap"]["host_kv_bytes_resident"],
        "kv_spilled_pages": on["snap"]["kv_pages_spilled"],
        "kv_restored_pages": on["snap"]["kv_pages_restored"],
        "spill_tier_hit_rate": on["snap"]["spill_tier_hit_rate"],
        "kv_prefetch_hit_pages": on["snap"]["kv_prefetch_hit_pages"],
        "spill_prefix_hit_rate": on["prefix_hit_rate"],
        "baseline_spill_prefix_hit_rate": off["prefix_hit_rate"],
        "spill_re_prefill_tokens": on["re_prefill_tokens"],
        "baseline_spill_re_prefill_tokens": off["re_prefill_tokens"],
        "spill_outputs_match": on["outs"] == off["outs"],
        "spill_compile_counts_equal": on["compiles"] == off["compiles"],
        "spill_stream_compiled": bool(on["stream_compiled"]
                                      or off["stream_compiled"]),
        **_slo_keys(dtype_snap),
        **_window_keys(dtype_snap),
    }


def run_weight_bench(smoke: bool, n_requests: int, seed: int,
                     backend: str, weight_dtype: str,
                     kv_dtype: str = "float32", tp: int = 1):
    """--weight-pressure: fixed-HBM A/B between a float32 weight pool
    and a ``--weight-dtype`` quantized one.  Both arms get the SAME
    per-chip byte budget (the f32 weights plus 52 f32-era KV pages);
    the bytes the quantized pool hands back buy extra KV pages, so the
    record shows the residency headroom weight streaming creates at
    matched silicon — plus the roofline-modeled decode cost of the
    tuned ``quant_matmul`` kernel against the dense f32 XLA matmul at
    a llama-sm projection shape."""
    import numpy as np

    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.pressure import DegradationController
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.tune import cost
    from paddle_tpu.tune.registry import candidate_configs, get_kernel

    # --weight-dtype float32 still wants an A/B: default the quantized
    # arm to int8 so the mode always measures something
    wdt = weight_dtype if weight_dtype != "float32" else "int8"
    cfg = LlamaConfig.tiny(vocab=64, hidden=32, layers=2, heads=4,
                           ffn=64, seq=256)
    engine_kw = dict(max_num_seqs=16, block_size=8, max_model_len=256,
                     max_prefill_tokens=128, prefill_token_bucket=64)
    page = _page_bytes(cfg, engine_kw["block_size"], kv_dtype) // tp
    model = LlamaForCausalLM(cfg)

    # probe builds measure each arm's resident weight bytes; the f32
    # number anchors the shared budget (weights + 52 f32-sized pages,
    # binding PER CHIP like the KV pressure bench)
    weight_bytes = {}
    for dt in ("float32", wdt):
        # 33 = one full max_model_len sequence + the manager's null block
        probe = LLMEngine(model, num_blocks=33, kv_dtype=kv_dtype,
                          weight_dtype=dt, tp=tp, **engine_kw)
        weight_bytes[dt] = probe.weight_bytes_resident()
    budget = weight_bytes["float32"] // tp \
        + 52 * _page_bytes(cfg, engine_kw["block_size"], "float32") // tp

    runs = {}
    for dt in ("float32", wdt):
        nb = max(33, (budget - weight_bytes[dt] // tp) // page)
        engine = LLMEngine(model, kv_dtype=kv_dtype, weight_dtype=dt,
                           num_blocks=int(nb),
                           pressure=DegradationController(), tp=tp,
                           **engine_kw)
        engine.stats.enable_windows()
        rng = np.random.RandomState(seed)
        stream = _pressure_stream(rng, n_requests, cfg.vocab_size)
        wall, peak_bytes = _drive_peak(engine, stream)
        s = engine.stats.summary()
        runs[dt] = {
            "num_blocks": int(nb),
            "weight_bytes_resident": engine.weight_bytes_resident(),
            "peak_resident_seqs": engine.peak_resident_seqs,
            "peak_kv_bytes_resident": int(peak_bytes),
            "preempted": s["preemptions"],
            "retired": s["retired"],
            "wall_s": round(wall, 3),
        }

    # modeled decode cost of ONE llama-sm decoder layer's matmuls
    # (4x qkv/o projections, gate+up, down): best tuned quant_matmul
    # candidate per shape vs the one-program dense f32 XLA contraction
    m = engine_kw["max_num_seqs"]
    layer_shapes = [(512, 512)] * 4 + [(512, 1408)] * 2 + [(1408, 512)]
    kern = get_kernel("quant_matmul")
    quant_s = sum(
        min(cost.estimate("quant_matmul",
                          {"m": m, "k": k, "n": n, "dtype": wdt}, c)
            for c in candidate_configs(kern))
        for k, n in layer_shapes)
    f32_s = sum(cost.f32_matmul_estimate(m, k, n)
                for k, n in layer_shapes)

    q, base = runs[wdt], runs["float32"]
    return {
        "metric": "serve_weight_resident_seqs",
        "value": q["peak_resident_seqs"],
        "unit": "seqs",
        "backend": backend,
        "weight_dtype": wdt,
        "kv_dtype": kv_dtype,
        "requests": n_requests,
        "hbm_budget_bytes": int(budget),
        "weight_bytes_resident": q["weight_bytes_resident"],
        "baseline_weight_bytes_resident": base["weight_bytes_resident"],
        "weight_compression_ratio": round(
            base["weight_bytes_resident"] / q["weight_bytes_resident"], 3)
        if q["weight_bytes_resident"] else 0.0,
        "num_blocks": q["num_blocks"],
        "baseline_num_blocks": base["num_blocks"],
        "peak_resident_seqs": q["peak_resident_seqs"],
        "baseline_peak_resident_seqs": base["peak_resident_seqs"],
        "resident_ratio": round(q["peak_resident_seqs"]
                                / base["peak_resident_seqs"], 3)
        if base["peak_resident_seqs"] else 0.0,
        "peak_kv_bytes_resident": q["peak_kv_bytes_resident"],
        "baseline_peak_kv_bytes_resident": base["peak_kv_bytes_resident"],
        "preempted": q["preempted"],
        "baseline_preempted": base["preempted"],
        "retired": q["retired"],
        "baseline_retired": base["retired"],
        "modeled_decode_layer_s": quant_s,
        "modeled_f32_layer_s": f32_s,
        "modeled_decode_cost_ratio": round(f32_s / quant_s, 3)
        if quant_s else 0.0,
        **_slo_keys(engine.stats.snapshot()),
        **_window_keys(engine.stats.snapshot()),
    }


def run_window_bench(smoke: bool, n_requests: int, window_k: int,
                     seed: int, backend: str, kv_dtype: str = "float32",
                     tp: int = 1, weight_dtype: str = "float32"):
    """--decode-window K: one steady pure-decode workload, A/B'd between
    the per-step engine (decode_window=1) and the device-resident
    K-step window engine — same prompts, same budgets, greedy, so the
    outputs must match byte-for-byte and the only difference is how
    often the host blocked on the device.  The headline value is the
    window arm's decode tok/s, but on CPU hosts the honest win is
    ``decode_window_host_round_trips_per_token`` (~1.0 per-step,
    -> ~1/K windowed): round-trip COUNT is hardware-independent, the
    latency each trip costs is not."""
    import time

    import numpy as np

    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if smoke or backend == "cpu":
        cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                               ffn=128, seq=128)
        engine_kw = dict(max_num_seqs=4, block_size=8, max_model_len=128,
                         max_prefill_tokens=256, prefill_token_bucket=64)
        max_new = 48
    else:
        cfg = LlamaConfig(vocab_size=8192, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=1024)
        engine_kw = dict(max_num_seqs=16, block_size=16, max_model_len=1024,
                         max_prefill_tokens=2048, prefill_token_bucket=256)
        max_new = 128
    # every request admitted up front, at most one per slot: after the
    # shared prefill the whole stream is the steady pure-decode state
    # the window targets, so windows (not the fallback) carry the run
    n_rows = max(1, min(n_requests, engine_kw["max_num_seqs"]))
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab_size,
                           int(rng.randint(8, 17))).tolist()
               for _ in range(n_rows)]

    def arm(k):
        eng = LLMEngine(model, kv_dtype=kv_dtype, weight_dtype=weight_dtype, tp=tp,
                        decode_window=k, **engine_kw)
        eng.stats.enable_windows()
        eng.add_request(prompts[0][:4], max_new_tokens=max(4, 2 * k))
        eng.run()                      # compile outside the timed pass
        eng.stats.reset()
        rids = [eng.add_request(p, max_new_tokens=max_new)
                for p in prompts]
        outs = {}
        t0 = time.perf_counter()
        while eng.has_unfinished():
            for fo in eng.step():
                outs[fo.rid] = list(fo.generated)
        wall = time.perf_counter() - t0
        return eng, [outs[r] for r in rids], wall

    base_eng, base_out, base_wall = arm(1)
    win_eng, win_out, win_wall = arm(window_k)
    b = base_eng.stats.summary()
    w = win_eng.stats.summary()
    return {
        "metric": "serve_window_tokens_per_s",
        "value": w["decode_tokens_per_s"],
        "unit": "tok/s",
        "backend": backend,
        "requests": n_rows,
        "max_new_tokens": max_new,
        "outputs_match": base_out == win_out,
        "window_wall_s": round(win_wall, 4),
        "baseline_wall_s": round(base_wall, 4),
        "baseline_tokens_per_s": b["decode_tokens_per_s"],
        "baseline_host_round_trips": b["host_round_trips"],
        "baseline_host_round_trips_per_token":
            _window_keys(b)["decode_window_host_round_trips_per_token"],
        "host_round_trips": w["host_round_trips"],
        "tokens_per_launch": w["tokens_per_launch"],
        "decode_window_fallbacks": w["decode_window_fallbacks"],
        "window_compiles": win_eng.compile_counts.get("scan", 0),
        "p50_token_ms": w["p50_token_ms"],
        "p99_token_ms": w["p99_token_ms"],
        **_window_keys(w),
        **_mem_keys(win_eng),
        **_slo_keys(win_eng.stats.snapshot()),
    }


def run_bench(smoke: bool, n_requests: int, seed: int, backend: str,
              kv_dtype: str = "float32", tp: int = 1,
              weight_dtype: str = "float32"):
    import numpy as np

    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if smoke or backend == "cpu":
        cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                               ffn=128, seq=128)
        engine_kw = dict(max_num_seqs=4, block_size=8, max_model_len=128,
                         max_prefill_tokens=256, prefill_token_bucket=64)
    else:
        # TPU: serving-shaped tiny-llama (kernel-eligible head_dim 128)
        cfg = LlamaConfig(vocab_size=8192, hidden_size=1024,
                          intermediate_size=2816, num_hidden_layers=4,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=1024)
        engine_kw = dict(max_num_seqs=16, block_size=16, max_model_len=1024,
                         max_prefill_tokens=2048, prefill_token_bucket=256)

    model = LlamaForCausalLM(cfg)
    engine = LLMEngine(model, kv_dtype=kv_dtype, weight_dtype=weight_dtype, tp=tp, **engine_kw)
    engine.stats.enable_windows()
    rng = np.random.RandomState(seed)
    stream = _request_stream(rng, n_requests, cfg.vocab_size,
                             engine_kw["max_model_len"])

    # warmup: compile prefill+decode outside the timed stats
    wid = engine.add_request(stream[0][1], max_new_tokens=4)
    engine.run()
    engine.stats.reset()

    step_no = 0
    pending = list(stream)
    while pending or engine.has_unfinished():
        while pending and pending[0][0] <= step_no:
            _, prompt, max_new = pending.pop(0)
            engine.add_request(prompt, max_new_tokens=max_new)
        engine.step()
        step_no += 1

    s = engine.stats.summary()
    return {
        "metric": "serve_decode_tokens_per_s",
        "value": s["decode_tokens_per_s"],
        "unit": "tok/s",
        "backend": backend,
        "p50_token_ms": s["p50_token_ms"],
        "p99_token_ms": s["p99_token_ms"],
        "batch_occupancy": s["mean_batch_occupancy"],
        "decode_compiles": engine.num_decode_programs,
        "prefill_compiles": engine.num_prefill_programs,
        "requests": n_requests,
        "preempted": s["preemptions"],
        "decode_tokens": s["decode_tokens"],
        **_mem_keys(engine),
        **_slo_keys(engine.stats.snapshot()),
        **_window_keys(engine.stats.snapshot()),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + short stream (CI / CPU)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefix-share", type=int, default=None, metavar="K",
                    help="shared-prefix workload with K distinct system "
                         "prompts; runs cache off vs on and reports the "
                         "speedup + cache surface")
    ap.add_argument("--spec", type=int, default=None, metavar="K",
                    help="repetitive-text workload with the n-gram drafter "
                         "proposing K tokens; runs speculation off vs on "
                         "and reports the speedup + acceptance surface")
    ap.add_argument("--http", action="store_true",
                    help="drive the same workload through the real HTTP "
                         "frontend (concurrent SSE clients on localhost) "
                         "next to an engine-direct run")
    ap.add_argument("--slo", action="store_true",
                    help="drive the stream through the HTTP frontend with "
                         "the SLO observatory armed (windowed telemetry, "
                         "flight recorder, anomaly spool) and build the "
                         "record from GET /slo and GET /debug/requests")
    ap.add_argument("--mixed", action="store_true",
                    help="interleave long prefills, chunked resumes, plain "
                         "decodes and speculative verify rounds in one "
                         "stream; report the padding-waste ratio of the "
                         "single ragged program vs the retired per-phase "
                         "programs")
    ap.add_argument("--chaos", action="store_true",
                    help="run the stream through the supervised runner "
                         "under a seeded FaultPlan (crash, hang, NaN row, "
                         "pool window); report goodput including the "
                         "recovery stalls")
    ap.add_argument("--kv-dtype", choices=("float32", "int8"),
                    default="float32",
                    help="KV-page dtype for every engine the bench "
                         "builds (int8 = quantized pages + f32 scale "
                         "pools, dequantized in-kernel)")
    ap.add_argument("--memory-pressure", action="store_true",
                    help="size the page pool from a fixed HBM byte "
                         "budget and run the same burst stream on a "
                         "float32 pool vs a --kv-dtype pool; report "
                         "resident sequences, preemptions and "
                         "degradation tier entries for both")
    ap.add_argument("--host-kv-bytes", type=int, default=None,
                    metavar="BYTES",
                    help="with --memory-pressure: host spill-tier "
                         "capacity for the tier-on A/B arm (default "
                         "4x the HBM page budget)")
    ap.add_argument("--weight-dtype", choices=("float32", "int8", "int4"),
                    default="float32",
                    help="weight-pool dtype for every engine the bench "
                         "builds (int8/int4 = quantized pools + f32 "
                         "scales, dequantized in the fused quant_matmul "
                         "kernel)")
    ap.add_argument("--weight-pressure", action="store_true",
                    help="A/B a float32 weight pool vs a --weight-dtype "
                         "quantized one under the SAME per-chip HBM "
                         "budget (weights + pages); report resident "
                         "weight bytes, the KV headroom they free, and "
                         "the roofline-modeled decode matmul cost")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="tensor-parallel shards for every engine the "
                         "bench builds (heads + KV pages split over an "
                         "N-way mesh inside one compiled step; host "
                         "devices forced on CPU)")
    ap.add_argument("--replicas", type=int, default=1, metavar="D",
                    help="with --http: D data-parallel engine replicas "
                         "behind the prefix-affinity router, A/B'd "
                         "against random routing on the shared-prefix "
                         "workload")
    ap.add_argument("--decode-window", type=int, default=None,
                    metavar="K",
                    help="A/B the device-resident K-step decode window "
                         "engine against the per-step one on a steady "
                         "pure-decode stream; the record carries "
                         "decode_window_{k,tokens_per_s,"
                         "host_round_trips_per_token} and the "
                         "byte-identity verdict")
    ap.add_argument("--overlap", choices=("on", "off"), default="on",
                    help="with --mixed: which async-pipeline arm is the "
                         "headline (and --trace'd) one; BOTH arms always "
                         "run and land in the record, this picks the one "
                         "the tok/s value and the timeline describe")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="with --mixed: record a step timeline of the "
                         "timed pass (plus a short HTTP/router pass so "
                         "all four tiers appear) and write it as Chrome "
                         "trace-event JSON — open in ui.perfetto.dev or "
                         "feed tools/perf/step_timeline.py")
    ap.add_argument("--dump-workload", default=None, metavar="OUT.json",
                    help="with --mixed: write the exact request stream "
                         "(step-indexed arrivals, token ids) plus the "
                         "engine config, fingerprint-linked to the "
                         "record, for paddle_tpu.sim validation replay")
    args = ap.parse_args(argv)

    if args.tp > 1 and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        # must land before this process's first jax import (they are all
        # function-local below)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.tp}").strip()

    from paddle_tpu.core.runtime import (configure_compile_cache,
                                         resolve_device)
    configure_compile_cache()
    device = resolve_device()
    backend = device["platform"]
    if args.http and args.replicas > 1:
        n_requests = args.requests or (16 if (args.smoke
                                              or backend == "cpu") else 64)
        record = {"metric": "serve_router_tokens_per_s", "value": 0.0,
                  "unit": "tok/s", "backend": backend}
    elif args.decode_window:
        n_requests = args.requests or (4 if (args.smoke
                                             or backend == "cpu") else 16)
        record = {"metric": "serve_window_tokens_per_s", "value": 0.0,
                  "unit": "tok/s", "backend": backend}
    elif args.weight_pressure:
        n_requests = args.requests or 16
        record = {"metric": "serve_weight_resident_seqs", "value": 0.0,
                  "unit": "seqs", "backend": backend}
    elif args.memory_pressure:
        n_requests = args.requests or 16
        record = {"metric": "serve_pressure_resident_seqs", "value": 0.0,
                  "unit": "seqs", "backend": backend}
    elif args.chaos:
        n_requests = args.requests or (8 if (args.smoke or backend == "cpu")
                                       else 32)
        record = {"metric": "serve_chaos_goodput_tokens_per_s",
                  "value": 0.0, "unit": "tok/s", "backend": backend}
    elif args.mixed:
        n_requests = args.requests or (16 if (args.smoke
                                              or backend == "cpu") else 64)
        record = {"metric": "serve_mixed_tokens_per_s", "value": 0.0,
                  "unit": "tok/s", "backend": backend}
    elif args.slo:
        n_requests = args.requests or (8 if (args.smoke or backend == "cpu")
                                       else 32)
        record = {"metric": "serve_slo_tokens_per_s", "value": 0.0,
                  "unit": "tok/s", "backend": backend}
    elif args.http:
        n_requests = args.requests or (8 if (args.smoke or backend == "cpu")
                                       else 32)
        record = {"metric": "serve_http_tokens_per_s", "value": 0.0,
                  "unit": "tok/s", "backend": backend}
    elif args.spec:
        n_requests = args.requests or (16 if (args.smoke
                                              or backend == "cpu") else 64)
        record = {"metric": "serve_spec_tokens_per_s", "value": 0.0,
                  "unit": "tok/s", "backend": backend}
    elif args.prefix_share:
        n_requests = args.requests or (16 if (args.smoke
                                              or backend == "cpu") else 64)
        record = {"metric": "serve_prefix_tokens_per_s", "value": 0.0,
                  "unit": "tok/s", "backend": backend}
    else:
        n_requests = args.requests or (8 if (args.smoke or backend == "cpu")
                                       else 64)
        record = {"metric": "serve_decode_tokens_per_s", "value": 0.0,
                  "unit": "tok/s", "backend": backend}
    record["tp"] = args.tp
    record["replicas"] = args.replicas
    record["weight_dtype"] = args.weight_dtype
    record["device"] = device
    tracer = None
    if args.trace:
        if args.mixed:
            from paddle_tpu.profiler import Tracer
            tracer = Tracer()
        else:
            record["trace_note"] = "--trace records the --mixed workload"
    if args.http and args.replicas > 1:
        record.update(run_router_bench(
            args.smoke, n_requests, args.prefix_share or 4,
            args.seed, backend, args.kv_dtype, args.replicas,
            args.tp, weight_dtype=args.weight_dtype))
    elif args.decode_window:
        record.update(run_window_bench(
            args.smoke, n_requests, args.decode_window, args.seed,
            backend, args.kv_dtype, args.tp,
            weight_dtype=args.weight_dtype))
    elif args.weight_pressure:
        record.update(run_weight_bench(args.smoke, n_requests,
                                       args.seed, backend,
                                       args.weight_dtype,
                                       kv_dtype=args.kv_dtype,
                                       tp=args.tp))
    elif args.memory_pressure:
        record.update(run_pressure_bench(
            args.smoke, n_requests, args.seed, backend,
            args.kv_dtype, args.tp,
            weight_dtype=args.weight_dtype,
            host_kv_bytes=args.host_kv_bytes))
    elif args.chaos:
        record.update(run_chaos_bench(
            args.smoke, n_requests, args.seed, backend,
            args.kv_dtype, args.tp, weight_dtype=args.weight_dtype))
    elif args.mixed:
        record.update(run_mixed_bench(
            args.smoke, n_requests, args.seed, backend,
            args.kv_dtype, args.tp, tracer=tracer,
            overlap=args.overlap,
            weight_dtype=args.weight_dtype,
            dump_workload=args.dump_workload))
    elif args.slo:
        record.update(run_slo_bench(
            args.smoke, n_requests, args.seed, backend,
            args.kv_dtype, args.tp, weight_dtype=args.weight_dtype))
    elif args.http:
        record.update(run_http_bench(
            args.smoke, n_requests, args.seed, backend,
            args.kv_dtype, args.tp, weight_dtype=args.weight_dtype))
    elif args.spec:
        record.update(run_spec_bench(
            args.smoke, n_requests, args.spec, args.seed, backend,
            args.kv_dtype, args.tp, weight_dtype=args.weight_dtype))
    elif args.prefix_share:
        record.update(run_prefix_bench(
            args.smoke, n_requests, args.prefix_share, args.seed,
            backend, args.kv_dtype, args.tp,
            weight_dtype=args.weight_dtype))
    else:
        record.update(run_bench(
            args.smoke, n_requests, args.seed, backend,
            args.kv_dtype, args.tp, weight_dtype=args.weight_dtype))
    record["tp"] = args.tp
    record["replicas"] = args.replicas
    record["weight_dtype"] = args.weight_dtype
    # every record carries a workload fingerprint; modes that build
    # their stream internally (mixed) stamp a richer one themselves
    record.setdefault("workload_fingerprint", _workload_fingerprint({
        "mode": record.get("metric", ""), "seed": args.seed,
        "requests": n_requests, "smoke": bool(args.smoke),
        "kv_dtype": args.kv_dtype, "weight_dtype": args.weight_dtype,
        "tp": args.tp, "replicas": args.replicas,
        "backend": record.get("backend", "")}))
    if tracer is not None:
        try:
            record["trace_events"] = tracer.dump(args.trace)
            record["trace_path"] = args.trace
            record["trace_dropped_events"] = tracer.dropped
            record["trace_unbalanced_spans"] = tracer.unbalanced
        except Exception as e:
            record.setdefault("error", f"{type(e).__name__}: {e}")
    try:
        # post-baseline race-lint count over the serving stack this bench
        # just exercised — bench_history gates on it staying 0, so a race
        # regression fails the perf gate even when throughput is fine
        from paddle_tpu.analysis import (default_baseline_path,
                                         filter_baseline, load_baseline,
                                         race_lint_paths)
        from paddle_tpu.analysis.race_rules import default_race_paths
        repo = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        record["race_findings"] = len(filter_baseline(
            race_lint_paths(default_race_paths(repo), root=repo),
            load_baseline(default_baseline_path())))
    except Exception as e:
        record.setdefault("error", f"{type(e).__name__}: {e}")
    _emit(record)
    return 0


if __name__ == "__main__":
    sys.exit(main())
