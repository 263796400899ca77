"""CLI: serve a model over HTTP.

    python -m paddle_tpu.inference.frontend --model llama-sm
    curl -N http://127.0.0.1:8000/v1/completions \\
      -d '{"prompt": [1, 17, 29], "max_tokens": 32, "stream": true}'

Model presets (randomly-initialised weights — this CLI demonstrates and
load-tests the serving stack; checkpoint loading arrives with the HF
bridge):

    tiny       2-layer toy (vocab 256) — starts in seconds, CPU-friendly
    llama-sm   ~8-layer small config — a realistic serving shape
    llama-7b   LLaMA-7B widths; 32 layers do not fit one 16 GB chip, so
               cut depth with --layers and hold weights in --dtype
               bfloat16 (8 layers: 3.8 GB of weights, held twice while
               the engine keeps its layer-stacked copy)
    mla-moe-sm a small latent-attention (MLA) decoder with a leading dense
               layer and expert layers after it (8 experts, 3 a token)
    sarvam-105b  sarvam-105b's widths (MLA, 128 routed experts, 8 a
               token, a shared expert) as ONE chip of an expert-parallel-4
               host holds it: 32 of the 128 experts a layer, a quarter of
               the vocabulary.  Cut depth with --layers (6: one dense and
               five expert layers, 10.9 GB in bfloat16, held once: the
               engine serves the model's own arrays)
    smallthinker-sm  a small decoder of global (no positions) and
               sliding-window (rotary) layers, period 4, every layer
               with ReGLU experts routed from the pre-attention norm (8
               experts, 3 a token, window 64).  Two page pools and two
               block tables: serve it with --no-prefix-caching
    smallthinker-21b  SmallThinker-21BA3B-Instruct's widths (28 query
               heads over 4 K/V heads of 128, 64 experts of width 768, 6
               a token, window 4096).  Cut depth with --layers (8: two
               periods, 7.9 GB in bfloat16, held once)
    laguna-sm  a small decoder of full and sliding-window layers, period
               4, that differ in their query heads (6 and 8 over 2 K/V
               heads) and rotary (half the head with YaRN frequencies,
               the whole head plain), a gated attention output, a dense
               first layer then 16 small experts (4 a token) beside a
               shared one, window 64.  Serve it with --no-prefix-caching
    laguna-xs2  Laguna-XS.2's widths (48 and 64 query heads over 8 K/V
               heads of 128, 256 experts of width 512, 8 a token, window
               512).  Cut depth with --layers (7: the dense layer and six
               sparse ones, 11.3 GB in bfloat16, held once)
    dots3-sm   a small latent-attention decoder whose full layers attend
               to the 16 keys a learned indexer selects and whose
               sliding-window layers (window 24) keep a wider latent of
               their own, a headwise gate on both, a dense first layer
               then 8 experts (3 a token) beside a shared one.  Three
               pools (latents, index keys, the window layers' latents):
               serve it with --no-prefix-caching
    dots3-note-prev  dots3-note-prev's widths (128 heads on a 512-wide
               latent behind a 64-head top-2048 indexer; 64 heads on a
               1024-wide latent under a window of 513; 256 experts of
               width 1536, 8 a token) as ONE chip of an expert-parallel-8
               slice holds it: 32 of the 256 experts a layer, an eighth
               of the vocabulary.  Cut depth with --layers (5: the dense
               layer and one period, 8.2 GB in bfloat16, held once)
    phi4flash-sm  a small decoder-hybrid-decoder: Mamba-1 layers with a
               state a sequence beside the pages, differential attention
               under a window of 24 and over all, gated memory units and
               cross layers that read ONE layer's K/V (12 layers, 4 query
               heads over 2 K/V heads).  Serve it with --no-prefix-caching
    phi4-mini-flash-reasoning  Phi-4-mini-flash-reasoning WHOLE: 32
               layers (9 Mamba-1 of d_inner 5120 and 16 states, 8
               differential-attention layers under a window of 512 and
               one over all, 40 query heads over 20 K/V heads of 64, 7
               memory units, 7 cross layers), a tied 200064-token head:
               7.7 GB in bfloat16, held once; no --layers cut is needed

The process computes on whatever device JAX resolves, and says which on
its start-up line together with the attention and matmul paths the
engine chose.  Landing on the CPU without JAX_PLATFORMS=cpu is an error.

SIGINT/SIGTERM trigger a graceful drain: admissions stop (503),
in-flight streams finish, the engine thread parks, then the process
exits.  A second SIGINT aborts in-flight work instead of finishing it.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import sys


def _ensure_host_devices(n: int) -> None:
    """Make sure XLA exposes >= n host devices for --tp on CPU.  Must
    run BEFORE the first jax import (which is why every jax import in
    this module is function-local)."""
    if n <= 1:
        return
    flag = f"--xla_force_host_platform_device_count={n}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()


def _model_config(args):
    from paddle_tpu.models.llama import LlamaConfig

    if args.model == "tiny":
        cfg = LlamaConfig.tiny(vocab=256, hidden=64, layers=2, heads=4,
                               ffn=128, seq=args.max_model_len or 256)
    elif args.model == "llama-sm":
        cfg = LlamaConfig(vocab_size=8192, hidden_size=512,
                          intermediate_size=1408, num_hidden_layers=8,
                          num_attention_heads=8, num_key_value_heads=8,
                          max_position_embeddings=args.max_model_len or 2048)
    elif args.model == "llama-7b":
        cfg = LlamaConfig.llama_7b()
        if args.max_model_len:
            cfg.max_position_embeddings = args.max_model_len
    elif args.model == "mla-moe-sm":
        from paddle_tpu.models.mla_moe import MlaMoeConfig
        cfg = MlaMoeConfig.tiny(vocab=512, hidden=128, layers=4, heads=4,
                                experts=8, seq=args.max_model_len or 1024)
    elif args.model == "sarvam-105b":
        from paddle_tpu.models.mla_moe import MlaMoeConfig
        cfg = MlaMoeConfig(
            vocab_size=262144 // 4, experts_held=32, ep_size=4, ep_rank=0,
            max_position_embeddings=args.max_model_len or 16384)
    elif args.model == "smallthinker-sm":
        from paddle_tpu.models.smallthinker import SmallThinkerConfig
        cfg = SmallThinkerConfig.tiny(
            vocab=512, hidden=128, layers=8, heads=7, kv_heads=1,
            head_dim=32, experts=8, active=3, ffn=64, window=64,
            seq=args.max_model_len or 1024)
    elif args.model == "smallthinker-21b":
        from paddle_tpu.models.smallthinker import SmallThinkerConfig
        cfg = SmallThinkerConfig(
            max_position_embeddings=args.max_model_len or 16384)
    elif args.model == "laguna-sm":
        from paddle_tpu.models.laguna import LagunaConfig
        cfg = LagunaConfig.tiny(
            vocab=512, hidden=128, layers=7, full_heads=6, window_heads=8,
            kv_heads=2, head_dim=32, experts=16, active=4, ffn=64,
            dense_ffn=256, window=64, seq=args.max_model_len or 1024)
    elif args.model == "laguna-xs2":
        from paddle_tpu.models.laguna import LagunaConfig
        cfg = LagunaConfig(
            max_position_embeddings=args.max_model_len or 16384)
    elif args.model == "dots3-sm":
        from paddle_tpu.models.dots3 import Dots3Config
        cfg = Dots3Config.tiny(vocab=512, hidden=128, layers=5, experts=8,
                               topk=16, window=24,
                               seq=args.max_model_len or 1024)
    elif args.model == "dots3-note-prev":
        from paddle_tpu.models.dots3 import Dots3Config
        cfg = Dots3Config(
            vocab_size=152064 // 8, experts_held=32, ep_size=8, ep_rank=0,
            max_position_embeddings=args.max_model_len or 32768)
    elif args.model == "phi4flash-sm":
        from paddle_tpu.models.phi4flash import Phi4FlashConfig
        cfg = Phi4FlashConfig.tiny(vocab=512, hidden=128, layers=12,
                                   heads=4, kv_heads=2, ffn=256, window=24,
                                   seq=args.max_model_len or 1024)
    elif args.model == "phi4-mini-flash-reasoning":
        from paddle_tpu.models.phi4flash import Phi4FlashConfig
        cfg = Phi4FlashConfig(
            max_position_embeddings=args.max_model_len or 8192)
    else:
        raise SystemExit(f"unknown --model {args.model!r}")
    if args.layers:
        cfg.num_hidden_layers = args.layers
    return cfg


def _refuse_unservable(args, engine) -> None:
    """Refuse at start-up what would otherwise serve through a path the
    flags did not ask for.  ``--kv-dtype int8`` exists to run the int8
    page kernel; where the engine found on a TPU that the kernel does
    not take its block size or its pool, every step would go through
    the dense fake-quant gather instead."""
    p = engine.paths()
    if args.kv_dtype == "int8" and p["platform"] == "tpu" \
            and not p["attention"].startswith("pallas"):
        raise SystemExit(
            "--kv-dtype int8: the int8 page kernel does not take this "
            f"configuration: {p['attention']}")


def _build_engine(args, cfg):
    import jax

    import paddle_tpu
    from paddle_tpu.models.llama import LlamaForCausalLM
    from ..serving import LLMEngine

    paddle_tpu.seed(0)
    if getattr(cfg, "architecture", None) == "mla_moe":
        from paddle_tpu.models.mla_moe import MlaMoeForCausalLM
        # drawn leaf by leaf in the served type: no float32 model first
        model = MlaMoeForCausalLM(cfg, dtype=args.dtype)
    elif getattr(cfg, "architecture", None) == "smallthinker":
        from paddle_tpu.models.smallthinker import SmallThinkerForCausalLM
        model = SmallThinkerForCausalLM(cfg, dtype=args.dtype)
    elif getattr(cfg, "architecture", None) == "laguna":
        from paddle_tpu.models.laguna import LagunaForCausalLM
        model = LagunaForCausalLM(cfg, dtype=args.dtype)
    elif getattr(cfg, "architecture", None) == "dots3":
        from paddle_tpu.models.dots3 import Dots3ForCausalLM
        model = Dots3ForCausalLM(cfg, dtype=args.dtype)
    elif getattr(cfg, "architecture", None) == "phi4flash":
        from paddle_tpu.models.phi4flash import Phi4FlashForCausalLM
        model = Phi4FlashForCausalLM(cfg, dtype=args.dtype)
    else:
        model = LlamaForCausalLM(cfg)
        if args.dtype != "float32":
            model.to(dtype=args.dtype)
    drafter = "ngram" if args.spec_k > 0 else None
    need = args.tp * args.replicas
    if len(jax.devices()) < need:
        raise SystemExit(
            f"--tp {args.tp} x --replicas {args.replicas} needs {need} "
            f"devices and JAX has {len(jax.devices())}: every replica "
            "computes on devices of its own")

    def make_engine(replica: int = 0):
        # shares the model (same weights!) so supervised recovery can
        # rebuild the engine and replay journals byte-identically; each
        # replica holds its own copy of them on its own devices
        kv_tier = None
        if args.host_kv_bytes > 0:
            # per-engine tier: each replica spills to its own host pool
            # (chain hashes are replica-local residency claims).  A
            # supervised rebuild gets a fresh tier — spilled pages are
            # a cache, not state recovery depends on.
            from ..kv_tier import HostSpillPool
            kv_tier = HostSpillPool(args.host_kv_bytes)
        return LLMEngine(
            model, max_num_seqs=args.max_num_seqs,
            block_size=args.block_size,
            max_model_len=cfg.max_position_embeddings,
            max_prefill_tokens=args.max_prefill_tokens,
            enable_prefix_caching=not args.no_prefix_caching,
            drafter=drafter, spec_k=args.spec_k,
            kv_dtype=args.kv_dtype, weight_dtype=args.weight_dtype,
            tp=args.tp, retain_outputs=False, kv_tier=kv_tier,
            devices=jax.devices()[replica * args.tp:
                                  (replica + 1) * args.tp])

    return make_engine


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.inference.frontend",
        description="Serve an LLM over HTTP (OpenAI-style /v1/completions "
                    "with SSE streaming, /healthz, /metrics).")
    ap.add_argument("--model", default="tiny",
                    choices=["tiny", "llama-sm", "llama-7b", "mla-moe-sm",
                             "sarvam-105b", "smallthinker-sm",
                             "smallthinker-21b", "laguna-sm",
                             "laguna-xs2", "dots3-sm", "dots3-note-prev",
                             "phi4flash-sm", "phi4-mini-flash-reasoning"])
    ap.add_argument("--layers", type=int, default=0,
                    help="depth cut: build this many decoder layers "
                         "(0 = the preset's depth); widths are never cut")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="dtype the weights are held and the step "
                         "computes in (KV pages follow it unless "
                         "--kv-dtype int8)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000)
    ap.add_argument("--max-num-seqs", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--max-model-len", type=int, default=0,
                    help="0 = the preset's max_position_embeddings")
    ap.add_argument("--max-prefill-tokens", type=int, default=512)
    ap.add_argument("--no-prefix-caching", action="store_true")
    ap.add_argument("--kv-dtype", default="float32",
                    choices=["float32", "int8"],
                    help="KV page storage dtype; int8 quarters the page "
                         "pool's HBM cost (per-page scales, in-kernel "
                         "dequant) for 2x+ resident sequences")
    ap.add_argument("--weight-dtype", default="float32",
                    choices=["float32", "int8", "int4"],
                    help="weight pool storage dtype; int8/int4 cut "
                         "resident weight bytes 4x/8x (per-channel "
                         "scales, fused dequant-matmul kernel)")
    ap.add_argument("--host-kv-bytes", type=int, default=0,
                    help="host-DRAM KV spill tier capacity per engine "
                         "replica, in bytes: pressure-evicted parked "
                         "pages spill there instead of dying and are "
                         "restored HBM-side when their prefix returns "
                         "(0 disables the tier)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative draft length (0 disables; >0 enables "
                         "the n-gram drafter)")
    ap.add_argument("--max-pending", type=int, default=0,
                    help="admission bound before shedding 429s "
                         "(0 = 4 x max-num-seqs)")
    ap.add_argument("--deadline-ms", type=float, default=0,
                    help="default per-request deadline (0 = none)")
    ap.add_argument("--drain-timeout-s", type=float, default=30.0)
    ap.add_argument("--step-deadline-s", type=float, default=0,
                    help="supervised recovery: rebuild the engine and "
                         "replay in-flight requests when a step crashes "
                         "or runs past this wall budget (0 = off)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards per engine: heads and KV "
                         "pages split over a tp-way mesh inside one "
                         "compiled step (byte-identical to --tp 1; on CPU "
                         "host devices are forced automatically)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel engine replicas behind one "
                         "listener, fed by the replica router")
    ap.add_argument("--router-policy", default="affinity",
                    choices=["affinity", "least", "random"],
                    help="replica routing: prefix-affinity (shared "
                         "prompts land on the replica already holding "
                         "their KV pages), least-outstanding-tokens, or "
                         "random (ignored with --replicas 1)")
    ap.add_argument("--flight-capacity", type=int, default=512,
                    help="per-replica flight-recorder bound for "
                         "GET /debug/requests (0 disables)")
    ap.add_argument("--anomaly-spool", default=None, metavar="DIR",
                    help="directory for anomaly-triggered trace "
                         "captures: slow-step/slow-request outliers "
                         "snapshot the trace window + slowest flight "
                         "records there (bounded; drops are counted)")
    ap.add_argument("--slo-ttft-p95-ms", type=float, default=500.0,
                    help="SLO objective: 95%% of first tokens under "
                         "this many ms")
    ap.add_argument("--slo-itl-p99-ms", type=float, default=200.0,
                    help="SLO objective: 99%% of inter-token intervals "
                         "under this many ms")
    ap.add_argument("--slo-deadline-attainment", type=float, default=0.99,
                    help="SLO objective: fraction of deadline-carrying "
                         "requests that must finish in budget")
    ap.add_argument("--slo-availability", type=float, default=0.999,
                    help="SLO objective: fraction of requests that must "
                         "finish without error/quarantine")
    return ap


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    _ensure_host_devices(args.tp * args.replicas)
    from paddle_tpu.core.runtime import (CompileWatch,
                                         configure_compile_cache,
                                         resolve_device)
    cache_dir = configure_compile_cache()
    watch = CompileWatch()
    device = resolve_device()
    cfg = _model_config(args)
    print(f"[frontend] building {args.model} engine"
          + (f" x{args.replicas}" if args.replicas > 1 else "")
          + (f" (tp={args.tp})" if args.tp > 1 else "")
          + f" on {json.dumps(device)}, compile cache {cache_dir} ...",
          flush=True)
    make_engine = _build_engine(args, cfg)
    engine = make_engine()
    _refuse_unservable(args, engine)

    from .app import ServingFrontend
    frontend = ServingFrontend(
        engine, model_name=args.model, host=args.host, port=args.port,
        max_pending=args.max_pending or None,
        default_deadline_s=(args.deadline_ms / 1e3
                            if args.deadline_ms else None),
        engine_factory=(make_engine if args.step_deadline_s
                        or args.replicas > 1 else None),
        step_deadline_s=args.step_deadline_s or None,
        replicas=args.replicas, router_policy=args.router_policy,
        slo_config={"ttft_p95_ms": args.slo_ttft_p95_ms,
                    "itl_p99_ms": args.slo_itl_p99_ms,
                    "deadline_attainment": args.slo_deadline_attainment,
                    "availability": args.slo_availability},
        flight_capacity=args.flight_capacity,
        anomaly_spool=args.anomaly_spool, compile_watch=watch)

    async def run():
        await frontend.start()
        for i, e in enumerate(frontend.engines):
            p = e.paths()
            print(f"[frontend] replica {i}: layers="
                  f"{cfg.num_hidden_layers} dtype={args.dtype} "
                  f"devices={p['devices']} attention={p['attention']!r} "
                  f"matmul={p['matmul']!r}", flush=True)
        print(f"[frontend] listening on http://{frontend.host}:"
              f"{frontend.port}  (model={args.model}, "
              f"platform={device['platform']}, "
              f"device_kind={device['kind']!r}, "
              f"max_num_seqs={engine.max_num_seqs})", flush=True)
        stop = asyncio.Event()
        second = asyncio.Event()
        hits = {"n": 0}

        def on_signal():
            hits["n"] += 1
            stop.set()
            if hits["n"] > 1:
                second.set()

        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, on_signal)
            except NotImplementedError:
                pass
        serve = asyncio.ensure_future(frontend.serve_forever())
        await stop.wait()
        impatient = hits["n"] > 1
        print("[frontend] draining "
              f"({frontend.runner.inflight()} in flight"
              f"{', aborting' if impatient else ''}) ...", flush=True)
        drain = asyncio.ensure_future(frontend.shutdown(
            drain_timeout_s=args.drain_timeout_s,
            abort_inflight=impatient))
        if not impatient:
            # a second signal at ANY point during the drain escalates:
            # abort the in-flight set so the drain completes now
            escalate = asyncio.ensure_future(second.wait())
            done, _ = await asyncio.wait(
                {drain, escalate}, return_when=asyncio.FIRST_COMPLETED)
            if drain not in done:
                n = frontend.runner.abort_all("shutdown")
                print(f"[frontend] second signal: aborting {n} in-flight "
                      "request(s) ...", flush=True)
            escalate.cancel()
        drained = await drain
        serve.cancel()
        print(f"[frontend] {'drained' if drained else 'DRAIN TIMED OUT'}; "
              "bye", flush=True)
        return 0 if drained else 1

    return asyncio.run(run())


if __name__ == "__main__":
    sys.exit(main())
