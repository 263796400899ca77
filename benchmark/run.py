"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One cell, one process tree.  The last line of standard output is the
result; everything else is on earlier lines.  See PERF.md.

Nothing here knows a cell, a model or a metric: ``--workload`` names an
entry of ``BENCHMARK.json``, and ``harness/spec.py`` finds the rest by
name (its docstring has the table): the configuration's file, the
traffic file and its generator, each metric's reader, and by the
configuration's ``reference`` the architecture's three files (plain
reference, shapes and counts, the builder of the program's model)."""
from __future__ import annotations

import time

T_START_NS = time.monotonic_ns()

import argparse
import gc
import hashlib
import json
import os
import shutil
import subprocess
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for p in (HERE, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

from harness import client, compare, loadgen, spec, stats  # noqa: E402

OUT_DIR = os.path.join(REPO, ".bench_out")
SAMPLE_REQUESTS = 12


def say(*a):
    print(*a, flush=True)


def _overlay(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _overlay(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def rehearsal_traffic_file(traffic: dict) -> str:
    """A shrunk mix written where the generator's child can read it,
    under a name made from its content and moved into place whole: two
    rehearsals at once (the tests under several workers) share a file
    only where they share the mix."""
    body = json.dumps(traffic, sort_keys=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, "rehearsal_traffic.%s.json"
                        % hashlib.sha256(body.encode()).hexdigest()[:12])
    with open(f"{path}.{os.getpid()}", "w", encoding="utf-8") as f:
        f.write(body)
    os.replace(f"{path}.{os.getpid()}", path)
    return path


class _Profile:
    """A device trace of part of the window, started and stopped from a
    thread of its own."""

    def __init__(self, t_open_ns: int, seconds: float, trace_dir: str):
        self.dir = trace_dir
        self.start_at = t_open_ns + int(0.3 * seconds * 1e9)
        self.length = min(10.0, 0.4 * seconds)
        self.t0 = self.t1 = self.t_mark = None
        self.error = None
        self.thread = threading.Thread(target=self._run, name="bench-prof",
                                       daemon=True)
        self.thread.start()

    def _run(self):
        import jax
        try:
            shutil.rmtree(self.dir, ignore_errors=True)
            wait = (self.start_at - time.monotonic_ns()) / 1e9
            if wait > 0:
                time.sleep(wait)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t0 = time.monotonic_ns()
            with jax.profiler.TraceAnnotation("bench.mark"):
                self.t_mark = time.monotonic_ns()
                time.sleep(0.001)
            time.sleep(self.length)
            self.t1 = time.monotonic_ns()
            jax.profiler.stop_trace()
        except Exception as e:                      # reported, not hidden
            self.error = f"{type(e).__name__}: {e}"

    def join(self):
        self.thread.join(timeout=300)


def run_cell(bench: dict, cell: dict, cfg: dict, traffic_file: str,
             traffic: dict, seed: int, seconds: float, trace: bool,
             device: dict, watch, chip_start_s: float = 0.0,
             lower: str | None = None, break_path=None) -> dict:
    """Everything of a run after the look for a chip.  ``break_path`` is
    for the tests: a callable given the engine before it serves, to
    break the timed path underneath."""
    import jax

    from harness import server, spans as S

    split: dict = {"chip_start_s": chip_start_s,
                   "imports_s": (time.monotonic_ns() - T_START_NS) / 1e9
                   - chip_start_s}
    tracer = server.new_tracer() if trace else None
    model = server.build_model(cfg, seed, split)
    engine = server.build_engine(cfg, model, split)
    if break_path is not None:
        break_path(engine)
    paths = engine.paths()
    say("[bench] paths", json.dumps({k: paths[k] for k in
                                     ("platform", "device_kind", "attention",
                                      "matmul")}))
    srv = server.start_frontend(engine, cfg["name"], watch, tracer)

    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "harness", "loadgen.py"),
         "--port", str(srv.port), "--traffic", traffic_file,
         "--vocab", str(cfg["vocab_size"]), "--seed", str(seed),
         "--seconds", str(seconds)],
        stdout=subprocess.PIPE, stderr=None, text=True, cwd=REPO)
    t_serve = time.monotonic()
    got: dict = {}
    prof = None
    try:
        for line in child.stdout:
            line = line.strip()
            if not line:
                continue
            ev = json.loads(line)
            kind = ev["event"]
            if kind == "warm_done":
                split["warm_requests_s"] = ev["seconds"]
                got["compile_warm"] = watch.snapshot()
            elif kind == "window_open":
                got["t_open"] = ev["t_ns"]
                split["loop_warm_s"] = ev["loop_warm_s"] \
                    - split.get("warm_requests_s", 0.0)
                # process start to window open, less the start of the
                # chip's runtime: the machine's, 8 to 17 s from run to run
                got["setup_s"] = (ev["t_ns"] - T_START_NS) / 1e9 \
                    - chip_start_s
                got["c0"] = server.counters(engine)
                got["compile0"] = watch.snapshot()
                if trace:
                    prof = _Profile(ev["t_ns"], seconds,
                                    os.path.join(OUT_DIR, "trace"))
            elif kind == "window_close":
                got["c1"] = server.counters(engine)
                got["compile1"] = watch.snapshot()
                got["mem_peak"] = max(
                    (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in jax.local_devices())
            elif kind == "records":
                got["records"] = ev["records"]
                got["t_open"], got["t_close"] = ev["t_open"], ev["t_close"]
        rc = child.wait(timeout=120)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    if prof is not None:
        prof.join()
    # the engine's own map from instruction to scope, of the programs it
    # built, asked while it is there to ask
    program_scopes = {}
    if trace and hasattr(engine, "program_scopes"):
        program_scopes = engine.program_scopes()
        say("[bench] program_scopes", json.dumps(
            {k: len(v) for k, v in sorted(program_scopes.items())}))
    host_spans = S.normalise(tracer.events()) if tracer is not None else []
    tracer_dropped = tracer.dropped if tracer is not None else 0
    srv.stop(drain_timeout_s=20.0, abort_inflight=True)
    if rc != 0 or "records" not in got or "t_open" not in got:
        raise SystemExit(f"[bench] the load generator gave no records "
                         f"(exit {rc}, after {time.monotonic() - t_serve:.0f} s)")

    # the program's state goes before the reference runs
    srv = engine = model = tracer = None
    gc.collect()
    jax.clear_caches()
    gc.collect()

    records = got["records"]
    t_open, t_close = got["t_open"], got["t_close"]
    view = client.window_view(records, t_open, t_close)
    e2e = client.end_to_end(view)
    e2e["setup_s"] = got["setup_s"]
    attempted, failed, short = client.failures(records)
    pool = compare.finished_in_window(records, t_open, t_close)
    compiles_in_window = (got["compile1"]["cache_hits"]
                          + got["compile1"]["cache_misses"]
                          - got["compile0"]["cache_hits"]
                          - got["compile0"]["cache_misses"])

    say("[bench] setup split", json.dumps(
        {k: round(v, 3) for k, v in split.items()}))
    say("[bench] compile", json.dumps(
        {"before_window": got["compile0"],
         "in_window_lookups": compiles_in_window,
         "in_window_seconds": got["compile1"]["compile_seconds"]
         - got["compile0"]["compile_seconds"]}))
    say("[bench] window", json.dumps(
        {"seconds": view["seconds"], "tokens": view["tokens"],
         "gaps": len(view["gaps_ms"]), "ttfts": len(view["ttfts_ms"]),
         "ttfts_ms": sorted(round(t) for t in view["ttfts_ms"]),
         "finished": len(pool),
         "thirds_tokens_per_s": client.thirds(records, t_open, t_close)}))
    say("[bench] gaps", json.dumps(
        {"edges_ms": list(client.GAP_EDGES_MS),
         "counts": stats.histogram(view["gaps_ms"], client.GAP_EDGES_MS),
         **{k: e2e.get(k) for k in ("gap_p50_ms", "gap_p95_ms", "gap_p99_ms",
                                    "gap_top5_mean_ms")}}))
    steps = (got["c1"].get("engine_steps") or 0) \
        - (got["c0"].get("engine_steps") or 0)
    say("[bench] counters", json.dumps(
        {k: (got["c1"].get(k) or 0) - (got["c0"].get(k) or 0)
         for k in ("engine_steps", "cache_hit_tokens", "cache_miss_tokens",
                   "cow_copies", "preemptions", "admitted", "retired",
                   "step_time_s", "dispatch_time_s", "block_time_s")}))

    # ---- correct: the served tokens against the plain reference ----
    t_ref = time.monotonic()
    reference = spec.load_reference(cfg["reference"])
    kind_mod = loadgen.load_kind(traffic["kind"])
    sample = compare.draw_sample(pool, seed, SAMPLE_REQUESTS)
    seqs, n_prompt = [], []
    for r in sample:
        prompt = kind_mod.rebuild_prompt(traffic, seed, r, cfg["vocab_size"])
        seqs.append(prompt + list(r["tokens"]))
        n_prompt.append(len(prompt))
    lim = cfg["correct"]
    numbers = []
    served = {"max": float("inf"), "mean": float("inf"), "tokens": 0}
    if seqs:
        served = compare.served_gaps(
            reference, cfg, seed, seqs, n_prompt,
            pad_to=int(traffic["reference_pad_to"]),
            n_score=int(traffic["reference_score_rows"]))
    numbers.append({"name": "served_gap_max", "value": served["max"],
                    "limit": lim["served_gap_max"], "sense": "max"})
    numbers.append({"name": "served_gap_mean",
                    "value": served.get("mean", float("inf")),
                    "limit": lim["served_gap_mean"], "sense": "max"})
    numbers.append({"name": "tokens_compared", "value": served["tokens"],
                    "limit": lim["tokens_compared_min"], "sense": "min"})
    numbers.append({"name": "requests_failed", "value": failed,
                    "limit": 0, "sense": "max"})
    numbers.append({"name": "compiles_in_window", "value": compiles_in_window,
                    "limit": 0, "sense": "max"})
    extra = {}
    if lower is not None and seqs:
        ctl = compare.served_gaps(
            reference, cfg, seed, seqs, n_prompt,
            pad_to=int(traffic["reference_pad_to"]),
            n_score=int(traffic["reference_score_rows"]), lower=lower)
        extra["control"] = {"lower": lower, **ctl,
                            "correct": ctl["max"] <= lim["served_gap_max"]
                            and ctl["mean"] <= lim["served_gap_mean"]}
        say("[bench] control", json.dumps(extra["control"]))
    ref_s = time.monotonic() - t_ref
    say("[bench] reference", json.dumps(
        {"seconds": round(ref_s, 2), "requests": len(seqs),
         "lengths": [len(s) for s in seqs], "served": served}))
    correct = compare.verdict(numbers)

    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": got["mem_peak"]}
    metrics: dict = {}
    breakdown = None
    if not trace:
        for m in spec.metrics_for(bench, "end_to_end", cell["name"]):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        say("[bench] end_to_end (traced run, for information)",
            json.dumps(e2e))
        ctx = {"cfg": cfg, "traffic": traffic, "cell": cell,
               "records": records, "t_open": t_open, "t_close": t_close,
               "view": view, "e2e": e2e, "spans": host_spans,
               "tracer_dropped": tracer_dropped,
               "c0": got["c0"], "c1": got["c1"],
               "memory_peak_bytes": got["mem_peak"],
               "device_kind": device["kind"], "trace": None,
               "arch": spec.load_shapes(cfg["reference"]),
               "program_scopes": program_scopes}
        if prof is not None and prof.error is None:
            try:
                ctx["trace"] = _reduce_trace(prof, cfg, host_spans)
            except Exception as e:
                say(f"[bench] trace reduction failed: "
                    f"{type(e).__name__}: {e}")
        elif prof is not None:
            say(f"[bench] profiler failed: {prof.error}")
        if ctx["trace"] is not None:
            dev["busy_s"] = ctx["trace"]["busy_s"]
            dev["window_s"] = ctx["trace"]["window_s"]
            breakdown = ctx["trace"]["breakdown"]
        reader_s = {}
        for m in spec.metrics_for(bench, "per_layer", cell["name"]):
            t_reader = time.monotonic()
            try:
                v = spec.load_reader(m["name"])(ctx)
            except Exception as e:
                say(f"[bench] reader {m['name']} failed: "
                    f"{type(e).__name__}: {e}")
                v = None
            reader_s[m["name"]] = time.monotonic() - t_reader
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        # what the traced run paid to read its trace: the first reader
        # of the scoped events pays for reading them (kept in ctx)
        slowest = max(reader_s, key=reader_s.get, default=None)
        print("[bench] reduction", json.dumps(
            {**(ctx["trace"] or {}).get("cost", {}),
             "readers_s": sum(reader_s.values()), "readers": len(reader_s),
             "slowest_reader": [slowest, reader_s.get(slowest)],
             "process_s": (time.monotonic_ns() - T_START_NS) / 1e9}),
            file=sys.stderr, flush=True)

    for n in numbers:
        print(f"[bench] compared {n['name']} = {n['value']!r} "
              f"(limit {n['sense']} {n['limit']!r})", file=sys.stderr)
    print(f"[bench] correct = {correct}", file=sys.stderr, flush=True)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["extra"] = {"seed": seed, "workload": cell["name"],
                       "reference_s": ref_s, "short_outputs": short,
                       "engine_steps": steps, **extra}
    result["compared"] = {n["name"]: {"value": n["value"], "limit": n["limit"]}
                          for n in numbers}
    return result


def _engine_spans(host_spans: list, t0: int, t1: int) -> list:
    """The engine thread's spans that an idle gap of the traced window
    (``t0`` to ``t1`` on the host clock) can go to, in the order they
    have in ``host_spans``: not the wrappers round a whole turn, and not
    a span that ends before the window or begins after it, which covers
    no gap inside."""
    return [s for s in host_spans if s["ph"] == "X"
            and s["name"].startswith("engine.")
            and s["name"] not in ("engine.step", "engine.dispatch",
                                  "engine.complete",
                                  "engine.device_inflight")
            and s["ts"] < t1 and s["ts"] + s["dur"] > t0]


def _reduce_trace(prof: _Profile, cfg: dict, host_spans: list) -> dict:
    """``cost``, beside what the readers read: the seconds each part of
    the reduction took and the sizes of the two lists it walked, for the
    ``[bench] reduction`` line."""
    from harness import xplane as X

    stamps = [time.monotonic()]

    def lap() -> float:
        stamps.append(time.monotonic())
        return stamps[-1] - stamps[-2]

    data = X.read_planes(X.find_xplane(prof.dir))
    planes = X.device_plane_names(data)
    if not planes:
        raise RuntimeError("no device plane in the trace")
    plane = planes[0]
    events = X.read_device_events(data, plane)
    if not events:
        raise RuntimeError(f"no operation on {plane} in the trace")
    mark = X.find_host_marker(data, "bench.mark")
    cost = {"read_xplane_s": lap()}
    if mark is not None:
        offset = mark - prof.t_mark            # device clock - host clock
    else:
        offset = events[0]["start_ns"] - prof.t0
    w0, w1 = prof.t0 + offset, prof.t1 + offset
    evs = X.clip(events, w0, w1)
    busy = X.busy_ns(evs)
    selfs = X.self_times(evs)
    ops = sorted(X.by_label(selfs).items(), key=lambda kv: -kv[1])
    cost["clip_busy_self_s"] = lap()
    engine_spans = _engine_spans(host_spans, prof.t0, prof.t1)
    gaps = X.idle_gaps(evs, w0, w1)
    cost["idle_gaps_s"] = lap()
    idle = sorted(X.attribute_gaps(gaps, engine_spans, offset).items(),
                  key=lambda kv: -kv[1])
    cost.update(attribute_gaps_s=lap(), gaps=len(gaps),
                spans=len(engine_spans), host_events=len(host_spans),
                device_events=len(events))
    return {"plane": plane, "events": selfs, "window": (w0, w1),
            "offset_ns": offset, "host_window": (prof.t0, prof.t1),
            "busy_s": busy / 1e9, "window_s": (w1 - w0) / 1e9, "cost": cost,
            "breakdown": {
                "device_ops": [[k, v / 1e9] for k, v in ops[:10]],
                "idle_gaps": [[k, v / 1e9] for k, v in idle[:10]]}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--lower", default=None,
                    help="also read the control: the reference in this "
                         "lower precision in the program's place")
    ap.add_argument("--rehearsal", default=None,
                    help="a file of overrides that shrink the cell for a "
                         "CPU rehearsal; skips the look for a chip")
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    cfg = spec.load_config(bench, cell["config"])
    traffic_file = spec.traffic_path(cell["traffic"])
    traffic = spec.load_traffic(cell["traffic"])
    if args.rehearsal:
        with open(args.rehearsal, encoding="utf-8") as f:
            over = json.load(f)
        cfg = _overlay(cfg, over.get("config", {}))
        traffic = _overlay(traffic, over.get("traffic", {}))
        traffic_file = rehearsal_traffic_file(traffic)

    from harness import server
    cache_dir, watch, device, chip_start_s = server.start_jax()
    say("[bench] device", json.dumps(device), "compile cache", cache_dir)
    if not args.rehearsal:
        if device["platform"] != "tpu":
            print(f"[bench] this cell runs on a TPU; JAX reports "
                  f"{device['platform']!r}", file=sys.stderr)
            return 3
        if device["count"] < int(cell["chips"]):
            print(f"[bench] the cell asks for {cell['chips']} chips; JAX "
                  f"reports {device['count']}", file=sys.stderr)
            return 3
    result = run_cell(bench, cell, cfg, traffic_file, traffic, args.seed,
                      args.seconds, bool(args.trace), device, watch,
                      chip_start_s=chip_start_s, lower=args.lower)
    watch.close()
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
