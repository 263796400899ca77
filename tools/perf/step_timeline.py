#!/usr/bin/env python
"""Host/device attribution from a serving step-timeline trace.

Reads the Chrome trace-event JSON that ``serve_bench --trace OUT.json``
(or ``LLMEngine.dump_trace`` / ``GET /debug/trace``) writes, and answers
the question the raw Perfetto view makes you eyeball: where does one
engine step's wall-clock go, and how much of it is HOST bookkeeping
parked next to an idle accelerator?

Per engine-step phase ("engine.admit" .. "engine.retire") it prints
count, p50/p95/total milliseconds and the share of summed step time,
then three derived numbers:

  host-bubble fraction   host-phase time (admit/schedule/pack/
                         block-table-stage/sample-commit/retire plus the
                         untracked step remainder) over summed step time
                         — the fraction of the step the device program
                         is NOT the thing being waited on
  device fraction        device_launch + block_on_result over step time
  overlap opportunity    per step, min(packing, device_launch), packing
                         being ``pack_ns`` + ``table_ns`` of
                         ``engine.schedule`` (an older trace's pack +
                         block_table_stage): the host packing work that an
                         async engine could overlap UNDER the previous
                         step's device span; summed, as a fraction of
                         step time.  This is the number the async-engine
                         roadmap item banks on.
  overlap achieved       host-phase time that actually ran INSIDE an
                         ``engine.device_inflight`` window (the async
                         engine's launch→materialize span, emitted at
                         completion).  Zero on a synchronous trace or
                         with ``--overlap off`` — this is the measured
                         payoff of the async pipeline, reported next to
                         the opportunity it was sized against.

Usage:
  python tools/perf/step_timeline.py TRACE.json
  python tools/perf/step_timeline.py TRACE.json --fit sim_calibration.json

Last stdout line is a one-line JSON record (same contract as the other
tools/perf benches) with metric ``step_timeline_host_bubble_frac``
(plus ``step_timeline_overlap_achieved_frac`` as a secondary key).

Two analysis details added for the fleet simulator:

* **Ring-head repair.**  The tracer's ring drops OLDEST events, so a
  long recording's surviving window can open mid-span: inner phase
  events whose parent ``engine.step`` was dropped, and a first step
  whose own phases were partially dropped.  Counting those orphans
  charges host time against no step and skews every fraction, so when
  the trace reports ``dropped_events`` the analysis clips, per engine
  track, everything before the end of the first surviving step (and
  that suspect step itself) — reported as ``head_clipped_events`` /
  ``head_clipped_steps``.

* **``--fit OUT.json``** fits the simulator's ``CostModel`` from the
  trace: each ``engine.step`` span is joined with the ragged tokens and
  rows its ``engine.schedule`` packed (an older trace's ``engine.pack``
  args), then total step wall time regresses on
  packed tokens (base + per-token line), pure-decode steps
  (tokens == rows) tabulate a median-by-rows refinement, and the
  host-only share (step minus device phases) calibrates what a decode
  window amortizes.  ``--flight FLIGHT.json`` (the ``/debug/requests``
  flight-recorder dump) adds queue-wait/TTFT distribution summaries to
  the calibration's meta for cross-checking.  The output is exactly
  what ``paddle_tpu.sim.CostModel.from_json`` loads.
"""
from __future__ import annotations

import argparse
import json
import sys

# (``engine.pack`` and ``engine.block_table_stage`` are in traces
# recorded before ``engine.schedule`` ran on over the packing of the rows
# it chose: since then it carries what they did, ``rows``, ``tokens``,
# ``pack_ns`` and ``table_ns``)
_HOST_PHASES = ("engine.admit", "engine.schedule", "engine.pack",
                "engine.block_table_stage", "engine.sample_commit",
                "engine.retire")
_DEVICE_PHASES = ("engine.device_launch", "engine.block_on_result")


def _packing_us(ev) -> float:
    """What an ``engine.schedule`` span spent packing its launch."""
    a = ev.get("args", {})
    return (a.get("pack_ns", 0) + a.get("table_ns", 0)) / 1e3
_PHASE_ORDER = ("engine.admit", "engine.schedule", "engine.pack",
                "engine.block_table_stage", "engine.device_launch",
                "engine.block_on_result", "engine.sample_commit",
                "engine.retire")
# async-pipeline WRAPPER spans: they contain the leaf phases above (and
# engine.device_inflight brackets whole launch→materialize windows), so
# counting them as phases would double-charge host time and drive the
# untracked remainder negative.  They feed the overlap-achieved
# computation instead.  (``engine.prestage`` is what engines before the
# dispatch-ahead pipeline wrote; a trace without it reads the same.)
_WRAPPER_SPANS = ("engine.dispatch", "engine.complete", "engine.prestage",
                  "engine.device_inflight")


def _pct(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def load_trace(path):
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    events = doc.get("traceEvents", [])
    tracks = {}                           # tid -> track name
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            tracks[ev["tid"]] = ev["args"]["name"]
    return doc, events, tracks


def _engine_spans(doc, events, tracks):
    """(steps, inner, inflight, head_clipped_events, head_clipped_steps)
    over every engine track, with the ring-buffer head repaired.

    When the ring dropped its oldest events, the surviving window can
    begin mid-span: inner phase events orphaned from a dropped
    ``engine.step`` parent, plus a first step whose own phases were
    partially dropped.  Per engine track, clip everything before the
    end of the first surviving step and discard that suspect step —
    attribution then only ever charges phases against steps that are
    whole.  A clean trace (``dropped_events == 0``) clips nothing.
    """
    engine_tids = {tid for tid, name in tracks.items()
                   if name == "engine" or name.startswith("engine-")}
    xs = [ev for ev in events if ev.get("ph") == "X"
          and ev["tid"] in engine_tids]
    steps = sorted((ev for ev in xs if ev["name"] == "engine.step"),
                   key=lambda e: e["ts"])
    inner = [ev for ev in xs if ev["name"] != "engine.step"
             and ev["name"] not in _WRAPPER_SPANS]
    inflight = [ev for ev in xs if ev["name"] == "engine.device_inflight"]

    dropped = doc.get("otherData", {}).get("dropped_events", 0)
    clipped_steps = 0
    thresh = {}                           # tid -> clip timestamp
    by_tid = {}
    for st in steps:
        by_tid.setdefault(st["tid"], []).append(st)
    kept_steps = []
    for tid, sts in by_tid.items():
        if dropped and len(sts) > 1:
            first = sts.pop(0)
            thresh[tid] = first["ts"] + first["dur"]
            clipped_steps += 1
        else:
            thresh[tid] = sts[0]["ts"]
        kept_steps.extend(sts)
    kept_steps.sort(key=lambda e: e["ts"])

    def keep(ev):
        t = thresh.get(ev["tid"])
        return t is None or ev["ts"] >= t - 1e-6

    n_before = len(inner) + len(inflight)
    inner = [ev for ev in inner if keep(ev)]
    inflight = [ev for ev in inflight if keep(ev)]
    clipped_ev = n_before - len(inner) - len(inflight)
    if dropped:
        clipped_ev += len(steps) - len(kept_steps)
    return kept_steps, inner, inflight, clipped_ev, clipped_steps


def analyze(doc, events, tracks):
    """Attribution over every engine track in the trace (a replicated
    trace sums its engines — the phases are per step either way)."""
    steps, inner, inflight, clipped_ev, clipped_steps = \
        _engine_spans(doc, events, tracks)

    durs = {}                             # phase -> [dur_us,...]
    for ev in inner:
        durs.setdefault(ev["name"], []).append(ev["dur"])

    step_total = sum(ev["dur"] for ev in steps)
    host_us = sum(d for p in _HOST_PHASES for d in durs.get(p, ()))
    device_us = sum(d for p in _DEVICE_PHASES for d in durs.get(p, ()))
    tracked_us = host_us + device_us
    untracked_us = max(0.0, step_total - tracked_us)

    # overlap opportunity: per step, the packing host work that could
    # hide under a device span of this size in an async engine
    overlap_us = 0.0
    by_tid = {}
    for ev in inner:
        by_tid.setdefault(ev["tid"], []).append(ev)
    for st in steps:
        t0, t1 = st["ts"], st["ts"] + st["dur"]
        mine = [ev for ev in by_tid.get(st["tid"], ())
                if t0 <= ev["ts"] and ev["ts"] + ev["dur"] <= t1 + 1e-6]
        pack = sum(ev["dur"] for ev in mine
                   if ev["name"] in ("engine.pack",
                                     "engine.block_table_stage"))
        pack += sum(_packing_us(ev) for ev in mine
                    if ev["name"] == "engine.schedule")
        dev = sum(ev["dur"] for ev in mine
                  if ev["name"] == "engine.device_launch")
        overlap_us += min(pack, dev)

    # overlap ACHIEVED: host-phase wall time that ran inside an
    # engine.device_inflight window (launch → materialize of the async
    # ticket).  Computed globally per track, not per step window — the
    # in-flight window deliberately CROSSES the step() boundary (launch
    # in one call, materialize in the next), which is the whole point.
    achieved_us = 0.0
    infl_by_tid = {}
    for ev in inflight:
        infl_by_tid.setdefault(ev["tid"], []).append(
            (ev["ts"], ev["ts"] + ev["dur"]))
    for tid, wins in infl_by_tid.items():
        wins.sort()
        for ev in by_tid.get(tid, ()):
            if ev["name"] not in _HOST_PHASES:
                continue
            a0, a1 = ev["ts"], ev["ts"] + ev["dur"]
            for w0, w1 in wins:
                if w0 >= a1:
                    break
                if w1 <= a0:
                    continue
                achieved_us += min(a1, w1) - max(a0, w0)

    phases = {}
    for name in _PHASE_ORDER:
        vals = sorted(durs.get(name, []))
        if not vals:
            continue
        phases[name] = {
            "count": len(vals),
            "p50_ms": round(_pct(vals, 50) / 1e3, 4),
            "p95_ms": round(_pct(vals, 95) / 1e3, 4),
            "total_ms": round(sum(vals) / 1e3, 3),
            "share": round(sum(vals) / step_total, 4) if step_total else 0.0,
        }
    step_vals = sorted(ev["dur"] for ev in steps)
    other = doc.get("otherData", {})
    return {
        "metric": "step_timeline_host_bubble_frac",
        "value": round((host_us + untracked_us) / step_total, 4)
        if step_total else 0.0,
        "unit": "frac",
        "steps": len(steps),
        "step_p50_ms": round(_pct(step_vals, 50) / 1e3, 4),
        "step_p95_ms": round(_pct(step_vals, 95) / 1e3, 4),
        "step_total_ms": round(step_total / 1e3, 3),
        "host_ms": round(host_us / 1e3, 3),
        "device_ms": round(device_us / 1e3, 3),
        "untracked_ms": round(untracked_us / 1e3, 3),
        "device_frac": round(device_us / step_total, 4)
        if step_total else 0.0,
        "overlap_opportunity_ms": round(overlap_us / 1e3, 3),
        "overlap_opportunity_frac": round(overlap_us / step_total, 4)
        if step_total else 0.0,
        "overlap_achieved_ms": round(achieved_us / 1e3, 3),
        "overlap_achieved_frac": round(achieved_us / step_total, 4)
        if step_total else 0.0,
        "step_timeline_overlap_achieved_frac":
        round(achieved_us / step_total, 4) if step_total else 0.0,
        "inflight_windows": len(inflight),
        "phases": phases,
        "tiers": sorted(set(tracks.values())),
        "dropped_events": other.get("dropped_events", 0),
        "unbalanced_spans": other.get("unbalanced_spans", 0),
        "head_clipped_events": clipped_ev,
        "head_clipped_steps": clipped_steps,
    }


def _linfit(xs, ys):
    """Least-squares line ``y = a + b*x``; (a, b, r2).  Degenerate x
    (all equal) pins the slope at 0 and the intercept at the y-mean."""
    n = len(xs)
    if not n:
        return 0.0, 0.0, 0.0
    mx = sum(xs) / n
    my = sum(ys) / n
    sxx = sum((x - mx) ** 2 for x in xs)
    if sxx <= 0.0:
        return my, 0.0, 0.0
    sxy = sum((x - mx) * (y - my) for x, y in zip(xs, ys))
    b = sxy / sxx
    a = my - b * mx
    syy = sum((y - my) ** 2 for y in ys)
    ss_res = sum((y - (a + b * x)) ** 2 for x, y in zip(xs, ys))
    r2 = 1.0 - ss_res / syy if syy > 0 else 1.0
    return a, b, r2


def fit(doc, events, tracks, flight=None, trace_path=None):
    """Fit the fleet simulator's CostModel from the trace: the dict
    ``paddle_tpu.sim.CostModel.from_json`` loads (this tool stays
    stdlib-only on purpose — fitting must not need a JAX install).

    Per step, the joined sample is (packed tokens, rows, step wall us,
    device-phase us inside the step).  The regression runs on total
    step wall vs packed tokens — the ragged single-program step makes
    that a clean line — and pure-decode steps (tokens == rows) also
    feed an exact median-by-rows table, since those are the shapes a
    steady fleet lives in.
    """
    steps, inner, _, clipped_ev, clipped_steps = \
        _engine_spans(doc, events, tracks)
    by_tid = {}
    for ev in inner:
        by_tid.setdefault(ev["tid"], []).append(ev)

    samples = []                          # (tokens, rows, dur_us, dev_us)
    empty_us = []
    for st in steps:
        t0, t1 = st["ts"], st["ts"] + st["dur"]
        mine = [ev for ev in by_tid.get(st["tid"], ())
                if t0 <= ev["ts"] and ev["ts"] + ev["dur"] <= t1 + 1e-6]
        packs = [ev for ev in mine if ev["name"] == "engine.pack"
                 or (ev["name"] == "engine.schedule"
                     and "pack_ns" in ev.get("args", {}))]
        tokens = sum(int(ev.get("args", {}).get("tokens", 0))
                     for ev in packs)
        rows = sum(int(ev.get("args", {}).get("rows", 0)) for ev in packs)
        dev = sum(ev["dur"] for ev in mine
                  if ev["name"] in _DEVICE_PHASES)
        # engine-ACTIVE time: what the engine stamps ITL samples with
        # (dispatch section + completion block) — every phase except
        # the post-block commit/retire tail.  Under async overlap the
        # untracked step remainder is device-inflight, not active.
        act = sum(ev["dur"] for ev in mine
                  if ev["name"] not in ("engine.sample_commit",
                                        "engine.retire"))
        if tokens > 0:
            samples.append((tokens, rows, st["dur"], dev,
                            min(act / st["dur"], 1.0) if st["dur"] else 1.0))
        else:
            empty_us.append(st["dur"])

    # Compile steps poison the regression: a first call on a fresh pack
    # shape spends SECONDS in device_launch where a steady step spends
    # milliseconds, and least squares chases those points.  The steady
    # state is what the simulator models, so trim steps beyond 20x the
    # median wall — wide enough to keep every honest prefill burst,
    # narrow enough to shed compiles — and say how many were dropped.
    outliers = 0
    if len(samples) >= 4:
        med = _pct(sorted(s[2] for s in samples), 50)
        cut = 20.0 * med
        kept = [s for s in samples if s[2] <= cut]
        outliers = len(samples) - len(kept)
        samples = kept

    xs = [s[0] for s in samples]
    ys = [s[2] for s in samples]
    base_us, per_tok_us, r2 = _linfit(xs, ys)
    base_us = max(base_us, 0.0)
    per_tok_us = max(per_tok_us, 0.0)

    host_meds = sorted(max(d - dev, 0.0) for _, _, d, dev, _ in samples)
    host_us = _pct(host_meds, 50)
    active_frac = _pct(sorted(s[4] for s in samples), 50) \
        if samples else 1.0

    by_rows = {}
    for tokens, rows, dur, _, _ in samples:
        if rows > 0 and tokens == rows:   # pure decode pack
            by_rows.setdefault(rows, []).append(dur)
    decode_table = {str(r): round(_pct(sorted(v), 50) / 1e6, 9)
                    for r, v in sorted(by_rows.items())}

    meta = {
        "source": "fit",
        "trace": trace_path,
        "steps_fit": len(samples),
        "outlier_steps_dropped": outliers,
        "empty_steps": len(empty_us),
        "empty_step_p50_s": round(_pct(sorted(empty_us), 50) / 1e6, 9),
        "r2": round(r2, 4),
        "dropped_events": doc.get("otherData", {}).get(
            "dropped_events", 0),
        "head_clipped_events": clipped_ev,
        "head_clipped_steps": clipped_steps,
    }
    if flight:
        qw = sorted(r["queue_wait_s"] for r in flight
                    if r.get("queue_wait_s") is not None)
        tt = sorted(r["ttft_s"] for r in flight
                    if r.get("ttft_s") is not None)
        ch = sorted(r["prefill_chunks"] for r in flight
                    if r.get("prefill_chunks"))
        meta["flight"] = {
            "records": len(flight),
            "queue_wait_p50_s": round(_pct(qw, 50), 6),
            "queue_wait_p95_s": round(_pct(qw, 95), 6),
            "ttft_p50_s": round(_pct(tt, 50), 6),
            "ttft_p95_s": round(_pct(tt, 95), 6),
            "prefill_chunks_p50": _pct(ch, 50),
        }
    return {
        "step_base_s": round(base_us / 1e6, 9),
        "step_per_token_s": round(per_tok_us / 1e6, 9),
        "host_per_step_s": round(host_us / 1e6, 9),
        "active_frac": round(active_frac, 4),
        "decode_table": decode_table,
        "meta": meta,
    }


def print_table(rec, out=sys.stdout):
    w = out.write
    w(f"step timeline: {rec['steps']} steps, "
      f"step p50 {rec['step_p50_ms']:.3f} ms / "
      f"p95 {rec['step_p95_ms']:.3f} ms, tiers: "
      f"{', '.join(rec['tiers'])}\n\n")
    w(f"{'phase':<26}{'count':>7}{'p50 ms':>10}{'p95 ms':>10}"
      f"{'total ms':>11}{'share':>8}\n")
    for name, p in rec["phases"].items():
        kind = ("device" if name in _DEVICE_PHASES else "host")
        w(f"{name:<26}{p['count']:>7}{p['p50_ms']:>10.4f}"
          f"{p['p95_ms']:>10.4f}{p['total_ms']:>11.3f}"
          f"{p['share']:>8.1%}  [{kind}]\n")
    if rec["untracked_ms"]:
        share = rec["untracked_ms"] / rec["step_total_ms"] \
            if rec["step_total_ms"] else 0.0
        w(f"{'(untracked step time)':<26}{'':>7}{'':>10}{'':>10}"
          f"{rec['untracked_ms']:>11.3f}{share:>8.1%}  [host]\n")
    w("\n")
    w(f"host-bubble fraction:  {rec['value']:.1%} "
      f"({rec['host_ms'] + rec['untracked_ms']:.3f} ms host-side of "
      f"{rec['step_total_ms']:.3f} ms stepped)\n")
    w(f"device fraction:       {rec['device_frac']:.1%} "
      f"({rec['device_ms']:.3f} ms in launch + result sync)\n")
    w(f"overlap opportunity:   {rec['overlap_opportunity_frac']:.1%} "
      f"({rec['overlap_opportunity_ms']:.3f} ms of packing that an "
      f"async engine could hide under device spans)\n")
    if rec.get("inflight_windows"):
        w(f"overlap achieved:      {rec['overlap_achieved_frac']:.1%} "
          f"({rec['overlap_achieved_ms']:.3f} ms of host work inside "
          f"{rec['inflight_windows']} in-flight device windows)\n")
    else:
        w("overlap achieved:      0.0% (no engine.device_inflight "
          "windows — synchronous engine or overlap off)\n")
    if rec["dropped_events"]:
        w(f"NOTE: ring dropped {rec['dropped_events']} oldest events — "
          f"totals cover the surviving window only "
          f"(head repair clipped {rec['head_clipped_events']} orphaned "
          f"events and {rec['head_clipped_steps']} partial first "
          f"step(s))\n")
    w("\n")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="host/device attribution over a serve_bench --trace "
                    "step timeline")
    ap.add_argument("trace", help="Chrome trace-event JSON "
                                  "(serve_bench --trace OUT.json)")
    ap.add_argument("--json-only", action="store_true",
                    help="skip the table; print only the record line")
    ap.add_argument("--fit", metavar="OUT.json", default=None,
                    help="fit the fleet simulator's cost model from the "
                         "trace and write it here (sim_calibration.json; "
                         "loaded by paddle_tpu.sim.CostModel.from_json)")
    ap.add_argument("--flight", metavar="FLIGHT.json", default=None,
                    help="flight-recorder dump (/debug/requests JSON) to "
                         "summarize into the calibration's meta")
    args = ap.parse_args(argv)

    doc, events, tracks = load_trace(args.trace)
    rec = analyze(doc, events, tracks)
    if rec["steps"] == 0:
        rec["error"] = "no engine.step spans in trace"
    elif not args.json_only:
        print_table(rec)
    if args.fit is not None:
        flight = None
        if args.flight is not None:
            with open(args.flight, "r", encoding="utf-8") as f:
                flight = json.load(f)
            if isinstance(flight, dict):
                flight = flight.get("requests", [])
        cal = fit(doc, events, tracks, flight=flight,
                  trace_path=args.trace)
        with open(args.fit, "w", encoding="utf-8") as f:
            json.dump(cal, f, indent=1, sort_keys=True)
            f.write("\n")
        rec["fit"] = {
            "calibration": args.fit,
            "steps_fit": cal["meta"]["steps_fit"],
            "r2": cal["meta"]["r2"],
            "step_base_ms": round(cal["step_base_s"] * 1e3, 4),
            "step_per_token_us": round(cal["step_per_token_s"] * 1e6, 4),
            "host_per_step_ms": round(cal["host_per_step_s"] * 1e3, 4),
            "decode_table_rows": len(cal["decode_table"]),
        }
        if not args.json_only and rec["steps"]:
            print(f"cost-model fit: {cal['meta']['steps_fit']} steps, "
                  f"r2 {cal['meta']['r2']:.3f} -> {args.fit}")
    print(json.dumps(rec))
    sys.stdout.flush()
    return 0 if rec["steps"] else 1


if __name__ == "__main__":
    sys.exit(main())
