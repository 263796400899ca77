"""Pallas kernel autotuner CLI: sweep the registered search spaces,
write the persistent tuning cache, and report untuned launches.

Two measurement modes share one search loop (paddle_tpu.tune.search):

* default (wall-clock): each candidate runs in its own subprocess on the
  live backend — the mfu_ablation.py worker pattern — so a config that
  OOMs VMEM or wedges the compiler kills only its child.
* ``--cost-model``: candidates are ranked in-process by the
  arithmetic-intensity roofline model; no chip needed, so CPU CI
  exercises the full search -> persist -> trace-time-lookup pipeline.

Prints one report line per (kernel, shape) sweep row, a graft-lint-style
section listing Pallas launches whose geometry does NOT flow from the
tuning-cache lookup helper, then ONE final JSON record line (the
serve_bench convention):

  {"metric": "autotune_cache_entries", "value": ..., "unit": "entries",
   "device": ..., "cache": ..., "measure": ..., "results": [...],
   "untuned_launches": [...]}

Usage:
  python tools/perf/autotune.py --cost-model            # CPU CI path
  python tools/perf/autotune.py                         # on-chip sweep
  python tools/perf/autotune.py --kernel flash_attention --cache /tmp/t.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cost-model", action="store_true",
                    help="rank candidates with the roofline cost model "
                         "in-process (no chip; the CPU CI path)")
    ap.add_argument("--cache", default=None,
                    help="cache file to write (default: the resolved "
                         "runtime path — PADDLE_TPU_TUNE_CACHE or "
                         "tuning_cache.json at the root of the checkout)")
    ap.add_argument("--kernel", action="append", default=None,
                    help="restrict the sweep to this kernel (repeatable)")
    ap.add_argument("--device", default=None,
                    help="the device key (default: the attached "
                         "backend's device kind; a wall-clock sweep asks "
                         "a child for it, so that this process never "
                         "takes the chip its children need)")
    ap.add_argument("--iters", type=int, default=5,
                    help="timing iterations per candidate (wall-clock)")
    ap.add_argument("--timeout", type=int, default=900,
                    help="per-candidate subprocess timeout seconds")
    ap.add_argument("--verbose", action="store_true",
                    help="log every candidate's score, not just winners")
    args = ap.parse_args(argv)

    from paddle_tpu.tune import (CostModelMeasurer, SubprocessMeasurer,
                                 all_kernels, cache_path, run_sweep,
                                 untuned_launch_report)

    known = {k.name for k in all_kernels()}
    if args.kernel and set(args.kernel) - known:
        ap.error(f"unknown kernel(s) {sorted(set(args.kernel) - known)}; "
                 f"choose from {sorted(known)}")

    if args.cost_model:
        measurer = CostModelMeasurer()
    else:
        measurer = SubprocessMeasurer(timeout=args.timeout,
                                      iters=args.iters)
    cache_file = args.cache or cache_path()
    log = (lambda s: print(s, flush=True)) if args.verbose else None
    report = run_sweep(measurer, cache_file, kernels=args.kernel,
                       device=args.device, log=log)

    for row in report["results"]:
        if "error" in row:
            print(f"{row['kernel']:24s} {row['sig']:48s} {row['error']}",
                  flush=True)
            continue
        sp = row["speedup"]
        print(f"{row['kernel']:24s} {row['sig']:48s} "
              f"winner={json.dumps(row['config'])} "
              f"score={row['score_s'] * 1e6:.2f}us "
              f"vs-default={'n/a' if sp is None else f'{sp:.2f}x'}",
              flush=True)

    # graft-lint-style trailer: launches the tuner cannot reach
    untuned = untuned_launch_report()
    if untuned:
        print(f"-- {len(untuned)} untuned pallas launch(es):", flush=True)
        for row in untuned:
            print(f"WARNING untuned-pallas-launch "
                  f"{row['file']}:{row['line']} ({row['func']})",
                  flush=True)
    else:
        print("-- all pallas launches flow from the tuning cache",
              flush=True)

    print(json.dumps({
        "metric": "autotune_cache_entries", "value": report["entries"],
        "unit": "entries", "device": report["device"],
        "cache": report["cache"], "measure": report["measure"],
        "results": report["results"], "untuned_launches": untuned,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
