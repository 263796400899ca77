"""Run a cell several times, as the driver does (a new process each),
keep every result line, and print the spreads the bounds are set from.

    python3 benchmark/tools/runs.py --workload mistral7b.chat \\
        --seeds 11,12,13,14,15,16 --sets 2 --seconds 45 --tag chat_full

Each set uses the same seeds.  A spread is the distance between the
first and third quartile over the median (statistics.quantiles, n=4);
the bound is about five times the wider set's.  This process never
touches JAX: the chip belongs to the run it starts."""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))

from harness import stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--tag", required=True)
    ap.add_argument("--lower", default=None)
    ap.add_argument("--lower-sets", type=int, default=99,
                    help="read the control only in the first N sets")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    lines_path = os.path.join(out_dir, f"{args.tag}.jsonl")
    sets = []
    for k in range(args.sets):
        rows = []
        for seed in seeds:
            cmd = [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
                   "--workload", args.workload, "--seed", str(seed),
                   "--seconds", str(args.seconds), "--trace", str(args.trace)]
            if args.lower and k < args.lower_sets:
                cmd += ["--lower", args.lower]
            t = time.monotonic()
            p = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True)
            wall = time.monotonic() - t
            with open(os.path.join(out_dir, f"{args.tag}.log"), "a") as f:
                f.write(f"===== set {k} seed {seed} rc {p.returncode} "
                        f"wall {wall:.1f}\n")
                f.write("\n".join(l for l in p.stdout.splitlines()[:-1]
                                  if l.startswith("[bench]")) + "\n")
                f.write(p.stderr[-3000:] + "\n")
            last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
            try:
                res = json.loads(last)
            except ValueError:
                print(f"set {k} seed {seed}: rc {p.returncode}, no result; "
                      f"stderr tail: {p.stderr[-1500:]}", flush=True)
                continue
            res["_set"], res["_wall_s"] = k, wall
            with open(lines_path, "a") as f:
                f.write(json.dumps(res) + "\n")
            vals = {n: m["value"] for n, m in res["metrics"].items()}
            rows.append(vals)
            info = [l for l in p.stdout.splitlines()
                    if l.startswith(("[bench] gaps", "[bench] window",
                                     "[bench] setup split",
                                     "[bench] control", "[bench] reference", "[bench] counters",
                                     "[bench] compile"))]
            print(f"set {k} seed {seed} rc {p.returncode} wall {wall:.0f}s "
                  f"correct {res['correct']} "
                  + " ".join(f"{n}={v:.5g}" for n, v in vals.items())
                  + f" compared={json.dumps(res['compared'])}"
                  + f" mem={res['device'].get('memory_peak_bytes')}",
                  flush=True)
            for l in info:
                print("   ", l[:900], flush=True)
            if res.get("breakdown"):
                print("    breakdown", json.dumps(res["breakdown"])[:3000],
                      flush=True)
        sets.append(rows)
    for k, rows in enumerate(sets):
        if len(rows) < 2:
            continue
        print(f"--- set {k}: {len(rows)} runs")
        for name in rows[0]:
            xs = [r[name] for r in rows if name in r]
            sp = stats.spread(xs) if len(xs) >= 2 else float("nan")
            print(f"    {name}: median {statistics.median(xs):.6g} "
                  f"min {min(xs):.6g} max {max(xs):.6g} spread {sp:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
