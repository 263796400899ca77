"""A traced run of one cell that keeps the two lists its trace reduction
walks, and holds ``xplane.attribute_gaps`` to the double loop it replaced
(PR 44) on them, in the same process, once the run's result is out:

    python3 benchmark/tools/gap_lists.py --workload yi6b.chat --seed 7 \\
        --seconds 51

The lists go to ``.bench_out/gap_lists.json``: {"offset_ns", "gaps":
[[start, end]], "spans": [[name, ts, dur]]}, as ``run._reduce_trace``
handed them over.  The double loop is the one copy kept as the tests'
oracle (``tests/test_xplane.py``); on an expert cell's lists it takes
minutes.  The last line of standard error says whether the two
dictionaries are equal, and both times; the exit code is 1 where they
differ."""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import xplane as X  # noqa: E402

LISTS = os.path.join(run.OUT_DIR, "gap_lists.json")


def _oracle():
    spec = importlib.util.spec_from_file_location(
        "test_xplane", os.path.join(BENCH, "tests", "test_xplane.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.attribute_gaps_oracle


def main(argv=None) -> int:
    walk = X.attribute_gaps
    kept = {}

    def keep(gaps, host_spans, offset_ns):
        kept.update(gaps=gaps, spans=host_spans, offset_ns=offset_ns)
        return walk(gaps, host_spans, offset_ns)

    X.attribute_gaps = keep
    try:
        rc = run.main(list(argv if argv is not None else sys.argv[1:])
                      + ["--trace", "1"])
    finally:
        X.attribute_gaps = walk
    if rc != 0 or not kept:
        print(f"[gap_lists] no lists kept (exit {rc})", file=sys.stderr)
        return rc or 1
    gaps, spans, offset = kept["gaps"], kept["spans"], kept["offset_ns"]
    os.makedirs(run.OUT_DIR, exist_ok=True)
    with open(LISTS, "w", encoding="utf-8") as f:
        json.dump({"offset_ns": offset, "gaps": gaps,
                   "spans": [[s["name"], s["ts"], s["dur"]] for s in spans]},
                  f)
    t = time.monotonic()
    got = walk(gaps, spans, offset)
    t_walk = time.monotonic() - t
    t = time.monotonic()
    want = _oracle()(gaps, spans, offset)
    t_loop = time.monotonic() - t
    equal = list(got.items()) == list(want.items())
    print("[gap_lists]", json.dumps(
        {"equal": equal, "gaps": len(gaps), "spans": len(spans),
         "walk_s": t_walk, "double_loop_s": t_loop, "walk": got,
         "double_loop": want}), file=sys.stderr, flush=True)
    return 0 if equal else 1


if __name__ == "__main__":
    sys.exit(main())
