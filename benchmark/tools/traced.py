"""A traced run of one cell, for the builder: ``benchmark/run.py --trace 1``
with two things it has no option for.

    python3 benchmark/tools/traced.py --workload mistral7b.chat --seed 7 \\
        --seconds 51 [--no-profiler | --whole-window]

``--no-profiler``  installs the Tracer and leaves the device profiler
    out: what the Tracer alone costs (PERF.md, tracing overhead (b)).
    The end-to-end numbers are on the ``[bench] end_to_end`` line.
``--whole-window``  profiles the device for the whole window and not
    for 10 s of its middle: a stalled launch (``step.stall_s``) then lies
    inside the profile, and its ``[bench] stall`` line says how busy the
    device was meanwhile.  Five times the trace; for a hunt, not a rate.

Everything else is ``run.py``'s: this file changes nothing of what is
measured.  It stands on one private name of ``run.py``, ``_Profile``
(constructor ``(t_open_ns, seconds, trace_dir)``, ``join()``, ``error``,
``length``); ``tests/test_scopes.py`` holds that name and that shape."""
from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run  # noqa: E402


class _NoProfile:
    """Stands where ``run._Profile`` does, and profiles nothing."""
    error = "left out (--no-profiler)"

    def __init__(self, *_a, **_k):
        pass

    def join(self):
        pass


class _WholeWindow(run._Profile):
    """``run._Profile`` from 2% to 97% of the window."""

    def __init__(self, t_open_ns: int, seconds: float, trace_dir: str):
        # the parent starts at 30% of the window: ask it 28% earlier
        super().__init__(t_open_ns - int(0.28 * seconds * 1e9), seconds,
                         trace_dir)
        self.length = 0.95 * seconds      # read only once tracing began


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    how = ap.add_mutually_exclusive_group()
    how.add_argument("--no-profiler", action="store_true")
    how.add_argument("--whole-window", action="store_true")
    mine, rest = ap.parse_known_args(argv)
    if mine.no_profiler:
        run._Profile = _NoProfile
    elif mine.whole_window:
        run._Profile = _WholeWindow
    return run.main(rest + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
