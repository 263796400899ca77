"""Tests of the benchmark's own code.  Run them with

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They sit under ``benchmark/`` because a benchmark PR may add files
nowhere else; the tier-1 command (``pytest tests/``) does not collect
them."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)
