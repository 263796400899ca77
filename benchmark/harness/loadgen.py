"""The load generator's child: its own interpreter, so that the clients'
readers never share a GIL with the engine's host loop.  Imports neither
JAX nor the program.  Reads a traffic file, finds the generator for its
``kind`` by name under ``harness/kinds/``, and writes one JSON object per
line to standard output for the parent."""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def load_kind(kind: str):
    """The generator module of a traffic kind, found by file name: a new
    kind arrives as a new file, with no edit here."""
    path = os.path.join(HERE, "kinds", f"{kind}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"no generator for traffic kind {kind!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_kind_{kind}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with open(args.traffic, encoding="utf-8") as f:
        spec = json.load(f)
    mod = load_kind(spec["kind"])

    def emit(obj):
        sys.stdout.write(json.dumps(obj) + "\n")
        sys.stdout.flush()

    mod.drive(args.port, spec, args.vocab, args.seed, args.seconds, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
