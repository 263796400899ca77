"""Finding things by name.  ``BENCHMARK.json`` names cells, their
configurations, traffic mixes and metrics; each lives in a file of its
own under ``benchmark/``, so a later PR adds files and entries and edits
nothing that is there.  Which name finds which file:

=====================================  ================================
a cell's ``config``                    the ``file`` of that entry of
                                       ``configs`` (``configs/*.json``)
a cell's ``traffic``                   ``traffic/<traffic>.json``
the traffic file's ``kind``            ``harness/kinds/<kind>.py``
                                       (``loadgen.load_kind``)
a metric's ``name``                    ``metrics/<name>.py``
the configuration file's               ``references/<reference>.py``, the
``reference``: its architecture        plain reference; ``shapes/
                                       <reference>.py``, its leaves,
                                       shapes, names and counts; and
                                       ``builders/<reference>.py``, the
                                       program's model of it
=====================================  ================================

The first two kinds of file that an architecture's name finds import
nothing of the program; the builder is the one that does."""
from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)                 # benchmark/
REPO = os.path.dirname(ROOT)


def load_benchmark(path: str | None = None) -> dict:
    with open(path or os.path.join(REPO, "BENCHMARK.json"),
              encoding="utf-8") as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json (has: "
                     f"{[w['name'] for w in bench['workloads']]})")


def load_config(bench: dict, name: str, root: str = REPO) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"]), encoding="utf-8") as f:
                return json.load(f)
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def traffic_path(name: str) -> str:
    path = os.path.join(ROOT, "traffic", f"{name}.json")
    if not os.path.isfile(path):
        raise SystemExit(f"no traffic mix {name!r}: {path}")
    return path


def load_traffic(name: str) -> dict:
    with open(traffic_path(name), encoding="utf-8") as f:
        return json.load(f)


def _load_module(subdir: str, name: str, what: str):
    path = os.path.join(ROOT, subdir, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {what} {name!r}: {path}")
    modname = f"benchmark_{subdir}_" + name.replace(".", "_").replace("-", "_")
    ms = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(ms)
    ms.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    """The reader of one per-layer metric: ``metrics/<name>.py`` with a
    ``read(ctx)`` that returns a number, or None where it finds nothing
    to read."""
    return _load_module("metrics", name, "reader for metric").read


def load_reference(name: str):
    """A configuration's plain reference, found by the name in its file:
    ``references/<name>.py``."""
    return _load_module("references", name, "plain reference")


def load_shapes(name: str):
    """An architecture's leaves, shapes, names and counts, program-free:
    ``shapes/<name>.py`` (``shapes/llama_dense.py`` says what such a
    file states)."""
    return _load_module("shapes", name, "shapes file")


def load_builder(name: str):
    """The program's model of an architecture: ``builders/<name>.py``
    with ``construct(cfg)`` and ``place(model, made)``."""
    return _load_module("builders", name, "builder")


def metrics_for(bench: dict, group: str, cell: str) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell
    reports: those with no ``workloads`` key, and those that list it; a
    per-layer metric only where the cell reports the end-to-end metric
    that it moves."""
    def listed(m):
        return "workloads" not in m or cell in m["workloads"]

    e2e = {m["name"] for m in bench["end_to_end"] if listed(m)}
    return [m for m in bench[group] if listed(m)
            and (group == "end_to_end" or m["moves"] in e2e)]
