"""Process start-up for entry points: which device this process computes
on, where its compile cache lives, what the device can do at best.

Entry points (the frontend CLI, ``chip_smoke.py``, ``bench.py``,
``tools/perf/serve_bench.py``, ``tests/conftest.py``) call these before
first device use.  Nothing here runs at import.
"""
from __future__ import annotations

import os
import threading

__all__ = ["REPO_ROOT", "DEFAULT_CACHE_DIR", "configure_compile_cache",
           "CompileWatch", "resolve_device", "DEVICE_PEAKS", "MODELED_DEVICE",
           "device_peaks"]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The cache key includes the directory, so a directory that moves never
# hits: one normalised absolute path inside the checkout (git-ignored).
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself
    and this sets no directory.  Where it is not, the cache goes to
    ``DEFAULT_CACHE_DIR``.

    Either way the process lowers with no Python frames in its MLIR
    locations (``jax_traceback_in_locations_limit`` 0, JAX's own
    option, set once and for the whole process).  XLA strips its own
    metadata from the cache's key, but Mosaic serializes a Pallas
    kernel's module, locations and all, into the custom call's payload,
    which the key takes as it is: with frames in it a step program
    reached from another line, another depth of the call stack or
    another checkout compiled again, 80 to 95 s each on the v5e
    (PERF.md section 6; tests/test_chip_lowering.py holds the payload
    to the kernel alone).  The names of scopes and operations stay in
    the locations; what goes is the source line in HLO metadata and in a
    message of Mosaic's about a kernel."""
    import jax
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


class CompileWatch:
    """Counts this process's XLA compiles from ``jax.monitoring``:
    seconds in the backend compiler (a persistent-cache hit counts its
    retrieval time), and how many compile requests consulted the
    persistent cache and how many of those it answered.  ``close()``
    unregisters the listeners."""

    _DURATION = "/jax/core/compile/backend_compile_duration"
    _LOOKUP = "/jax/compilation_cache/compile_requests_use_cache"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax.monitoring as mon
        self._mon = mon
        self._lock = threading.Lock()
        self._seconds = 0.0
        self._lookups = 0
        self._hits = 0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_kw):
        if event == self._LOOKUP or event == self._HIT:
            with self._lock:
                if event == self._HIT:
                    self._hits += 1
                else:
                    self._lookups += 1

    def _on_duration(self, event, duration_secs, **_kw):
        if event == self._DURATION:
            with self._lock:
                self._seconds += float(duration_secs)

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_seconds": self._seconds,
                    "cache_hits": self._hits,
                    "cache_misses": self._lookups - self._hits}

    def close(self) -> None:
        self._mon.unregister_event_listener(self._on_event)
        self._mon.unregister_event_duration_listener(self._on_duration)


def resolve_device() -> dict:
    """The device this process computes on, as JAX reports it:
    ``{"platform", "kind", "count"}``.

    JAX falls back to the CPU when it finds no accelerator.  An entry
    point that lands there without having been asked to would report
    CPU work under a device's name, so that is an error here: a CPU run
    happens only under an explicit ``JAX_PLATFORMS=cpu``."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    asked = [p.strip() for p in
             os.environ.get("JAX_PLATFORMS", "").split(",")]
    if dev.platform == "cpu" and "cpu" not in asked:
        raise RuntimeError(
            "JAX found no accelerator and fell back to the CPU; set "
            "JAX_PLATFORMS=cpu to run on the CPU on purpose")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud TPU documentation, system architecture pages for each
# generation (v5e: 197 bf16 TFLOP/s, 393 int8 TOP/s, 16 GB HBM2e at
# 819 GB/s).  int8 is given where the page publishes it.
DEVICE_PEAKS = {
    "TPU v4": {"bf16_flops": 275e12, "hbm_bytes": 32e9,
               "hbm_bytes_per_s": 1228e9},
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9},
    "TPU v5": {"bf16_flops": 459e12, "int8_ops": 918e12,
               "hbm_bytes": 95e9, "hbm_bytes_per_s": 2765e9},
    "TPU v6 lite": {"bf16_flops": 918e12, "int8_ops": 1836e12,
                    "hbm_bytes": 32e9, "hbm_bytes_per_s": 1640e9},
}

# The chip this repository runs on: what the off-chip cost models
# (tune/cost.py, distributed/auto_tuner/cost_model.py) plan for when
# there is no device to ask.
MODELED_DEVICE = "TPU v5 lite"


def device_peaks(device_kind: str) -> dict:
    """Peaks of a named device.  A device that is not in the table is an
    error, not a default: a utilization against a guessed peak is not a
    measurement."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add it "
            f"to DEVICE_PEAKS with its source (known: "
            f"{sorted(DEVICE_PEAKS)})") from None
