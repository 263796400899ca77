"""Test harness config.

Mirrors the reference's GPU-free distributed test strategy (SURVEY.md §4):
run on a virtual 8-device CPU mesh so sharding/collective code paths execute
without TPU hardware.  Must run before jax is imported anywhere.
"""
import os

_HW = os.environ.get("PADDLE_TPU_HW_TESTS", "").lower() not in (
    "", "0", "false", "no", "off")

# The suite runs on the CPU; PADDLE_TPU_HW_TESTS=1 leaves the platform
# alone so that tests/test_tpu_hardware.py reaches the chip.
if not _HW:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

from paddle_tpu.core.runtime import configure_compile_cache  # noqa: E402

# Persistent XLA compile cache: the suite is compile-bound, and warm runs
# skip recompilation.  The directory is the entry points' own (an outside
# JAX_COMPILATION_CACHE_DIR wins); exporting it hands the CLI children
# tests spawn (serve_bench, autotune, frontend, launch, chip_smoke) the
# same cache.  The thresholds are the suite's: its programs are tiny.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", configure_compile_cache())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@pytest.fixture
def reference_tree():
    """Root of the upstream checkout whose ``__all__`` lists the surface
    tests hold this package to.  It is no part of the repository: where
    it is absent those tests skip, and say that this is why."""
    root = "/root/reference"
    if not os.path.isdir(root):
        pytest.skip(f"{root} (the upstream source the surface's names are "
                    "read from) is not on this machine")
    return root


@pytest.fixture
def launch_results(monkeypatch):
    """Every step launch of the engines a test builds after asking for
    this, one dict each: ``kind`` (``mixed``: a chunk beside decode
    rows; ``decode_only``: every row of the batch a decode row;
    ``padding_rows``: decode rows and rows to spare; else ``other``),
    ``front`` (the device arrays it gave back before the pools) and
    ``parts``, the sampled tokens, finiteness flags and, of a model with
    expert layers, counts as the DEVICE had them, taken where the step
    program packs them into the one vector the host reads
    (``serving._pack_results``)."""
    import numpy as np
    from paddle_tpu.inference import serving

    parts, seen = [], []
    real_pack = serving._pack_results
    real_call = serving.LLMEngine._call_program

    def pack(*results):
        jax.debug.callback(
            lambda *a: parts.append([np.asarray(x) for x in a]),
            *(r for r in results if r is not None), ordered=True)
        return real_pack(*results)

    def call(self, prog, host_args, bucket):
        q = np.diff(host_args[1])            # a step's ``cu``
        chunks, rows = int((q > 1).sum()), int((q > 0).sum())
        kind = "mixed" if 0 < chunks < rows else "other" if chunks \
            else "decode_only" if rows == self.max_num_seqs \
            else "padding_rows"
        front = real_call(self, prog, host_args, bucket)
        jax.effects_barrier()
        seen.append({"kind": kind, "front": front, "parts": parts.pop()})
        return front

    monkeypatch.setattr(serving, "_pack_results", pack)
    monkeypatch.setattr(serving.LLMEngine, "_call_program", call)
    return seen


def pytest_configure(config):
    """Register the graft-lint plugin HERE, not via addopts -p: a
    command-line plugin imports before this conftest pins
    JAX_PLATFORMS=cpu, and nothing may touch jax before that pin.  The
    plugin AST-lints paddle_tpu/ once per session and fails the run on
    ERROR findings not in the committed baseline."""
    from paddle_tpu.analysis import pytest_plugin as _gl

    if _gl.plugin_enabled() \
            and not config.pluginmanager.has_plugin(_gl.PLUGIN_NAME):
        config.pluginmanager.register(_gl.GraftLintPlugin(),
                                      _gl.PLUGIN_NAME)
