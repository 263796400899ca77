"""The chip smoke and the rules it rests on, as far as a CPU can check
them: no chip means failure, the CPU run is an opt-in, a kernel the chip
refuses raises, and a flag that cannot get its kernel is refused."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference import LLMEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops.pallas import paged_attention as pa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*args, env):
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)


def test_smoke_without_a_chip_fails_and_prints_no_result():
    # JAX_PLATFORMS=cpu is this sandbox's own setting: held to the CPU,
    # the full-width run must refuse, not carry on there (the other way
    # to have no chip, JAX falling back by itself, is refused by
    # resolve_device: test_resolve_device_refuses_a_silent_cpu)
    r = _run_smoke(env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_tiny_smoke_is_an_explicit_cpu_run():
    r = _run_smoke("--tiny", env=dict(os.environ))
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": last["device"]["count"]}}
    assert "attention='xla-reference (cpu platform)'" in r.stdout


@pytest.fixture
def tiny_model():
    return LlamaForCausalLM(LlamaConfig.tiny(
        vocab=97, hidden=256, layers=1, heads=2, ffn=64, seq=64))


def test_kernel_refused_on_a_tpu_raises_out_of_the_step(tiny_model,
                                                        monkeypatch):
    """On the tpu platform the engine takes the kernel wherever it claims
    the shape, and a launch the compiler refuses propagates: there is no
    probe to swallow it and no reference to fall back to."""
    eng = LLMEngine(tiny_model, max_num_seqs=2, block_size=8,
                    max_model_len=64)
    assert eng.attention_path == "xla-reference (cpu platform)"
    monkeypatch.setattr(eng, "_platform", "tpu")    # head_dim 128, bs 8
    eng.attention_path = eng._resolve_attention_path()
    assert eng.attention_path == "pallas"

    def refuse(*a, **k):
        raise RuntimeError("Mosaic failed to compile TPU kernel: "
                           "the whole message")
    monkeypatch.setattr(pa, "_ragged_launch", refuse)
    eng.add_request([1, 2, 3], max_new_tokens=2)
    with pytest.raises(RuntimeError, match="the whole message"):
        eng.run()


def test_static_ineligibility_takes_the_reference_visibly(tiny_model,
                                                          monkeypatch):
    """A shape the kernel does not claim may serve through the XLA path
    on a tpu, and every program built says so, with the reason."""
    eng = LLMEngine(tiny_model, max_num_seqs=2, block_size=4,
                    max_model_len=64)
    monkeypatch.setattr(eng, "_platform", "tpu")
    why = ("xla-reference (block_size 4 is not a multiple of 8 "
           "(float32 pages))")
    assert eng._resolve_attention_path() == why
    eng.attention_path = why
    eng.add_request([1, 2, 3], max_new_tokens=2)
    eng.run()
    paths = eng.summary()["paths"]
    assert paths["device_kind"] == "cpu" and paths["attention"] == why
    assert set(paths["programs"]) == {"ragged:2", "ragged:64"}
    assert all(p == {"attention": why, "matmul": "xla-dense"}
               for p in paths["programs"].values())


def test_prefetched_operands_must_fit_scalar_memory():
    def why(num_blocks, rows=4, nblk=8, dtype=jnp.int8):
        return pa.ineligible(32, 32, 128, 32, dtype,
                             launch=(rows, nblk, num_blocks))

    # the two pools measured on the v5e: 257 pages compiled, 1025 did not
    assert why(257) is None
    assert "scalar memory" in why(1025)
    # 960 pages fit beside a small block table and not beside a large one
    assert why(960) is None
    assert "[66, 256] block table" in why(960, rows=66, nblk=256)
    # float pages prefetch no scale pool, whatever the pool's size
    assert why(1 << 20, dtype=jnp.bfloat16) is None
    assert pa.ineligible(32, 32, 128, 32, jnp.int8) is None    # not checked


def test_cli_refuses_int8_pages_the_kernel_cannot_take(tiny_model,
                                                       monkeypatch):
    """The CLI refuses from the engine's own decision, before it
    listens."""
    from paddle_tpu.inference.frontend import __main__ as cli

    def refusal(platform="tpu", kv_dtype="int8", **kw):
        eng = LLMEngine(tiny_model, max_num_seqs=2, max_model_len=64,
                        kv_dtype=kv_dtype, **kw)
        monkeypatch.setattr(eng, "_platform", platform)
        eng.attention_path = eng._resolve_attention_path()
        args = cli._parser().parse_args(["--kv-dtype", kv_dtype])
        try:
            cli._refuse_unservable(args, eng)
        except SystemExit as e:
            return str(e)
        return None

    assert "block_size 16 is not a multiple of 32" in refusal(block_size=16)
    assert refusal(block_size=32) is None
    assert "scalar memory" in refusal(block_size=32, num_blocks=1025)
    # the CPU serves through the reference by definition, and says so
    assert refusal(platform="cpu", block_size=16) is None
    # float pages on a shape the kernel does not claim serve visibly
    assert refusal(kv_dtype="float32", block_size=4) is None


def test_resolve_device_refuses_a_silent_cpu(monkeypatch):
    from paddle_tpu.core.runtime import device_peaks, resolve_device

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert resolve_device()["platform"] == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(RuntimeError, match="no accelerator"):
        resolve_device()
    assert device_peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks(jax.devices()[0].device_kind)
