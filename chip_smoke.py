#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the serving path still starts
and answers on the chip.

It drives the main path once, through the entry point a user calls:

1. a child runs the ragged paged-attention kernel and its dense-gather
   reference directly at the served model's head shapes on a small page
   table, and reports the device JAX resolved and the versions;
2. a child runs the server, ``python -m paddle_tpu.inference.frontend``,
   at LLaMA-7B widths (hidden 4096, 32 heads of 128, FFN 11008,
   vocabulary 32000) with depth cut to 8 layers and weights in bf16 so
   that it fits one 16 GB chip; this process, which imports neither jax
   nor paddle_tpu, sends it a handful of requests over HTTP: prompts
   that land in two prefill buckets, one prompt long enough to be
   prefilled in chunks, follow-ups that diverge inside a shared cached
   page (a copy-on-write page copy), one streamed request, then reads
   ``GET /metrics`` and asks for a clean drain.

It fails (exit code 1, no result line) if a child fails, a request
fails, a sampled row was non-finite, any step program compiled a
reference path in place of its kernel, or the platform is not ``tpu``.
There is no fallback: ``--tiny`` is an opt-in that runs the same control
flow on a toy model under an explicit ``JAX_PLATFORMS=cpu``.

The children take the chip one after the other; this process never
does.  Times printed are smoke timings (compilation included in the
first request of each shape), not rates.

The last line of standard output on success is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import functools
import http.client
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# LLaMA-7B widths are the CLI's llama-7b preset.  Depth: one layer is
# 202M parameters, 0.40 GB in bf16, and the engine holds a layer-stacked
# copy beside the model's own, so 8 layers cost 6.5 GB; embedding and
# head are 0.52 GB, shared.  The weights are drawn in float32 first
# (7.5 GB, transient).  Pages: 8 sequences x 1024 positions is 513 pages
# of 2 MB (8 layers x 32 heads x 16 x 128, K and V, bf16), 1.0 GB.
# Context is held to 1024: the ragged kernel runs one grid program per
# (token, head, page), which at 512 x 32 x 64 is already a million
# programs a layer for one prefill chunk.
FULL = {
    "server": ["--model", "llama-7b", "--layers", "8",
               "--dtype", "bfloat16", "--max-model-len", "1024",
               "--block-size", "16", "--max-prefill-tokens", "512",
               "--max-num-seqs", "8"],
    "platform": "tpu", "attention": "pallas", "vocab": 32000,
    "max_num_seqs": 8, "chunk_bucket": 512,
    # prompt lengths: bucket 64, bucket 128, then 512 + 100 in chunks
    "prompts": (40, 100, 612), "new_tokens": 16, "stream_tokens": 48,
    # the served model's head layout (one query head a K/V head), then
    # a group of eight with a chunk row longer than the kernel's q tile,
    # then that over int8 pages
    "kernels": [{"H": 32, "Hkv": 32, "D": 128, "bs": 16, "nblk": 8,
                 "dtype": "bfloat16", "qlens": (17, 5, 1), "Tq": 32},
                {"H": 32, "Hkv": 4, "D": 128, "bs": 16, "nblk": 8,
                 "dtype": "bfloat16", "qlens": (40, 5, 1), "Tq": 64},
                {"H": 32, "Hkv": 4, "D": 128, "bs": 32, "nblk": 4,
                 "dtype": "bfloat16", "qlens": (40, 5, 1), "Tq": 64,
                 "pages": "int8"},
                # a group of seven (a decode item is 7 score rows, not a
                # multiple of the sublane 8), then that under a window
                # the second and third rows have passed
                {"H": 28, "Hkv": 4, "D": 128, "bs": 16, "nblk": 8,
                 "dtype": "bfloat16", "qlens": (40, 5, 1), "Tq": 64},
                {"H": 28, "Hkv": 4, "D": 128, "bs": 16, "nblk": 8,
                 "dtype": "bfloat16", "qlens": (40, 5, 1), "Tq": 64,
                 "window": 32},
                # two head counts of one model over 8 K/V heads: a group
                # of six, and a group of eight under a window SHORTER
                # than the 40-token chunk beside it
                {"H": 48, "Hkv": 8, "D": 128, "bs": 16, "nblk": 8,
                 "dtype": "bfloat16", "qlens": (40, 5, 1), "Tq": 64},
                {"H": 64, "Hkv": 8, "D": 128, "bs": 16, "nblk": 8,
                 "dtype": "bfloat16", "qlens": (40, 5, 1), "Tq": 64,
                 "window": 32}],
    "start_timeout_s": 300.0, "request_timeout_s": 600.0,
}
TINY = {
    "server": ["--model", "tiny", "--max-model-len", "512",
               "--block-size", "16", "--max-prefill-tokens", "192",
               "--max-num-seqs", "8"],
    "platform": "cpu", "attention": "xla-reference (cpu platform)",
    "vocab": 256, "max_num_seqs": 8, "chunk_bucket": 192,
    # bucket 64, bucket 128, then 192 + 68 in chunks
    "prompts": (20, 100, 260), "new_tokens": 4, "stream_tokens": 12,
    "kernels": [{"H": 4, "Hkv": 4, "D": 16, "bs": 16, "nblk": 4,
                 "dtype": "float32", "qlens": (17, 5, 1), "Tq": 32},
                {"H": 8, "Hkv": 1, "D": 16, "bs": 16, "nblk": 4,
                 "dtype": "float32", "qlens": (40, 5, 1), "Tq": 64},
                {"H": 8, "Hkv": 1, "D": 16, "bs": 32, "nblk": 4,
                 "dtype": "float32", "qlens": (40, 5, 1), "Tq": 64,
                 "pages": "int8"},
                {"H": 7, "Hkv": 1, "D": 16, "bs": 16, "nblk": 4,
                 "dtype": "float32", "qlens": (40, 5, 1), "Tq": 64},
                {"H": 7, "Hkv": 1, "D": 16, "bs": 16, "nblk": 4,
                 "dtype": "float32", "qlens": (40, 5, 1), "Tq": 64,
                 "window": 32},
                {"H": 12, "Hkv": 2, "D": 16, "bs": 16, "nblk": 4,
                 "dtype": "float32", "qlens": (40, 5, 1), "Tq": 64},
                {"H": 16, "Hkv": 2, "D": 16, "bs": 16, "nblk": 4,
                 "dtype": "float32", "qlens": (40, 5, 1), "Tq": 64,
                 "window": 32}],
    "start_timeout_s": 120.0, "request_timeout_s": 300.0,
}


class SmokeFailure(Exception):
    pass


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# child: kernel against reference (the only code here that imports jax
# or paddle_tpu, inside its functions)
# ---------------------------------------------------------------------------

def ragged_case(rng, H, D, bs, nblk, q_dtype, qlens=(17, 5, 1), Tq=32):
    """Three mixed-phase rows as the engine packs them (a fresh prefill,
    a resumed chunk, one decode token) plus tail padding, over a
    shuffled page table with the engine's [R+1]-row layout whose last
    row is the null row.  Returns (q, block_tables, cu, kv_lens,
    num_blocks, live tokens).  Shared with tests/test_tpu_hardware.py."""
    import jax.numpy as jnp
    import numpy as np

    R = 3
    qlens = np.asarray(qlens)
    kvl = np.array([qlens[0], qlens[1] + 35, nblk * bs - 3], np.int32)
    cu = np.concatenate([[0], np.cumsum(qlens)]).astype(np.int32)
    num_blocks = 1 + R * nblk
    bt = np.zeros((R + 1, nblk), np.int32)
    bt[:R] = 1 + rng.permutation(R * nblk).reshape(R, nblk)
    q = jnp.asarray(rng.randn(Tq, H, D), q_dtype)
    return (q, jnp.asarray(bt), jnp.asarray(cu), jnp.asarray(kvl),
            num_blocks, int(cu[-1]))


def kernel_check(size: dict) -> int:
    import importlib.metadata as md

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.core.runtime import (CompileWatch,
                                         configure_compile_cache,
                                         resolve_device)
    from paddle_tpu.ops.pallas import paged_attention as pa

    cache_dir = configure_compile_cache()
    watch = CompileWatch()
    device = resolve_device()
    if device["platform"] != size["platform"]:
        print(f"[kernel] platform is {device['platform']!r}, this run "
              f"needs {size['platform']!r}", file=sys.stderr)
        return 1
    cases = []
    for k in size["kernels"]:
        H, Hkv, D, bs, nblk = k["H"], k["Hkv"], k["D"], k["bs"], k["nblk"]
        dtype = jnp.dtype(k["dtype"])
        rng = np.random.RandomState(0)
        q, bt, cu, kvl, num_blocks, live = ragged_case(
            rng, H, D, bs, nblk, dtype, k["qlens"], k["Tq"])
        pool = (num_blocks, Hkv, bs, D)
        if k.get("pages") == "int8":
            # int8 pages under per-page-per-head scales: the same body
            # with a dequantising load
            kc = jnp.asarray(rng.randint(-127, 128, pool), jnp.int8)
            vc = jnp.asarray(rng.randint(-127, 128, pool), jnp.int8)
            ks = jnp.asarray(rng.uniform(0.5, 1.5, pool[:2]) / 127.0,
                             jnp.float32)
            vs = jnp.asarray(rng.uniform(0.5, 1.5, pool[:2]) / 127.0,
                             jnp.float32)
            out = jax.jit(pa.ragged_paged_attention_quant_packed)(
                q, kc, vc, ks, vs, bt, cu, kvl)
            kc = kc.astype(jnp.float32) * ks[:, :, None, None]
            vc = vc.astype(jnp.float32) * vs[:, :, None, None]
        else:
            kc = jnp.asarray(rng.randn(*pool), dtype)
            vc = jnp.asarray(rng.randn(*pool), dtype)
            out = jax.jit(functools.partial(
                pa.ragged_paged_attention_packed, window=k.get("window")))(
                    q, kc, vc, bt, cu, kvl)
        # the reference materialises [Tq, S, Hkv, D]: fine on this small
        # table, about 17 GB a layer at a full prefill launch
        with jax.default_matmul_precision("highest"):
            ref = pa.ragged_paged_reference(
                q.astype(jnp.float32), kc.astype(jnp.float32),
                vc.astype(jnp.float32), bt, cu, kvl,
                window=k.get("window"))
        out32 = out.astype(jnp.float32)
        err = float(jnp.max(jnp.abs(out32[:live] - ref[:live])))
        # live rows finite, padded rows zero (the kernel gives them no
        # work and writes them so)
        finite = bool(jnp.all(jnp.isfinite(out32))) \
            and not bool(jnp.any(out32[live:]))
        # bf16 pages: scores accumulate in f32 from exact bf16 products;
        # the probabilities are rounded to bf16 for the PV matmul and
        # the output to bf16, half an ulp (2^-9 relative) each on values
        # that reach 4: 1.6e-2 at worst, 1.3e-2 measured on the v5e.
        # Computing in anything narrower than bf16 would not pass.  The
        # float32 toy runs the kernel in the interpreter on the CPU,
        # where only summation order differs.
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        cases.append({"shape": k, "max_abs_err": err, "tolerance": tol,
                      "finite": finite})

    def _version(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return "not installed"

    print(json.dumps({
        "phase": "kernel_check", "device": device,
        "versions": {"jax": jax.__version__, "jaxlib": _version("jaxlib"),
                     "libtpu": _version("libtpu")},
        "interpret": pa.interpret_mode(), "cases": cases,
        "compile_cache_dir": cache_dir, **watch.snapshot()}), flush=True)
    return 0 if all(c["finite"] and c["max_abs_err"] < c["tolerance"]
                    for c in cases) else 1


# ---------------------------------------------------------------------------
# parent: children and HTTP, standard library only
# ---------------------------------------------------------------------------

class Server:
    """The frontend CLI as a child; its output is echoed and kept."""

    def __init__(self, args: list, env: dict):
        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "paddle_tpu.inference.frontend",
             "--port", "0", *args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=HERE, env=env)
        self.lines: list = []
        self._pump = threading.Thread(target=self._read, daemon=True)
        self._pump.start()

    def _read(self):
        for line in self.proc.stdout:
            self.lines.append(line)
            print("[server] " + line, end="", flush=True)

    def wait_port(self, timeout_s: float) -> int:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            m = re.search(r"listening on http://[\d.]+:(\d+)",
                          "".join(self.lines))
            if m:
                return int(m.group(1))
            _check(self.proc.poll() is None,
                   f"server exited with {self.proc.returncode} before "
                   "listening")
            time.sleep(0.1)
        raise SmokeFailure(f"server not listening after {timeout_s:.0f} s")

    def drain(self, timeout_s: float = 120.0) -> None:
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("server did not drain after SIGTERM")
        self._pump.join(timeout=10.0)
        _check(rc == 0, f"server exited with {rc} after SIGTERM")
        _check("drained; bye" in "".join(self.lines),
               "server did not report a clean drain")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=30.0)


def _post(port: int, body: dict, timeout_s: float):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
    try:
        conn.request("POST", "/v1/completions",
                     body=json.dumps(body).encode(),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _complete(port, name, prompt, max_tokens, size, results) -> list:
    """One unary completion, checked; returns its token ids."""
    t0 = time.monotonic()
    status, raw = _post(port, {"prompt": prompt, "max_tokens": max_tokens},
                        size["request_timeout_s"])
    wall = time.monotonic() - t0
    _check(status == 200, f"{name}: HTTP {status}: {raw[:300]!r}")
    choice = json.loads(raw)["choices"][0]
    toks = choice["token_ids"]
    _check_tokens(name, toks, choice["finish_reason"], max_tokens, size)
    results.append({"request": name, "prompt_tokens": len(prompt),
                    "completion_tokens": len(toks),
                    "wall_s": round(wall, 3)})
    return toks


def _check_tokens(name, toks, finish, max_tokens, size):
    # a non-finite logit row is quarantined by the engine and ends the
    # request early with its own finish reason
    _check(finish == "length", f"{name}: finish_reason {finish!r}")
    _check(len(toks) == max_tokens,
           f"{name}: {len(toks)} tokens, expected {max_tokens}")
    _check(all(isinstance(t, int) and 0 <= t < size["vocab"] for t in toks),
           f"{name}: token outside [0, {size['vocab']})")


def _stream(port, name, prompt, max_tokens, size, results) -> None:
    """One SSE completion, read frame by frame and checked."""
    t0 = time.monotonic()
    conn = http.client.HTTPConnection("127.0.0.1", port,
                                      timeout=size["request_timeout_s"])
    try:
        conn.request("POST", "/v1/completions", body=json.dumps(
            {"prompt": prompt, "max_tokens": max_tokens,
             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        _check(resp.status == 200, f"{name}: HTTP {resp.status}")
        _check("text/event-stream" in resp.getheader("Content-Type", ""),
               f"{name}: not an event stream")
        raw = resp.read().decode()
    finally:
        conn.close()
    wall = time.monotonic() - t0
    frames = [f[len("data: "):] for f in raw.split("\n\n")
              if f.startswith("data: ")]
    _check(frames and frames[-1] == "[DONE]", f"{name}: no [DONE] frame")
    events = [json.loads(f)["choices"][0] for f in frames[:-1]]
    toks = [e["token"] for e in events if e["token"] is not None]
    _check_tokens(name, toks, events[-1]["finish_reason"], max_tokens, size)
    results.append({"request": name, "prompt_tokens": len(prompt),
                    "completion_tokens": len(toks), "streamed": True,
                    "wall_s": round(wall, 3)})


_SAMPLE = re.compile(r"^paddle_tpu_(\w+?)(?:\{(.*)\})? (\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def _metrics(port: int) -> list:
    """GET /metrics parsed into (name, labels, value) samples."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60.0)
    try:
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        _check(resp.status == 200, f"/metrics: HTTP {resp.status}")
        text = resp.read().decode()
    finally:
        conn.close()
    out = []
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if m:
            out.append((m.group(1), dict(_LABEL.findall(m.group(2) or "")),
                        float(m.group(3))))
    return out


def _value(samples, name, **labels) -> float:
    hits = [v for n, lb, v in samples if n == name
            and all(lb.get(k) == w for k, w in labels.items())]
    _check(len(hits) == 1, f"/metrics: {len(hits)} samples of {name} "
           f"{labels}")
    return hits[0]


def drive(port: int, size: dict) -> dict:
    """The request mix of the smoke, then the checks on /metrics."""
    import random
    rng = random.Random(0)
    vocab = size["vocab"]

    def prompt(n):
        return [rng.randrange(1, vocab) for _ in range(n)]

    results: list = []
    # one after the other, so that each prompt is a launch of its own:
    # two prefill buckets, then a prompt longer than the step's budget
    for n in size["prompts"]:
        _complete(port, f"prompt-{n}", prompt(n), size["new_tokens"], size,
                  results)
    # a conversation whose two follow-ups arrive together and diverge
    # inside the page its cached tail shares: one is written first and
    # must copy the page.  One follow-up streams, and decodes long
    # enough that the steady decode program runs many times.
    head = prompt(11)
    gen = _complete(port, "conversation", head, 8, size, results)
    # what the finished request left cached: every position but its last
    # token's, the tail of it in a partly filled page
    base = head + gen[:-1]
    errors: list = []

    def guarded(fn, *a):
        try:
            fn(*a)
        except Exception as e:         # re-raised on the main thread
            errors.append(e)

    threads = [
        threading.Thread(target=guarded, args=(
            _stream, port, "follow-up-stream", base + [3],
            size["stream_tokens"], size, results)),
        threading.Thread(target=guarded, args=(
            _complete, port, "follow-up", base + [7], size["new_tokens"],
            size, results)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=2 * size["request_timeout_s"])
        _check(not t.is_alive(), "a follow-up request never returned")
    if errors:
        raise errors[0]

    samples = _metrics(port)
    n_req = len(results)
    _check(_value(samples, "requests_finished_total") == n_req,
           "requests_finished_total does not match the requests sent")
    _check(_value(samples, "quarantined_total") == 0,
           "a sampled row was non-finite (request quarantined)")
    programs = [lb for n, lb, _ in samples if n == "engine_program_path"]
    _check(programs, "/metrics names no step program")
    for lb in programs:
        _check(lb["platform"] == size["platform"],
               f"program {lb['program']} ran on {lb['platform']!r}")
        _check(lb["attention"] == size["attention"],
               f"program {lb['program']} compiled attention path "
               f"{lb['attention']!r}, expected {size['attention']!r}")
        _check(lb["matmul"] == "xla-dense",
               f"program {lb['program']} compiled matmul path "
               f"{lb['matmul']!r}")
    buckets = sorted(int(lb["program"].split(":")[1]) for lb in programs
                     if lb["program"].startswith("ragged:"))
    prefill = [b for b in buckets if b > size["max_num_seqs"]]
    _check(size["max_num_seqs"] in buckets, "no decode-sized program ran")
    _check(len(prefill) >= 3 and prefill[-1] == size["chunk_bucket"],
           f"prefill buckets {prefill}: expected two small buckets and "
           f"the {size['chunk_bucket']}-token chunk")
    _check(_value(samples, "engine_compiles_total", kind="cow") >= 1,
           "no copy-on-write page copy ran")
    return {
        "requests": results, "programs": programs,
        "compile_seconds": _value(samples, "compile_seconds_total"),
        "compile_cache_hits": _value(
            samples, "compile_cache_requests_total", result="hit"),
        "compile_cache_misses": _value(
            samples, "compile_cache_requests_total", result="miss"),
        "decode_launches": _value(samples, "host_round_trips_total"),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="same control flow on a toy model under an "
                         "explicit JAX_PLATFORMS=cpu (an opt-in, not a "
                         "fallback)")
    ap.add_argument("--kernel-check", action="store_true",
                    help=argparse.SUPPRESS)     # the first child's entry
    args = ap.parse_args(argv)
    size = TINY if args.tiny else FULL
    if args.kernel_check:
        return kernel_check(size)

    env = dict(os.environ)
    if args.tiny:
        env["JAX_PLATFORMS"] = "cpu"
    print(f"[smoke] {'tiny (CPU, opt-in)' if args.tiny else 'full width'}"
          " smoke run: times below include compilation and are not rates",
          flush=True)

    t0 = time.monotonic()
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--kernel-check"]
        + (["--tiny"] if args.tiny else []),
        cwd=HERE, env=env, stdout=subprocess.PIPE, text=True)
    print(child.stdout, end="", flush=True)
    if child.returncode != 0:
        print(f"[smoke] FAILED: kernel check exited with "
              f"{child.returncode}", flush=True)
        return 1
    kernel = json.loads(child.stdout.strip().splitlines()[-1])
    device = kernel["device"]
    print(f"[smoke] device: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"versions={kernel['versions']}", flush=True)
    for c in kernel["cases"]:
        print(f"[smoke] ragged kernel vs reference at {c['shape']}: max "
              f"abs err {c['max_abs_err']:.3e} (tolerance "
              f"{c['tolerance']:g}), {time.monotonic() - t0:.1f} s",
              flush=True)

    server = Server(size["server"], env)
    try:
        port = server.wait_port(size["start_timeout_s"])
        print(f"[smoke] server up after {time.monotonic() - t0:.1f} s",
              flush=True)
        report = drive(port, size)
        server.drain()
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", flush=True)
        return 1
    finally:
        server.stop()

    for r in report["requests"]:
        print(f"[smoke] request {r['request']}: {r['prompt_tokens']} "
              f"prompt tokens, {r['completion_tokens']} completion tokens"
              f"{' (streamed)' if r.get('streamed') else ''}, "
              f"{r['wall_s']} s wall (smoke timing)", flush=True)
    for lb in report["programs"]:
        print(f"[smoke] program {lb['program']}: attention="
              f"{lb['attention']!r} matmul={lb['matmul']!r} on "
              f"{lb['devices']}", flush=True)
    print(f"[smoke] server compile: {report['compile_seconds']:.1f} s, "
          f"persistent cache {report['compile_cache_hits']:.0f} hits / "
          f"{report['compile_cache_misses']:.0f} misses; kernel check "
          f"compile: {kernel['compile_seconds']:.1f} s, "
          f"{kernel['cache_hits']} hits / {kernel['cache_misses']} misses;"
          f" {report['decode_launches']:.0f} launches; total "
          f"{time.monotonic() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
