"""CLI shutdown contract for ``python -m paddle_tpu.inference.frontend``:
one SIGINT drains gracefully (exit 0), a second SIGINT during the drain
escalates to aborting the in-flight set."""
import http.client
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(os.name != "posix",
                                reason="POSIX signals required")


class _Server:
    """The frontend CLI as a subprocess, stdout pumped to a list."""

    def __init__(self, *extra_args):
        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "paddle_tpu.inference.frontend",
             "--model", "tiny", "--port", "0", *extra_args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
        self.lines = []
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self):
        for line in self.proc.stdout:
            self.lines.append(line)

    def output(self) -> str:
        return "".join(self.lines)

    def wait_for(self, substr, timeout_s=120.0) -> bool:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout_s:
            if substr in self.output():
                return True
            if self.proc.poll() is not None:
                return substr in self.output()
            time.sleep(0.05)
        return False

    def port(self) -> int:
        assert self.wait_for("listening on"), self.output()
        m = re.search(r"listening on http://[\d.]+:(\d+)", self.output())
        assert m, self.output()
        return int(m.group(1))

    def kill(self):
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(timeout=10)


def _stream_in_thread(port, max_tokens):
    """Open a streaming completion and read it to the end (or until the
    server closes it); returns the collector dict."""
    got = {"frames": 0, "finish": None}

    def run():
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=120)
            body = json.dumps({"prompt": [1, 2, 3], "stream": True,
                               "max_tokens": max_tokens}).encode()
            conn.request("POST", "/v1/completions", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            buf = b""
            while True:
                chunk = resp.read(64)
                if not chunk:
                    break
                buf += chunk
                got["frames"] = buf.count(b"data: ")
                m = re.search(rb'"finish_reason":\s*"([^"]+)"', buf)
                if m:
                    got["finish"] = m.group(1).decode()
            conn.close()
        except Exception:
            pass                       # server-side close mid-read is fine

    t = threading.Thread(target=run, daemon=True)
    t.start()
    got["thread"] = t
    return got


def test_cli_sigint_drains_and_exits_zero():
    srv = _Server("--drain-timeout-s", "60")
    try:
        srv.port()                         # up and listening
        srv.proc.send_signal(signal.SIGINT)
        rc = srv.proc.wait(timeout=90)
        assert rc == 0, srv.output()
        out = srv.output()
        assert "draining" in out
        assert "drained" in out and "bye" in out
        assert "DRAIN TIMED OUT" not in out
    finally:
        srv.kill()


def test_cli_second_sigint_aborts_inflight():
    srv = _Server("--drain-timeout-s", "120", "--max-model-len", "512")
    try:
        port = srv.port()
        # a long stream keeps the drain busy well past the second signal
        got = _stream_in_thread(port, max_tokens=400)
        t0 = time.monotonic()
        while got["frames"] < 2 and time.monotonic() - t0 < 120:
            time.sleep(0.05)
        assert got["frames"] >= 2, srv.output()

        srv.proc.send_signal(signal.SIGINT)
        assert srv.wait_for("draining"), srv.output()
        time.sleep(0.3)                    # the graceful drain is underway
        srv.proc.send_signal(signal.SIGINT)
        rc = srv.proc.wait(timeout=90)
        assert rc == 0, srv.output()
        assert "aborting" in srv.output(), srv.output()
        got["thread"].join(timeout=30)
        # the aborted stream got its terminal frame (or, at worst, the
        # closing server won the race and dropped the socket first)
        assert got["finish"] in ("shutdown", None), got
    finally:
        srv.kill()


def test_cli_serves_the_latent_attention_preset():
    """``--model mla-moe-sm``: a latent-attention decoder with expert
    layers through the same CLI, engine and HTTP path; the start-up line
    names its paths and a completion streams to its length."""
    srv = _Server("--model", "mla-moe-sm", "--dtype", "float32",
                  "--max-num-seqs", "4", "--max-prefill-tokens", "32")
    try:
        port = srv.port()
        assert "attention='xla-reference (cpu platform)'" in srv.output()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        body = json.dumps({"prompt": list(range(1, 70)),
                           "max_tokens": 6}).encode()
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        doc = json.loads(resp.read())
        conn.close()
        assert resp.status == 200
        choice = doc["choices"][0]
        assert choice["finish_reason"] == "length"
        assert len(choice["token_ids"]) == 6
        srv.proc.send_signal(signal.SIGINT)
        assert srv.proc.wait(timeout=60) == 0
    finally:
        srv.kill()


def test_cli_serves_the_window_and_global_preset():
    """``--model smallthinker-sm``: global and sliding-window layers over
    two page pools through the same CLI, engine and HTTP path; a
    completion whose prompt is longer than the window (64) streams to its
    length, and ``/metrics`` is served from the engine's summary."""
    srv = _Server("--model", "smallthinker-sm", "--dtype", "float32",
                  "--max-num-seqs", "4", "--max-prefill-tokens", "32",
                  "--no-prefix-caching")
    try:
        port = srv.port()
        assert "attention='xla-reference (cpu platform)'" in srv.output()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        body = json.dumps({"prompt": list(range(1, 150)),
                           "max_tokens": 6}).encode()
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        doc = json.loads(resp.read())
        assert resp.status == 200
        choice = doc["choices"][0]
        assert choice["finish_reason"] == "length"
        assert len(choice["token_ids"]) == 6
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        assert resp.status == 200 and resp.read()
        conn.close()
        srv.proc.send_signal(signal.SIGINT)
        assert srv.proc.wait(timeout=60) == 0
    finally:
        srv.kill()


def test_cli_serves_the_two_head_count_preset():
    """``--model laguna-sm``: full layers of 6 query heads (half-rotated)
    and sliding-window layers of 8 (window 64) over 2 K/V heads, a gated
    attention output, a dense layer then experts beside a shared one,
    through the same CLI, engine and HTTP path: a completion whose prompt
    is longer than the window, prefilled in chunks as long as it."""
    srv = _Server("--model", "laguna-sm", "--dtype", "float32",
                  "--max-num-seqs", "4", "--max-prefill-tokens", "64",
                  "--no-prefix-caching")
    try:
        port = srv.port()
        assert "attention='xla-reference (cpu platform)'" in srv.output()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        body = json.dumps({"prompt": list(range(1, 150)),
                           "max_tokens": 6}).encode()
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        doc = json.loads(resp.read())
        assert resp.status == 200
        choice = doc["choices"][0]
        assert choice["finish_reason"] == "length"
        assert len(choice["token_ids"]) == 6
        conn.close()
        srv.proc.send_signal(signal.SIGINT)
        assert srv.proc.wait(timeout=60) == 0
    finally:
        srv.kill()


def test_cli_serves_the_indexed_latent_preset():
    """``--model dots3-sm``: latent full layers that attend to the 16
    keys an indexer selects, windowed latent layers (window 24) with a
    wider latent, a headwise gate, experts beside a shared one, through
    the same CLI, engine and HTTP path: a completion whose prompt is
    longer than the selection, the window and a chunk."""
    srv = _Server("--model", "dots3-sm", "--dtype", "float32",
                  "--max-num-seqs", "4", "--max-prefill-tokens", "64",
                  "--no-prefix-caching")
    try:
        port = srv.port()
        assert "attention='xla-reference (cpu platform)'" in srv.output()
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        body = json.dumps({"prompt": list(range(1, 150)),
                           "max_tokens": 6}).encode()
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        doc = json.loads(resp.read())
        assert resp.status == 200
        choice = doc["choices"][0]
        assert choice["finish_reason"] == "length"
        assert len(choice["token_ids"]) == 6
        conn.close()
        srv.proc.send_signal(signal.SIGINT)
        assert srv.proc.wait(timeout=60) == 0
    finally:
        srv.kill()


def test_cli_serves_the_state_space_hybrid_preset():
    """``--model phi4flash-sm``: Mamba-1 layers with a state a sequence
    beside the pages, differential attention under a window and over
    all, memory units and cross layers on one layer's K/V, through the
    same CLI, engine and HTTP path: a completion whose prompt is longer
    than the window and a chunk, then a second one in the slot the
    first left."""
    srv = _Server("--model", "phi4flash-sm", "--dtype", "float32",
                  "--max-num-seqs", "1", "--max-prefill-tokens", "64",
                  "--no-prefix-caching")
    try:
        port = srv.port()
        assert "attention='xla-reference (cpu platform)'" in srv.output()
        got = []
        for n in (150, 9):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=120)
            body = json.dumps({"prompt": list(range(1, n)),
                               "max_tokens": 6}).encode()
            conn.request("POST", "/v1/completions", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            doc = json.loads(resp.read())
            assert resp.status == 200
            choice = doc["choices"][0]
            assert choice["finish_reason"] == "length"
            assert len(choice["token_ids"]) == 6
            got.append(choice["token_ids"])
            conn.close()
        srv.proc.send_signal(signal.SIGINT)
        assert srv.proc.wait(timeout=60) == 0
    finally:
        srv.kill()


def test_cli_refuses_prefix_caching_over_a_state_by_name():
    srv = _Server("--model", "phi4flash-sm", "--dtype", "float32",
                  "--max-num-seqs", "4")
    try:
        assert srv.proc.wait(timeout=120) != 0
        assert "enable_prefix_caching=True is not supported for a model " \
            "with state-space layers" in srv.output()
        assert "snapshot of the state" in srv.output()
    finally:
        srv.kill()


def test_cli_refuses_int8_pages_over_latent_and_index_pools_by_name():
    srv = _Server("--model", "dots3-sm", "--dtype", "float32",
                  "--max-num-seqs", "4", "--no-prefix-caching",
                  "--kv-dtype", "int8")
    try:
        assert srv.proc.wait(timeout=120) != 0
        assert "kv_dtype='int8' is not supported for a model with " \
            "latent-attention (MLA) layers" in srv.output()
        assert "indexer's keys quantised" in srv.output()
    finally:
        srv.kill()


def test_cli_refuses_prefix_caching_over_the_window_pool_by_name():
    srv = _Server("--model", "smallthinker-sm", "--dtype", "float32",
                  "--max-num-seqs", "4")
    try:
        assert srv.proc.wait(timeout=120) != 0
        assert "enable_prefix_caching=True is not supported for a model " \
            "with sliding-window layers" in srv.output()
    finally:
        srv.kill()
