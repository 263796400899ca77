"""Traffic of kind ``closed_loop``: N clients, each sending its next
request when the last one has finished.  One general generator; a mix is
a data file of parameters under ``benchmark/traffic/``.

This module is imported by the load generator's child, which must stay
off JAX and off the program: standard library and numpy only.

What the file fixes (see ``traffic/chat.json``): the client count, the
64 (prompt, output) pairs (not drawn: the same multiset of work under
every seed) and the order they are dealt in (``deal.order``, the same
under every seed too: PERF.md says why), shared prefixes, the warm-up stages
that fill this cell's token buckets, the primers that stagger the
clients' phases, and when the window opens.  What the seed decides: the
token ids.
"""
from __future__ import annotations

import http.client
import json
import socket
import statistics
import threading
import time

import numpy as np

_PREFIX_STREAM = 1_000_003
_PRIMER_STREAM = 2_000_003
_WARM_STREAM = 3_000_003


# ---------------------------------------------------------------------------
# what the seed decides (pure functions: the parent regenerates prompts
# for the comparison from the same calls)
# ---------------------------------------------------------------------------

def stratified(median: float, sigma: float, lo: int, hi: int,
               n: int = 64) -> list:
    """Midpoints of n equal-probability slices of a log-normal, cut to
    [lo, hi] and rounded: the length list a traffic file states."""
    nd = statistics.NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = median * float(np.exp(sigma * z))
        out.append(int(min(hi, max(lo, round(v)))))
    return out


def tokens(seed: int, stream: int, index: int, n: int, vocab: int) -> list:
    """n token ids uniform over the vocabulary, a function of
    (seed, stream, index) alone."""
    rng = np.random.default_rng([int(seed), int(stream), int(index)])
    return rng.integers(0, int(vocab), size=int(n)).tolist()


def balanced_order(seed: int, n_pairs: int, block: int) -> list:
    """How the orders in the traffic files were made (the generator
    itself only reads ``deal.order``): the list, which a file keeps
    sorted by prompt length, is cut into strata of ``n_pairs / block``
    neighbours, and every ``block`` pairs in a row hold one of each
    stratum; the seed decides which, and the order inside the block."""
    rng = np.random.default_rng([int(seed), 7])
    groups = int(n_pairs) // int(block)
    blocks = [[] for _ in range(groups)]
    for s in range(int(block)):
        for g, b in enumerate(rng.permutation(groups)):
            blocks[b].append(s * groups + g)
    out = []
    for b in blocks:
        out.extend(b[i] for i in rng.permutation(len(b)))
    return out


def primer_phases(clients: int, phase_max: int) -> list:
    """Each client's primer length: phases spread evenly over
    1..phase_max, handed to clients in one fixed shuffled order.  Not
    from the seed: clients start in turn and the engine prefills them
    first come first served, so which client holds which phase moves
    every later arrival, and with it the whole run (PERF.md section 2)."""
    if clients == 1:
        return [1]
    phases = [int(round(1 + (phase_max - 1) * i / (clients - 1)))
              for i in range(clients)]
    rng = np.random.default_rng([11, int(clients)])
    return [phases[i] for i in rng.permutation(clients)]


def prefix_tokens(spec: dict, seed: int, which: int, vocab: int) -> list:
    n = int(spec["prefixes"][which]["tokens"])
    return tokens(seed, _PREFIX_STREAM, which, n, vocab)


def dealt_request(spec: dict, seed: int, j: int, vocab: int) -> dict:
    """The j-th dealt request: its pair, its prefix, its prompt."""
    pairs = spec["pairs"]
    order = spec["deal"]["order"]
    p_len, o_len = pairs[order[j % len(pairs)]]
    prompt = tokens(seed, 0, j, p_len, vocab)
    prefix = None
    if spec.get("prefixes"):
        prefix = j % len(spec["prefixes"])
        prompt = prefix_tokens(spec, seed, prefix, vocab) + prompt
    return {"kind": "deal", "index": j, "prefix": prefix,
            "prompt": prompt, "max_tokens": int(o_len)}


def primer_request(spec: dict, seed: int, client: int, vocab: int) -> dict:
    """A client's first request: a short prompt of its own (after a
    shared prefix, dealt in turn, where the mix has prefixes: its K/V is
    then as long as a dealt request's) and as many tokens as its phase."""
    pr = spec["primer"]
    phase = primer_phases(int(spec["clients"]), int(pr["phase_max"]))[client]
    prompt = tokens(seed, _PRIMER_STREAM, client, int(pr["prompt_tokens"]),
                    vocab)
    prefix = None
    if spec.get("prefixes"):
        prefix = client % len(spec["prefixes"])
        prompt = prefix_tokens(spec, seed, prefix, vocab) + prompt
    return {"kind": "primer", "index": client, "prefix": prefix,
            "prompt": prompt, "max_tokens": phase}


def warm_request(spec: dict, seed: int, k: int, item: dict,
                 vocab: int) -> dict:
    prompt = tokens(seed, _WARM_STREAM, k, int(item["prompt_tokens"]), vocab)
    prefix = item.get("prefix")
    if prefix is not None:
        prompt = prefix_tokens(spec, seed, int(prefix), vocab) + prompt
    return {"kind": "warm", "index": k, "prefix": prefix, "prompt": prompt,
            "max_tokens": int(item["max_tokens"])}


def rebuild_prompt(spec: dict, seed: int, rec: dict, vocab: int) -> list:
    """The prompt of a record the child returned, from the seed."""
    if rec["kind"] == "deal":
        return dealt_request(spec, seed, rec["index"], vocab)["prompt"]
    if rec["kind"] == "primer":
        return primer_request(spec, seed, rec["index"], vocab)["prompt"]
    k = rec["index"]
    flat = [it for stage in spec["warmup"] for it in stage["requests"]]
    return warm_request(spec, seed, k, flat[k], vocab)["prompt"]


# ---------------------------------------------------------------------------
# the client (the SSE loop of tools/perf/serve_bench.py's _http_drive,
# with read1 so that a frame is stamped when its own chunk arrives)
# ---------------------------------------------------------------------------

class _Flight:
    """One request on the wire; ``cut()`` ends it from another thread."""

    def __init__(self):
        self.conn = None
        self.lock = threading.Lock()
        self.was_cut = False

    def cut(self):
        with self.lock:
            self.was_cut = True
            conn = self.conn
        if conn is not None and conn.sock is not None:
            try:
                conn.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


def send(port: int, req: dict, flight: _Flight | None = None,
         on_token=None) -> dict:
    """POST one streaming completion; returns its record.  Times are
    ``time.monotonic_ns`` (CLOCK_MONOTONIC: the same clock in the parent
    and in the program's Tracer)."""
    rec = {"kind": req["kind"], "index": req["index"],
           "prefix": req["prefix"], "prompt_tokens": len(req["prompt"]),
           "max_tokens": req["max_tokens"], "tokens": [], "stamps": [],
           "finish": None, "error": None, "cut": False}
    body = json.dumps({"prompt": req["prompt"],
                       "max_tokens": req["max_tokens"],
                       "temperature": 0, "stream": True}).encode()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    if flight is not None:
        with flight.lock:
            flight.conn = conn
    try:
        rec["t_send"] = time.monotonic_ns()
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"http {resp.status}: {resp.read(300)!r}"
            return rec
        buf, done = b"", False
        while not done:
            chunk = resp.read1(65536)
            if not chunk:
                break
            now = time.monotonic_ns()
            buf += chunk
            while b"\n\n" in buf:
                frame, buf = buf.split(b"\n\n", 1)
                data = frame.partition(b"data: ")[2].decode()
                if data == "[DONE]":
                    done = True
                    continue
                ch = json.loads(data)["choices"][0]
                if ch["finish_reason"] is not None:
                    rec["finish"] = ch["finish_reason"]
                    continue
                rec["tokens"].append(ch["token"])
                rec["stamps"].append(now)
                if on_token is not None:
                    on_token(rec)
        if not done:
            rec["error"] = "stream ended before [DONE]"
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()
    if flight is not None and flight.was_cut and rec["finish"] is None:
        rec["cut"], rec["error"] = True, None
    return rec


def _run_stage(port, spec, seed, vocab, stage, k0, records):
    """One warm-up stage: its requests start in order, each later one
    once the first has ``after_tokens`` tokens; the stage ends when all
    have finished."""
    items = stage["requests"]
    gate = threading.Event()
    need = int(stage.get("after_tokens", 1))

    def first_cb(rec):
        if len(rec["tokens"]) >= need:
            gate.set()

    out = [None] * len(items)

    def one(i):
        req = warm_request(spec, seed, k0 + i, items[i], vocab)
        out[i] = send(port, req, on_token=first_cb if i == 0 else None)
        if i == 0:
            gate.set()

    threads = []
    for i in range(len(items)):
        if i > 0:
            gate.wait(timeout=600)
        t = threading.Thread(target=one, args=(i,))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    records.extend(out)


def drive(port: int, spec: dict, vocab: int, seed: int, seconds: float,
          emit) -> None:
    """Warm up, start the loop, open the window on the running system,
    close it ``seconds`` later, cut what is in flight, hand back every
    record.  ``emit(dict)`` writes one line to the parent."""
    records: list = []
    lock = threading.Lock()
    t0 = time.monotonic_ns()
    k = 0
    for stage in spec.get("warmup", []):
        _run_stage(port, spec, seed, vocab, stage, k, records)
        k += len(stage["requests"])
    emit({"event": "warm_done", "t_ns": time.monotonic_ns(),
          "seconds": (time.monotonic_ns() - t0) / 1e9,
          "requests": len(records)})

    n_clients = int(spec["clients"])
    stop = threading.Event()
    primed = [threading.Event() for _ in range(n_clients)]
    flights = [_Flight() for _ in range(n_clients)]
    state = {"next_deal": 0}
    enough = threading.Event()
    open_after = int(spec["window_open"]["after_dealt_sent"])

    def client(c):
        def on_primer_token(rec):
            primed[c].set()

        fl = flights[c]
        rec = send(port, primer_request(spec, seed, c, vocab), fl,
                   on_token=on_primer_token)
        rec["client"] = c
        primed[c].set()
        with lock:
            records.append(rec)
        while not stop.is_set() and rec["error"] is None:
            with lock:
                j = state["next_deal"]
                state["next_deal"] += 1
            req = dealt_request(spec, seed, j, vocab)
            if j + 1 >= open_after:
                enough.set()
            fl = flights[c] = _Flight()
            if stop.is_set():
                break
            rec = send(port, req, fl)
            rec["client"] = c
            with lock:
                records.append(rec)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for ev in primed:
        ev.wait(timeout=600)
    enough.wait(timeout=600)
    t_open = time.monotonic_ns()
    emit({"event": "window_open", "t_ns": t_open,
          "loop_warm_s": (t_open - t0) / 1e9})
    t_close = t_open + int(seconds * 1e9)
    while True:
        left = (t_close - time.monotonic_ns()) / 1e9
        if left <= 0:
            break
        time.sleep(min(left, 0.05))
    emit({"event": "window_close", "t_ns": time.monotonic_ns()})
    stop.set()
    for fl in list(flights):
        fl.cut()
    for t in threads:
        t.join(timeout=30)
        if t.is_alive():
            # a reader that did not see the cut: cut whatever it holds now
            for fl in list(flights):
                fl.cut()
            t.join(timeout=30)
    with lock:
        out = list(records)
    emit({"event": "records", "t_open": t_open, "t_close": t_close,
          "records": out})
