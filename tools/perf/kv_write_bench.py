"""``kv_write`` alone on the chip: the Pallas page writer
(``ops/pallas/kv_page_write.py``) against the XLA row scatter
(``layer_stack._set_rows``), as a step program has them: inside an
eight-layer ``lax.scan`` with the donated pools in the carry
(``layer_stack.scan_layers``), at the benchmark's pool sizes.  A call
runs that scan ``--repeats`` times over, so that the host's dispatch of
a call (0.27 ms on the v5e's host, eight writer layers' worth) does not
stand in for the device's time.

    chiprun -- python3 tools/perf/kv_write_bench.py

A launch is a chunk beside decode rows (32 tokens: 32 decode rows alone;
192: a 128-token chunk that starts mid-page beside 32 rows; 576: a
512-token chunk beside 28 rows), the bucket's tail padded.  Both sides
first write the same launch into equal pools and every page but the null
page is compared bit for bit; then each is timed over ``--calls`` calls,
a host readback at the end.  ``--tiny`` is the same
control flow at toy sizes for the CPU (the kernel interpreted).  One
JSON line a case on stdout, the table under ``chiprun_out/``.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core import runtime
from paddle_tpu.inference import layer_stack as ls
from paddle_tpu.ops.pallas import kv_page_write as kw
from paddle_tpu.ops.pallas import paged_attention as pa

LAYERS, BS, D, ROWS = 8, 16, 128, 32
# K/V heads -> pages a layer (mistral-7b, yi-1.5-6b: 4097; phi4flash's
# window pool: 1569)
POOLS = {4: 4097, 8: 4097, 10: 1569}


def launch(tq, rng, nblk):
    """(cu, kvl): the bucket's launch; a decode row at 100 to 1500 keys,
    the chunk at 37 keys before it (mid-page)."""
    chunk = {32: 0, 192: 128, 576: 512}.get(tq, max(tq - ROWS, 0))
    decode = min(28 if tq >= 576 else ROWS - (chunk > 0), tq - chunk)
    q = np.zeros(ROWS, np.int32)
    q[:decode] = 1
    hi = max(2, min(1500, nblk * BS - chunk - 40))
    before = rng.integers(min(100, hi - 1), hi, ROWS)
    if chunk:
        q[decode], before[decode] = chunk, 37
    cu = np.concatenate([[0], np.cumsum(q)]).astype(np.int32)
    return cu, (before + q).astype(np.int32) * (q > 0)


def case(tq, hkv, num_blocks, nblk, dtype, calls, repeats):
    rng = np.random.default_rng(tq * 131 + hkv)
    cu, kvl = launch(tq, rng, nblk)
    bt = np.zeros((ROWS + 1, nblk), np.int32)
    bt[:ROWS] = rng.permutation(np.arange(1, num_blocks))[
        :ROWS * nblk].reshape(ROWS, nblk)
    cu, kvl, bt = jnp.asarray(cu), jnp.asarray(kvl), jnp.asarray(bt)
    seg, rel = pa.ragged_segments(cu, kvl, tq)
    key = jax.random.PRNGKey(tq + hkv)
    rows = jax.random.normal(key, (LAYERS, 2, tq, hkv, D), dtype)

    def program(use_pallas):
        c = ls.step_context(seg=seg, rel=rel, cu=cu, kvl=kvl, bs=BS,
                            use_pallas=use_pallas, scanned=True)

        def body(x, p, pools, l):
            with jax.named_scope("kv_write"):
                return x, ls._commit_float(p[0], p[1], pools, l, bt, c)

        def run(kc, vc):
            return jax.lax.fori_loop(
                0, repeats, lambda _, pools: ls.scan_layers(
                    body, jnp.zeros(()), rows, pools)[1], (kc, vc))
        return jax.jit(run, donate_argnums=(0, 1))

    def pools():
        shape = (LAYERS, num_blocks, hkv, BS, D)
        return tuple(jax.random.normal(jax.random.PRNGKey(s), shape, dtype)
                     for s in (1, 2))

    out = {"tq": tq, "hkv": hkv, "num_blocks": num_blocks,
           "live_tokens": int(cu[-1]),
           "pages": int(jnp.sum(jnp.where(
               kvl > 0, (kvl - 1) // BS - (kvl - jnp.diff(cu)) // BS + 1,
               0)))}
    progs = {"scatter": program(False), "writer": program(True)}
    got = {name: prog(*pools()) for name, prog in progs.items()}
    out["equal"] = all(bool(jnp.array_equal(a[:, 1:], b[:, 1:]))
                       for a, b in zip(got["scatter"], got["writer"]))
    for name, prog in progs.items():
        kc, vc = got.pop(name)
        t0 = time.perf_counter()
        for _ in range(calls):
            kc, vc = prog(kc, vc)
        float(kc[0, 0, 0, 0, 0])
        out[f"{name}_ms_a_layer"] = (time.perf_counter() - t0) \
            / calls / repeats / LAYERS * 1e3
        del kc, vc
    out["speedup"] = out["scatter_ms_a_layer"] / out["writer_ms_a_layer"]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=32)
    ap.add_argument("--slots", default="",
                    help="comma-separated page_slots settings to sweep")
    args = ap.parse_args()
    if args.tiny:
        pa.INTERPRET = True
        cases = [(32, 4, 257, 8, jnp.float32),
                 (64, 10, 257, 8, jnp.bfloat16)]
        args.calls, args.repeats = 1, 2
    else:
        runtime.configure_compile_cache()
        dev = runtime.resolve_device()
        if dev["platform"] != "tpu":
            raise SystemExit("kv_write_bench measures on the chip")
        cases = [(tq, hkv, POOLS[hkv], (POOLS[hkv] - 1) // ROWS,
                  jnp.bfloat16)
                 for hkv in (4, 8, 10) for tq in (32, 192, 576)]
    sweep = [int(s) for s in args.slots.split(",") if s] \
        or [kw._DEFAULTS["page_slots"]]
    table = []
    for slots in sweep:
        os.environ["PADDLE_TPU_TUNE_FORCE"] = json.dumps(
            {"kv_page_write": {"page_slots": slots}})
        kw._launch.clear_cache()
        for cs in cases:
            rec = {"slots": slots,
                   **case(*cs, calls=args.calls, repeats=args.repeats)}
            table.append(rec)
            print(json.dumps(rec), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kv_write_bench.json", "w") as f:
        json.dump(table, f, indent=1)


if __name__ == "__main__":
    main()
