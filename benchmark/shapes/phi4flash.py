"""Shapes and counts of a decoder-hybrid-decoder (``model_type``
``phi4flash``: Mamba-1 layers, differential attention under a window and
over all, gated memory units and cross-attention layers that read ONE
layer's K/V; ``references/phi4flash.py`` has the equations), as the
engine serves it.  Imports nothing of the program.  What every shapes
file states is listed in ``shapes/llama_dense.py``; what differs here:

- a leaf the step program scans over is DECLARED stacked: the stack is
  (Mamba, window attention) x L/4, a Mamba layer, a full-attention
  layer, (memory unit, cross attention) x (L/4 - 1), and ``leaves``
  gives each of those four runs ONE tag (0..3) whose leaves carry a
  leading axis over the run's repeats (none for a single layer) and a
  name ``<k>.<leaf>``, k the layer's place in its period.  ``layer_of``
  turns a depth into (tag, k, repeat) for whoever wants one layer;
- ``published``: the leaves whose scale decides whether a state lives
  (``A_log``, ``b_dt``, ``D``, the lambda vectors, the convolution) are
  drawn as ``norm`` / ``matrix`` leaves like the others and mapped to
  their published initialisation by this ONE function, which reference
  and builder both call;
- three sets of cached rows: K and V of the ONE full-attention layer
  ``[1, num_blocks, kvh/2, block, 2 hd]`` under the block table, of the
  window layers ``[L/4, Nw, ...]`` under a table of their own (``Nw``
  the engine's arithmetic, ``window_blocks``), and a state a sequence a
  Mamba layer by batch slot: the convolution's last inputs
  ``[L/4 + 1, slots + 1, taps - 1, d_inner]`` and the scan's state
  ``[L/4 + 1, slots + 1, d_state, d_inner]`` float32;
- the counts are what the MATHEMATICS needs: scores 64 wide (the program
  widens a query head with zeros to the 128-wide rows it caches, which
  doubles its score products: not counted), a window layer's pairs and
  bytes inside the window, the cross layers' reads of the one layer's
  rows once a layer; ``scan_row`` the least of a segment's scan."""
from __future__ import annotations

import math

TOP = (("embed", "embedding"), ("norm_f", "norm"), ("norm_f_b", "zero"))
_NORMS = (("ln1", "norm"), ("ln1_b", "zero"))
_FFN = (("ln2", "norm"), ("ln2_b", "zero"), ("gate", "matrix"),
        ("up", "matrix"), ("down", "matrix"))
_LAMBDA = (("lq1", "norm"), ("lk1", "norm"), ("lq2", "norm"),
           ("lk2", "norm"), ("subln", "norm"))
MIXER = {
    "ssm": (("w_in", "matrix"), ("conv_w", "matrix"), ("conv_b", "zero"),
            ("w_x", "matrix"), ("w_dt", "matrix"), ("b_dt", "norm"),
            ("A_log", "norm"), ("D", "norm"), ("w_out", "matrix")),
    "gmu": (("w_in", "matrix"), ("w_out", "matrix")),
    "diff": (("wqkv", "matrix"), ("bqkv", "zero")) + _LAMBDA
    + (("wo", "matrix"), ("bo", "zero")),
    "cross": (("wq", "matrix"), ("bq", "zero")) + _LAMBDA
    + (("wo", "matrix"), ("bo", "zero")),
}

WINDOW_KERNELS = ("ragged_paged_attention_window",)
CROSS_KERNELS = ("ragged_paged_attention_cross",)
SSM_KERNELS = ("ragged_selective_scan",)
KERNELS = ("ragged_paged_attention",) + WINDOW_KERNELS + CROSS_KERNELS
SCOPES = ("embed", "norm", "qkv", "kv_write", "attn", "attn_window",
          "attn_cross", "attn_diff", "o_proj", "ssm_proj", "ssm_conv",
          "ssm_scan", "gmu", "mlp", "head", "sample")
LOOP = "layers"
MATMUL_SCOPES = ("qkv", "o_proj", "ssm_proj", "gmu", "mlp", "head")
SAMPLE_SCOPES = ("sample",)
POOL_SCOPES = ("kv_write", "attn", "attn_window", "attn_cross", "ssm_conv",
               "ssm_scan")
SSM_SCOPES = ("ssm_proj", "ssm_conv", "ssm_scan")
GMU_SCOPES = ("gmu",)


def dims(cfg: dict) -> dict:
    H, nh = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    L = int(cfg["num_hidden_layers"])
    if L % 4 or L < 12 or int(cfg["mb_per_layer"]) != 2:
        raise ValueError(f"{L} layers at mb_per_layer "
                         f"{cfg['mb_per_layer']}: not this stack")
    a = cfg["assumed_sizes"]
    return {"H": H, "nh": nh, "kvh": int(cfg["num_key_value_heads"]),
            "hd": H // nh, "F": int(cfg["intermediate_size"]),
            "W": int(cfg["sliding_window"]), "V": int(cfg["vocab_size"]),
            "L": L, "Lw": L // 4, "Ls": L // 4 + 1, "Lx": L // 4 - 1,
            "di": int(a["expand"]) * H, "N": int(a["d_state"]),
            "taps": int(a["d_conv"]), "R": int(a["dt_rank"]),
            "eps": float(cfg["layer_norm_eps"])}


# the four runs of the stack: (the kinds of one period, repeats)
def runs(m: dict) -> list:
    return [(("ssm", "diff"), m["Lw"]), (("ssm",), 1), (("diff",), 1),
            (("gmu", "cross"), m["Lx"])]


def layer_of(m: dict, i: int) -> tuple:
    """(tag, place in the period, repeat) of the layer at depth ``i``."""
    at = 0
    for tag, (kinds, n) in enumerate(runs(m)):
        if i < at + len(kinds) * n:
            return tag, (i - at) % len(kinds), (i - at) // len(kinds)
        at += len(kinds) * n
    raise IndexError(i)


def kind_of(m: dict, i: int) -> str:
    """``ssm``, ``ssm_keep``, ``diff_window``, ``diff``, ``gmu`` or
    ``diff_cross``: the layer at depth ``i``."""
    half = m["L"] // 2
    if i % 2 == 0:
        return "ssm" if i < half else "ssm_keep" if i == half else "gmu"
    return "diff_window" if i < half else "diff" if i == half + 1 \
        else "diff_cross"


def _shape(m: dict, name: str) -> tuple:
    H, F, di, N, R = m["H"], m["F"], m["di"], m["N"], m["R"]
    nh, kvh, hd = m["nh"], m["kvh"], m["hd"]
    wide = (nh + 2 * kvh) * hd
    return {"ln1": (H,), "ln1_b": (H,), "ln2": (H,), "ln2_b": (H,),
            "norm_f": (H,), "norm_f_b": (H,), "embed": (m["V"], H),
            "gate": (H, F), "up": (H, F), "down": (F, H),
            "w_in": (H, 2 * di),
            "conv_w": (m["taps"], di), "conv_b": (di,),
            "w_x": (di, R + 2 * N), "w_dt": (R, di), "b_dt": (di,),
            "A_log": (N, di), "D": (di,), "w_out": (di, H),
            "wqkv": (H, wide), "bqkv": (wide,), "wq": (H, nh * hd),
            "bq": (nh * hd,), "lq1": (hd,), "lk1": (hd,), "lq2": (hd,),
            "lk2": (hd,), "subln": (2 * hd,), "wo": (nh * hd, H),
            "bo": (H,)}[name]


def leaves(cfg: dict) -> list:
    """[(name, tag or None, shape, kind)]; a leaf's place in the list is
    the index its draw is folded from.  The tag is a run of the stack;
    a leaf of a run that repeats has the repeats as its first axis."""
    m = dims(cfg)
    out = [(n, None, _shape(m, n), k) for n, k in TOP]
    for tag, (kinds, n) in enumerate(runs(m)):
        lead = (n,) if n > 1 else ()
        for k, kind in enumerate(kinds):
            for name, how in _NORMS + MIXER[kind] + _FFN:
                # (a memory unit's input projection has no z half)
                shape = (m["H"], m["di"]) if (kind, name) == ("gmu", "w_in") \
                    else _shape(m, name)
                out.append((f"{k}.{name}", tag, lead + shape, how))
    return out


# the leaves ``published`` maps; every other is handed on as drawn
PUBLISHED = ("A_log", "b_dt", "lq1", "lk1", "lq2", "lk2", "embed", "conv_w")


def published(name: str, drawn):
    """A drawn leaf as its published initialisation has it (``name``
    without its place): ``A_log`` = log(1..N) down the states with the
    draw's deviation about it, ``D`` near 1 as drawn, ``softplus(b_dt)``
    log-uniform over 1e-3..1e-1, the lambda vectors at deviation 0.1
    about 0, the convolution's taps at the deviation of a uniform draw
    over +-1/sqrt(taps), the embedding at deviation 0.02 (it is the
    head too: at the draw's unit deviation a token's logit for ITSELF
    stands 50 deviations over the rest and every model repeats its
    input, whatever its layers compute).  Every other leaf as drawn.  In
    the type of the draw: reference and program start from the same
    bits."""
    import jax.numpy as jnp
    f = drawn.astype(jnp.float32)
    if name == "A_log":
        n = drawn.shape[-2]
        out = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[:, None] \
            + (f - 1.0)
    elif name == "b_dt":
        share = jnp.clip(((f - 1.0) / 0.3 + 1.0) / 2.0, 0.0, 1.0)
        dt = jnp.exp(share * (math.log(1e-1) - math.log(1e-3))
                     + math.log(1e-3))
        out = dt + jnp.log(-jnp.expm1(-dt))          # softplus's inverse
    elif name in ("lq1", "lk1", "lq2", "lk2"):
        out = f - 1.0
    elif name == "embed":
        # drawn at unit deviation; the family's initializer_range
        out = f * 0.02
    elif name == "conv_w":
        taps, di = drawn.shape[-2:]
        out = f * (math.sqrt((taps + di) / 2.0) / math.sqrt(3.0 * taps))
    else:
        return drawn
    return out.astype(drawn.dtype)


def window_blocks(cfg: dict) -> int:
    """Pages of the window layers' pool as the engine derives them."""
    s, m = cfg["serving"], dims(cfg)
    bs = int(s["block_size"])
    per_seq = min(-(-int(s["max_model_len"]) // bs),
                  -(-m["W"] // bs) + -(-int(s["max_prefill_tokens"]) // bs)
                  + 1)
    return 1 + int(s["max_num_seqs"]) * per_seq


def pool_shapes(cfg: dict) -> set:
    """Dimension lists of the two pairs of page pools, of the two state
    arrays, and of one layer of each (also with a leading 1)."""
    s, m = cfg["serving"], dims(cfg)
    page = [m["kvh"] // 2, int(s["block_size"]), 2 * m["hd"]]
    slots = int(s["max_num_seqs"]) + 1
    out = set()
    for layers, one in ((1, [int(s["num_blocks"])] + page),
                        (m["Lw"], [window_blocks(cfg)] + page),
                        (m["Ls"], [slots, m["taps"] - 1, m["di"]]),
                        (m["Ls"], [slots, m["N"], m["di"]])):
        out |= {tuple([layers] + one), tuple(one), tuple([1] + one)}
    return out


def state_bytes(cfg: dict) -> int:
    """Bytes of the two state arrays."""
    s, m = cfg["serving"], dims(cfg)
    return m["Ls"] * (int(s["max_num_seqs"]) + 1) * m["di"] \
        * (m["N"] * 4 + (m["taps"] - 1) * 2)


def parameters(cfg: dict) -> int:
    """Elements of every leaf (the embedding once: the head is tied)."""
    return sum(math.prod(shape) for _n, _t, shape, _k in leaves(cfg))


def mixer_weights(m: dict, kind: str) -> int:
    """Matrix elements of one layer's mixer that XLA's dots read."""
    H, di, N, R, nh, kvh, hd = (m["H"], m["di"], m["N"], m["R"], m["nh"],
                                m["kvh"], m["hd"])
    return {"ssm": H * 2 * di + di * (R + 2 * N) + R * di + di * H,
            "gmu": 2 * H * di,
            "diff": H * (nh + 2 * kvh) * hd + nh * hd * H,
            "cross": 2 * H * nh * hd}[kind]


def step_matmuls(cfg: dict, tokens: int, logit_rows: int, *,
                 bytes_per: int = 2, logit_bytes: int = 4) -> tuple:
    """(operations, bytes) of the products that run as XLA dots in one
    step of ``tokens`` real query tokens and ``logit_rows`` scored rows:
    every layer's mixer projections and FFN, and the tied head.

    Operations: 2 per weight element per token.  Bytes: those weights
    once a step; per token and layer the activations each product reads
    and writes; per logit row its hidden state in and its float32 logits
    out."""
    m = dims(cfg)
    H, F, di, V = m["H"], m["F"], m["di"], m["V"]
    nh, kvh, hd = m["nh"], m["kvh"], m["hd"]
    counts = {"ssm": m["Ls"], "gmu": m["Lx"], "diff": m["Lw"] + 1,
              "cross": m["Lx"]}
    # what a layer's products read and write per token
    acts = {"ssm": (H + 2 * di) + (di + m["R"] + 2 * m["N"])
            + (m["R"] + di) + (di + H),
            "gmu": (H + di) + (di + H),
            "diff": (H + (nh + 2 * kvh) * hd) + (nh * hd + H),
            "cross": (H + nh * hd) + (nh * hd + H)}
    ffn_w = 3 * H * F
    ffn_a = (H + 2 * F) + (F + H)
    w = sum(n * (mixer_weights(m, k) + ffn_w) for k, n in counts.items())
    a = sum(n * (acts[k] + ffn_a) for k, n in counts.items())
    ops = 2 * tokens * w + 2 * logit_rows * H * V
    byt = (w + H * V) * bytes_per + tokens * a * bytes_per \
        + logit_rows * (H * bytes_per + V * logit_bytes)
    return ops, byt


def _pairs(n_q: int, kv_len: int, window) -> tuple:
    """((query, key) pairs, keys read) of one layer for a row of ``n_q``
    queries that ends at ``kv_len`` keys."""
    first = kv_len - n_q
    if window is None or kv_len <= window:
        return n_q * kv_len - n_q * (n_q - 1) // 2, kv_len
    short = max(0, min(n_q, window - 1 - first))
    pairs = short * (first + 1) + short * (short - 1) // 2 \
        + (n_q - short) * window
    return pairs, kv_len - max(0, first - window + 1)


def _row(m: dict, n_q: int, kv_len: int, window, writes: bool,
         bytes_per: int) -> tuple:
    """One attention layer's (operations, bytes) for a row.  A pair
    costs a 64-wide score for each of the nh query heads and, for each
    of the nh / 2 head pairs, two maps over a 128-wide value pair."""
    nh, kvh, hd = m["nh"], m["kvh"], m["hd"]
    pairs, keys = _pairs(n_q, kv_len, window)
    ops = pairs * (2 * nh * hd + 2 * (nh // 2) * 2 * (2 * hd))
    byt = 2 * keys * kvh * hd + 2 * n_q * nh * hd      # K, V; q in
    byt += n_q * nh * 2 * hd                           # both maps out
    if writes:
        byt += 2 * n_q * kvh * hd
    return ops, byt * bytes_per


def window_attention_row(cfg: dict, n_q: int, kv_len: int, *,
                         bytes_per: int = 2) -> tuple:
    """(operations, bytes) of the WINDOW layers' attention for one row:
    only the pairs and the keys inside the window."""
    m = dims(cfg)
    ops, byt = _row(m, n_q, kv_len, m["W"], True, bytes_per)
    return ops * m["Lw"], byt * m["Lw"]


def cross_attention_row(cfg: dict, n_q: int, kv_len: int, *,
                        bytes_per: int = 2) -> tuple:
    """(operations, bytes) of the cross layers' attention for one row:
    each reads the one full layer's rows again and writes none."""
    m = dims(cfg)
    ops, byt = _row(m, n_q, kv_len, None, False, bytes_per)
    return ops * m["Lx"], byt * m["Lx"]


def attention_row(cfg: dict, n_q: int, kv_len: int, *,
                  bytes_per: int = 2) -> tuple:
    """(operations, bytes) of attention, all sixteen layers, for one row
    of ``n_q`` queries that ends at ``kv_len`` keys."""
    m = dims(cfg)
    of, bf = _row(m, n_q, kv_len, None, True, bytes_per)
    ow, bw = window_attention_row(cfg, n_q, kv_len, bytes_per=bytes_per)
    ox, bx = cross_attention_row(cfg, n_q, kv_len, bytes_per=bytes_per)
    return of + ow + ox, bf + bw + bx


def scan_step(cfg: dict, rows: int, segments: int, starts: int, *,
              bytes_per: int = 2) -> tuple:
    """(operations, bytes) of the selective scan, all Mamba layers, for
    a launch of ``segments`` rows that hold ``rows`` tokens, ``starts``
    of them a sequence's first (no state to read).

    Operations, a token and state element: the decay's product and
    exponential, its product with the state, the input's product and
    sum, the output's product and sum: 7; a token and channel 3 more
    (delta * u, D * u and its sum).  Bytes: a token's u, delta and y
    (d_inner each) and its B and C; a segment's state written (float32)
    and, unless it starts, read."""
    m = dims(cfg)
    di, N = m["di"], m["N"]
    ops = rows * (7 * N * di + 3 * di)
    byt = rows * (3 * di + 2 * N) * bytes_per \
        + (2 * segments - starts) * N * di * 4
    return ops * m["Ls"], byt * m["Ls"]


def scan_row(cfg: dict, n_rows: int, start: bool, *,
             bytes_per: int = 2) -> tuple:
    """``scan_step`` of ONE segment of ``n_rows`` tokens."""
    return scan_step(cfg, n_rows, 1, int(bool(start)), bytes_per=bytes_per)
