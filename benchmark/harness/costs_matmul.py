"""What the dense matrix products of one step need, counted from shapes:
operations from the tokens that are real (not the bucket's padding) and
the logit rows whose result is used, bytes from every weight read once
in the step plus the activations in and out of each product.  The
companion of ``costs.py`` (attention); ``costs.least_seconds`` turns the
pair into the least time the chip needs."""
from __future__ import annotations


def layer_weights(m: dict) -> int:
    """Matrix elements of one decoder layer: q and o are H x nh*d, k and
    v are H x kvh*d, gate, up and down are H x F.  ``m`` is
    ``weights.dims(cfg)``."""
    H, nh, kvh, d, F = m["H"], m["nh"], m["kvh"], m["d"], m["F"]
    return 2 * H * nh * d + 2 * H * kvh * d + 3 * H * F


def step_matmuls(tokens: int, logit_rows: int, m: dict, *,
                 bytes_per: int = 2, logit_bytes: int = 4) -> tuple:
    """(operations, bytes) of one step that carries ``tokens`` query
    tokens and scores ``logit_rows`` of them against the vocabulary.

    Operations: a multiply-add per weight element per token, 2 ops.
    Bytes: the layers' weights and the head once each, however many
    tokens ride (that is what batching buys); per token and layer the
    activations each product reads and writes (x into q, k, v; the heads
    into o; x into gate and up; the F-wide product into down); per logit
    row its hidden state in and its logits out (float32)."""
    H, nh, kvh, d, F, V, L = (m["H"], m["nh"], m["kvh"], m["d"], m["F"],
                              m["V"], m["L"])
    w = layer_weights(m)
    ops = 2 * tokens * w * L + 2 * logit_rows * H * V
    acts = (H + (nh + 2 * kvh) * d) + (nh * d + H) + (H + 2 * F) + (F + H)
    byt = (w * L + H * V) * bytes_per \
        + tokens * acts * L * bytes_per \
        + logit_rows * (H * bytes_per + V * logit_bytes)
    return ops, byt


def least_seconds(steps, m: dict, peak: dict, **kw) -> float:
    """The least time the chip needs for these (tokens, logit_rows)
    steps: each step's own bound (operations or bytes, whichever is the
    larger), summed: a step cannot borrow another step's slack."""
    from . import costs
    return sum(costs.least_seconds(*step_matmuls(t, r, m, **kw), peak)[0]
               for t, r in steps)
