"""Fused-op functional surface.

Parity with /root/reference/python/paddle/incubate/nn/functional/:
fused_rms_norm.py, fused_layer_norm.py, fused_rotary_position_embedding.py,
swiglu.py, fused_matmul_bias.py, fused_dropout_add.py.  Each op is ONE
compiled XLA program (the eager dispatch compiles+caches per shape); the
norms additionally route to Pallas row-kernels on TPU when
FLAGS_use_pallas_kernels is set and shapes qualify.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ....core import dispatch as D
from ....core import random_state
from ....core.flags import get_flag
from ....ops.pallas.fused_norms import (
    _ln_ref, _rms_ref, layer_norm_fused, rms_norm_fused,
)

__all__ = [
    "fused_rms_norm", "fused_layer_norm", "fused_rotary_position_embedding",
    "swiglu", "fused_matmul_bias", "fused_linear", "fused_dropout_add",
    "fused_bias_dropout_residual_layer_norm",
]


def _check_norm_axis(x, begin_norm_axis):
    """Only the trailing-dim case (what every transformer block uses) is
    supported; reject other values loudly rather than normalizing the
    wrong dims."""
    if begin_norm_axis not in (-1, x.ndim - 1):
        raise NotImplementedError(
            f"begin_norm_axis={begin_norm_axis} normalizes over multiple "
            f"dims; only the last axis (begin_norm_axis={x.ndim - 1} or -1) "
            f"is supported")


def _add_bias_residual(x, bias, residual):
    def impl(x, *rest, has_bias, has_res):
        i = 0
        out = x
        if has_bias:
            out = out + rest[i]
            i += 1
        if has_res:
            out = out + rest[i]
        return out
    args = (x,) + tuple(t for t in (bias, residual) if t is not None)
    if len(args) == 1:
        return x
    return D.apply("fused_add_bias_residual", impl, args,
                   {"has_bias": bias is not None,
                    "has_res": residual is not None})


def _norm_core(x, weight, bias, eps, kind):
    """Dispatch one rms/layer-norm op, Pallas-routed when eligible."""
    if kind == "rms":
        if (get_flag("use_pallas_kernels") and weight is not None
                and rms_norm_fused.supports(x.shape, x.dtype.name)):
            return D.apply("fused_rms_norm", rms_norm_fused, (x, weight),
                           {"eps": float(eps)})
        def impl(x, *rest, eps, has_w):
            w = rest[0] if has_w else jnp.ones((x.shape[-1],), jnp.float32)
            return _rms_ref(x, w, eps)
        args = (x,) + ((weight,) if weight is not None else ())
        return D.apply("fused_rms_norm", impl, args,
                       {"eps": float(eps), "has_w": weight is not None})
    else:
        if (get_flag("use_pallas_kernels") and weight is not None
                and bias is not None
                and layer_norm_fused.supports(x.shape, x.dtype.name)):
            return D.apply("fused_layer_norm", layer_norm_fused,
                           (x, weight, bias), {"eps": float(eps)})
        def impl(x, *rest, eps, has_w, has_b):
            H = x.shape[-1]
            w = rest[0] if has_w else jnp.ones((H,), jnp.float32)
            b = rest[-1] if has_b else jnp.zeros((H,), jnp.float32)
            return _ln_ref(x, w, b, eps)
        args = (x,) + tuple(t for t in (weight, bias) if t is not None)
        return D.apply("fused_layer_norm", impl, args,
                       {"eps": float(eps), "has_w": weight is not None,
                        "has_b": bias is not None})


def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None,
                   quant_scale=-1, quant_round_type=0, quant_max_bound=0,
                   quant_min_bound=0):
    """y = rms_norm(x [+ bias] [+ residual]) * w [+ norm_bias].

    Returns (out, residual_out) like the reference fused_rms_norm (the
    pre-norm sum is reused as the next block's residual stream).
    """
    _check_norm_axis(x, begin_norm_axis)
    residual_out = _add_bias_residual(x, bias, residual)
    out = _norm_core(residual_out, norm_weight, None, epsilon, "rms")
    if norm_bias is not None:
        out = out + norm_bias
    return out, residual_out


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5,
                     begin_norm_axis=-1, bias=None, residual=None,
                     quant_scale=-1, quant_round_type=0, quant_max_bound=0,
                     quant_min_bound=0):
    """y = layer_norm(x [+ bias] [+ residual]) * w + b; returns
    (out, residual_out) (reference fused_layer_norm.py)."""
    _check_norm_axis(x, begin_norm_axis)
    residual_out = _add_bias_residual(x, bias, residual)
    out = _norm_core(residual_out, norm_weight, norm_bias, epsilon, "layer")
    return out, residual_out


def _rope_impl(q, *rest, has_k, has_v, has_cs, has_pos, use_neox, theta):
    """q/k/v: [B, S, H, D].  Interleaved (GPT-NeoX) or half-split rotary."""
    i = 0
    k = rest[i] if has_k else None
    i += has_k
    v = rest[i] if has_v else None
    i += has_v
    if has_cs:
        sin, cos = rest[i], rest[i + 1]
        i += 2
        sin = sin.astype(jnp.float32)
        cos = cos.astype(jnp.float32)
        # accept [1, S, 1, D], [S, D], or a longer [S_max, D] table
        if sin.ndim == 4:
            sin = sin[:, :, 0, :]
            cos = cos[:, :, 0, :]
        if sin.ndim == 2:
            sin = sin[None]
            cos = cos[None]                                  # [1, S*, D]
        if has_pos:
            # gather the table rows at the requested positions (KV-cache
            # decode at an offset) — reference fused_rope gathers likewise
            pos = rest[i]                                    # [B, S] int
            sin = jnp.take(sin[0], pos, axis=0)              # [B, S, D]
            cos = jnp.take(cos[0], pos, axis=0)
        sin = sin[:, :, None, :]
        cos = cos[:, :, None, :]
    else:
        S, Dh = q.shape[1], q.shape[3]
        pos = (rest[i].astype(jnp.float32) if has_pos
               else jnp.arange(S, dtype=jnp.float32)[None, :])
        inv = theta ** (-jnp.arange(0, Dh, 2, dtype=jnp.float32) / Dh)
        freqs = pos[..., None] * inv[None, None, :]          # [B?, S, D/2]
        emb = jnp.repeat(freqs, 2, axis=-1) if use_neox else jnp.concatenate(
            [freqs, freqs], axis=-1)
        sin = jnp.sin(emb)[:, :, None, :]
        cos = jnp.cos(emb)[:, :, None, :]

    def rot(x):
        if x is None:
            return None
        xf = x.astype(jnp.float32)
        if use_neox:
            x1, x2 = xf[..., 0::2], xf[..., 1::2]
            rotated = jnp.stack([-x2, x1], axis=-1).reshape(xf.shape)
        else:
            half = xf.shape[-1] // 2
            rotated = jnp.concatenate([-xf[..., half:], xf[..., :half]],
                                      axis=-1)
        return (xf * cos + rotated * sin).astype(x.dtype)

    outs = tuple(r for r in (rot(q), rot(k), rot(v)) if r is not None)
    return outs if len(outs) > 1 else outs[0]


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None,
                                    use_neox_rotary_style=True,
                                    rotary_emb_base=10000.0, name=None):
    """Apply RoPE to q (and optionally k, v) in one compiled op
    (reference fused_rotary_position_embedding.py; CUDA kernel
    paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu).
    Returns a 3-tuple (q_out, k_out, v_out) with None placeholders,
    matching the reference API."""
    has_cs = sin is not None and cos is not None
    args = (q,) + tuple(t for t in (k, v) if t is not None)
    if has_cs:
        args = args + (sin, cos)
    if position_ids is not None:
        args = args + (position_ids,)
    out = D.apply("fused_rope", _rope_impl, args,
                  {"has_k": k is not None, "has_v": v is not None,
                   "has_cs": has_cs, "has_pos": position_ids is not None,
                   "use_neox": bool(use_neox_rotary_style),
                   "theta": float(rotary_emb_base)})
    outs = list(out) if isinstance(out, tuple) else [out]
    result = []
    for t in (q, k, v):
        result.append(outs.pop(0) if t is not None else None)
    return tuple(result)


def _swiglu_impl(x, *rest, has_y):
    if has_y:
        gate, up = x, rest[0]
    else:
        gate, up = jnp.split(x, 2, axis=-1)
    return (jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up)


def swiglu(x, y=None, name=None):
    """silu(x) * y; with y=None, x is split in half on the last axis
    (reference swiglu.py; CUDA kernel phi/kernels/fusion/gpu/swiglu)."""
    args = (x,) + ((y,) if y is not None else ())
    return D.apply("swiglu", _swiglu_impl, args, {"has_y": y is not None})


def _matmul_bias_impl(x, y, *rest, has_bias, trans_x, trans_y):
    a = jnp.swapaxes(x, -1, -2) if trans_x else x
    b = jnp.swapaxes(y, -1, -2) if trans_y else y
    out = jnp.matmul(a, b)
    if has_bias:
        out = out + rest[0]
    return out


def fused_matmul_bias(x, y, bias=None, transpose_x=False, transpose_y=False,
                      name=None):
    """matmul + bias-add in one program (reference fused_matmul_bias.py,
    cuBLASLt epilogue; on TPU the XLA fusion IS the epilogue)."""
    args = (x, y) + ((bias,) if bias is not None else ())
    return D.apply("fused_matmul_bias", _matmul_bias_impl, args,
                   {"has_bias": bias is not None,
                    "trans_x": bool(transpose_x),
                    "trans_y": bool(transpose_y)})


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    """Reference fused_linear (fused_gemm_epilogue op)."""
    return fused_matmul_bias(x, weight, bias, False, transpose_weight)


def fused_dropout_add(x, y, p=0.5, training=True, mode="upscale_in_train",
                      name=None):
    """dropout(x) + y in one program (reference fused_dropout_add.py)."""
    if not training or float(p) == 0.0:
        # downscale_in_infer trained with unscaled keeps -> scale at eval
        scale = (1.0 - float(p)) if (not training
                                     and mode == "downscale_in_infer") else 1.0

        def impl(x, y, *, scale):
            return x * scale + y
        return D.apply("fused_dropout_add", impl, (x, y), {"scale": scale})
    key = random_state.next_key()

    def impl(k, x, y, *, p, upscale):
        keep = jax.random.bernoulli(k, 1.0 - p, x.shape)
        if upscale:
            xd = jnp.where(keep, x / (1.0 - p), jnp.zeros((), x.dtype))
        else:
            xd = jnp.where(keep, x, jnp.zeros((), x.dtype))
        return xd.astype(x.dtype) + y
    return D.apply("fused_dropout_add", impl, (key, x, y),
                   {"p": float(p), "upscale": mode == "upscale_in_train"})


def fused_bias_dropout_residual_layer_norm(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate=0.5, ln_epsilon=1e-5, training=True,
        mode="upscale_in_train", name=None):
    """layer_norm(residual + dropout(x + bias)) — reference
    fused_bias_dropout_residual_layer_norm."""
    h = _add_bias_residual(x, bias, None)
    h = fused_dropout_add(h, residual, dropout_rate, training, mode)
    return _norm_core(h, ln_scale, ln_bias, ln_epsilon, "layer")
