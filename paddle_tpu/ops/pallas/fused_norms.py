"""Fused RMSNorm / LayerNorm (TPU Pallas).

TPU-native analog of the reference fused norm CUDA kernels
(/root/reference/paddle/phi/kernels/fusion/gpu/fused_rms_norm*.cu and
fused_layernorm*.cu, exposed via python/paddle/incubate/nn/functional/
fused_rms_norm.py / fused_layer_norm.py).  Forward is a row-tiled Pallas
kernel (single HBM pass, fp32 accumulation in VMEM); backward pairs it with
XLA's fused gradient of the reference composition via custom_vjp — same
structure as ops/pallas/flash_attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

try:
    from jax.experimental import pallas as pl
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

_BLOCK_R = 256  # built-in preference; the tuning cache can widen/narrow it


def _rms_ref(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _ln_ref(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps) * w[None, :]).astype(o_ref.dtype)


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w[None, :] + b[None, :]).astype(o_ref.dtype)


def _pick_block_r(R, pref=None):
    """Largest power-of-two block <= pref that exactly divides R.

    The grid is R // block_r with no ragged-tail masking, so block_r MUST
    divide R; _supports guarantees R % 8 == 0, making 8 the floor here.
    """
    pref = _BLOCK_R if pref is None else pref
    for b in (1024, 512, 256, 128, 64, 32, 16, 8):
        if b <= pref and R % b == 0:
            return b
    return None


def _row_call(kernel, out_dtype, x2d, *vecs):
    from ...tune import kernel_config
    R, H = x2d.shape
    cfg = kernel_config("fused_norms",
                        {"rows": R, "hidden": H,
                         "dtype": jnp.dtype(x2d.dtype).name})
    block_r = _pick_block_r(R, int(cfg["block_r"]))
    vec_specs = [pl.BlockSpec((H,), lambda r: (0,)) for _ in vecs]
    return pl.pallas_call(
        kernel,
        grid=(R // block_r,),
        in_specs=[pl.BlockSpec((block_r, H), lambda r: (r, 0))] + vec_specs,
        out_specs=pl.BlockSpec((block_r, H), lambda r: (r, 0)),
        out_shape=jax.ShapeDtypeStruct((R, H), out_dtype),
    )(x2d, *vecs)


def _supports(shape, dtype_name):
    if not _HAS_PALLAS or jax.default_backend() != "tpu":
        return False
    if dtype_name not in ("float32", "bfloat16"):
        return False
    H = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    return H % 128 == 0 and rows % 8 == 0 and _pick_block_r(rows) is not None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rms_pallas(eps, x, w):
    shape = x.shape
    y = _row_call(functools.partial(_rms_kernel, eps=eps), x.dtype,
                  x.reshape(-1, shape[-1]), w)
    return y.reshape(shape)


def _rms_fwd(eps, x, w):
    return _rms_pallas(eps, x, w), (x, w)


def _rms_bwd(eps, res, g):
    x, w = res
    _, vjp = jax.vjp(lambda x, w: _rms_ref(x, w, eps), x, w)
    return vjp(g)


_rms_pallas.defvjp(_rms_fwd, _rms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ln_pallas(eps, x, w, b):
    shape = x.shape
    y = _row_call(functools.partial(_ln_kernel, eps=eps), x.dtype,
                  x.reshape(-1, shape[-1]), w, b)
    return y.reshape(shape)


def _ln_fwd(eps, x, w, b):
    return _ln_pallas(eps, x, w, b), (x, w, b)


def _ln_bwd(eps, res, g):
    x, w, b = res
    _, vjp = jax.vjp(lambda x, w, b: _ln_ref(x, w, b, eps), x, w, b)
    return vjp(g)


_ln_pallas.defvjp(_ln_fwd, _ln_bwd)


class _RmsNormOp:
    def __call__(self, x, w, eps):
        return _rms_pallas(float(eps), x, w)

    @staticmethod
    def supports(shape, dtype_name):
        return _supports(shape, dtype_name)

    def __hash__(self):
        return hash("pallas_rms_norm")

    def __eq__(self, other):
        return isinstance(other, _RmsNormOp)


class _LayerNormOp:
    def __call__(self, x, w, b, eps):
        return _ln_pallas(float(eps), x, w, b)

    @staticmethod
    def supports(shape, dtype_name):
        return _supports(shape, dtype_name)

    def __hash__(self):
        return hash("pallas_layer_norm")

    def __eq__(self, other):
        return isinstance(other, _LayerNormOp)


rms_norm_fused = _RmsNormOp()
layer_norm_fused = _LayerNormOp()
