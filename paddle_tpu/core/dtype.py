"""Data types.

TPU-native analog of the reference dtype surface
(/root/reference/paddle/phi/common/data_type.h): one canonical DataType object
per dtype, string aliases, and numpy/jax interop.  Unlike the reference we back
every dtype directly with a jax/numpy dtype object — XLA is the only kernel
backend so no per-backend dtype tables are needed.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = [
    "dtype", "bool_", "uint8", "int8", "int16", "int32", "int64",
    "float16", "bfloat16", "float32", "float64", "complex64", "complex128",
    "float8_e4m3fn", "float8_e5m2", "pstring", "raw",
    "convert_dtype", "to_jax_dtype", "is_floating_point_dtype", "is_integer_dtype",
]


class dtype:
    """A framework dtype: thin, interned wrapper over a numpy dtype."""

    _registry: dict[str, "dtype"] = {}

    def __init__(self, name: str, np_dtype):
        self.name = name
        self.np_dtype = np.dtype(np_dtype)
        dtype._registry[name] = self

    def __repr__(self):
        return f"paddle_tpu.{self.name}"

    def __eq__(self, other):
        if isinstance(other, dtype):
            return self.name == other.name
        try:
            return self.np_dtype == np.dtype(other)
        except TypeError:
            return NotImplemented

    def __hash__(self):
        return hash(self.name)

    @property
    def is_floating_point(self):
        return self.name in ("float16", "bfloat16", "float32", "float64")

    @property
    def is_complex(self):
        return self.name in ("complex64", "complex128")

    @property
    def is_integer(self):
        return self.name in ("bool", "uint8", "int8", "int16", "int32", "int64")

    @property
    def itemsize(self):
        return self.np_dtype.itemsize


bool_ = dtype("bool", np.bool_)
uint8 = dtype("uint8", np.uint8)
int8 = dtype("int8", np.int8)
int16 = dtype("int16", np.int16)
int32 = dtype("int32", np.int32)
int64 = dtype("int64", np.int64)
float16 = dtype("float16", np.float16)
bfloat16 = dtype("bfloat16", jnp.bfloat16)
float32 = dtype("float32", np.float32)
float64 = dtype("float64", np.float64)
complex64 = dtype("complex64", np.complex64)
complex128 = dtype("complex128", np.complex128)
# fp8 training dtypes (reference exposes both; ml_dtypes provides them)
import ml_dtypes as _mld
float8_e4m3fn = dtype("float8_e4m3fn", _mld.float8_e4m3fn)
float8_e5m2 = dtype("float8_e5m2", _mld.float8_e5m2)
# legacy dtype markers (reference pstring / raw VarTypes)
pstring = dtype("pstring", np.object_)
raw = dtype("raw", np.void)

_ALIASES = {
    "bool": bool_,
    "float": float32,
    "double": float64,
    "half": float16,
    "int": int32,
    "long": int64,
    "bfloat": bfloat16,
}


def convert_dtype(d) -> dtype:
    """Normalize any dtype-like (str, np.dtype, jnp dtype, dtype) to a dtype."""
    if d is None:
        return None
    if isinstance(d, dtype):
        return d
    if isinstance(d, str):
        if d in dtype._registry:
            return dtype._registry[d]
        if d in _ALIASES:
            return _ALIASES[d]
    npd = np.dtype(d)
    name = npd.name
    if name in dtype._registry:
        return dtype._registry[name]
    raise TypeError(f"Unsupported dtype: {d!r}")


def to_jax_dtype(d):
    d = convert_dtype(d)
    return None if d is None else d.np_dtype


def is_floating_point_dtype(d) -> bool:
    return convert_dtype(d).is_floating_point


def is_integer_dtype(d) -> bool:
    return convert_dtype(d).is_integer


_X64_NAMES = frozenset({"int64", "uint64", "float64", "complex128"})


def x64_scope(*dtype_likes):
    """Context manager enabling 64-bit array creation when any requested
    dtype is 64-bit.

    jax_enable_x64 stays globally OFF (it widens intermediates on a bf16
    machine and breaks Pallas/Mosaic index-map lowering); parity with the
    reference's first-class int64/float64 tensors
    (/root/reference/python/paddle/tensor/creation.py default int64) is
    scoped to the creation ops: arrays requested as 64-bit are built under
    jax.enable_x64(True) and keep that dtype afterwards.  Mixed 64/32-bit
    compute may demote results to 32-bit — the documented TPU-first
    deviation.
    """
    import contextlib

    from jax import enable_x64

    for d in dtype_likes:
        if d is None:
            continue
        try:
            name = np.dtype(d.np_dtype if isinstance(d, dtype) else d).name
        except TypeError:
            continue
        if name in _X64_NAMES:
            return enable_x64(True)
    return contextlib.nullcontext()
