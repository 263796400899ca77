"""paddle_tpu: a TPU-native deep-learning framework.

Capability parity with PaddlePaddle (reference: /root/reference), built
idiomatically on JAX/XLA/Pallas/pjit.  See SURVEY.md for the layer map this
package follows.
"""
from __future__ import annotations

# jax_enable_x64 stays OFF: it widens default intermediates on a bf16
# machine and breaks Pallas/Mosaic lowering (r2 BENCH + index-map
# RecursionError).  int64/float64 parity with the reference (python ints ->
# int64 tensors, python/paddle/tensor/creation.py) is scoped to creation ops
# via core.dtype.x64_scope, which builds 64-bit arrays under
# jax.enable_x64(True); the arrays keep their dtype afterwards.
import warnings as _warnings

import jax as _jax  # noqa: F401

_warnings.filterwarnings(
    "ignore", message="Explicitly requested dtype.*truncated", category=UserWarning)

__version__ = "0.1.0"

from .core import dispatch as _dispatch
from .core import tape as _tape
from .core.dtype import (  # noqa: F401
    bfloat16, bool_, complex128, complex64, dtype, float16, float32, float64,
    float8_e4m3fn, float8_e5m2, int16, int32, int64, int8, pstring, raw,
    uint8,
)
from .core.enforce import EnforceError  # noqa: F401
from .core.place import (  # noqa: F401
    CPUPlace, CUDAPinnedPlace, CUDAPlace, Place, TPUPlace, XPUPlace,
    device_count, get_device, is_compiled_with_cuda, is_compiled_with_distribute,
    is_compiled_with_rocm, is_compiled_with_xpu, set_device,
)
from .core.flags import get_flags, set_flags  # noqa: F401
from .core.selected_rows import SelectedRows, merge_selected_rows  # noqa: F401
from .core.tensor import Parameter, Tensor, to_tensor  # noqa: F401

no_grad = _dispatch.no_grad
enable_grad = _dispatch.enable_grad
set_grad_enabled = _dispatch.set_grad_enabled
is_grad_enabled = _dispatch.is_grad_enabled
grad = _tape.grad

from . import ops as _ops

_ops.monkey_patch_tensor()

# Public op namespace: paddle_tpu.add / paddle_tpu.reshape / ...
_g = globals()
for _name, _fn in _ops.PUBLIC_OPS.items():
    _g.setdefault(_name, _fn)
del _g, _name, _fn

from .ops.creation import complex_ as complex  # noqa: F401,E402
from .ops.math import einsum  # noqa: F401,E402
from .ops.random import get_rng_state, seed, set_rng_state  # noqa: F401,E402

bool = bool_  # paddle.bool

# Subpackages (imported lazily where heavy).
from . import amp  # noqa: E402
from . import audio  # noqa: E402
from . import autograd  # noqa: E402
from . import device  # noqa: E402
from . import distributed  # noqa: E402
from . import distribution  # noqa: E402
from . import fft  # noqa: E402
from . import framework  # noqa: E402
from . import geometric  # noqa: E402
from . import hapi  # noqa: E402
from . import incubate  # noqa: E402
from . import io  # noqa: E402
from . import jit  # noqa: E402
from . import linalg  # noqa: E402
from . import metric  # noqa: E402
from . import nn  # noqa: E402
from . import profiler  # noqa: E402
from . import quantization  # noqa: E402
from . import reader  # noqa: E402
from . import dataset  # noqa: E402
from . import cost_model  # noqa: E402
from . import inference  # noqa: E402
from . import optimizer  # noqa: E402
from . import hub  # noqa: E402
from . import onnx  # noqa: E402
from . import regularizer  # noqa: E402
from . import signal  # noqa: E402
from . import sparse  # noqa: E402
from . import static  # noqa: E402
from . import sysconfig  # noqa: E402
from . import version  # noqa: E402
from .nn.initializer.attr import ParamAttr  # noqa: E402


_default_dtype = "float32"


def set_default_dtype(d):
    """Default float dtype for parameter/tensor creation (reference
    framework set_default_dtype)."""
    global _default_dtype
    from .core.dtype import convert_dtype
    _default_dtype = convert_dtype(d)


def get_default_dtype():
    return _default_dtype


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Tensor repr options (reference paddle.set_printoptions; reprs here
    render through numpy, so this drives numpy's printoptions)."""
    import numpy as _np
    kw = {}
    if precision is not None:
        kw["precision"] = int(precision)
    if threshold is not None:
        kw["threshold"] = int(threshold)
    if edgeitems is not None:
        kw["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        kw["linewidth"] = int(linewidth)
    if sci_mode is not None:
        kw["suppress"] = not bool(sci_mode)
    _np.set_printoptions(**kw)


def create_parameter(shape, dtype="float32", name=None, attr=None,
                     is_bias=False, default_initializer=None):
    """Standalone Parameter factory (reference base/layers creation;
    used by custom layers outside Layer.create_parameter)."""
    import jax.numpy as _jnp

    from .core.dtype import to_jax_dtype
    from .nn.initializer import Constant, XavierNormal
    init = default_initializer or (Constant(0.0) if is_bias
                                   else XavierNormal())
    data = init(tuple(int(s) for s in shape), dtype)
    p = Parameter(_jnp.asarray(data, to_jax_dtype(dtype)))
    if attr is not None and getattr(attr, "regularizer", None) is not None:
        p.regularizer = attr.regularizer
    return p


class LazyGuard:
    """Deferred-init guard (reference paddle.LazyGuard).  Parameter init is
    a cheap jnp allocation under XLA, so laziness buys nothing — the guard
    is accepted and is a no-op."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def get_cuda_rng_state():
    """Accelerator RNG state (maps to the framework RNG; reference
    get_cuda_rng_state)."""
    return get_rng_state()


def set_cuda_rng_state(state):
    return set_rng_state(state)


def to_dlpack(x):
    from .utils.dlpack import to_dlpack as _impl
    return _impl(x)


def from_dlpack(capsule):
    from .utils.dlpack import from_dlpack as _impl
    return _impl(capsule)


def batch(reader, batch_size, drop_last=False):
    """Mini-batch reader decorator (reference python/paddle/batch.py)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


def iinfo(dtype):
    """Integer dtype info (reference paddle.iinfo over ml_dtypes)."""
    import numpy as _np
    from .core.dtype import to_jax_dtype
    return _np.iinfo(_np.dtype(to_jax_dtype(dtype)))


def finfo(dtype):
    """Float dtype info incl bfloat16 (reference paddle.finfo)."""
    import ml_dtypes as _mld
    import numpy as _np
    from .core.dtype import to_jax_dtype
    dt = _np.dtype(to_jax_dtype(dtype))
    if dt == _np.dtype(_mld.bfloat16):
        return _mld.finfo(_mld.bfloat16)
    return _np.finfo(dt)
from . import strings  # noqa: E402
from . import text  # noqa: E402
from . import utils  # noqa: E402
from . import vision  # noqa: E402

from .framework.io import load, save  # noqa: E402
from .hapi.model import Model, summary  # noqa: E402
from .hapi import callbacks  # noqa: E402  (paddle.callbacks alias)
from .nn.layer.layers import Layer  # noqa: E402

DataParallel = distributed.DataParallel


def disable_static(place=None):
    return None


def enable_static():
    raise NotImplementedError(
        "paddle_tpu is eager-first; use paddle_tpu.jit.to_static for the "
        "captured/compiled execution path."
    )


def in_dynamic_mode():
    return True


def disable_signal_handler():
    return None


def flops(net, input_size, custom_ops=None, print_detail=False):
    from .hapi.model import flops as _flops
    return _flops(net, input_size, custom_ops, print_detail)
