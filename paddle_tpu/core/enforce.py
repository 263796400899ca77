"""Error/enforce utilities.

Analog of the reference PADDLE_ENFORCE machinery
(/root/reference/paddle/phi/core/enforce.h): typed framework errors with
consistent messages, the operator-context stack the reference prepends to
kernel failures ("[operator < conv2d > error]"), and runtime-error
enrichment — the reference ships lookup tables decoding CUDA/cuDNN/NCCL
status codes into actionable text (paddle/phi/core/external_error.proto,
tools/externalError); `explain_runtime_error` is the TPU analog for
XLA/PJRT status strings.  Stack traces come for free from Python.
"""
from __future__ import annotations

import contextlib
import threading

__all__ = [
    "EnforceError", "InvalidArgumentError", "NotFoundError", "OutOfRangeError",
    "AlreadyExistsError", "PreconditionNotMetError", "UnimplementedError",
    "UnavailableError", "ExecutionTimeoutError", "AnalysisError", "enforce",
    "enforce_eq", "enforce_shape", "error_context", "current_error_context",
    "explain_runtime_error",
]


class EnforceError(RuntimeError):
    pass


class InvalidArgumentError(EnforceError, ValueError):
    pass


class NotFoundError(EnforceError, KeyError):
    pass


class OutOfRangeError(EnforceError, IndexError):
    pass


class AlreadyExistsError(EnforceError):
    pass


class PreconditionNotMetError(EnforceError):
    pass


class UnimplementedError(EnforceError, NotImplementedError):
    pass


class UnavailableError(EnforceError):
    pass


class ExecutionTimeoutError(EnforceError, TimeoutError):
    pass


class AnalysisError(PreconditionNotMetError):
    """graft-lint found ERROR-severity hazards under PT_ANALYSIS=strict."""
    pass


# --- operator context stack (reference enforce.h error summary prefixes
# kernel failures with the running operator) -------------------------------

_ctx = threading.local()


def current_error_context() -> tuple:
    return tuple(getattr(_ctx, "stack", ()))


@contextlib.contextmanager
def error_context(name: str):
    """Push an operator/frame name onto the error-context stack; any
    EnforceError raised inside is prefixed ``[operator < name > error]``."""
    stack = getattr(_ctx, "stack", None)
    if stack is None:
        stack = _ctx.stack = []
    stack.append(name)
    try:
        yield
    except EnforceError as e:
        prefix = " ".join(f"[operator < {n} > error]" for n in stack)
        if e.args and isinstance(e.args[0], str) \
                and not e.args[0].startswith("[operator"):
            e.args = (f"{prefix} {e.args[0]}",) + e.args[1:]
        raise
    finally:
        stack.pop()


# TPU analog of the reference's external-error tables: decode the status
# prefixes XLA/PJRT put in RuntimeError text into actionable hints.
_XLA_HINTS = (
    ("RESOURCE_EXHAUSTED", "the program does not fit in device HBM — "
     "reduce batch/sequence length, enable remat "
     "(HybridParallelConfig.remat), shard optimizer state (zero_stage>=1), "
     "or add tp/pp axes"),
    ("DEADLINE_EXCEEDED", "a device operation timed out — multi-host, "
     "suspect a desynchronized collective (see "
     "FLAGS_comm_watchdog_timeout)"),
    ("UNAVAILABLE", "the backend is unreachable — verify JAX_PLATFORMS, "
     "and that no other process holds the chip (it belongs to one "
     "process at a time)"),
    ("UNIMPLEMENTED", "XLA cannot lower this op on the current backend — "
     "check dtype (x64 is off by default) and dynamic-shape use"),
    ("INTERNAL", "an XLA/Mosaic compiler fault — if a Pallas kernel is "
     "involved, set FLAGS_use_pallas_kernels=False to fall back to the "
     "XLA composition and report the kernel shape"),
    ("FAILED_PRECONDITION", "device state is invalid — a previous async "
     "error may have poisoned the client; restart the process"),
)


def explain_runtime_error(e: BaseException) -> str:
    """Best-known hint for an XLA/PJRT runtime error, or '' if unknown."""
    text = str(e)
    for code, hint in _XLA_HINTS:
        if code in text:
            return hint
    return ""


def enforce(cond, msg: str, exc=InvalidArgumentError):
    if not cond:
        raise exc(msg)


def enforce_eq(a, b, msg: str = "", exc=InvalidArgumentError):
    if a != b:
        raise exc(f"Expected {a!r} == {b!r}. {msg}")


def enforce_shape(t, expected_rank=None, msg: str = ""):
    if expected_rank is not None and len(t.shape) != expected_rank:
        raise InvalidArgumentError(
            f"Expected rank-{expected_rank} tensor, got shape {tuple(t.shape)}. {msg}"
        )
