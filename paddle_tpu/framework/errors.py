"""Enforce-style error helpers.

TPU-native equivalent of ``PADDLE_ENFORCE*`` and ``platform::errors``
(reference: paddle/fluid/platform/enforce.h; errors typed as
InvalidArgument/NotFound/OutOfRange/... in paddle/fluid/platform/errors.h).
We keep the typed-error hierarchy (it surfaces in user-visible messages and in
tests) but implement it as plain Python exceptions — the XLA runtime already
produces rich device-side errors, so no status-decoding layer is needed.
"""
from __future__ import annotations

__all__ = [
    "EnforceNotMet",
    "InvalidArgumentError",
    "NotFoundError",
    "OutOfRangeError",
    "AlreadyExistsError",
    "PermissionDeniedError",
    "UnimplementedError",
    "UnavailableError",
    "PreconditionNotMetError",
    "ExecutionTimeoutError",
    "TransientDeviceError",
    "DivergenceError",
    "is_transient",
    "wrap_transient",
    "enforce",
    "enforce_eq",
    "enforce_gt",
    "enforce_shape_rank",
]


class EnforceNotMet(RuntimeError):
    """Base error, parity with paddle's EnforceNotMet."""


class InvalidArgumentError(EnforceNotMet, ValueError):
    pass


class NotFoundError(EnforceNotMet, KeyError):
    pass


class OutOfRangeError(EnforceNotMet, IndexError):
    pass


class AlreadyExistsError(EnforceNotMet):
    pass


class PermissionDeniedError(EnforceNotMet):
    pass


class UnimplementedError(EnforceNotMet, NotImplementedError):
    pass


class UnavailableError(EnforceNotMet):
    pass


class PreconditionNotMetError(EnforceNotMet):
    pass


class ExecutionTimeoutError(EnforceNotMet, TimeoutError):
    pass


class TransientDeviceError(UnavailableError):
    """A device/runtime failure that is expected to clear on retry —
    preempted donated buffer, transient ICI/DCN link error, runtime
    RESOURCE_EXHAUSTED from a concurrent burst.  ``resilience.RetryPolicy``
    retries these; anything else is fatal and propagates immediately."""


class DivergenceError(EnforceNotMet):
    """Training diverged beyond what rollback can fix: the supervisor
    (``resilience.TrainingSupervisor``) exhausted its rollback budget or
    kept tripping at the same restored step — restarting from the same
    checkpoint would loop forever, so the run must stop with the
    diagnostic instead."""


#: lowercase substrings of XLA / jax runtime error messages that indicate a
#: transient condition worth retrying (the runtime has no typed error
#: classes — status strings are the stable surface, same approach as gRPC
#: clients)
_TRANSIENT_PATTERNS = (
    "resource_exhausted",
    "resource exhausted",
    "unavailable",
    "deadline_exceeded",
    "deadline exceeded",
    "aborted",
    "connection reset",
    "broken pipe",
    "socket closed",
    "too many pings",
    "transient",
)

#: ... unless the message also says the condition is permanent.  On a
#: locally attached chip RESOURCE_EXHAUSTED is an out-of-memory verdict on
#: THIS program ("XLA:TPU compile permanent error. Ran out of memory in
#: memory space hbm", "Error allocating device buffer"): the same program
#: on the same device fails the same way, and a retry only re-pays a
#: compile that can take minutes before failing again.
_PERMANENT_PATTERNS = (
    "permanent error",
    "out of memory",
    "error allocating device buffer",
)

#: exception type names (by class name, so jaxlib need not be imported
#: here) whose messages are eligible for pattern classification
_RUNTIME_ERROR_TYPES = ("XlaRuntimeError", "JaxRuntimeError", "RpcError")


def is_transient(exc: BaseException) -> bool:
    """True when ``exc`` should be retried: either already typed transient
    (:class:`TransientDeviceError` / :class:`UnavailableError`) or a raw
    XLA/jax runtime error whose status message matches a known-transient
    pattern.  Typed framework errors other than Unavailable are *never*
    transient — an InvalidArgumentError does not fix itself."""
    if isinstance(exc, TransientDeviceError):
        return True
    if isinstance(exc, UnavailableError):
        return True
    if isinstance(exc, EnforceNotMet):
        return False  # typed errors: everything else is deterministic
    name = type(exc).__name__
    if name in _RUNTIME_ERROR_TYPES or isinstance(exc, (RuntimeError, OSError)):
        msg = str(exc).lower()
        if any(p in msg for p in _PERMANENT_PATTERNS):
            return False
        return any(p in msg for p in _TRANSIENT_PATTERNS)
    return False


def wrap_transient(exc: BaseException) -> BaseException:
    """Classify ``exc``: a recognizable transient runtime error comes back
    wrapped as :class:`TransientDeviceError` (chained, so the original
    stack survives); anything else is returned unchanged."""
    if isinstance(exc, TransientDeviceError) or not is_transient(exc):
        return exc
    wrapped = TransientDeviceError(
        f"transient device error ({type(exc).__name__}): {exc}")
    wrapped.__cause__ = exc
    return wrapped


def enforce(cond, msg="", error_cls=InvalidArgumentError):
    """PADDLE_ENFORCE equivalent: raise ``error_cls`` when ``cond`` is falsy."""
    if not cond:
        raise error_cls(msg)


def enforce_eq(a, b, msg="", error_cls=InvalidArgumentError):
    if a != b:
        raise error_cls(f"expected {a!r} == {b!r}. {msg}")


def enforce_gt(a, b, msg="", error_cls=InvalidArgumentError):
    if not a > b:
        raise error_cls(f"expected {a!r} > {b!r}. {msg}")


def enforce_shape_rank(shape, rank, name="input"):
    if len(shape) != rank:
        raise InvalidArgumentError(
            f"{name} expected rank {rank}, got shape {tuple(shape)}"
        )
