"""Transient-vs-fatal error classification + capped exponential backoff.

The classification contract: only errors that a *re-execution of the same
pure program* could plausibly clear are transient — injected harness faults,
XLA runtime errors whose status codes name infrastructure conditions
(UNAVAILABLE, RESOURCE_EXHAUSTED, ...), connection/timeout errors, and
checkpoint-IO OSErrors. Everything else (shape errors, user exceptions,
verification failures, NaN detections) is fatal and propagates after a
single attempt — retrying a deterministic failure only hides it.
"""
from __future__ import annotations

import errno as _errno
import random
from typing import Optional

from ..core import flags
from .faults import InjectedFault

__all__ = ["RetryPolicy", "default_policy", "is_transient"]

# substrings of XLA/PJRT runtime-status messages that mark infrastructure
# (not program) failures — the codes CheckFreq-style runtimes retry on
_TRANSIENT_MARKERS = (
    "UNAVAILABLE",
    "RESOURCE_EXHAUSTED",
    "DEADLINE_EXCEEDED",
    "ABORTED",
    "CANCELLED",
    "connection reset",
    "socket closed",
    "temporarily unavailable",
)
_TRANSIENT_TYPE_NAMES = ("XlaRuntimeError", "JaxRuntimeError", "RpcError")

# deterministic program/user errors: never retried even when a message
# happens to contain a marker word
_FATAL_TYPES = (
    FloatingPointError,
    AssertionError,
    TypeError,
    ValueError,
    KeyError,
    IndexError,
    AttributeError,
    NotImplementedError,
)

# OSErrors whose cause is deterministic — a bad path, permissions, a full or
# read-only disk: retrying the same call cannot succeed, and backing off
# `retry_max` times before surfacing them only delays the real error
_FATAL_OS_TYPES = (
    PermissionError,
    FileNotFoundError,
    FileExistsError,
    IsADirectoryError,
    NotADirectoryError,
)
_FATAL_ERRNOS = frozenset(
    e for e in (
        _errno.EACCES, _errno.EPERM, _errno.ENOENT, _errno.EEXIST,
        _errno.ENOSPC, _errno.EROFS, _errno.EISDIR, _errno.ENOTDIR,
        _errno.ENOTEMPTY, _errno.ENAMETOOLONG, _errno.EINVAL, _errno.EBADF,
    ) if e is not None
)


def is_transient(e: BaseException) -> bool:
    """True when retrying the failed (pure) call could plausibly succeed."""
    if isinstance(e, InjectedFault):
        return e.transient
    if not isinstance(e, Exception):
        return False  # KeyboardInterrupt / SystemExit / Preempted propagate
    if isinstance(e, _FATAL_TYPES):
        return False
    if isinstance(e, OSError):
        # connection drops / flaky mounts retry; deterministic filesystem
        # failures (ENOSPC, EACCES, ENOENT, ...) fail loud on attempt one
        if isinstance(e, _FATAL_OS_TYPES) or e.errno in _FATAL_ERRNOS:
            return False
        return True
    if type(e).__name__ in _TRANSIENT_TYPE_NAMES:
        # PJRT runtime errors surface infra failures (device preempted,
        # connection dropped); compile-time program errors raise python types
        # handled above, so a runtime-status error here is worth one retry
        return True
    return any(m in str(e) for m in _TRANSIENT_MARKERS)


class RetryPolicy:
    """Capped exponential backoff with jitter.

    Arguments default to the FLAGS_retry_* values at call time, so a policy
    object constructed once stays in sync with runtime flag changes; pass
    explicit values to pin a policy."""

    def __init__(self, max_retries: Optional[int] = None,
                 backoff_ms: Optional[float] = None,
                 backoff_max_ms: Optional[float] = None,
                 jitter: float = 0.25):
        self._max_retries = max_retries
        self._backoff_ms = backoff_ms
        self._backoff_max_ms = backoff_max_ms
        self.jitter = float(jitter)

    @property
    def max_retries(self) -> int:
        if self._max_retries is not None:
            return self._max_retries
        return int(flags.flag("retry_max"))

    @property
    def backoff_ms(self) -> float:
        if self._backoff_ms is not None:
            return self._backoff_ms
        return float(flags.flag("retry_backoff_ms"))

    @property
    def backoff_max_ms(self) -> float:
        if self._backoff_max_ms is not None:
            return self._backoff_max_ms
        return float(flags.flag("retry_backoff_max_ms"))

    def delay_ms(self, attempt: int) -> float:
        """Backoff before retry `attempt` (1-based): base * 2^(attempt-1),
        capped, with multiplicative jitter so synchronized workers don't
        retry in lockstep."""
        base = self.backoff_ms * (2.0 ** max(0, attempt - 1))
        base = min(base, self.backoff_max_ms)
        if base <= 0:
            return 0.0
        return base * (1.0 + self.jitter * random.random())


_default = RetryPolicy()


def default_policy() -> RetryPolicy:
    return _default
