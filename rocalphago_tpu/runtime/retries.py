"""Retry with deterministic-jitter exponential backoff.

The classifier draws one line: hardware and
infrastructure flake (device unavailable, RPC deadline, filesystem
hiccough, preempted TPU worker) is TRANSIENT — re-dispatching the
same pure program is safe and usually succeeds — while programming
errors (shape mismatches, bad arguments, assertion failures) must
surface immediately; retrying those just burns the backoff budget in
front of the real traceback.

Jitter is DETERMINISTIC (hashed from a seed, the wrapped function's
name, and the attempt index) so an interrupted-and-resumed run
replays the identical sleep schedule — the same discipline the
trainers use for every other random draw (exact resume is the
invariant the chaos tests assert).

Only retry PURE work: a functional train step (state in, new state
out) or an idempotent artifact write. Never wrap a step whose input
buffers were donated to the device program — after a failed dispatch
the donated buffers may already be invalid, so the retry would
compute on garbage. Since the pipelined-dispatch PR this hazard is
ENFORCED, not just documented: chunk programs that donate advertise
``donates_buffers = True`` (the convention
``runtime.pipeline``-driven loops follow), and :func:`retry` /
:func:`retry_call` refuse to wrap such a callable with an explicit
``ValueError``. Retry remains valid one level up — the chunked
iterations confine donation to loop-internal carries and re-derive
them from never-donated state, so wrapping the *iteration* is safe
(and is what the trainers do).
"""

from __future__ import annotations

import functools
import hashlib
import sys
import time

# gRPC/absl status words XLA surfaces for infrastructure failures
# (the jaxlib exception type is one opaque XlaRuntimeError — the
# status word in the message is the only classification signal)
_TRANSIENT_STATUS = (
    "RESOURCE_EXHAUSTED", "UNAVAILABLE", "DEADLINE_EXCEEDED",
    "ABORTED", "CANCELLED", "DATA_LOSS", "INTERNAL",
    "failed to connect", "socket closed", "connection reset",
    "premature end of", "device or resource busy",
)
_TRANSIENT_TYPE_NAMES = (
    "XlaRuntimeError", "JaxRuntimeError", "RpcError",
    "DeadlineExceeded", "ServiceUnavailable",
)
# programming errors: never retry, whatever the message says
_FATAL_TYPES = (TypeError, ValueError, KeyError, IndexError,
                AttributeError, AssertionError, ZeroDivisionError,
                NotImplementedError, KeyboardInterrupt, SystemExit)


def is_transient(exc: BaseException) -> bool:
    """True if ``exc`` looks like infrastructure flake worth a
    re-dispatch; False for programming errors."""
    if isinstance(exc, _FATAL_TYPES):
        return False
    # filesystem / network / device-file errors (includes the chaos
    # harness's InjectedFault, an OSError subclass — by design: the
    # injection models exactly this class of failure)
    if isinstance(exc, (OSError, TimeoutError, ConnectionError)):
        return True
    name = type(exc).__name__
    if any(name == t or name.endswith(t)
           for t in _TRANSIENT_TYPE_NAMES):
        msg = str(exc)
        # XlaRuntimeError also wraps genuine programming errors
        # (INVALID_ARGUMENT shape mismatches) — only the
        # infrastructure status words are retryable
        return any(s in msg for s in _TRANSIENT_STATUS)
    return False


def backoff_delay(attempt: int, base: float, cap: float,
                  seed: int, key: str) -> float:
    """Exponential backoff with deterministic jitter in
    [0.5x, 1.0x] of the exponential envelope."""
    envelope = min(cap, base * (2.0 ** attempt))
    digest = hashlib.sha256(
        f"{seed}:{key}:{attempt}".encode()).digest()
    frac = int.from_bytes(digest[:8], "big") / float(1 << 64)
    return envelope * (0.5 + 0.5 * frac)


def donates(fn) -> bool:
    """Does ``fn`` declare that it donates its input buffers?
    Convention: donating jitted chunk programs (and wrappers around
    them, e.g. ``obs.jaxobs.track``, which delegates attributes) set
    ``donates_buffers = True``."""
    return bool(getattr(fn, "donates_buffers", False))


def retry(max_attempts: int = 3, base_delay: float = 0.5,
          max_delay: float = 30.0, classify=is_transient,
          seed: int = 0, sleep=time.sleep, logger=None):
    """Decorator: re-invoke on transient failures, with
    deterministic-jitter exponential backoff between attempts.

    ``classify(exc) -> bool`` decides retry vs raise; non-transient
    exceptions and the final attempt's exception propagate unchanged.
    ``logger`` (optional callable, e.g. ``MetricsLogger.log``) gets
    ``("retry", attempt=..., of=..., error=..., delay_s=...)`` per
    retry so flake is visible in metrics.jsonl.

    Refuses (``ValueError``, at wrap time) a callable that declares
    ``donates_buffers = True``: after ANY dispatch — including a
    failed one — the donated inputs may be invalid, so re-invoking
    with the same arguments would compute on garbage. Wrap the
    enclosing iteration (which rebuilds its donated carries from
    never-donated state) instead.
    """
    if max_attempts < 1:
        raise ValueError(f"max_attempts must be >= 1, got {max_attempts}")

    def decorate(fn):
        if donates(fn):
            raise ValueError(
                f"retry would re-dispatch {getattr(fn, '__name__', fn)!r}"
                " whose inputs are DONATED (donates_buffers=True) — a "
                "failed attempt may already have invalidated them. "
                "Retry the enclosing iteration instead (see "
                "runtime/retries.py module docstring).")
        key = getattr(fn, "__qualname__", None) or repr(fn)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            for attempt in range(max_attempts):
                try:
                    return fn(*args, **kwargs)
                except BaseException as e:  # noqa: BLE001 — classified below
                    if attempt + 1 >= max_attempts or not classify(e):
                        raise
                    delay = backoff_delay(attempt, base_delay,
                                          max_delay, seed, key)
                    if logger is not None:
                        logger("retry", of=key, attempt=attempt + 1,
                               max_attempts=max_attempts,
                               error=f"{type(e).__name__}: {e}",
                               delay_s=round(delay, 3))
                    else:
                        print(f"retries: {key} attempt "
                              f"{attempt + 1}/{max_attempts} failed "
                              f"({type(e).__name__}: {e}); retrying "
                              f"in {delay:.2f}s", file=sys.stderr)
                    sleep(delay)
            raise AssertionError("unreachable")  # pragma: no cover

        return wrapper

    return decorate


def retry_call(fn, *args, _retry_kwargs: dict | None = None, **kwargs):
    """One-shot form: ``retry_call(f, x, y)`` ≡ ``retry()(f)(x, y)``."""
    return retry(**(_retry_kwargs or {}))(fn)(*args, **kwargs)
