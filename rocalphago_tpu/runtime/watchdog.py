"""Heartbeat watchdog for long training loops.

A wedged device program (a device worker lost mid-program hangs the
host dispatch forever) leaves a ``nohup`` run silently stuck for
hours. The watchdog is a daemon thread the loop
feeds with :meth:`Watchdog.beat` once per iteration; if no beat
arrives within the deadline it logs a ``stall`` event (to the run's
``metrics.jsonl`` via the supplied logger) and — in abort mode —
calls the caller's ``abort_fn``, whose job is to persist the last
COMPLETED state (the in-flight iteration is unrecoverable from a
sibling thread) and ``os._exit``. Logging mode just leaves a
greppable trail for the operator.

Stall events carry WHERE the process hung, not just that it hung:
the ``span`` field is the deepest open tracing span across all
threads (:func:`rocalphago_tpu.obs.trace.where`) at the moment the
watchdog fired — e.g. ``zero.iteration/zero.selfplay`` — so the
operator reads the stuck phase straight off ``metrics.jsonl``.

Starvation vs deadlock: a learner blocked on an empty replay buffer
produces the same no-beat signature as a wedged device program. Code
that blocks *by design* wraps the wait in :func:`waiting_on`, and the
stall event gains a ``waiting_on`` field (e.g. ``replay_fill``) so
soak analysis can tell "waiting for producers" from "hung".
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time

from rocalphago_tpu.analysis import lockcheck
from rocalphago_tpu.obs import trace

STALL_EXIT_CODE = 170

_waiting_lock = lockcheck.make_lock("watchdog._waiting_lock")
_waiting: dict[int, str] = {}  # guarded-by: _waiting_lock


@contextlib.contextmanager
def waiting_on(phase: str):
    """Tag the calling thread as deliberately blocked on ``phase``.

    Nested tags restore the outer phase on exit; the registry is
    keyed by thread ident so concurrent waiters don't clobber each
    other. The lock is released across the yield — the tag is a
    plain dict entry while the caller blocks.
    """
    ident = threading.get_ident()
    with _waiting_lock:
        prev = _waiting.get(ident)
        _waiting[ident] = phase
    try:
        yield
    finally:
        with _waiting_lock:
            if prev is None:
                _waiting.pop(ident, None)
            else:
                _waiting[ident] = prev


def waiting_phases() -> tuple[str, ...]:
    """Sorted distinct phases threads are currently blocked on."""
    with _waiting_lock:
        return tuple(sorted(set(_waiting.values())))


class Watchdog:
    """``with Watchdog(deadline_s, metrics=logger) as wd: wd.beat()``.

    ``metrics``: a ``MetricsLogger``-shaped object (``log(event,
    **fields)``) or None for stderr. ``abort_fn``: optional callable
    run once on the first stall; after it returns the watchdog exits
    the process with ``STALL_EXIT_CODE`` (pass ``exit=False`` to keep
    the process — tests). Repeated stalls without an ``abort_fn`` log
    every ``deadline_s``.
    """

    def __init__(self, deadline_s: float, metrics=None,
                 abort_fn=None, name: str = "train",
                 exit: bool = True, poll_s: float | None = None):
        if deadline_s <= 0:
            raise ValueError(f"deadline must be > 0, got {deadline_s}")
        self.deadline_s = deadline_s
        self.metrics = metrics
        self.abort_fn = abort_fn
        self.name = name
        self.exit = exit
        self.stalls = 0
        self._poll_s = poll_s or min(1.0, deadline_s / 4.0)
        # deliberately lock-free (so deliberately NOT `# guarded-by:`
        # annotated): one writer (beat) and one reader (_watch), and
        # a torn/stale read of a monotonic float only shifts a stall
        # report by one poll — see docs/CONCURRENCY.md's benign list
        self._last_beat = time.monotonic()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._watch, name=f"watchdog-{name}", daemon=True)

    # ------------------------------------------------------ lifecycle

    def start(self) -> "Watchdog":
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)

    def __enter__(self) -> "Watchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------ heartbeat

    def beat(self) -> None:
        self._last_beat = time.monotonic()

    def _log(self, elapsed: float) -> None:
        at = trace.where()          # deepest open span, any thread
        waits = waiting_phases()
        waiting = ",".join(waits) if waits else None
        if self.metrics is not None:
            self.metrics.log("stall", watchdog=self.name,
                             elapsed_s=round(elapsed, 1),
                             deadline_s=self.deadline_s, span=at,
                             waiting_on=waiting)
        else:
            print(f"watchdog[{self.name}]: no heartbeat for "
                  f"{elapsed:.0f}s (deadline {self.deadline_s:.0f}s)"
                  f"{f' in {at}' if at else ''}"
                  f"{f' waiting on {waiting}' if waiting else ''}",
                  file=sys.stderr)

    def _watch(self) -> None:
        while not self._stop.wait(self._poll_s):
            elapsed = time.monotonic() - self._last_beat
            if elapsed < self.deadline_s:
                continue
            self.stalls += 1
            self._log(elapsed)
            if self.abort_fn is not None:
                try:
                    self.abort_fn()
                finally:
                    if self.exit:
                        sys.stdout.flush()
                        sys.stderr.flush()
                        os._exit(STALL_EXIT_CODE)
                return
            # keep logging, but not more than once per deadline
            self._last_beat = time.monotonic()
