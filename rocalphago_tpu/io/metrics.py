"""Structured training metrics: JSONL stream + stdout.

Parity: the reference's observability is the Keras progress bar plus
``metadata.json`` (SURVEY.md §5 "Metrics / logging"). The rebuild logs
one JSON object per event to ``metrics.jsonl`` (step, loss, accuracy,
games/min, …) — greppable and plottable. TensorBoard is
intentionally not a dependency; the JSONL is trivially convertible.

The same stream carries the observability subsystem's records
(``span``/``compile``/``registry`` events — see
:mod:`rocalphago_tpu.obs` and docs/OBSERVABILITY.md), emitted through
:meth:`MetricsLogger.write` (file-only: high-rate telemetry must not
spam the console ``log`` echoes).

Strict-parser contract: non-finite floats (NaN/Inf — e.g. the
``evaluate`` path's empty-split NaN) are sanitized to JSON ``null``
before serialization, so no line ever contains a bare ``NaN``/
``Infinity`` token (valid for ``json.loads`` only by a non-standard
extension many parsers reject). ``json.dumps`` runs with
``allow_nan=False`` to make the guarantee load-bearing.
"""

from __future__ import annotations

import json
import math
import os
import time


# the crash-tolerant reader matching this module's writer; it lives
# in runtime (stdlib-only) so light scripts can import it without
# pulling this package's jax/orbax dependencies
from rocalphago_tpu.runtime.jsonl import read_jsonl  # noqa: F401
# instrumented-lock factory (plain threading.Lock unless
# ROCALPHAGO_LOCKCHECK=1) — also stdlib-only
from rocalphago_tpu.analysis import lockcheck


def sanitize(value):
    """Recursively replace non-finite floats with None (JSON null);
    tuples become lists (their JSON form anyway)."""
    if isinstance(value, float):           # incl. np.float64 subclass
        return value if math.isfinite(value) else None
    if isinstance(value, dict):
        return {k: sanitize(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [sanitize(v) for v in value]
    return value


class MetricsLogger:
    """Line-buffered JSONL event stream (``with``-able: closing is
    ``close``; a crashed process that never exits the ``with`` loses
    at most the in-flight line — tests/test_runtime.py pins that).

    THREAD-SAFE: one logger is shared by every session of a serving
    pool (degradation events, watchdog stalls, spans from N session
    threads plus the evaluator's dispatcher), so emission is a single
    ``write()`` call under a lock — interleaved events can never tear
    each other's lines, and ``close`` can race an emit without
    writing to a closed file (pinned by the concurrent-emit test in
    ``tests/test_obs.py``). Serialization happens OUTSIDE the lock;
    only the file write is held."""

    def __init__(self, path: str | None, echo: bool = True):
        self.path = path
        self.echo = echo
        self._lock = lockcheck.make_lock("MetricsLogger._lock")
        if path:
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            self._f = open(path, "a", buffering=1)  # guarded-by: self._lock
        else:
            self._f = None                          # guarded-by: self._lock

    def write(self, event: str, **fields) -> None:
        """File-only emission (no console echo) — the channel for
        high-rate telemetry (spans, compile events, registry
        snapshots)."""
        rec = sanitize({"event": event, "time": time.time(), **fields})
        line = json.dumps(rec, allow_nan=False) + "\n"
        with self._lock:
            if self._f:
                self._f.write(line)

    def log(self, event: str, **fields) -> None:
        fields = sanitize(fields)
        self.write(event, **fields)
        if self.echo:
            shown = " ".join(
                f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
                for k, v in fields.items())
            print(f"[{event}] {shown}", flush=True)

    def close(self) -> None:
        with self._lock:
            if self._f:
                self._f.close()
                self._f = None

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
