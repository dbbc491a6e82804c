"""Unified observability: tracing spans, metrics registry, reports.

The resilience layers (``rocalphago_tpu.runtime``, PR 1/2) made the
stack survive faults; this package makes its behavior *visible*.
Three stdlib-only pieces share one output channel — the existing
``metrics.jsonl`` stream written by
:class:`~rocalphago_tpu.io.metrics.MetricsLogger`:

* :mod:`.trace` — nested wall-clock ``span(name)`` context managers
  emitting structured ``span`` records (duration, parent path, tags).
  Every trainer wraps its iteration phases (data/step/eval/
  checkpoint), so a run directory's ``metrics.jsonl`` carries a full
  per-phase time breakdown that ``scripts/obs_report.py`` renders.
* :mod:`.registry` — process-wide counters, gauges, and
  bounded-bucket histograms with a deterministic snapshot API and
  Prometheus-style text rendering. The hot paths (device search
  chunks, self-play, the serving ladder) record here with no logger
  plumbing; the GTP ``rocalphago-stats`` probe returns the live
  snapshot.
* :mod:`.jaxobs` — compile-event tracking for jitted entry points
  (recompiles surface as named ``compile`` events + counters) and an
  opt-in ``jax.profiler`` trace capture gated by env var/flag.

Record schema and report format: docs/OBSERVABILITY.md.
"""

from rocalphago_tpu.obs import registry, trace  # noqa: F401
from rocalphago_tpu.obs.registry import (  # noqa: F401
    REGISTRY,
    counter,
    gauge,
    histogram,
    render_text,
    reset,
    snapshot,
    timed,
)
from rocalphago_tpu.obs.trace import (  # noqa: F401
    annotation,
    configure,
    current_path,
    emit,
    span,
    where,
)
