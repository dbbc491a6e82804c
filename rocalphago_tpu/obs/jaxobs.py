"""JAX-side observability: compile tracking + opt-in profiler capture.

COMPILE TRACKING — on accelerators the difference between a healthy
run and a pathological one is often invisible recompiles (a shape
drifting per iteration recompiles a trainer step every time; KataGo/
Pgx-style throughput work lives on exactly this distinction).
:func:`track` wraps a jitted entry point; every call that grows the
function's executable cache (``PjitFunction._cache_size`` — exact,
not a heuristic) is recorded as:

* counter ``jax_compiles_total{entry=...}`` + histogram
  ``jax_compile_seconds{entry=...}`` in the default registry;
* one ``compile`` event through the trace sink (``recompile: true``
  from the second compile on), so ``metrics.jsonl`` names the entry
  point and the wall cost.

On runtimes without ``_cache_size`` the first call counts as the
compile (first-call-vs-steady heuristic). Steady-state dispatch time
is kept as an EMA on the wrapper (``.steady_ema_s``) so first-call vs
steady timing per entry point is one attribute read. The wrapper
delegates unknown attributes to the wrapped function, so
``.lower()``/``.clear_cache()`` and the chunk-program attribute
conventions (``search.run_sims``) keep working.

DEVICE RECORD — :func:`device_record` names the device a process is
running on (platform, ``device_kind``, count), the installed
jax/jaxlib/libtpu and the engine formulation the platform selected.
``run_training`` and the gateway / GTP ``main``s log it once at start
as a ``device`` event, so every run's artifacts say where they were
made — a CPU run can never pass for a chip run (``chip_smoke.py``
asserts on this record).

PROFILER CAPTURE — ``maybe_start_profiler()`` starts a
``jax.profiler`` trace into a directory given explicitly (trainer
``--profile-dir`` flags) or via :data:`PROFILE_ENV`; no-op otherwise,
so it is safe to call unconditionally. ``stop_profiler`` is
idempotent and also registered via ``atexit`` (a crashed run still
flushes its trace). ``jax`` is imported lazily — importing this
module stays stdlib-cheap.
"""

from __future__ import annotations

import atexit
import os
import sys
import time

from rocalphago_tpu.obs import registry as _registry
from rocalphago_tpu.obs import trace as _trace

PROFILE_ENV = "ROCALPHAGO_JAX_PROFILE"


def _cache_size(fn):
    f = getattr(fn, "_cache_size", None)
    if f is None:
        return None
    try:
        return int(f())
    except Exception:  # noqa: BLE001 — introspection is best-effort
        return None


class TrackedFunction:
    """Callable wrapper; see module docstring. Attributes:
    ``entry`` (name), ``calls``, ``compiles``, ``first_call_s``,
    ``steady_ema_s``; everything else delegates to the wrapped fn."""

    def __init__(self, entry: str, fn, registry=None):
        self._fn = fn
        self.entry = entry
        self.registry = registry or _registry.REGISTRY
        self.calls = 0
        self.compiles = 0
        self.first_call_s = None
        self.steady_ema_s = None

    def __call__(self, *args, **kwargs):
        n0 = _cache_size(self._fn)
        t0 = time.monotonic()
        out = self._fn(*args, **kwargs)
        dt = time.monotonic() - t0
        self.calls += 1
        n1 = _cache_size(self._fn)
        compiled = (n1 > n0 if n1 is not None and n0 is not None
                    else self.calls == 1)
        if compiled:
            self.compiles += 1
            if self.first_call_s is None:
                self.first_call_s = dt
            self.registry.counter("jax_compiles_total",
                                  entry=self.entry).inc()
            self.registry.histogram("jax_compile_seconds",
                                    entry=self.entry).observe(dt)
            _trace.emit("compile", entry=self.entry,
                        dur_s=round(dt, 6), calls=self.calls,
                        recompile=self.compiles > 1)
        else:
            ema = self.steady_ema_s
            self.steady_ema_s = (dt if ema is None
                                 else 0.9 * ema + 0.1 * dt)
        return out

    def __getattr__(self, item):
        # only reached for names NOT on the wrapper; '_fn' is set
        # first in __init__ so delegation can never recurse
        return getattr(self._fn, item)

    def stats(self) -> dict:
        return {"entry": self.entry, "calls": self.calls,
                "compiles": self.compiles,
                "first_call_s": self.first_call_s,
                "steady_ema_s": self.steady_ema_s}

    def __repr__(self) -> str:
        return (f"TrackedFunction({self.entry!r}, calls={self.calls}, "
                f"compiles={self.compiles})")


def track(entry: str, fn=None, registry=None):
    """Wrap a (jitted) callable with compile-event tracking —
    ``track("name", fn)`` or as a decorator ``@track("name")``."""
    if fn is None:
        return lambda f: TrackedFunction(entry, f, registry)
    return TrackedFunction(entry, fn, registry)


# --------------------------------------------------- device record

def device_record() -> dict:
    """Where this process runs, as JAX reports it: the fields of the
    one ``device`` event each entry point logs at start."""
    import importlib.metadata

    import jax
    import jaxlib

    from rocalphago_tpu.engine.jaxgo import engine_formulation

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "count": len(devices),
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu, "engine": engine_formulation()}


# ------------------------------------------------ profiler capture

_profiling = {"dir": None}


def maybe_start_profiler(out_dir: str | None = None) -> bool:
    """Start a ``jax.profiler`` trace into ``out_dir`` (or
    ``$ROCALPHAGO_JAX_PROFILE``); returns whether a capture started.
    Safe to call unconditionally — no directory means no-op; a second
    start while one is active is a no-op too."""
    out = out_dir or os.environ.get(PROFILE_ENV)
    if not out or _profiling["dir"] is not None:
        return False
    import jax

    jax.profiler.start_trace(out)
    _profiling["dir"] = out
    atexit.register(stop_profiler)
    _trace.emit("profiler", action="start", out_dir=out)
    print(f"jaxobs: profiler capture -> {out}", file=sys.stderr)
    return True


def stop_profiler() -> None:
    """Stop an active capture (idempotent; also runs via atexit)."""
    if _profiling["dir"] is None:
        return
    import jax

    out, _profiling["dir"] = _profiling["dir"], None
    jax.profiler.stop_trace()
    _trace.emit("profiler", action="stop", out_dir=out)
