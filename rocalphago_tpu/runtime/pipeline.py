"""Pipelined chunk dispatch: keep a compiled chunk in flight while
the host decides.

The PR 1-2 chunked runners made every hot loop watchdog-safe by
splitting one long device program into ``chunk``-sized compiled
programs driven from a host loop — but the loops then paid a full
host sync per chunk (``jax.block_until_ready`` for the deadline
check, a blocking ``device_get`` for the self-play done-poll), so the
device idled in every gap, on exactly the sims/sec and games/min
paths. This module takes the host back out of
the steady state: a :class:`ChunkPipeline` lets the loop dispatch
chunk N+1 while the host inspects chunk N's already-materialized
scalars, so deadline checks, done-polls and per-chunk observability
run ONE CHUNK BEHIND with the device never idle.

Semantics: pipelining is a SCHEDULING change, not a semantics change.
The chunk programs run in the same order with the same operands —
results are bit-identical to the sync path at any depth
(tier-1-asserted for PUCT search, gumbel search, chunked self-play
and a zero iteration). What shifts is *when the host learns things*:

* a hard deadline (``runtime.deadline.Deadline``) is still checked
  between chunks, but the host may have one extra chunk in flight
  when it sees the expiry — the hard-stop overshoot bound becomes
  "at most ``depth`` in-flight chunks" (one, at the default depth)
  on top of the sync bound; the anytime answer and the one-chunk
  floor are unchanged (docs/RESILIENCE.md);
* the self-play done-poll reads the done-scalar of a RETIRED chunk
  (already materialized — the fetch never syncs the fresh dispatch);
  an extra chunk dispatched onto all-done states is a proven no-op
  (the engine freezes finished games) and its recorded rows are
  replaced by the same zero padding the sync path writes, so the
  result stays bit-identical;
* fault barriers (``runtime.faults``) keep firing once per chunk, in
  dispatch order, on the host — injection points are unmoved.

Depth: ``depth`` = how many dispatched-but-unretired chunks the host
may run ahead. ``depth=0`` reproduces today's fully synchronous
behavior (every ``push`` blocks on the chunk just pushed);
``depth=1`` (the default) keeps one chunk in flight. The default is
env-overridable via ``ROCALPHAGO_PIPELINE_DEPTH`` so a sweep can A/B
without code changes.

Donation: pipelining must not double slab memory — the chunk loops
donate their big device-resident carries (DeviceTree slabs, self-play
``GoState``, replay grad accumulators) into the next chunk's program
(``jax.jit(..., donate_argnums=...)``). Donating programs advertise
``donates_buffers = True``; :mod:`runtime.retries` REFUSES to wrap
them (a failed dispatch may already have invalidated the donated
input, so a re-dispatch would compute on garbage). Retry stays valid
one level up: the trainers re-invoke the whole iteration from
never-donated state. See docs/PERFORMANCE.md for the full donation
rules.

Observability (``obs.registry``): every pipeline records the
``dispatch_gap_s{runner=...}`` histogram (host-side gaps during which
the device had NOTHING in flight — the idle the sync path pays per
chunk), a ``device_occupancy{runner=...}`` gauge (1 − gap/wall over
the pipeline's active windows) and ``dispatch_chunks_total``;
``scripts/obs_report.py`` renders them and the chip benchmark's
self-play driver reads ``host_gap_frac`` (``chipbench/layers/
dispatch_gap_pct.selfplay.py``). The same three
intervals go on the profiler's clock (``obs.trace.annotation``):
``pipeline.dispatch`` (a push's bookkeeping), ``pipeline.wait`` (a
retire blocked on the device) and ``pipeline.host_work`` (the gap the
histogram counts: nothing in flight until the next push) — so a
device trace shows each gap beside the chip's own idle time.
"""

from __future__ import annotations

import os
import time
from collections import deque

from rocalphago_tpu.obs import trace

DEPTH_ENV = "ROCALPHAGO_PIPELINE_DEPTH"
DEFAULT_DEPTH = 1


def default_depth() -> int:
    """The process-default pipeline depth: ``$ROCALPHAGO_PIPELINE_
    DEPTH`` if set (0 = sync), else :data:`DEFAULT_DEPTH`. Read at
    call time so tests and sweeps can flip it per run."""
    raw = os.environ.get(DEPTH_ENV, "").strip()
    if not raw:
        return DEFAULT_DEPTH
    try:
        depth = int(raw)
    except ValueError as e:
        raise ValueError(
            f"{DEPTH_ENV} must be a non-negative integer, got {raw!r}"
        ) from e
    if depth < 0:
        raise ValueError(f"{DEPTH_ENV} must be >= 0, got {depth}")
    return depth


class ChunkPipeline:
    """Bounded window of in-flight compiled chunks.

    Protocol (one pipeline per chunked run, or one per bench shared
    across reps)::

        pipe = ChunkPipeline(depth=None, runner="device_mcts")
        for ...:                      # the host chunk loop
            out = chunk_program(...)  # async dispatch
            retired = pipe.push(out.some_scalar, payload=...)
            # decide on `retired` chunks' scalars — they are READY
            # (the push blocked until ≤ depth chunks stayed in flight)
        pipe.drain()    # block the tail (deadline-enforced paths)
        # -- or --
        pipe.finish()   # just close the accounting window (async
                        #    paths; a later fetch syncs the tail)

    ``push`` registers a freshly dispatched chunk via a small output
    array ``handle`` (any per-chunk output leaf; a done-scalar when
    the caller wants to read it) and blocks until at most ``depth``
    chunks remain in flight — so the host is paced by real device
    completion, never more than ``depth`` chunks ahead. It returns
    the ``(payload, handle)`` pairs of the chunks retired by this
    call, oldest first; their handles are materialized, so a
    ``device_get`` on them cannot sync the fresh dispatch.

    Gap accounting: a "gap" is host wall time during which NO chunk
    was in flight between two pushes of the same window — the device
    idle the sync path pays once per chunk. ``host_gap_frac`` is
    gap time over active-window wall time; the tail after the last
    retire of a window is NOT a gap (the run is over). Stats survive
    ``finish``; a later ``push`` opens a new window (benches share
    one pipeline across reps). ``reset_stats`` zeroes them (after a
    warmup/compile rep).
    """

    def __init__(self, depth: int | None = None, runner: str = "",
                 registry=None):
        self.depth = default_depth() if depth is None else int(depth)
        if self.depth < 0:
            raise ValueError(f"depth must be >= 0, got {self.depth}")
        self.runner = runner
        self._inflight: deque = deque()
        self._gap_started = None     # queue drained mid-window
        self._gap_ann = None         # the open gap on the profiler
        self._window_start = None
        self.chunks = 0
        self.gaps = 0
        self.gap_s = 0.0
        self.wall_s = 0.0            # closed windows only
        self._gap_h = self._occ_g = self._chunks_c = None
        if runner:
            from rocalphago_tpu.obs import registry as obs_registry

            reg = registry or obs_registry.REGISTRY
            self._gap_h = reg.histogram("dispatch_gap_s", runner=runner)
            self._occ_g = reg.gauge("device_occupancy", runner=runner)
            self._chunks_c = reg.counter("dispatch_chunks_total",
                                         runner=runner)

    # ------------------------------------------------------ protocol

    def push(self, handle, payload=None) -> list:
        """Register a dispatched chunk; block until ≤ ``depth`` stay
        in flight; return the retired ``(payload, handle)`` pairs."""
        now = time.monotonic()
        self._close_gap_annotation()
        with trace.annotation("pipeline.dispatch"):
            if self._window_start is None:
                self._window_start = now
            if self._gap_started is not None:
                gap = now - self._gap_started
                self._gap_started = None
                self.gaps += 1
                self.gap_s += gap
                if self._gap_h is not None:
                    self._gap_h.observe(gap)
            self._inflight.append((payload, handle))
            self.chunks += 1
            if self._chunks_c is not None:
                self._chunks_c.inc()
        retired = []
        while len(self._inflight) > self.depth:
            retired.append(self._retire())
        return retired

    def _retire(self):
        payload, handle = self._inflight.popleft()
        if handle is not None:
            import jax

            with trace.annotation("pipeline.wait"):
                jax.block_until_ready(handle)
        if not self._inflight:
            # nothing left in flight: the device is (potentially)
            # idle from here until the next push — that span is the
            # gap the pipeline exists to remove
            self._gap_started = time.monotonic()
            self._gap_ann = trace.annotation("pipeline.host_work")
            self._gap_ann.__enter__()
        return payload, handle

    def _close_gap_annotation(self) -> None:
        if self._gap_ann is not None:
            self._gap_ann.__exit__(None, None, None)
            self._gap_ann = None

    def pending(self) -> int:
        return len(self._inflight)

    def drain(self) -> list:
        """Retire (block) every in-flight chunk, then close the
        window. The deadline-enforced paths drain so their rate and
        margin metrics measure real execution, not dispatch."""
        retired = []
        while self._inflight:
            retired.append(self._retire())
        self.finish()
        return retired

    def finish(self) -> None:
        """Close the accounting window WITHOUT blocking the tail —
        the async (training) paths' natural end, where a downstream
        fetch syncs whatever is still in flight. Idempotent."""
        self._close_gap_annotation()
        if self._window_start is None:
            return
        end = (self._gap_started if self._gap_started is not None
               and not self._inflight else time.monotonic())
        self.wall_s += max(end - self._window_start, 0.0)
        self._window_start = None
        self._gap_started = None
        if self._occ_g is not None:
            self._occ_g.set(self.occupancy)

    # ------------------------------------------------------- stats

    @property
    def host_gap_frac(self) -> float:
        """Gap time over active wall time (closed windows; the
        current window, if any, counts up to now)."""
        wall = self.wall_s
        if self._window_start is not None:
            wall += time.monotonic() - self._window_start
        if wall <= 0.0:
            return 0.0
        return min(1.0, self.gap_s / wall)

    @property
    def occupancy(self) -> float:
        """1 − ``host_gap_frac``: fraction of the pipeline's active
        wall time with work in flight (the gauge value)."""
        return 1.0 - self.host_gap_frac

    def reset_stats(self) -> None:
        """Zero the counters/accounting (keeps depth and metric
        handles). Benches call this after their warmup/compile rep so
        the A/B numbers cover measured reps only. Refuses while
        chunks are in flight — drain or finish first."""
        if self._inflight:
            raise RuntimeError(
                "reset_stats with chunks still in flight — drain() "
                "first")
        self._close_gap_annotation()
        self.chunks = self.gaps = 0
        self.gap_s = self.wall_s = 0.0
        self._window_start = self._gap_started = None

    def __repr__(self) -> str:
        return (f"ChunkPipeline(depth={self.depth}, "
                f"runner={self.runner!r}, chunks={self.chunks}, "
                f"inflight={len(self._inflight)}, "
                f"gap_frac={self.host_gap_frac:.4f})")
