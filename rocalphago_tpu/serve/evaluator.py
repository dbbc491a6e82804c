"""The shared batching evaluator: cross-game leaf evaluation.

One dispatcher thread owns ONE jit-compiled policy+value program
(``search.eval_batch`` from :func:`rocalphago_tpu.search.device_mcts.
make_device_mcts`) compiled at a few FIXED batch sizes. Sessions
submit pending leaf states (typically one row per live search per
simulation); the dispatcher coalesces whole requests across sessions
into one device batch, pads to the nearest compiled size (padded rows
replicate row 0 and are sliced off — per-row programs, so real rows
are bit-independent of the padding; pinned by
``tests/test_serve.py``), evaluates, and hands each request back its
slice.

Dispatch policy (docs/SERVING.md):

* **fill target** — dispatch as soon as pending rows reach
  ``min(max_batch, live sessions)``: every live search has at most
  one leaf in flight, so a full convoy is the most that can ever
  arrive and waiting past it is pure stall. With no admission
  controller attached the target is ``max_batch``.
* **max wait** — a partial batch is flushed when its OLDEST request
  has waited ``max_wait_us`` (degraded sessions stop submitting; the
  tail must not stall the fleet). ``ROCALPHAGO_SERVE_MAX_WAIT_US``
  overrides the 500 µs default.
* **bounded queue** — ``submit`` past the admission controller's
  ``queue_rows`` bound sheds (:class:`~rocalphago_tpu.serve.
  admission.EvaluatorOverload`) instead of queueing; the session's
  resilience ladder absorbs it.

A failed batch (injected fault at the ``serve.eval`` barrier, or a
real device error) fails ONLY the requests in that batch — their
futures carry the exception, the dispatcher loop survives, and every
other session keeps being served (the soak test's core claim). The
dispatcher THREAD itself is a supervised unit
(:class:`~rocalphago_tpu.runtime.supervisor.SupervisedThread`): an
exception that escapes the per-batch handler — the ``serve.dispatch``
barrier at the top of the loop is the chaos harness's kill point —
re-enters the loop after a classified backoff (queue, counters and
stop flag all live on the evaluator, so nothing is lost), and a
crash LOOP parks the dispatcher and fails pending requests instead
of hanging its sessions.

Batch sizes default to ``1,8,32,128,256`` (clipped to the admission
session cap); ``ROCALPHAGO_SERVE_BATCH_SIZES`` overrides with a
comma list. Each size is one XLA program, compiled on first use (or
ahead of time via ``ServePool.warm``).

Versioned params (docs/ROLLOUT.md): the evaluator holds a registry
of ``version -> (params_p, params_v)`` pairs with one CURRENT
pointer. :meth:`set_params` installs a new pair and flips the
pointer — params are jit ARGUMENTS at fixed compiled shapes, so a
swap is O(1) and never recompiles. A session pins one version for
the whole genmove (:meth:`acquire`/:meth:`release`), so a search
never mixes two nets; the dispatcher never coalesces requests of
different versions into one batch (it splits at a version edge), so
a device batch is single-version by construction. Non-current
versions retire as soon as the last pin (or queued request) drops.

Transposition cache (docs/SERVING.md "Evaluation cache"): with an
:class:`~rocalphago_tpu.serve.evalcache.EvalCache` attached, the
dispatcher keys every coalesced row by its eval signature (device
arrays riding each request via ``keys=``, or computed by ``key_fn``
for requests without them), serves hits from the cache, collapses
duplicate-key misses to ONE device row (in-batch dedup — convoyed
fleets walking shared openings stop paying per-session evals), pads
only the UNIQUE rows to a compiled size, and fans results back out.
Hits and dedup fan-outs are host copies of exact device outputs, so
the cached path is bit-identical to the uncached one (pinned by
``tests/test_serve.py``); a batch of pure hits skips the device
entirely. Version retirement evicts that version's entries — the
registry reuses version numbers, so this is correctness, not
hygiene. The gather/pad work on the cached path is EAGER jax (no
tracked jit entry), so ``jax_compiles_total`` stays flat.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from rocalphago_tpu.analysis import lockcheck
from rocalphago_tpu.obs import registry as obs_registry
from rocalphago_tpu.obs import trace
from rocalphago_tpu.runtime import faults, supervisor

MAX_WAIT_ENV = "ROCALPHAGO_SERVE_MAX_WAIT_US"
BATCH_SIZES_ENV = "ROCALPHAGO_SERVE_BATCH_SIZES"

#: batch-occupancy histogram edges (real rows / compiled size)
OCC_EDGES = (0.05, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0)


def default_batch_sizes(cap: int | None = None) -> tuple:
    """The compiled-size ladder: env override or ``1,8,32,64,256``,
    clipped to ``cap`` (the session cap — no point compiling a batch
    no convoy can fill). ``cap`` itself joins the ladder: the full
    convoy — every live session's leaf, the steady-state batch — must
    be a compiled size, not padded up to one (a cap of 48 padded to
    256 would waste 4× the eval)."""
    raw = os.environ.get(BATCH_SIZES_ENV, "")
    sizes = (tuple(int(s) for s in raw.split(",") if s.strip())
             if raw else (1, 8, 32, 64, 256))
    sizes = tuple(sorted(set(s for s in sizes if s > 0)))
    if not sizes:
        raise ValueError(f"no usable batch sizes in {raw!r}")
    if cap is not None and cap >= sizes[0]:
        sizes = tuple(sorted(
            set(s for s in sizes if s <= cap) | {cap}))
    return sizes


class _Pending:
    """A submitted evaluation request: rows in, a future out.
    ``komi`` is None (the pool's pinned komi) or the request's custom
    komi — a float applied to every row, or a per-row sequence.
    ``keys`` is None or the rows' eval signatures (uint32 [rows, 2],
    device or host) — the transposition-cache keys the searcher
    already computed on device (``SimStep.eval_keys``)."""

    __slots__ = ("states", "rows", "komi", "version", "keys",
                 "t_submit", "_event", "_result", "_exc")

    def __init__(self, states, rows: int, komi=None,
                 version: int = 0, keys=None):
        self.states = states
        self.rows = rows
        self.komi = komi
        self.version = version
        self.keys = keys
        self.t_submit = time.monotonic()
        self._event = threading.Event()
        self._result = None
        self._exc = None

    def _finish(self, result) -> None:
        self._result = result
        self._event.set()

    def _fail(self, exc: BaseException) -> None:
        self._exc = exc
        self._event.set()

    def result(self, timeout: float | None = None):
        """Block for the batch containing this request; returns
        ``(priors [rows, A], values [rows])`` or re-raises the
        batch's failure. ``timeout`` (tests) raises TimeoutError."""
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"evaluation not served within {timeout}s")
        if self._exc is not None:
            raise self._exc
        return self._result


class BatchingEvaluator:
    """Coalesce leaf-eval requests from many sessions into fixed-size
    device batches (module docstring has the dispatch policy).

    Parameters
    ----------
    eval_fn : ``(params_p, params_v, states[B]) -> (priors, values)``
        — a jitted per-row program (``search.eval_batch``); one
        compile per distinct padded size.
    params_p, params_v : the weights, bound for the pool's lifetime.
    batch_sizes : compiled-size ladder (default
        :func:`default_batch_sizes`).
    max_wait_us : partial-batch flush age (default env / 500 µs).
    admission : optional :class:`~rocalphago_tpu.serve.admission.
        AdmissionController` — provides the queue bound and the
        live-session fill target.
    start : tests pass False to drive/fill the queue by hand.
    eval_komi_fn : optional ``(params_p, params_v, states[B],
        komi f32 [B]) -> (priors, values)`` (``search.
        eval_batch_komi``) — engaged ONLY for batches that contain a
        custom-komi request; default-komi batches stay on ``eval_fn``
        bit-for-bit. Rows without a custom komi ride the komi program
        at ``default_komi``, which scores identically by
        construction.
    default_komi : the pool's pinned komi (``cfg.komi``) — the fill
        value for non-custom rows in a mixed batch.
    cache : optional :class:`~rocalphago_tpu.serve.evalcache.
        EvalCache` — enables the transposition-cache + in-batch-dedup
        dispatch path (module docstring). None keeps the plain path
        byte-for-byte.
    key_fn : ``(states[B]) -> uint32 [B, 2]`` (``search.eval_key``) —
        computes eval signatures for requests that arrive without
        ``keys``. Required with a non-symmetry ``cache``.
    board : the pool's board size — part of every cache key, so one
        cache is shareable across a ``MultiSizePool``'s members.
    """

    def __init__(self, eval_fn, params_p, params_v,
                 batch_sizes=None, max_wait_us: float | None = None,
                 admission=None, start: bool = True,
                 eval_komi_fn=None, default_komi: float = 0.0,
                 metrics=None, restart_policy=None, cache=None,
                 key_fn=None, board: int = 0):
        self._eval_fn = eval_fn
        self._eval_komi_fn = eval_komi_fn
        self.default_komi = float(default_komi)
        self.cache = cache
        self._key_fn = key_fn
        self.board = int(board)
        if cache is not None and key_fn is None \
                and not cache.symmetry:
            raise ValueError(
                "an EvalCache needs key_fn (search.eval_key) to key "
                "requests that arrive without precomputed keys")
        # the versioned-params registry (module docstring): pairs are
        # jit arguments, the CURRENT pointer is what unversioned
        # submits resolve to, pins keep a version alive across a swap
        self._params = {0: (params_p, params_v)}  # guarded-by: _cond
        self._current = 0                 # guarded-by: self._cond
        self._pins: dict = {}             # guarded-by: self._cond
        self.swaps = 0                    # guarded-by: self._cond
        cap = admission.max_sessions if admission is not None else None
        self.batch_sizes = (tuple(sorted(batch_sizes)) if batch_sizes
                            else default_batch_sizes(cap))
        self.max_batch = self.batch_sizes[-1]
        if max_wait_us is None:
            raw = os.environ.get(MAX_WAIT_ENV, "")
            max_wait_us = float(raw) if raw else 500.0
        self.max_wait_s = max_wait_us / 1e6
        self.admission = admission
        self._cond = lockcheck.make_condition("BatchingEvaluator._cond")
        self._queue: deque = deque()      # guarded-by: self._cond
        self._pending_rows = 0            # guarded-by: self._cond
        self._stop = False                # guarded-by: self._cond
        # dispatch accounting (stats() + the serve probes)
        self.batches = 0
        self.komi_batches = 0
        self.failures = 0
        self.rows_total = 0
        # occupancy honesty under dedup: rows_total counts LOGICAL
        # rows served, unique_rows_total the rows that actually hit
        # the device (equal on the plain path), dedup_rows_saved the
        # duplicate miss rows collapsed away; batch_occupancy = unique
        # / padded, so dedup cannot inflate it past 1
        self.unique_rows_total = 0
        self.dedup_rows_saved_total = 0
        self.padded_total = 0
        self._uniq_c = obs_registry.counter("serve_unique_rows_total")
        self._dedup_c = obs_registry.counter(
            "serve_dedup_rows_saved_total")
        self._occ_h = obs_registry.histogram("serve_batch_occupancy",
                                             edges=OCC_EDGES)
        self._wait_h = obs_registry.histogram(
            "serve_queue_wait_seconds")
        self._rows_c = obs_registry.counter("serve_eval_rows_total")
        self._fail_c = obs_registry.counter(
            "serve_eval_failures_total")
        self._depth_g = obs_registry.gauge("serve_queue_depth")
        self._swap_c = obs_registry.counter("serve_param_swaps_total")
        self._ver_g = obs_registry.gauge("serve_params_version")
        self._ver_g.set(0)
        # resurrect-on-death: the loop's state is all on self, so
        # re-entering it after an escaped exception loses nothing; a
        # crash loop parks and fails the queue (no hanging clients)
        self._thread = supervisor.SupervisedThread(
            self._loop, name="serve:dispatcher", metrics=metrics,
            policy=restart_policy, on_park=self._fail_pending)
        if start:
            self._thread.start()

    # ----------------------------------------------------- versions

    @property
    def params_version(self) -> int:
        """The CURRENT version — what an unpinned submit resolves to."""
        with self._cond:
            return self._current

    def add_version(self, params_p, params_v,
                    version: int | None = None) -> int:
        """Register a pair WITHOUT flipping the current pointer (the
        canary's staging path). The new version arrives pinned once —
        :meth:`release` drops the stage pin (retiring the version
        unless it was promoted current meanwhile)."""
        with self._cond:
            v = (max(self._params) + 1 if version is None
                 else int(version))
            self._params[v] = (params_p, params_v)
            self._pins[v] = self._pins.get(v, 0) + 1
            return v

    def set_params(self, params_p=None, params_v=None,
                   version: int | None = None) -> int:
        """The hot swap: install ``(params_p, params_v)`` — or, with
        params omitted, promote an already-registered ``version`` —
        as the new current pair. Params are arguments to the compiled
        programs at fixed shapes, so this is a pointer flip: no
        recompile, no dropped requests; in-flight pinned searches
        finish on the version they started. Returns the version."""
        with self._cond:
            if params_p is None:
                v = int(version)
                if v not in self._params:
                    raise KeyError(
                        f"params version {v} is not registered "
                        f"(have {sorted(self._params)})")
            else:
                v = (max(self._params) + 1 if version is None
                     else int(version))
                self._params[v] = (params_p, params_v)
            prev = self._current
            self._current = v
            if v != prev:
                self.swaps += 1
            # retire every version that is neither current nor pinned
            # (by a session's genmove, a canary's stage, or a queued
            # request)
            dead = [o for o in self._params
                    if o != v and not self._pins.get(o)]
            for old in dead:
                del self._params[old]
            self._cond.notify_all()
        # cache eviction AFTER dropping _cond: shard locks must never
        # nest under the dispatcher condition (lock-order graph)
        self._evict_retired(dead)
        if v != prev:
            self._swap_c.inc()
        self._ver_g.set(v)
        return v

    def acquire(self, version: int | None = None) -> int:
        """Pin a version (None = current) for a whole search: the
        session's per-genmove consistency guarantee. Raises KeyError
        when a requested (e.g. rolled-back canary) version is
        retired — callers fall back to ``acquire(None)``."""
        with self._cond:
            v = self._current if version is None else int(version)
            if v not in self._params:
                raise KeyError(
                    f"params version {v} is retired "
                    f"(current {self._current})")
            self._pins[v] = self._pins.get(v, 0) + 1
            return v

    def release(self, version: int) -> None:
        """Drop one pin; a non-current version with no pins left
        retires immediately (its params become collectable, its cache
        entries evict — version numbers are REUSED, so a recycled
        number must never see a stale entry)."""
        with self._cond:
            n = self._pins.get(version, 0) - 1
            if n > 0:
                self._pins[version] = n
            else:
                self._pins.pop(version, None)
            dead = [o for o in self._params
                    if o != self._current
                    and not self._pins.get(o)]
            for old in dead:
                del self._params[old]
        self._evict_retired(dead)

    def _evict_retired(self, versions) -> None:
        """Cache-side half of retirement — called with NO lock held."""
        if self.cache is not None:
            for v in versions:
                self.cache.evict_version(v)

    def version_params(self, version: int | None = None) -> tuple:
        """The ``(params_p, params_v)`` pair of ``version`` (None =
        current) — the promotion path hands these to the facade nets
        so degraded rungs follow the swap."""
        with self._cond:
            v = self._current if version is None else int(version)
            return self._params[v]

    # ------------------------------------------------------- client

    def submit(self, states, rows: int | None = None,
               komi=None, version: int | None = None,
               keys=None) -> _Pending:
        """Enqueue a [rows]-batched GoState for evaluation. Raises
        :class:`~rocalphago_tpu.serve.admission.EvaluatorOverload`
        when the bounded queue is full (the shed path) — the caller's
        resilience ladder owns what happens next. ``komi`` (float, or
        a per-row sequence) scores this request's terminal rows under
        that komi instead of the pool's pinned one; it requires
        ``eval_komi_fn`` and only changes which compiled program the
        containing batch runs, not how it is coalesced. ``version``
        pins the request to a registered params version (None = the
        current pointer at enqueue time); the queued request holds a
        pin until it is served, so a swap cannot retire its net.
        ``keys`` rides the rows' precomputed eval signatures to the
        transposition cache (ignored without one attached)."""
        if rows is None:
            rows = int(states.board.shape[0])
        if rows > self.max_batch:
            raise ValueError(
                f"request of {rows} rows exceeds the largest "
                f"compiled batch ({self.max_batch})")
        if komi is not None and self._eval_komi_fn is None:
            raise ValueError(
                "per-request komi needs an eval_komi_fn "
                "(search.eval_batch_komi)")
        with self._cond:
            if self._stop:
                raise RuntimeError("evaluator is closed")
            v = self._current if version is None else int(version)
            if v not in self._params:
                raise KeyError(
                    f"params version {v} is retired "
                    f"(current {self._current})")
            if self.admission is not None:
                self.admission.admit_rows(self._pending_rows, rows)
            req = _Pending(states, rows, komi, version=v, keys=keys)
            self._pins[v] = self._pins.get(v, 0) + 1
            self._queue.append(req)
            self._pending_rows += rows
            self._cond.notify_all()
        return req

    def evaluate(self, states, rows: int | None = None,
                 timeout: float | None = None, komi=None,
                 version: int | None = None, keys=None):
        """Blocking submit: ``(priors, values)`` for ``states``."""
        return self.submit(states, rows, komi=komi, version=version,
                           keys=keys).result(timeout)

    def eval_direct(self, states, komi=None,
                    version: int | None = None):
        """Run the compiled eval program directly, bypassing the
        queue — warmup (compile each ladder size ahead of traffic)
        and the degraded paths that must not add queue load. ``komi``
        (f32 [B] array) selects the komi-aware program."""
        pp, pv = self.version_params(version)
        if komi is None:
            return self._eval_fn(pp, pv, states)
        return self._eval_komi_fn(pp, pv, states, komi)

    # ---------------------------------------------------- dispatcher

    def _fill_target(self) -> int:
        live = (self.admission.live()
                if self.admission is not None else 0)
        return min(self.max_batch, live) if live > 0 else \
            self.max_batch

    def _padded_size(self, rows: int) -> int:
        for s in self.batch_sizes:
            if s >= rows:
                return s
        return self.max_batch

    def _loop(self) -> None:
        while True:
            # the dispatcher-kill point: OUTSIDE the per-batch try
            # and before any request is popped, so an injected kill
            # takes the THREAD down with the queue intact — the
            # supervised restart serves the same requests
            faults.barrier("serve.dispatch", iteration=self.batches)
            with trace.span("serve.collect"), self._cond:
                while not self._queue and not self._stop:
                    self._cond.wait(0.1)
                if self._stop and not self._queue:
                    return
                # dispatch policy: fill to target, else flush when
                # the oldest request has aged out (close() can clear
                # the queue under us — re-check it each wake)
                while not self._stop and self._queue:
                    if self._pending_rows >= self._fill_target():
                        break
                    age = time.monotonic() - self._queue[0].t_submit
                    if age >= self.max_wait_s:
                        break
                    self._cond.wait(self.max_wait_s - age)
                take, total = [], 0
                while self._queue and (
                        total + self._queue[0].rows <= self.max_batch):
                    if take and (self._queue[0].version
                                 != take[0].version):
                        # never coalesce across a version edge: one
                        # device batch = one net (swap consistency);
                        # the other version's convoy is next round
                        break
                    req = self._queue.popleft()
                    take.append(req)
                    total += req.rows
                self._pending_rows -= total
                depth = self._pending_rows
            self._depth_g.set(depth)
            if take:
                self._dispatch(take, total)

    def _dispatch(self, take: list, total: int) -> None:
        import jax
        import jax.numpy as jnp

        now = time.monotonic()
        for req in take:
            self._wait_h.observe(now - req.t_submit)
        size = self._padded_size(total)
        self.batches += 1
        try:
            # the soak tests' injection point: a fault here fails
            # exactly this batch's requests, never the dispatcher
            faults.barrier("serve.eval", iteration=self.batches)
            with trace.span("serve.assemble"):
                states = take[0].states
                if len(take) > 1:
                    states = jax.tree.map(
                        lambda *xs: jnp.concatenate(xs, axis=0),
                        *[r.states for r in take])
                komi = None
                if any(r.komi is not None for r in take):
                    # a custom-komi request switches the WHOLE batch
                    # to the komi program; default-komi requests ride
                    # along at default_komi, which scores identically
                    self.komi_batches += 1
                    komi = jnp.concatenate([
                        jnp.full((r.rows,), self.default_komi,
                                 jnp.float32) if r.komi is None
                        else jnp.broadcast_to(
                            jnp.asarray(r.komi, jnp.float32),
                            (r.rows,))
                        for r in take])
                if self.cache is None and size > total:
                    # pad rows replicate row 0 (valid states, no NaN
                    # hazards) and are sliced off below — per-row
                    # programs make real rows independent of them
                    pad = size - total
                    states = jax.tree.map(
                        lambda x: jnp.concatenate(
                            [x, jnp.broadcast_to(
                                x[:1], (pad,) + x.shape[1:])],
                            axis=0),
                        states)
                    if komi is not None:
                        komi = jnp.concatenate(
                            [komi, jnp.broadcast_to(komi[:1],
                                                    (pad,))])
            with trace.span("serve.dispatch"):
                if self.cache is not None:
                    # the cached path keys, dedups and pads the
                    # unique rows itself
                    priors, values, devrows, size = self._eval_cached(
                        states, komi, take, total)
                else:
                    priors, values = self.eval_direct(
                        states, komi=komi, version=take[0].version)
                    devrows = total
        except Exception as e:  # noqa: BLE001 — fail the batch, not
            #                     the dispatcher (classified by the
            #                     sessions' resilience ladders)
            self.failures += 1
            self._fail_c.inc()
            for req in take:
                req._fail(e)
                self.release(req.version)
            return
        self.rows_total += total
        self.unique_rows_total += devrows
        self.padded_total += size
        self._rows_c.inc(total)
        if devrows:
            self._uniq_c.inc(devrows)
        if size:
            self._occ_h.observe(devrows / size)
            obs_registry.counter("serve_eval_batches_total",
                                 size=str(size)).inc()
        with trace.span("serve.deliver"):
            offset = 0
            for req in take:
                req._finish((priors[offset:offset + req.rows],
                             values[offset:offset + req.rows]))
                offset += req.rows
                self.release(req.version)

    # ------------------------------------------------- cached dispatch

    def _row_keys(self, states, take: list, total: int,
                  komi_rows: list, version: int):
        """Cache key + (symmetry) orientation per coalesced row.

        Zobrist mode: signatures come from the requests' precomputed
        device keys (one host transfer) or ``key_fn`` on the
        coalesced states; key = ``(sig_hi, sig_lo, board, komi,
        version)``. Symmetry mode: exact canonical byte keys from the
        host copies of the rows' plane-relevant fields.
        """
        import jax
        import numpy as np

        from rocalphago_tpu.serve import evalcache

        if not self.cache.symmetry:
            if all(r.keys is not None for r in take):
                sig = np.concatenate(
                    [np.asarray(jax.device_get(r.keys)).reshape(
                        r.rows, 2) for r in take], axis=0)
            else:
                sig = np.asarray(jax.device_get(
                    self._key_fn(states))).reshape(total, 2)
            keys = [(int(s[0]), int(s[1]), self.board, komi_rows[i],
                     version) for i, s in enumerate(sig)]
            return keys, None
        board_h, ages_h, steps_h, ko_h, turn_h, done_h = \
            jax.device_get((states.board, states.stone_ages,
                            states.step_count, states.ko, states.turn,
                            states.done))
        board_h = np.asarray(board_h)
        # the same age BUCKET the turns_since planes one-hot; -1
        # marks empty points so the byte key covers exactly what the
        # nets can see
        buckets = np.clip(
            np.asarray(steps_h).reshape(-1, 1) - 1
            - np.asarray(ages_h), 0, 7).astype(np.int8)
        buckets[board_h == 0] = -1
        keys, perms = [], []
        for i in range(total):
            core, t = evalcache.canonical_key(
                self.board, board_h[i], buckets[i], int(ko_h[i]),
                int(turn_h[i]), bool(done_h[i]))
            keys.append(core + (self.board, komi_rows[i], version))
            perms.append(t)
        return keys, perms

    def _eval_cached(self, states, komi, take: list, total: int):
        """The transposition-cache dispatch path: lookup → in-batch
        dedup of the misses → one padded device eval of the UNIQUE
        rows (skipped entirely when everything hits) → fan-out +
        insert. Returns ``(priors [total, A], values [total], unique
        device rows, padded size)`` with outputs as host arrays —
        bit-identical to the plain path because every returned row IS
        a device output row (fresh or cached). The gather/pad of the
        missed rows happens on HOST (one ``device_get`` of the
        coalesced states, then numpy takes) — eager per-shape device
        gathers would compile a throwaway kernel per (leaf, miss
        count) pair and make the cold path pay seconds of XLA; the
        host path costs nothing to warm, and the only device program
        is ``eval_direct`` at an already-compiled ladder size, so
        ``jax_compiles_total`` stays flat.
        """
        import jax
        import jax.numpy as jnp
        import numpy as np

        from rocalphago_tpu.serve import evalcache

        cache = self.cache
        # the cache path's fault barrier (soak: io_error@serve.cache
        # must fail only this batch, never the dispatcher)
        faults.barrier("serve.cache", iteration=self.batches)
        version = take[0].version
        if komi is None:
            komi_rows = [self.default_komi] * total
        else:
            komi_rows = [float(k) for k in
                         np.asarray(jax.device_get(komi))]
        keys, perms = self._row_keys(states, take, total, komi_rows,
                                     version)
        boards_b = None
        if cache.verify:
            bh = np.asarray(jax.device_get(states.board))
            boards_b = [bh[i].tobytes() for i in range(total)]
        out_p: list = [None] * total
        out_v = np.zeros(total, np.float32)
        miss_idx: list = []        # first occurrence of each missed key
        dup_of: list = [None] * total
        first_miss: dict = {}
        for i, key in enumerate(keys):
            hit = cache.lookup(
                key, board_bytes=boards_b[i] if boards_b else None)
            if hit is not None:
                p, v = hit
                if perms is not None:
                    p = evalcache.orient_priors(p, perms[i],
                                                self.board)
                out_p[i] = p
                out_v[i] = v
                continue
            j = first_miss.get(key)
            if j is None:
                first_miss[key] = i
                miss_idx.append(i)
            else:
                dup_of[i] = j
        unique = len(miss_idx)
        padded = 0
        if unique:
            padded = self._padded_size(unique)
            # combined gather+pad in one numpy take per leaf: the
            # index vector is pre-padded to the compiled size with
            # the first missed row (the sliced-off replicate rows the
            # plain path also pads with)
            idx = np.full(padded, miss_idx[0], np.int32)
            idx[:unique] = miss_idx
            states_h = jax.device_get(states)
            # the re-asarray matters: the jit signature cache keys on
            # Python input types, so numpy leaves would grow
            # eval_batch's cache (a counted "compile") even though
            # XLA reuses the executable — one transfer keeps
            # jax_compiles_total honest AND flat
            ustates = jax.tree.map(
                lambda x: jnp.asarray(np.asarray(x)[idx]), states_h)
            ukomi = (jnp.asarray(
                np.asarray(komi_rows, np.float32)[idx])
                if komi is not None else None)
            priors_d, values_d = self.eval_direct(
                ustates, komi=ukomi, version=version)
            pr, va = jax.device_get((priors_d, values_d))
            pr = np.asarray(pr)[:unique]
            va = np.asarray(va, np.float32)[:unique]
            for r, i in enumerate(miss_idx):
                out_p[i] = pr[r]
                out_v[i] = va[r]
                store = pr[r]
                if perms is not None:
                    store = evalcache.canonicalize_priors(
                        store, perms[i], self.board)
                cache.insert(
                    keys[i], (store, va[r]),
                    board_bytes=boards_b[i] if boards_b else None)
        saved = 0
        for i, j in enumerate(dup_of):
            if j is not None:
                out_p[i] = out_p[j]
                out_v[i] = out_v[j]
                saved += 1
        if saved:
            self.dedup_rows_saved_total += saved
            self._dedup_c.inc(saved)
        return np.stack(out_p), out_v, unique, padded

    def _fail_pending(self) -> None:
        """Parked-dispatcher cleanup: fail everything queued so no
        session blocks forever on a dead dispatcher."""
        with self._cond:
            leftovers = list(self._queue)
            self._queue.clear()
            self._pending_rows = 0
        err = self._thread.error
        for req in leftovers:
            req._fail(RuntimeError(
                f"evaluator dispatcher parked"
                f"{f' ({type(err).__name__}: {err})' if err else ''}"))
            self.release(req.version)

    # ------------------------------------------------------ lifecycle

    def drain_once(self) -> None:
        """Tests (``start=False``): run one dispatch round inline."""
        with self._cond:
            take, total = [], 0
            while self._queue and (
                    total + self._queue[0].rows <= self.max_batch):
                if take and (self._queue[0].version
                             != take[0].version):
                    break  # single-version batches (see _loop)
                req = self._queue.popleft()
                take.append(req)
                total += req.rows
            self._pending_rows -= total
        if take:
            self._dispatch(take, total)

    def close(self) -> None:
        """Stop the dispatcher; pending requests fail (closed)."""
        with self._cond:
            self._stop = True
            leftovers = list(self._queue)
            self._queue.clear()
            self._pending_rows = 0
            self._cond.notify_all()
        for req in leftovers:
            req._fail(RuntimeError("evaluator closed"))
            self.release(req.version)
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)

    # ---------------------------------------------------------- stats

    def stats(self) -> dict:
        """Probe snapshot (`rocalphago-health`'s ``serve`` block)."""
        with self._cond:
            depth = self._pending_rows
            version = self._current
            swaps = self.swaps
        from rocalphago_tpu.serve import evalcache
        return {
            "batches": self.batches,
            "komi_batches": self.komi_batches,
            "rows": self.rows_total,
            "unique_rows": self.unique_rows_total,
            "dedup_saved": self.dedup_rows_saved_total,
            "failures": self.failures,
            "queue_depth": depth,
            "params_version": version,
            "swaps": swaps,
            # unique device rows / padded rows: dedup cannot inflate
            # occupancy past 1 (the plain path has unique == rows)
            "batch_occupancy": (
                round(self.unique_rows_total / self.padded_total, 4)
                if self.padded_total else None),
            "batch_sizes": list(self.batch_sizes),
            "max_wait_us": round(self.max_wait_s * 1e6, 1),
            "cache": (self.cache.stats() if self.cache is not None
                      else evalcache.disabled_stats()),
        }
