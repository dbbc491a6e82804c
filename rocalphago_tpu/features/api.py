"""Feature-encoder API: the reference's ``Preprocess`` contract, TPU-side.

Parity: ``AlphaGo/preprocessing/preprocess.py::Preprocess``
(``Preprocess(feature_list)``, ``.state_to_tensor(state)``,
``.output_dim``; SURVEY.md §1 L1) — except tensors are NHWC
``[B, size, size, F]`` float32 (TPU conv layout) instead of the
reference's Theano NCHW, and states are the device engine's
:class:`~rocalphago_tpu.engine.jaxgo.GoState` (use
:func:`~rocalphago_tpu.engine.jaxgo.from_pygo` at host boundaries).

Observability (docs/OBSERVABILITY.md): both jitted encode programs are
compile-tracked (``jax_compiles_total{entry="encode.one"|"encode.batch"}``
— the warm-cache smoke in ``tests/test_features.py`` pins that a
repeat call compiles nothing), every call lands in the per-position
encode-cost histogram ``encode_pos_us{board=...}`` plus the
``encode_positions_total`` counter, and each call opens an ``encode``
span so ``scripts/obs_report.py`` can show where encode time goes.
Calls BLOCK on the result (``jax.block_until_ready``) — this API is
the host boundary (GTP, host MCTS waves, data conversion), whose
callers consume the tensor immediately, and blocking is what makes
the per-position microseconds honest instead of dispatch latency.
"""

from __future__ import annotations

import functools
import time

import jax
import numpy as np

from rocalphago_tpu.engine.jaxgo import GoConfig, GoState
from rocalphago_tpu.features.planes import encode
from rocalphago_tpu.features.pyfeatures import (
    DEFAULT_FEATURES,
    FEATURE_PLANES,
    LADDER_FEATURES,
    output_planes,
)
from rocalphago_tpu.obs import jaxobs, trace
from rocalphago_tpu.obs import registry as obs_registry

#: per-position encode cost edges, MICROSECONDS (the headline CPU
#: encode sits at ~10³–10⁴ µs/pos; a healthy chip should land 10¹–10²)
ENCODE_US_EDGES = (10.0, 25.0, 50.0, 100.0, 250.0, 500.0, 1000.0,
                   2500.0, 5000.0, 10000.0, 25000.0, 50000.0,
                   100000.0, 250000.0, 1000000.0)


def observe_incremental(prev_stats, new_stats, positions=None):
    """Fold one incremental-encode step's device-side stat delta into
    the process obs registry (host boundaries only — the stats vector
    lives on device as part of the ``EncodeCache`` carry and callers
    snapshot it where they already sync).

    ``prev_stats``/``new_stats`` are the cache's int32 ``stats``
    vectors (``incremental.STAT_FIELDS`` layout) before and after the
    step; ``prev_stats=None`` means a fresh cache (all-zero baseline).
    Returns ``new_stats`` as a host array for the caller to carry.
    Counters: ``encode_delta_total`` (positions through the delta
    path — the from-scratch sibling is ``encode_full_total``) and
    ``encode_incr_<field>_total`` per stat field, the inputs of
    ``scripts/obs_report.py``'s incremental hit-rate line."""
    from rocalphago_tpu.features import incremental as _incr

    # batched caches carry one stats vector per game — fold to totals
    cur = np.asarray(jax.device_get(new_stats), np.int64) \
        .reshape(-1, len(_incr.STAT_FIELDS)).sum(axis=0)
    prev = (np.zeros_like(cur) if prev_stats is None
            else np.asarray(prev_stats, np.int64))
    if positions is None:   # default: the cache's own encode count
        positions = int(cur[_incr.STAT_ENCODES]
                        - prev[_incr.STAT_ENCODES])
    if positions > 0:
        obs_registry.counter("encode_delta_total").inc(positions)
    for i, field in enumerate(_incr.STAT_FIELDS):
        if field == "encodes":
            continue        # encode_delta_total already counts these
        d = int(cur[i] - prev[i])
        if d > 0:
            obs_registry.counter(f"encode_incr_{field}_total").inc(d)
    return cur


def count_cache_reset(reason: str) -> None:
    """Count one incremental-encode cache invalidation at a host
    boundary (``encode_cache_resets_total{reason=...}``): new games,
    rewinds/undo, board switches — the explicit full-re-encode
    fallbacks of the delta path."""
    obs_registry.counter("encode_cache_resets_total",
                         reason=reason).inc()


class Preprocess:
    """Jitted encoder over a fixed feature list and board config.

    ``feature_list`` entries name plane groups (see
    ``pyfeatures.FEATURE_PLANES``); the full default set is the 48-plane
    AlphaGo encoding.

    Ladder-plane capacity knobs (all static under jit):

    - ``ladder_depth``: max chase rungs read per ladder (default 40 —
      enough to cross a 19×19 board twice).
    - ``ladder_lanes``: max candidate (move, prey) pairs examined per
      plane (default 16).
    - ``ladder_chase_slots``: max ladder chases actually *run* per
      encode (default 6). When both ladder planes are requested the
      capacity is SHARED between them (one pooled gated chase,
      capture candidates first — ``ladders.ladder_planes``); a
      single-plane encode gets the full capacity for that plane.
      Chases beyond capacity are SILENTLY dropped in board row-major
      candidate order and their cells read the conservative ``False``
      (a truncated read never asserts a capture or an escape). Real
      positions essentially never hold >4 simultaneous live chases
      per color (randomized differential bound: <0.3% of cells;
      ``tests/test_features.py``), but dense whole-board ladder
      problems can — raise this (e.g. to 16) when encoding such
      positions; cost is roughly linear in the chase loop's width.
      MEASURED DEFAULT 6: the CPU A/B (dense 19×19,
      shared/phase1=2) ran ~85 pos/s at 4 slots, ~74 at
      6, ~69 at 8 — 6 trades ~13% against the fastest setting to keep
      the POOLED capacity near the pre-overhaul per-plane total
      (4 + 4) and dense-board truncation well inside the 1% oracle
      bound (CHANGES.md PR 5).
    """

    def __init__(self, feature_list=DEFAULT_FEATURES,
                 cfg: GoConfig = GoConfig(),
                 ladder_depth: int = 40, ladder_lanes: int = 16,
                 ladder_chase_slots: int = 6):
        unknown = [f for f in feature_list if f not in FEATURE_PLANES]
        if unknown:
            raise KeyError(f"unknown features: {unknown}")
        if not feature_list:
            raise ValueError("feature_list must name at least one feature")
        self.feature_list = tuple(feature_list)
        self.cfg = cfg
        self.output_dim = output_planes(self.feature_list)
        fn = functools.partial(
            encode, cfg, features=self.feature_list,
            ladder_depth=ladder_depth, ladder_lanes=ladder_lanes,
            ladder_chase_slots=ladder_chase_slots)
        self._one = jaxobs.track("encode.one", jax.jit(fn))
        self._batch = jaxobs.track("encode.batch",
                                   jax.jit(jax.vmap(fn)))
        board = str(cfg.size)
        self._pos_us = obs_registry.histogram(
            "encode_pos_us", edges=ENCODE_US_EDGES, board=board)
        self._positions = obs_registry.counter(
            "encode_positions_total", board=board)
        self._full = obs_registry.counter("encode_full_total")
        # which plane family this encoder pays for — the ladder-free
        # configuration's footprint in a run's metrics (serve pools,
        # trainers and actors all build their encoders here, so the
        # counter says whether ANY live encoder still carries the
        # handcrafted ladder planes)
        ladder = any(f in LADDER_FEATURES for f in self.feature_list)
        obs_registry.counter(
            "encode_encoders_total",
            planes="ladder" if ladder else "noladder").inc()
        # incremental (delta) encode state — see :meth:`advance`:
        # the jitted encode_step program (built on first use), the
        # carried EncodeCache, and the last snapshot of its on-device
        # stats vector (host side, for per-call registry deltas)
        self._lad_kw = dict(ladder_depth=ladder_depth,
                            ladder_lanes=ladder_lanes,
                            ladder_chase_slots=ladder_chase_slots)
        self._delta_step = None
        self._cache = None
        self._cache_stats = None
        self._sig = None        # jitted eval-signature program (lazy)

    def _timed(self, fn, arg, batch: int) -> jax.Array:
        with trace.span("encode", board=self.cfg.size, batch=batch):
            t0 = time.monotonic()
            out = jax.block_until_ready(fn(arg))
            dt = time.monotonic() - t0
        self._pos_us.observe(dt * 1e6 / max(batch, 1))
        self._positions.inc(batch)
        return out

    def state_to_tensor(self, state: GoState) -> jax.Array:
        """One state → ``[1, size, size, F]`` float32."""
        self._full.inc()
        return self._timed(self._one, state, 1)[None]

    def state_signature(self, states: GoState) -> jax.Array:
        """Eval signatures (uint32 ``[B, 2]``) of batched states — the
        transposition key under which this encoder's planes (and so
        any NN eval of them) may be reused, carried off the engine's
        incremental hash instead of rehashed on the host
        (:func:`rocalphago_tpu.engine.jaxgo.eval_signature`). Host
        boundaries that submit to a cache-enabled
        :class:`~rocalphago_tpu.serve.evaluator.BatchingEvaluator`
        pass this as ``keys=``."""
        if self._sig is None:
            from rocalphago_tpu.engine.jaxgo import eval_signature

            self._sig = jaxobs.track(
                "encode.signature",
                jax.jit(jax.vmap(functools.partial(eval_signature,
                                                   self.cfg))))
        return self._sig(states)

    def states_to_tensor(self, states: GoState) -> jax.Array:
        """Batched states (leading axis) → ``[B, size, size, F]``."""
        batch = int(jax.tree.leaves(states)[0].shape[0])
        self._full.inc(batch)
        return self._timed(self._batch, states, batch)

    # ------------------------------------------------- incremental API

    def reset_cache(self, reason: str = "new_game") -> None:
        """Drop the incremental-encode carry (explicit full-re-encode
        fallback): call on new games, rewinds/undo, or any history
        jump the caller knows about. NOT required for correctness —
        :meth:`advance` diffs boards and invalidates stale ladder
        verdicts by footprint, so a carried cache is always
        bit-identical — but an explicit reset keeps reuse stats
        honest and is counted per ``reason``
        (``encode_cache_resets_total{reason=...}``)."""
        if self._cache is not None:
            count_cache_reset(reason)
        self._cache = None
        self._cache_stats = None

    def advance(self, state: GoState, move=None) -> jax.Array:
        """Opt-in STATEFUL encode for sequential host-boundary callers
        → ``[1, size, size, F]`` float32, bit-identical to
        :meth:`state_to_tensor` at every call.

        Successive positions share almost all of their expensive
        ladder analysis; ``advance`` carries an
        :class:`~rocalphago_tpu.features.incremental.EncodeCache`
        across calls and re-runs the pooled ladder chase only for
        lanes whose recorded read footprint intersects the board
        delta (docs/PERFORMANCE.md "Incremental encode").

        ``move=None`` (the common form): encode ``state`` itself —
        the caller already stepped the engine. ``move`` (flat index,
        ``N`` = pass): step ``state`` by ``move`` on device and encode
        the successor (:func:`incremental.encode_delta`); the caller
        keeps its own engine state.

        A cold or reset cache re-encodes from scratch by construction
        (every lane refreshes); correctness never depends on the
        cache matching the position — see :meth:`reset_cache`."""
        from rocalphago_tpu.features import incremental as _incr

        if self._delta_step is None:
            step_fn = functools.partial(
                _incr.encode_step, self.cfg,
                features=self.feature_list, **self._lad_kw)
            self._delta_step = jaxobs.track(
                "encode.delta",
                jax.jit(lambda s, c: step_fn(s, c)))
        if move is not None:
            from rocalphago_tpu.engine.jaxgo import step as _step

            state = _step(self.cfg, state,
                          jax.numpy.asarray(move, jax.numpy.int32))
        if self._cache is None:
            self._cache = _incr.init_cache(self.cfg)
        with trace.span("encode", board=self.cfg.size, batch=1,
                        delta=True):
            t0 = time.monotonic()
            planes, self._cache = self._delta_step(state, self._cache)
            planes = jax.block_until_ready(planes)
            dt = time.monotonic() - t0
        self._pos_us.observe(dt * 1e6)
        self._positions.inc()
        self._cache_stats = observe_incremental(
            self._cache_stats, self._cache.stats)
        return planes[None]
