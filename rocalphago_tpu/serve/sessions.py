"""Session manager: N concurrent games over one compiled search.

:class:`ServePool` owns what is expensive and shared — ONE device
searcher (:func:`rocalphago_tpu.search.device_mcts.make_device_mcts`:
``prepare_sim``/``apply_sim``/``assemble_tree`` compiled once for
every session), ONE :class:`~rocalphago_tpu.serve.evaluator.
BatchingEvaluator` holding the weights, and ONE
:class:`~rocalphago_tpu.serve.admission.AdmissionController`.
:meth:`ServePool.open_session` hands out :class:`ServeSession`\\ s —
cheap per-game handles whose :class:`SessionPlayer` carries only its
own search tree.

A session's ``get_move`` is the device search driven per simulation
through the shared evaluator: ``prepare_sim`` (select + expand, batch
1) → ``evaluator.evaluate`` (the leaf coalesced with every other live
game's leaf into one device batch) → ``apply_sim`` (write + backup).
The split path is the fused in-search path by construction
(``device_mcts.SimStep``), so visits/priors cannot drift between a
pooled session and a standalone ``DeviceMCTSPlayer``.

Resilience: sessions are wrapped in the existing
:class:`~rocalphago_tpu.interface.resilient.ResilientPlayer` ladder —
an evaluator shed (:class:`~rocalphago_tpu.serve.admission.
EvaluatorOverload`, reason ``overload``) steps the session down to a
reduced-sims retry, then the raw policy net, then the rules fallback;
a hung session is abandoned by the ladder's watchdog without
touching the evaluator (other sessions keep being served — the soak
test in ``tests/test_serve.py``). The per-genmove SLO
(``slo_s`` / ``ROCALPHAGO_SERVE_SLO_MS``, or the GTP clock via
``set_move_time``) arms a :class:`~rocalphago_tpu.runtime.deadline.
Deadline` checked between simulations with a one-simulation anytime
floor — an overloaded pool serves shallower searches, never late
errors.

Komi: the pool config's komi is the pinned DEFAULT — default-komi
sessions run the exact compiled program they always did. A session
may carry its own komi (``open_session(komi=...)``, re-threaded live
by GTP ``komi`` via :meth:`ServeSession.set_komi`): komi rides the
request as DATA, and the evaluator rescored such batches through
``search.eval_batch_komi`` — one compiled program per batch size
serving every komi value, so a new komi is a new argument, not a
recompile. Rows at the default komi score identically on either
program (the rescore shifts the terminal margin by exactly ``0.0``).
"""

from __future__ import annotations

import os
import time

from rocalphago_tpu.analysis import lockcheck
from rocalphago_tpu.obs import registry as obs_registry
from rocalphago_tpu.obs import trace
from rocalphago_tpu.runtime.deadline import Deadline
from rocalphago_tpu.serve.admission import AdmissionController
from rocalphago_tpu.serve.evaluator import BatchingEvaluator

SLO_ENV = "ROCALPHAGO_SERVE_SLO_MS"


def _default_slo_s() -> float | None:
    raw = os.environ.get(SLO_ENV, "")
    return float(raw) / 1e3 if raw else None


class SessionPlayer:
    """Per-session search agent over the pool's shared programs.

    The ``get_move(pygo.GameState) -> move | None`` surface every
    wrapper in this stack expects (GTP engine, ResilientPlayer,
    tournament), plus the hooks the resilience ladder uses:
    ``n_sim``/``sim_limit`` (reduced-budget rung), ``policy`` (raw
    policy rung over the SAME net), and the deadline stats the
    health probe reads (``last_n_sim``, ``deadline_hits``,
    ``last_deadline_hit``).
    """

    def __init__(self, pool: "ServePool"):
        self.pool = pool
        self.policy = pool.policy
        self.board = pool.board
        self._cfg = pool.cfg
        self.komi: float | None = None    # None = the pool's pinned
        #   komi; a float rescales terminal leaf values per request
        self.sim_limit: int | None = None
        self.last_n_sim = None
        self.deadline_hits = 0
        self.last_deadline_hit = False
        self.genmoves = 0
        self._move_time: float | None = None
        #: canary arm hook: a session pinned to a STAGED params
        #: version searches on it every genmove; None follows the
        #: pool's current pointer. A rolled-back (retired) pin falls
        #: back to current — the game continues on the incumbent.
        self.pinned_version: int | None = None
        self.last_version: int | None = None
        import jax.numpy as jnp

        # the free-PUCT root_actions row, built once
        self._free = jnp.full((1,), -1, jnp.int32)

    @property
    def n_sim(self) -> int:
        return self.pool.n_sim

    def set_move_time(self, seconds) -> None:
        """GTP clock hook: per-move wall budget (None = no clock).
        The tighter of this and the pool SLO arms the deadline."""
        self._move_time = (None if seconds is None
                           else max(float(seconds), 0.0))

    def reset(self, reason: str = "new_game") -> None:
        """New game: sessions carry no cross-move state (trees are
        rebuilt per move — the shared-evaluator path's simplicity
        trade; subtree reuse is the standalone player's economy)."""

    def _budget_s(self) -> float | None:
        slo = self.pool.slo_s
        if self._move_time is None:
            return slo
        return self._move_time if slo is None else \
            min(self._move_time, slo)

    def _komi(self) -> float | None:
        """The komi to ride this session's requests: None (the
        pinned program) unless a custom komi differs from the pool
        default — equal values stay on the default path bit-for-bit."""
        k = self.komi
        if k is None or float(k) == float(self._cfg.komi):
            return None
        return float(k)

    def get_move(self, state):
        import jax
        import numpy as np

        from rocalphago_tpu.engine import jaxgo as _jaxgo
        from rocalphago_tpu.utils.coords import unflatten_idx

        pool = self.pool
        search = pool.search
        t0 = time.monotonic()
        self.genmoves += 1
        root = _jaxgo.from_pygo(self._cfg, state)
        roots = jax.tree.map(lambda x: x[None], root)
        eff = self.n_sim
        if self.sim_limit is not None:
            eff = max(1, min(eff, self.sim_limit))
        # the SLO/clock deadline enforces between simulations with a
        # one-simulation floor; the compile-bearing cold pool is
        # exempt (warm() — no honest wall budget spans a compile)
        deadline = Deadline.after(self._budget_s())
        enforce = not deadline.unlimited and pool.warmed
        komi = self._komi()
        # one params version per genmove: pinned for the WHOLE search
        # so a hot swap mid-search cannot mix nets within one tree; a
        # retired (rolled-back) pin falls back to the current pointer
        try:
            ver = pool.evaluator.acquire(self.pinned_version)
        except KeyError:
            self.pinned_version = None
            ver = pool.evaluator.acquire(None)
        self.last_version = ver
        try:
            # root priors through the shared evaluator, like every
            # leaf; with a transposition cache attached, the root's
            # eval signature rides along (leaf rows carry theirs via
            # SimStep.eval_keys — computed on device either way)
            keys0 = (search.eval_key(roots)
                     if pool.evaluator.cache is not None else None)
            priors0, _ = pool.evaluator.evaluate(roots, komi=komi,
                                                 version=ver,
                                                 keys=keys0)
            tree = search.assemble_tree(roots, priors0)
            # steady state is ONE device call per simulation
            # (advance_sim: apply + next prepare fused); the deadline
            # is checked between simulations, one-sim anytime floor
            # per-simulation annotations on the profiler's clock
            # (obs.trace.annotation: no record, no lock) — what this
            # session's thread was doing in a device idle gap
            with trace.annotation("session.prepare"):
                ctx = search.prepare_sim(tree, self._free)
            ran = 0
            while True:
                with trace.annotation("session.wait_eval"):
                    priors, values = pool.evaluator.evaluate(
                        ctx.eval_states, komi=komi, version=ver,
                        keys=ctx.eval_keys)
                ran += 1
                with trace.annotation("session.apply"):
                    if ran >= eff or (enforce and deadline.expired()):
                        tree = search.apply_sim(tree, ctx, priors,
                                                values)
                        break
                    tree, ctx = search.advance_sim(
                        tree, ctx, priors, values, self._free)
        finally:
            pool.evaluator.release(ver)
        visits, _ = search.root_stats(tree)
        counts = np.asarray(jax.device_get(visits))[0]
        action = int(counts.argmax())
        self.last_deadline_hit = ran < eff
        self.deadline_hits += int(self.last_deadline_hit)
        self.last_n_sim = ran
        pool.note_genmove(time.monotonic() - t0, ran)
        if action >= self._cfg.num_points or counts[action] == 0:
            return None                              # pass
        return unflatten_idx(action, self._cfg.size)


class FleetDriver:
    """Throughput drive: advance many sessions' searches in lockstep
    rounds, one convoy of cross-game leaves per simulation.

    The thread-per-session path (:class:`SessionPlayer` under the
    ladder) is the latency/robustness mode — every game its own
    thread, failures isolated per session. On a host whose per-row
    thread-handoff cost rivals the eval itself (one busy CPU core,
    hundreds of sessions) the same searches can instead be DRIVEN by
    one loop: the driver stacks the live games' independent per-game
    tree slabs on the batch axis the device search already has,
    requests every simulation's leaf rows from the shared evaluator
    as one submit (coalesced + padded exactly like any other
    client's), and steps all trees with one ``advance_sim`` call per
    round. Same trees, same eval program, same answers — only the
    host-side drive differs: per-row dispatch cost amortizes over
    the fleet instead of repeating per session.

    One driver call = one genmove for EVERY session it drives; games
    join/leave between calls (the fleet re-stacks each round). The
    pool SLO still applies — the deadline is checked between
    simulation convoys with a one-convoy anytime floor, truncating
    every driven search together.
    """

    def __init__(self, pool: "ServePool", sessions):
        self.pool = pool
        self.sessions = list(sessions)
        self.last_n_sim = None
        self.deadline_hits = 0

    def _komi_rows(self, n: int):
        """Per-row komi for a fleet convoy: None unless some driven
        session carries a custom komi (then one float per session,
        pool default where unset)."""
        default = float(self.pool.cfg.komi)
        if len(self.sessions) != n:
            return None
        ks = [getattr(getattr(s, "raw", s), "komi", None)
              for s in self.sessions]
        if all(k is None or float(k) == default for k in ks):
            return None
        return [default if k is None else float(k) for k in ks]

    def genmove_all(self, states) -> list:
        """One move for each of ``states`` (aligned with the driven
        sessions): list of ``(x, y)`` / None (pass)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from rocalphago_tpu.engine import jaxgo as _jaxgo
        from rocalphago_tpu.utils.coords import unflatten_idx

        pool = self.pool
        search = pool.search
        cfg = pool.cfg
        n = len(states)
        t0 = time.monotonic()
        roots = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[_jaxgo.from_pygo(cfg, st) for st in states])
        deadline = Deadline.after(pool.slo_s)
        enforce = not deadline.unlimited and pool.warmed
        komi = self._komi_rows(n)
        # the whole lockstep round searches ONE pinned version — the
        # same per-genmove consistency a threaded session gets
        ver = pool.evaluator.acquire(None)
        try:
            keys0 = (search.eval_key(roots)
                     if pool.evaluator.cache is not None else None)
            priors0, _ = pool.evaluator.evaluate(roots, rows=n,
                                                 komi=komi,
                                                 version=ver,
                                                 keys=keys0)
            tree = search.assemble_tree(roots, priors0)
            free = jnp.full((n,), -1, jnp.int32)
            with trace.annotation("session.prepare"):
                ctx = search.prepare_sim(tree, free)
            ran = 0
            while True:
                with trace.annotation("session.wait_eval"):
                    priors, values = pool.evaluator.evaluate(
                        ctx.eval_states, rows=n, komi=komi,
                        version=ver, keys=ctx.eval_keys)
                ran += 1
                with trace.annotation("session.apply"):
                    if ran >= pool.n_sim or (enforce
                                             and deadline.expired()):
                        tree = search.apply_sim(tree, ctx, priors,
                                                values)
                        break
                    tree, ctx = search.advance_sim(
                        tree, ctx, priors, values, free)
        finally:
            pool.evaluator.release(ver)
        visits, _ = search.root_stats(tree)
        counts = np.asarray(jax.device_get(visits))
        self.last_n_sim = ran
        self.deadline_hits += int(ran < pool.n_sim)
        dt = time.monotonic() - t0
        for _ in range(n):
            pool.note_genmove(dt, ran)
        moves = []
        for i in range(n):
            action = int(counts[i].argmax())
            if action >= cfg.num_points or counts[i][action] == 0:
                moves.append(None)
            else:
                moves.append(unflatten_idx(action, cfg.size))
        return moves

    def warm(self) -> None:
        """Compile the driver's fleet-size programs (batch = fleet)
        plus the evaluator sizes the convoys pad to."""
        import jax
        import jax.numpy as jnp

        from rocalphago_tpu.engine.jaxgo import new_states

        pool = self.pool
        n = len(self.sessions)
        roots = new_states(pool.cfg, n)
        priors, _ = pool.evaluator.evaluate(roots, rows=n)
        tree = pool.search.assemble_tree(roots, priors)
        free = jnp.full((n,), -1, jnp.int32)
        ctx = pool.search.prepare_sim(tree, free)
        pr, va = pool.evaluator.evaluate(ctx.eval_states, rows=n)
        tree, ctx = pool.search.advance_sim(tree, ctx, pr, va, free)
        pr, va = pool.evaluator.evaluate(ctx.eval_states, rows=n)
        tree = pool.search.apply_sim(tree, ctx, pr, va)
        jax.block_until_ready(pool.search.root_stats(tree)[0])
        pool.warmed = True


class ServeSession:
    """One live game's handle: the (ladder-wrapped) player plus the
    admission slot, released by :meth:`close`."""

    def __init__(self, pool: "ServePool", sid: int, player, raw):
        self.pool = pool
        self.id = sid
        self.player = player        # what callers serve moves from
        self.raw = raw              # the unwrapped SessionPlayer
        self._closed = False

    def get_move(self, state):
        return self.player.get_move(state)

    @property
    def komi(self) -> float | None:
        """This session's komi (None = the pool's pinned default)."""
        return self.raw.komi

    def set_komi(self, komi: float | None) -> None:
        """Re-thread this session's komi (the GTP ``komi`` command
        lands here): takes effect on the next genmove, no rebuild —
        komi is data to the evaluator, not part of any compiled
        shape. None restores the pool default."""
        self.raw.komi = None if komi is None else float(komi)

    @property
    def params_version(self) -> int | None:
        """The version this session's LAST genmove searched on."""
        return self.raw.last_version

    def pin_version(self, version: int | None) -> None:
        """Pin future genmoves to a staged params version (the canary
        arm assignment); None rejoins the pool's current pointer."""
        self.raw.pinned_version = (None if version is None
                                   else int(version))

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self.pool._release(self.id)

    def __enter__(self) -> "ServeSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class ServePool:
    """The serving subsystem's root object (module docstring).

    Parameters mirror :class:`~rocalphago_tpu.search.device_mcts.
    DeviceMCTSPlayer` where they overlap (``n_sim``, ``max_nodes``,
    ``c_puct``); serving knobs: ``max_sessions`` / ``queue_rows``
    (admission), ``batch_sizes`` / ``max_wait_us`` (dispatch),
    ``slo_s`` (per-genmove deadline; env ``ROCALPHAGO_SERVE_SLO_MS``),
    ``hang_timeout_s`` + ``metrics`` (threaded into each session's
    resilience ladder); ``eval_cache`` (an
    :class:`~rocalphago_tpu.serve.evalcache.EvalCache` to share, None
    to follow ``ROCALPHAGO_EVAL_CACHE``, ``False`` to force-disable
    regardless of the env — refused either way under
    ``enforce_superko``, where NN output is not a pure function of
    the eval signature).
    """

    def __init__(self, value_net, policy_net, n_sim: int = 64,
                 max_nodes: int | None = None, c_puct: float = 5.0,
                 max_sessions: int | None = None,
                 queue_rows: int | None = None,
                 batch_sizes=None, max_wait_us: float | None = None,
                 slo_s: float | None = None,
                 hang_timeout_s: float | None = None, metrics=None,
                 searcher=None, label_board: bool = False,
                 eval_cache=None):
        from rocalphago_tpu.search.device_mcts import make_device_mcts
        from rocalphago_tpu.serve import evalcache

        self.policy = policy_net
        self.value = value_net
        self.cfg = policy_net.cfg
        self.board = policy_net.board
        self.n_sim = n_sim
        self.slo_s = _default_slo_s() if slo_s is None else slo_s
        self.hang_timeout_s = hang_timeout_s
        self.metrics = metrics
        # ``searcher``: share one compiled search across pools (the
        # bench sweep re-pools per session count; jit caches live on
        # the searcher's closures, so injecting it dodges recompiles)
        self.search = searcher if searcher is not None else \
            make_device_mcts(
                self.cfg, policy_net.feature_list,
                value_net.feature_list, policy_net.module.apply,
                value_net.module.apply, n_sim=n_sim,
                max_nodes=max_nodes, c_puct=c_puct)
        # label_board: a pool inside a MultiSizePool labels its
        # admission metrics per size (serve_sessions_live{board=});
        # a standalone pool keeps the unlabelled series
        self.admission = AdmissionController(
            max_sessions, queue_rows,
            board=self.board if label_board else None)
        # transposition cache: explicit instance, or built from the
        # env master switch. Under enforce_superko the NN output is
        # NOT a pure function of the eval signature (the sensible-
        # move mask reads the hash HISTORY), so caching is refused —
        # stats()["cache"]["enabled"] shows the outcome either way.
        cache = eval_cache
        if cache is None and evalcache.cache_enabled():
            cache = evalcache.EvalCache()
        if cache is False:      # explicit opt-out, overrides the env
            cache = None        # switch (the bench A/B's OFF arm)
        if self.cfg.enforce_superko:
            cache = None
        self.eval_cache = cache
        self.evaluator = BatchingEvaluator(
            self.search.eval_batch, policy_net.params, value_net.params,
            batch_sizes=batch_sizes, max_wait_us=max_wait_us,
            admission=self.admission,
            eval_komi_fn=getattr(self.search, "eval_batch_komi", None),
            default_komi=self.cfg.komi, cache=cache,
            key_fn=getattr(self.search, "eval_key", None),
            board=self.board)
        self.warmed = False
        self._lock = lockcheck.make_lock("ServePool._lock")
        self._sessions: dict = {}         # guarded-by: self._lock
        self._next_id = 0                 # guarded-by: self._lock
        self._move_h = obs_registry.histogram("serve_genmove_seconds")
        self._sims_c = obs_registry.counter("serve_session_sims_total")

    # ------------------------------------------------------- sessions

    def open_session(self, resilient: bool = True,
                     reduced_sims: int | None = None,
                     komi: float | None = None) -> ServeSession:
        """Admit one game (:class:`~rocalphago_tpu.serve.admission.
        AdmissionError` at capacity). ``resilient=False`` returns the
        raw player — benchmarks measuring the search alone. ``komi``
        gives THIS session its own komi (module docstring); None is
        the pool's pinned default."""
        self.admission.admit_session()
        raw = SessionPlayer(self)
        raw.komi = None if komi is None else float(komi)
        player = raw
        if resilient:
            from rocalphago_tpu.interface.resilient import (
                ResilientPlayer,
            )

            player = ResilientPlayer(
                raw, metrics=self.metrics, reduced_sims=reduced_sims,
                hang_timeout_s=self.hang_timeout_s)
        with self._lock:
            sid = self._next_id
            self._next_id += 1
            sess = ServeSession(self, sid, player, raw)
            self._sessions[sid] = sess
        return sess

    def _release(self, sid: int) -> None:
        with self._lock:
            if self._sessions.pop(sid, None) is None:
                return
        self.admission.release_session()

    def note_genmove(self, dt: float, sims: int) -> None:
        self._move_h.observe(dt)
        self._sims_c.inc(sims)

    def driver(self, sessions) -> FleetDriver:
        """The lockstep throughput drive over ``sessions`` (see
        :class:`FleetDriver`)."""
        return FleetDriver(self, sessions)

    # -------------------------------------------------------- rollout

    @property
    def params_version(self) -> int:
        return self.evaluator.params_version

    def set_params(self, params_p=None, params_v=None,
                   version: int | None = None) -> int:
        """Hot-swap the pool's net: install ``(params_p, params_v)``
        (or promote a staged ``version``) as the current pair — a
        pointer flip at the evaluator's fixed compiled shapes, live
        sessions keep playing, in-flight genmoves finish on the
        version they pinned. The facade nets follow so the degraded
        rungs (raw policy fallback) serve the same weights."""
        v = self.evaluator.set_params(params_p, params_v,
                                      version=version)
        pp, pv = self.evaluator.version_params(v)
        self.policy.params = pp
        self.value.params = pv
        return v

    def stage_params(self, params_p, params_v,
                     version: int | None = None) -> int:
        """Register a candidate pair WITHOUT flipping current (the
        canary's arm): sessions reach it only via
        :meth:`ServeSession.pin_version`."""
        return self.evaluator.add_version(params_p, params_v,
                                          version=version)

    def promote_version(self, version: int) -> int:
        """Full rollout of a staged version: flip current to it and
        drop the stage pin."""
        v = self.set_params(version=version)
        self.evaluator.release(v)
        return v

    def discard_version(self, version: int) -> None:
        """Roll a staged version back: drop the stage pin so it
        retires once in-flight pinned searches finish; sessions
        pinned to it fall back to current on their next genmove."""
        self.evaluator.release(version)

    # --------------------------------------------------------- warmup

    def warm(self, sizes=None) -> None:
        """Compile ahead of traffic: the per-session programs
        (prepare/apply/assemble/root_stats at batch 1) and the
        evaluator's ladder of padded sizes — so the first live
        genmove never pays XLA, and SLO enforcement (armed only on a
        warm pool) is honest from the first served move."""
        import jax

        from rocalphago_tpu.engine.jaxgo import new_states

        for size in (sizes or self.evaluator.batch_sizes):
            out = self.evaluator.eval_direct(
                new_states(self.cfg, size))
            jax.block_until_ready(out[0])
        roots = new_states(self.cfg, 1)
        if self.eval_cache is not None and \
                hasattr(self.search, "eval_key"):
            # the cached genmove path signs the root on device —
            # compile it here so jax_compiles_total stays flat from
            # the first served move (fleet-size signing compiles in
            # FleetDriver.warm via its keyless evaluate call)
            jax.block_until_ready(self.search.eval_key(roots))
        priors, _ = self.evaluator.eval_direct(roots)
        tree = self.search.assemble_tree(roots, priors)
        import jax.numpy as jnp

        free = jnp.full((1,), -1, jnp.int32)
        ctx = self.search.prepare_sim(tree, free)
        pr, va = self.evaluator.eval_direct(ctx.eval_states)
        tree, ctx = self.search.advance_sim(tree, ctx, pr, va, free)
        pr, va = self.evaluator.eval_direct(ctx.eval_states)
        tree = self.search.apply_sim(tree, ctx, pr, va)
        jax.block_until_ready(self.search.root_stats(tree)[0])
        self.warmed = True

    # ------------------------------------------------------ lifecycle

    def close(self) -> None:
        with self._lock:
            sessions = list(self._sessions.values())
        for sess in sessions:
            sess.close()
        self.evaluator.close()

    def __enter__(self) -> "ServePool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ---------------------------------------------------------- stats

    def stats(self) -> dict:
        """The probes' ``serve`` block (schema: docs/SERVING.md):
        live sessions, queue depth, batch occupancy, sheds — the
        fields a load balancer keys health on."""
        adm = self.admission.stats()
        ev = self.evaluator.stats()
        cs = ev["cache"]
        return {
            "sessions": {
                "live": adm["live_sessions"],
                "max": adm["max_sessions"],
                "rejects": adm["session_rejects"],
            },
            "queue": {
                "depth": ev["queue_depth"],
                "rows_bound": adm["queue_rows"],
                "sheds": adm["queue_sheds"],
            },
            "evaluator": {
                "batches": ev["batches"],
                "komi_batches": ev["komi_batches"],
                "rows": ev["rows"],
                "unique_rows": ev["unique_rows"],
                "dedup_saved": ev["dedup_saved"],
                "failures": ev["failures"],
                "batch_occupancy": ev["batch_occupancy"],
                "batch_sizes": ev["batch_sizes"],
                "max_wait_us": ev["max_wait_us"],
            },
            "cache": {
                "enabled": cs["enabled"],
                "entries": cs["entries"],
                "capacity": cs["capacity"],
                "hits": cs["hits"],
                "misses": cs["misses"],
                "evictions": cs["evictions"],
                "collisions": cs["collisions"],
                "hit_rate": cs["hit_rate"],
            },
            "params": {
                "version": ev["params_version"],
                "swaps": ev["swaps"],
            },
            "board": self.board,
            "komi_default": float(self.cfg.komi),
            "slo_ms": (None if self.slo_s is None
                       else round(self.slo_s * 1e3, 3)),
            "n_sim": self.n_sim,
            "warmed": self.warmed,
        }
