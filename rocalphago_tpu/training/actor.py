"""Self-play actors: paced producers feeding the replay buffer.

The actor half of the actor/learner split (docs/SCALE.md). Each
:class:`SelfplayActor` is a thread around ``iteration.play`` (the
self-play-only half ``training.zero.make_zero_iteration`` exposes)
that repeatedly: polls the :class:`ParamsPublisher` for a params
snapshot, walks its own rng chain with
:func:`rocalphago_tpu.training.zero.next_keys`, plays one batch of
games, and streams the host copy into the
:class:`rocalphago_tpu.data.replay.ReplayBuffer`.

Two pacing modes:

- **lockstep** (``lockstep=True``, 1 actor): game ``k`` waits for
  published version ``k`` and the rng chain starts from the trainer
  state's own rng — with a FIFO consumer this reproduces the
  synchronous loop bit-for-bit (the bit-exactness A/B `run_training
  --actor-learner` keeps).
- **free-run** (default): actors always play the latest snapshot;
  staleness is bounded by the buffer's pacing (blocking ``put``) and
  reported by its staleness histogram.

Preemption tolerance: each game is wrapped in the PR-1 retry
machinery (``runtime.retries``) — safe because ``play`` donates
nothing the caller can see — and a non-transient failure parks the
actor with ``error`` set instead of killing the process. Under
``runtime.supervisor`` the park becomes a death report: the
supervisor resurrects free-run actors from the factory (fresh rng
branch, in-flight game discarded) and REFUSES lockstep restarts
(docs/RESILIENCE.md "Fleet supervision"). Each game boundary
declares the ``actor.game`` fault barrier, and waits (params,
paced put) are tagged ``actor:<name>`` in the watchdog's
``waiting_on`` registry so stalls name the blocked fleet member.

Metrics: ``actor_games_total{actor=}`` counter,
``actor_params_version`` gauge; each game runs under an
``actor.play`` span.
"""

from __future__ import annotations

import os
import threading
import time

import jax

from rocalphago_tpu.analysis import lockcheck
from rocalphago_tpu.obs import registry, trace
from rocalphago_tpu.runtime import faults, retries, watchdog
from rocalphago_tpu.training.zero import next_keys

POLL_ENV = "ROCALPHAGO_ACTOR_POLL_S"

#: the rollout spill pointer a serving process watches
#: (docs/ROLLOUT.md): ``{"version", "policy", "value"}`` next to the
#: checkpoint pair it names, atomically replaced on each publish
SPILL_NAME = "rollout.json"


def default_poll_s() -> float:
    """Wait-slice for params/buffer waits (responsiveness of stop)."""
    return float(os.environ.get(POLL_ENV, "0.5"))


def write_spill(dir_path: str, *, version: int, policy_path: str,
                value_path: str) -> str:
    """Atomically write ``dir_path/rollout.json`` naming the latest
    gated checkpoint pair — the cross-process half of the rollout
    path: a :class:`~rocalphago_tpu.rollout.hotswap.SpillWatcher` (or
    a restarted serving process) reads it to pick up the promoted
    version without sharing a process with training."""
    from rocalphago_tpu.runtime.atomic import atomic_write_json

    path = os.path.join(dir_path, SPILL_NAME)
    atomic_write_json(path, {
        "version": int(version),
        "policy": os.path.basename(policy_path),
        "value": os.path.basename(value_path),
    })
    return path


def read_spill(dir_path: str) -> dict | None:
    """The current spill pointer (None when absent/partial — the
    atomic replace means a reader never sees a torn file)."""
    import json

    try:
        with open(os.path.join(dir_path, SPILL_NAME),
                  encoding="utf-8") as f:
            spill = json.load(f)
    except (OSError, ValueError):
        return None
    if not all(k in spill for k in ("version", "policy", "value")):
        return None
    return spill


class DispatchGang:
    """Serializes whole device sections between threads sharing one
    multi-device mesh.

    Two concurrently executing SPMD programs that both contain
    collectives over the SAME devices can interleave their per-device
    executions in different orders and deadlock at the collective
    rendezvous — each program holds some device queues while waiting
    for the rest (observed as an XLA-CPU ``AllReduceParticipantData
    ... may be stuck`` hang; the hazard is generic to any shared
    single-controller device set). The gang makes each participant's
    dispatch-to-fetch section atomic: one ``play`` or one learner
    step owns the devices at a time. Nothing real is lost on a shared
    mesh — the programs were time-sharing the same chips anyway; what
    the actor/learner split still buys is learner cadence decoupled
    from game cadence (sample mode) and host-side overlap (encode,
    buffer ops, spill I/O all run outside the gang).
    """

    def __init__(self, name: str = "DispatchGang._lock"):
        self._lock = lockcheck.make_lock(name)

    def run(self, fn, *args, **kwargs):
        """Run ``fn`` — a dispatch+sync section: jitted calls plus
        the ``device_get`` that retires them — holding the gang."""
        with self._lock:
            # the callback IS the protected resource (an atomic
            # device section), not a re-entrancy hazard: sections
            # never touch the gang from inside
            return fn(*args, **kwargs)  # jaxlint: disable=callback-under-lock


class ParamsPublisher:
    """Versioned params snapshot actors poll between games.

    The learner (or the gate, after a promotion) calls
    :meth:`publish`; actors block in :meth:`wait_version` until the
    version they need exists. Snapshots are jax arrays shared by
    reference — publish is O(1), no copies.
    """

    def __init__(self, spill_dir: str | None = None):
        self._cond = lockcheck.make_condition("ParamsPublisher._cond")
        self._version = -1     # guarded-by: self._cond
        self._policy = None    # guarded-by: self._cond
        self._value = None     # guarded-by: self._cond
        #: directory to mirror each publish into as an on-disk
        #: checkpoint pair + rollout.json pointer (None = in-process
        #: only); lets a serving process in ANOTHER process follow
        self.spill_dir = spill_dir

    def publish(self, policy_params, value_params,
                version: int | None = None) -> int:
        """Install a snapshot; bumps the version (or sets it
        explicitly — the lockstep path pins version = iteration)."""
        with self._cond:
            self._version = (self._version + 1 if version is None
                             else int(version))
            self._policy = policy_params
            self._value = value_params
            v = self._version
            self._cond.notify_all()
        registry.gauge("actor_params_version").set(v)
        if self.spill_dir is not None:
            self._spill(v, policy_params, value_params)
        return v

    def _spill(self, version: int, policy_params,
               value_params) -> None:
        """Mirror one publish to disk: serialize the pair (flax
        msgpack, host copies), then atomically flip rollout.json at
        it. Pointer-last ordering means a watcher that reads the
        pointer always finds both files; older spill pairs are pruned
        best-effort once the pointer has moved on."""
        from flax import serialization

        from rocalphago_tpu.runtime.atomic import atomic_write_bytes

        d = self.spill_dir
        os.makedirs(d, exist_ok=True)
        ppath = os.path.join(d, f"spill.{version:05d}.policy.msgpack")
        vpath = os.path.join(d, f"spill.{version:05d}.value.msgpack")
        atomic_write_bytes(ppath, serialization.to_bytes(
            jax.device_get(policy_params)))
        atomic_write_bytes(vpath, serialization.to_bytes(
            jax.device_get(value_params)))
        write_spill(d, version=version, policy_path=ppath,
                    value_path=vpath)
        for name in sorted(os.listdir(d)):
            if (name.startswith("spill.") and name.endswith(".msgpack")
                    and not name.startswith(f"spill.{version:05d}.")):
                try:
                    os.remove(os.path.join(d, name))
                except OSError:
                    pass  # a concurrent reader may hold it open

    def get(self):
        """Latest ``(version, policy_params, value_params)``;
        version -1 before the first publish."""
        with self._cond:
            return self._version, self._policy, self._value

    def wait_version(self, min_version: int,
                     timeout: float | None = None):
        """Block until a snapshot with version >= ``min_version`` is
        published; returns ``(version, pp, vp)`` or None on
        timeout."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self._cond:
            while self._version < min_version:
                rem = (None if deadline is None
                       else deadline - time.monotonic())
                if rem is not None and rem <= 0:
                    return None
                self._cond.wait(rem)
            return self._version, self._policy, self._value


class SelfplayActor:
    """A producer thread streaming finished game batches into the
    replay buffer (module docstring for the pacing modes).

    ``play_fn`` is ``iteration.play``; ``rng`` is the packed rng bits
    the chain starts from (the trainer state's own rng in lockstep, a
    ``fold_in``-derived per-actor key otherwise); ``games`` bounds
    how many batches to produce (None = until :meth:`stop`).
    """

    def __init__(self, play_fn, publisher: ParamsPublisher, buffer,
                 rng, *, name: str = "actor0", lockstep: bool = False,
                 start_index: int = 0, games: int | None = None,
                 pace: bool = True, poll_s: float | None = None,
                 gang: DispatchGang | None = None, metrics=None,
                 on_progress=None):
        self._play_fn = play_fn
        self._gang = gang
        self._publisher = publisher
        self._buffer = buffer
        self._rng = rng
        self.name = name
        self.lockstep = lockstep
        self._start_index = start_index
        self._games = games
        self._pace = pace
        self._poll_s = default_poll_s() if poll_s is None else poll_s
        self._metrics = metrics
        self._on_progress = on_progress   # supervisor heartbeat
        self.games_played = 0
        self.error: BaseException | None = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name=f"selfplay-{name}", daemon=True)

    # ------------------------------------------------------ lifecycle

    def start(self) -> "SelfplayActor":
        self._thread.start()
        return self

    def stop(self, timeout: float = 30.0) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout)

    def alive(self) -> bool:
        return self._thread.is_alive()

    # ------------------------------------------------------ producer

    def _run(self) -> None:
        rng = self._rng
        index = self._start_index
        while not self._stop.is_set():
            if (self._games is not None
                    and index - self._start_index >= self._games):
                break
            # lockstep: game k is played by the version-k snapshot
            # (exactly the pair the synchronous loop would use);
            # free-run: whatever is freshest
            need = index if self.lockstep else 0
            with watchdog.waiting_on(f"actor:{self.name}"):
                got = self._publisher.wait_version(need, self._poll_s)
            if got is None:
                continue
            version, pp, vp = got
            rng, game_key = next_keys(rng)

            def _play_synced():
                # dispatch AND fetch inside one gang section — the
                # devices are only free again once the host copy
                # retires every program the game dispatched
                games = retries.retry_call(
                    self._play_fn, pp, vp, game_key,
                    _retry_kwargs=dict(
                        max_attempts=3, base_delay=0.5,
                        logger=(self._metrics.log
                                if self._metrics else None)))
                return jax.device_get(games)

            try:
                faults.barrier("actor.game", iteration=index)
                with trace.span("actor.play", actor=self.name,
                                game=index):
                    host = (self._gang.run(_play_synced)
                            if self._gang else _play_synced())
            except BaseException as e:  # noqa: BLE001 — park, report
                self.error = e
                if self._metrics is not None:
                    self._metrics.log(
                        "actor_error", actor=self.name,
                        error=f"{type(e).__name__}: {e}")
                break
            while not self._stop.is_set():
                with watchdog.waiting_on(f"actor:{self.name}"):
                    accepted = self._buffer.put(
                        host, version=version, block=self._pace,
                        timeout=self._poll_s)
                if accepted:
                    registry.counter("actor_games_total",
                                     actor=self.name).inc()
                    self.games_played += 1
                    index += 1
                    if self._on_progress is not None:
                        self._on_progress()
                    break
                if self._buffer.closed:
                    self._stop.set()   # drain finished — park
                    break
