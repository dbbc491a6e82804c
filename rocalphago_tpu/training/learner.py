"""The sharded learner: replay updates at their own cadence.

The learner half of the actor/learner split (docs/SCALE.md).
:class:`ZeroLearner` wraps ``iteration.learn`` — the replay+update
half of ``training.zero.make_zero_iteration``, whose jitted programs
carry explicit ``NamedSharding`` in/out shardings when a mesh is
supplied (params/opt-state replicated, game batch sharded on
``data``) and keep their donated carries — and consumes the replay
buffer either FIFO (:meth:`ReplayBuffer.next_batch`, the bit-exact
lockstep path) or by prioritized-recency :meth:`ReplayBuffer.sample`.

The step is compiled once (same shapes every batch) and retried via
the PR-1 machinery on transient faults — legal because ``learn``
rebuilds its donated carry from never-donated state, the same
argument that lets the synchronous loop retry whole iterations.

Metrics: ``learner_steps_total`` counter, ``learner_wait_seconds``
histogram (time blocked on the buffer per step), and the headline
``learner_idle_frac`` gauge — cumulative wait over wall time, THE
number the actor/learner split exists to push down (the synchronous
loop's equivalent is its self-play phase fraction).
"""

from __future__ import annotations

import time

import jax

from rocalphago_tpu.obs import registry, trace
from rocalphago_tpu.runtime import faults, retries


class ZeroLearner:
    """``step(state)``: take one batch from the buffer, run one
    replay update, report idleness. No thread of its own — the
    training loop drives it (cadence = as fast as data allows)."""

    def __init__(self, learn_fn, buffer, *, sample: bool = False,
                 gang=None, metrics=None, retry_attempts: int = 3):
        self._learn_fn = learn_fn
        self._buffer = buffer
        self._sample = sample
        # training.actor.DispatchGang shared with the actors: on one
        # mesh, concurrent play/learn SPMD programs with collectives
        # can deadlock at the rendezvous — each step's dispatch+fetch
        # runs as one atomic device section when a gang is supplied
        self._gang = gang
        self._metrics = metrics
        self._retry_attempts = retry_attempts
        self._wait_s = 0.0
        self._busy_s = 0.0
        self.steps = 0

    @property
    def idle_frac(self) -> float:
        """Fraction of learner wall time spent waiting for games."""
        total = self._wait_s + self._busy_s
        return self._wait_s / total if total > 0 else 0.0

    def step(self, state, timeout: float | None = None):
        """One update. Returns ``(new_state, metrics_dict, entry)``
        — metrics fetched to host floats (the fetch is the sync
        point, so busy time is honest) — or None when the buffer
        timed out / closed empty. ``metrics_dict`` gains
        ``replay_version`` (the snapshot that played the batch) and
        ``replay_staleness_s``."""
        t0 = time.monotonic()
        entry = (self._buffer.sample(timeout) if self._sample
                 else self._buffer.next_batch(timeout))
        t1 = time.monotonic()
        if entry is None:
            self._wait_s += t1 - t0
            registry.gauge("learner_idle_frac").set(self.idle_frac)
            return None
        # mid-step kill point: the batch is already TAKEN, so a kill
        # here models the worst case the failover path must ride out
        # (a consumed-but-unlearned entry; see docs/RESILIENCE.md
        # "Fleet supervision" on why lockstep refuses the ride)
        faults.barrier("learner.step", iteration=self.steps)

        def _learn_synced():
            new_state, m = retries.retry_call(
                self._learn_fn, state, entry.games,
                _retry_kwargs=dict(
                    max_attempts=self._retry_attempts,
                    logger=(self._metrics.log
                            if self._metrics else None)))
            # the fetch is the sync point: busy time is honest and
            # the devices are free once the section returns
            return new_state, {k: float(jax.device_get(v))
                               for k, v in m.items()}

        with trace.span("learner.step", version=entry.version):
            new_state, m = (self._gang.run(_learn_synced)
                            if self._gang else _learn_synced())
        t2 = time.monotonic()
        self._wait_s += t1 - t0
        self._busy_s += t2 - t1
        self.steps += 1
        m["replay_version"] = entry.version
        m["replay_staleness_s"] = round(t1 - entry.t_ingest, 3)
        registry.counter("learner_steps_total").inc()
        registry.histogram("learner_wait_seconds").observe(t1 - t0)
        registry.gauge("learner_idle_frac").set(self.idle_frac)
        return new_state, m, entry
