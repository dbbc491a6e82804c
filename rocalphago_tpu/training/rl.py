"""REINFORCE policy training over on-device self-play.

Parity: ``AlphaGo/training/reinforcement_policy_trainer.py::run_training``
(lockstep game batches learner-vs-sampled-past-self, per-game gradient
of the log-likelihood of played moves scaled by the ±1 outcome, an
opponent pool of past checkpoints sampled uniformly, ``--game-batch 20
--policy-temp --move-limit 500 --save-every``, ``metadata.json`` resume;
SURVEY.md §2 "RL policy trainer", §3.2).

TPU-native design — the reference's two host hot loops (Python
``do_move`` and per-state featurization, SURVEY.md §3.2) are gone:

* games are played by :func:`rocalphago_tpu.search.selfplay.play_games`
  — the whole encode → forward → sample → rules-step loop is one
  ``lax.scan`` on device;
* the REINFORCE gradient needs the states the learner saw, which the
  game scan does not materialize (storing ``[T, B, 19, 19, 48]`` planes
  would blow HBM). Instead the iteration *replays* the recorded actions
  through the engine in a second scan, accumulating a per-ply policy
  gradient into a params-shaped carry — constant memory in game length,
  and only the learner's half-batch is re-forwarded per ply;
* no custom sign-flipped SGD (the reference's Keras hack): the ±z
  weight is just a per-sample coefficient on the log-likelihood loss,
  and plain ``optax.sgd`` applies the one accumulated update;
* the games batch axis carries a ``data``-mesh sharding constraint, so
  on a multi-chip mesh XLA shards the whole game scan and all-reduces
  the gradient over ICI.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import glob
import os
import sys
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax

from rocalphago_tpu.engine import jaxgo
from rocalphago_tpu.io.checkpoint import (
    MetadataWriter,
    TrainCheckpointer,
    pack_rng,
    unpack_rng,
)
from rocalphago_tpu.io.metrics import MetricsLogger
from rocalphago_tpu.models.nn_util import NeuralNetBase
from rocalphago_tpu.obs import jaxobs, scopes, trace
from rocalphago_tpu.obs import registry as obs_registry
from rocalphago_tpu.runtime.pipeline import ChunkPipeline
from rocalphago_tpu.parallel import mesh as meshlib
from rocalphago_tpu.runtime import faults, retries
from rocalphago_tpu.search.selfplay import (
    make_selfplay_chunked,
    play_games,
    sensible_mask,
)
from rocalphago_tpu.features.planes import batched_encoder


@dataclasses.dataclass
class RLConfig:
    """Flat, JSON-serializable stage config (SURVEY.md §5 "Config")."""

    model_json: str = ""
    out_dir: str = ""
    learning_rate: float = 0.001
    game_batch: int = 20          # reference default; TPU runs use 128+
    iterations: int = 100
    save_every: int = 10
    policy_temp: float = 0.67
    move_limit: int = 500
    seed: int = 0
    num_devices: int | None = None
    chunk: int = 0    # >0: plies per compiled segment (watchdog-safe
    #                   chunked iteration; 0 = one monolithic program)
    komi: float | None = None   # None = board size's standard
    #                   (engine.jaxgo.default_komi; VERDICT r4 weak 2)


class RLState(NamedTuple):
    params: dict
    opt_state: tuple
    iteration: jax.Array  # int32 []
    rng: jax.Array        # uint32 key data


def _make_replay_ply(cfg: jaxgo.GoConfig, features: tuple, apply_fn,
                     batch: int, temperature: float):
    """Shared REINFORCE replay body: one ply of re-stepping the
    recorded game while accumulating the z-weighted policy gradient
    into a params-shaped carry. Used by both the monolithic iteration
    (one scan) and the chunked iteration (host-driven segments)."""
    n = cfg.num_points
    half = batch // 2
    enc = batched_encoder(cfg, features)
    vsens = jax.vmap(functools.partial(sensible_mask, cfg))
    vstep = jax.vmap(functools.partial(jaxgo.step, cfg))

    def ply(params, z, carry, xs):
        states, grads = carry
        t, actions_t, live_t = xs
        # the learner moves games [0:half] on even plies and games
        # [half:batch] on odd plies (selfplay color split)
        start = jnp.where((t % 2) == 0, 0, half)
        take = lambda a: lax.dynamic_slice_in_dim(a, start, half)  # noqa: E731
        half_states = jax.tree.map(take, states)
        planes = enc(half_states)
        sens = vsens(half_states)
        acts = take(actions_t)
        w = (take(z) * take(live_t)
             * (acts < n).astype(jnp.float32))

        def loss_fn(p):
            logits = apply_fn(p, planes)
            with jax.named_scope(scopes.TRAIN_LOSS):
                neg = jnp.finfo(logits.dtype).min
                masked = jnp.where(sens, logits / temperature, neg)
                logp = jax.nn.log_softmax(masked, axis=-1)
                lp = jnp.take_along_axis(
                    logp, jnp.minimum(acts, n - 1)[:, None],
                    axis=1)[:, 0]
                return -(w * lp).sum() / batch

        grads = jax.tree.map(jnp.add, grads, jax.grad(loss_fn)(params))
        return (vstep(states, actions_t), grads)

    return ply


def _learner_z(winners: jax.Array, half: int) -> jax.Array:
    """Outcome from the LEARNER's perspective: the learner (net A) is
    Black in games [0:half], White in the rest."""
    w = winners.astype(jnp.float32)
    return jnp.concatenate([w[:half], -w[half:]])


def _update_and_metrics(tx, state: RLState, grads, z, num_moves, key):
    """Shared SGD apply + metrics assembly for both iterations."""
    with jax.named_scope(scopes.TRAIN_UPDATE):
        updates, opt_state = tx.update(
            grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
    # win rate over DECIDED games (draws excluded, reported
    # separately) — counting draws as losses biases the learner
    # win-rate low on integer-komi configs
    wins = (z > 0).sum()
    decided = (z != 0).sum()
    metrics = {
        "win_rate": jnp.where(decided > 0,
                              wins / jnp.maximum(decided, 1), 0.5),
        "draw_rate": (z == 0).mean(),
        "mean_moves": num_moves.astype(jnp.float32).mean(),
    }
    new = RLState(params, opt_state, state.iteration + 1,
                  pack_rng(key))
    return new, metrics


def make_rl_iteration(cfg: jaxgo.GoConfig, features: tuple, apply_fn,
                      tx, batch: int, move_limit: int,
                      temperature: float, mesh=None):
    """Pure ``(RLState, opp_params) -> (RLState, metrics)`` — one full
    REINFORCE iteration: play a game batch, accumulate the z-weighted
    policy gradient by replay, apply one SGD update."""
    if batch % 2:
        raise ValueError(f"game_batch must be even, got {batch}")
    half = batch // 2
    replay_ply = _make_replay_ply(cfg, features, apply_fn, batch,
                                  temperature)

    def iteration(state: RLState, opp_params):
        key = unpack_rng(state.rng)
        key, game_key = jax.random.split(key)
        params = state.params

        result = play_games(cfg, features, apply_fn, params, apply_fn,
                            opp_params, game_key, batch, move_limit,
                            temperature)
        z = _learner_z(result.winners, half)

        states0 = jaxgo.new_states(cfg, batch)
        if mesh is not None:
            states0 = lax.with_sharding_constraint(
                states0, meshlib.data_sharding(mesh))
        zero = jax.tree.map(jnp.zeros_like, params)
        (_, grads), _ = lax.scan(
            lambda c, xs: (replay_ply(params, z, c, xs), None),
            (states0, zero),
            (jnp.arange(result.actions.shape[0]), result.actions,
             result.live.astype(jnp.float32)))

        return _update_and_metrics(tx, state, grads, z,
                                   result.num_moves, key)

    return iteration


def make_rl_iteration_chunked(cfg: jaxgo.GoConfig, features: tuple,
                              apply_fn, tx, batch: int, move_limit: int,
                              temperature: float, chunk: int,
                              mesh=None):
    """Chunked ``(RLState, opp_params) -> (RLState, metrics)`` — the
    same REINFORCE iteration as :func:`make_rl_iteration`, but no
    single device program runs longer than one ``chunk``-ply segment.

    Why: the monolithic iteration is a full ``move_limit``-ply game
    scan PLUS an equally long replay scan with backward passes in ONE
    program — nothing on the host can check a deadline, a drain
    request or the watchdog until it returns. Here the game phase reuses :func:`make_selfplay_chunked` (host-driven
    segments, device-resident states) and the replay+gradient phase is
    its own segmented scan with the (states, grads) carry device-
    resident between segments. The math is IDENTICAL to the monolithic
    iteration — same per-ply op order, same gradient accumulation
    order, same rng split chain — verified bit-identical in
    ``tests/test_rl_trainer.py``.
    """
    if batch % 2:
        raise ValueError(f"game_batch must be even, got {batch}")
    half = batch // 2
    runner = make_selfplay_chunked(
        cfg, features, apply_fn, apply_fn, batch, move_limit,
        chunk=chunk, temperature=temperature, mesh=mesh)
    replay_ply = _make_replay_ply(cfg, features, apply_fn, batch,
                                  temperature)

    @jaxobs.track("rl.replay_segment")
    @functools.partial(jax.jit, static_argnames=("length",),
                       donate_argnums=(2, 3))
    def replay_segment(params, z, states, grads, actions, live,
                       offset, length):
        # states + grad accumulator are DONATED: both are
        # loop-internal (built fresh each iteration, so the
        # iteration-level retry wrapper stays valid) and donation
        # keeps pipelined dispatch from doubling the params-shaped
        # accumulator
        (states, grads), _ = lax.scan(
            lambda c, xs: (replay_ply(params, z, c, xs), None),
            (states, grads),
            (offset + jnp.arange(length), actions, live))
        return states, grads

    replay_segment.donates_buffers = True

    update = jax.jit(functools.partial(_update_and_metrics, tx))

    def iteration(state: RLState, opp_params):
        key = unpack_rng(state.rng)
        key, game_key = jax.random.split(key)
        params = state.params

        # phase spans (see training.zero.iteration for the async-
        # dispatch caveat: the caller's metrics fetch is the sync)
        with trace.span("rl.play"):
            result = runner(params, opp_params, game_key)
        z = _learner_z(result.winners, half)

        states = jaxgo.new_states(cfg, batch)
        if mesh is not None:
            states = meshlib.shard_batch(mesh, states)
        grads = jax.tree.map(jnp.zeros_like, params)
        live = result.live.astype(jnp.float32)
        plies = result.actions.shape[0]
        # pipelined dispatch (runtime.pipeline): paces the host to
        # `depth` in-flight segments and records gap/occupancy
        pipe = ChunkPipeline(runner="rl.replay")
        with trace.span("rl.replay", plies=plies):
            for offset in range(0, plies, chunk):
                length = min(chunk, plies - offset)
                states, grads = replay_segment(
                    params, z, states, grads,
                    result.actions[offset:offset + length],
                    live[offset:offset + length],
                    jnp.int32(offset), length)
                # fresh scalar handle — the next segment donates
                # `states`, so no leaf of it may be the handle
                pipe.push(states.turn.sum())
            pipe.finish()

        with trace.span("rl.update"):
            return update(state, grads, z, result.num_moves, key)

    return iteration


class OpponentPool:
    """Directory of past learner snapshots, sampled uniformly each
    iteration (reference opponent-pool semantics)."""

    def __init__(self, directory: str, net: NeuralNetBase,
                 write: bool = True):
        self.directory = directory
        self.net = net
        self.write = write
        os.makedirs(directory, exist_ok=True)
        if write and not self.snapshots():
            self.add(net.params, 0)

    def snapshots(self) -> list:
        return sorted(glob.glob(
            os.path.join(self.directory, "opponent.*.flax.msgpack")))

    def add(self, params, iteration: int) -> None:
        if not self.write:
            return
        self.net.params = jax.device_get(params)
        self.net.save_weights(os.path.join(
            self.directory, f"opponent.{iteration:05d}.flax.msgpack"))

    def sample(self, seed, iteration: int,
               save_every: int | None = None):
        """Uniform draw over the current pool, seeded by (seed,
        iteration) — stateless, so an interrupted-and-resumed run makes
        the same choices as an uninterrupted one with no RNG replay.
        ``self.net.params`` is used only as a read-only deserialization
        template (never mutated — no scratch-slot reentrancy hazard).

        With ``save_every`` the candidate set is RECONSTRUCTED from the
        save schedule (snapshots land at iterations 0, save_every,
        2·save_every, …) instead of listing the directory — every host
        of a multi-host run computes the identical choice even when
        shared-filesystem listings lag the coordinator's writes; the
        read then waits briefly for the chosen file to become visible.
        Without it (single-process default) the directory listing is
        the candidate set."""
        from flax import serialization

        rng = np.random.default_rng(
            np.random.SeedSequence([seed, iteration]))
        if save_every:
            iters = [0] + [k * save_every for k in
                           range(1, iteration // save_every + 1)]
            pick = iters[rng.integers(len(iters))]
            path = os.path.join(
                self.directory, f"opponent.{pick:05d}.flax.msgpack")
            deadline = time.time() + (30.0 if jax.process_count() > 1
                                      else 0.0)
            while not os.path.exists(path):
                if time.time() >= deadline:
                    raise FileNotFoundError(
                        f"opponent snapshot {path} not visible. "
                        "Multi-host: the coordinator writes snapshots; "
                        "a shared filesystem is required. Resumed run: "
                        "--save-every must match the value the out_dir "
                        "was populated with (the candidate set is "
                        "reconstructed from the save schedule, not the "
                        "directory listing, so every host agrees)")
                time.sleep(0.5)
        else:
            paths = self.snapshots()
            if not paths:
                raise FileNotFoundError(
                    f"no opponent snapshots in {self.directory}")
            path = paths[rng.integers(len(paths))]
        with open(path, "rb") as f:
            params = serialization.from_bytes(self.net.params, f.read())
        return params, os.path.basename(path)


class RLTrainer:
    """Wires learner + opponent pool + mesh into the iteration loop."""

    def __init__(self, cfg: RLConfig, net: NeuralNetBase | None = None):
        self.cfg = cfg
        self.net = net or NeuralNetBase.load_model(cfg.model_json)
        self.mesh = meshlib.make_mesh(cfg.num_devices)
        os.makedirs(cfg.out_dir, exist_ok=True)

        tx = optax.sgd(cfg.learning_rate)
        rep = meshlib.replicated(self.mesh)
        # scoring komi: per-board-size default unless overridden (the
        # net spec's GoConfig always carries the 19x19 value)
        game_cfg = dataclasses.replace(
            self.net.cfg, komi=cfg.komi if cfg.komi is not None
            else jaxgo.default_komi(self.net.cfg.size))
        cfg.komi = game_cfg.komi    # metadata records the resolved value
        if cfg.chunk:
            # host-driven segmented iteration (not itself jittable —
            # its internal segment programs are the jit units)
            self._iteration = make_rl_iteration_chunked(
                game_cfg, self.net.feature_list,
                self.net.module.apply, tx, cfg.game_batch,
                cfg.move_limit, cfg.policy_temp, chunk=cfg.chunk,
                mesh=self.mesh)
        else:
            iteration = make_rl_iteration(
                game_cfg, self.net.feature_list,
                self.net.module.apply, tx, cfg.game_batch,
                cfg.move_limit, cfg.policy_temp, mesh=self.mesh)
            self._iteration = jax.jit(iteration, donate_argnums=(0,),
                                      out_shardings=(rep, rep))

        self.state = meshlib.replicate(self.mesh, RLState(
            params=self.net.params,
            opt_state=tx.init(self.net.params),
            iteration=jnp.int32(0),
            rng=pack_rng(jax.random.key(cfg.seed))))
        # multi-host: artifact files are coordinator-only; Orbax saves
        # stay all-process (SURVEY.md §2b "Multi-host")
        self.coord = meshlib.is_coordinator()
        self.pool = OpponentPool(
            os.path.join(cfg.out_dir, "opponents"), self.net,
            write=self.coord)
        self.ckpt = TrainCheckpointer(
            os.path.join(cfg.out_dir, "checkpoints"))
        self.metrics = MetricsLogger(
            os.path.join(cfg.out_dir, "metrics.jsonl")
            if self.coord else None, echo=self.coord)
        # spans/compile events share the metrics stream (obs.trace)
        trace.configure(self.metrics)
        self.start_iteration = 0
        self._maybe_resume()

    def _maybe_resume(self):
        restored, _ = self.ckpt.restore(jax.device_get(self.state))
        if restored is None:
            return
        self.state = meshlib.replicate(self.mesh, RLState(*restored))
        self.start_iteration = int(restored.iteration)
        self.metrics.log("resume", iteration=self.start_iteration)

    def run(self) -> dict:
        cfg = self.cfg
        meta = MetadataWriter(
            os.path.join(cfg.out_dir, "metadata.json"),
            header={"cmd": " ".join(sys.argv),
                    "config": dataclasses.asdict(cfg)},
            enabled=self.coord)
        final = {}
        # transient-failure re-dispatch: safe for the chunked
        # (host-driven) iteration — its chunk programs donate only
        # loop-internal carries, rebuilt from the never-donated
        # `state` each invocation, so it recomputes the identical
        # result from the unchanged state (retries.retry refuses the
        # donating chunk programs themselves). The monolithic jit
        # DONATES the state buffers, so after a failed dispatch the
        # input may already be invalid: no retry there.
        step = self._iteration
        if cfg.chunk:
            step = retries.retry(max_attempts=3, base_delay=1.0,
                                 logger=self.metrics.log)(step)
        jaxobs.maybe_start_profiler()      # env-gated capture
        for it in range(self.start_iteration, cfg.iterations):
          with trace.span("rl.iteration", iteration=it):
            faults.barrier("rl.pre_iteration", it)
            with trace.span("rl.data"):    # opponent-pool draw (I/O)
                opp_params, opp_name = self.pool.sample(
                    cfg.seed, it, save_every=cfg.save_every)
                opp_params = meshlib.replicate(self.mesh, opp_params)
            t0 = time.time()
            self.state, m = step(self.state, opp_params)
            # the win-rate fetch syncs the iteration's programs, so
            # rl.iteration is real end-to-end wall time
            win = float(m["win_rate"])
            faults.barrier("rl.post_iteration", it)
            entry = {
                "iteration": it, "opponent": opp_name,
                "win_rate": win,
                "mean_moves": float(m["mean_moves"]),
                "games_per_min": cfg.game_batch * 60.0
                / max(time.time() - t0, 1e-9),
            }
            self.metrics.log("iteration", **entry)
            meta.record_epoch(entry)
            final = entry
            if (it + 1) % cfg.save_every == 0 or it + 1 == cfg.iterations:
              with trace.span("rl.save"):
                # pool snapshot and exports BEFORE the checkpoint
                # save (the commit point): a crash anywhere in here is
                # healed by resume re-running the iteration and
                # rewriting identical artifacts atomically
                self.pool.add(self.state.params, it + 1)
                self._export_weights(it + 1)
                faults.barrier("rl.pre_save", it)
                self.ckpt.save(it + 1, jax.device_get(self.state))
                if faults.active():
                    # deterministic barrier: commit the async save
                    # before post_save (see training.zero)
                    self.ckpt.wait()
                faults.barrier("rl.post_save", it)
        self.ckpt.wait()
        # the run's counter/histogram state, queryable by obs_report
        obs_registry.log_to(self.metrics)
        jaxobs.stop_profiler()
        return final

    def _export_weights(self, iteration: int) -> None:
        if not self.coord:
            return
        self.net.params = jax.device_get(self.state.params)
        weights = os.path.join(
            self.cfg.out_dir, f"weights.{iteration:05d}.flax.msgpack")
        # model.json always points at the latest weights (GTP-loadable)
        self.net.save_model(
            os.path.join(self.cfg.out_dir, "model.json"), weights)


def run_training(argv=None) -> dict:
    """CLI parity with the reference RL trainer."""
    from rocalphago_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()      # before any compile (env-tunable)
    # multi-host bring-up (DCN); no-op for single-process runs
    meshlib.distributed_init()
    ap = argparse.ArgumentParser(
        description="REINFORCE policy training via self-play")
    ap.add_argument("model_json")
    ap.add_argument("out_dir")
    ap.add_argument("--learning-rate", type=float, default=0.001)
    ap.add_argument("--game-batch", type=int, default=20)
    ap.add_argument("--iterations", type=int, default=100)
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--policy-temp", type=float, default=0.67)
    ap.add_argument("--move-limit", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--num-devices", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=0,
                    help="plies per compiled segment (0 = monolithic; "
                         "use e.g. 10-60 on backends that kill long "
                         "device programs)")
    ap.add_argument("--komi", type=float, default=None,
                    help="area-scoring komi (default: the board "
                         "size's standard; engine.jaxgo.default_komi)")
    a = ap.parse_args(argv)
    cfg = RLConfig(
        model_json=a.model_json, out_dir=a.out_dir,
        learning_rate=a.learning_rate, game_batch=a.game_batch,
        iterations=a.iterations, save_every=a.save_every,
        policy_temp=a.policy_temp, move_limit=a.move_limit,
        seed=a.seed, num_devices=a.num_devices, chunk=a.chunk,
        komi=a.komi)
    return RLTrainer(cfg).run()


if __name__ == "__main__":
    run_training(sys.argv[1:])
