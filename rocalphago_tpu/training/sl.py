"""Supervised policy training, data-parallel over the device mesh.

Parity: ``AlphaGo/training/supervised_policy_trainer.py::run_training``
(SGD + categorical cross-entropy on (state → expert move), minibatch 16,
lr ~0.003 with decay, .93/.05/.02 split, 8-symmetry augmentation,
per-epoch checkpoints + ``metadata.json``, persisted shuffle for resume;
SURVEY.md §2 "SL trainer", §3.1).

TPU-native design:
* one jitted ``train_step`` whose inputs carry `NamedSharding`s — batch
  split over the mesh ``data`` axis, params replicated; XLA inserts the
  gradient all-reduce over ICI (SURVEY.md §2b "Data parallel");
* dihedral augmentation runs *inside* the step on device
  (``symmetries.random_transform_batch``), not per-sample on host;
* input pipeline: sharded npz + double-buffered ``device_put`` prefetch;
* checkpoints are Orbax pytrees of (params, opt state, step, PRNG bits)
  — exact resume, async save.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from rocalphago_tpu.data.pipeline import (
    ShardedDataset,
    batch_iterator,
    device_prefetch,
    split_indices,
)
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
from rocalphago_tpu.parallel import mesh as meshlib
from rocalphago_tpu.runtime import faults
from rocalphago_tpu.training.symmetries import random_transform_batch


@dataclasses.dataclass
class SLConfig:
    """Flat, JSON-serializable stage config (SURVEY.md §5 "Config")."""

    model_json: str = ""
    train_data: str = ""          # shard prefix (npz pipeline)
    out_dir: str = ""
    minibatch: int = 16           # per *mesh*, like the reference's 16
    epochs: int = 10
    learning_rate: float = 0.003
    decay: float = 0.0            # Keras-style lr/(1+decay*step)
    momentum: float = 0.0
    train_val_test: tuple = (0.93, 0.05, 0.02)
    symmetries: bool = True
    seed: int = 0
    num_devices: int | None = None
    max_validation_batches: int = 200
    epoch_length: int | None = None   # steps per epoch; None = full pass
    save_every: int | None = None     # also checkpoint every N steps
    #                                   (mid-epoch preemption recovery)


class SLState(NamedTuple):
    params: dict
    opt_state: tuple
    step: jax.Array     # int32 []
    rng: jax.Array      # uint32 key data


def make_optimizer(cfg: SLConfig) -> optax.GradientTransformation:
    """SGD with the reference's Keras-style inverse-time lr decay."""
    if cfg.decay:
        sched = lambda step: cfg.learning_rate / (1.0 + cfg.decay * step)  # noqa: E731
    else:
        sched = cfg.learning_rate
    return optax.sgd(sched, momentum=cfg.momentum or None)


def _per_row(weights, like):
    """One weight per row, broadcast over ``like``'s other axes."""
    return weights.reshape(weights.shape + (1,) * (like.ndim - 1))


def policy_loss_fn(apply_fn, params, planes, actions, weights=None):
    """(loss, accuracy) of ``[B, N]`` logits against ``[B]`` moves —
    or of a sequence policy's ``[B, S, V]`` against ``[B, S]`` next
    ids: the mean is over every position either way."""
    return _policy_loss(apply_fn, params, planes, actions, weights)[:2]


#: weight of a multi-token-prediction module's loss beside the main
#: one (DeepSeek-V3 report §4.2: 0.3 for most of pre-training). The
#: trainer's, not the model's, unless the model's config has a key
#: for it (``mtp_loss_weight`` beside ``mtp_logits``)
MTP_LOSS_WEIGHT = 0.3


def _masked_xent(logits, actions, weights=None):
    """Mean cross-entropy and accuracy over the positions whose
    target is inside the logits' range (and whose row counts)."""
    # pass actions (== N, present when a corpus was converted with
    # include_passes) are outside the policy's board-point output
    # space — mask them out rather than letting the xent gather
    # clamp them onto the last board point
    valid = (actions < logits.shape[-1]).astype(jnp.float32)
    if weights is not None:
        valid = valid * _per_row(weights, valid)
    denom = jnp.maximum(valid.sum(), 1.0)
    xent = optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.minimum(actions, logits.shape[-1] - 1))
    loss = (xent * valid).sum() / denom
    acc = (((logits.argmax(axis=-1) == actions) * valid).sum()
           / denom)
    return loss, acc


def _policy_loss(apply_fn, params, planes, actions, weights=None):
    """``policy_loss_fn`` plus whatever the network returns beside its
    logits (a sequence policy's routing counts; else ``{}``).

    A sequence policy is given its next ids beside its ids: one with
    a multi-token-prediction module embeds them and returns
    ``mtp_logits``, at each position the logits of the id after the
    next. Their cross-entropy against the next ids one to the left
    (a row's last position has no target; a game separator is a
    target like any other: attention crosses it too) is added
    ``MTP_LOSS_WEIGHT`` times (or the model's own
    ``mtp_loss_weight`` times) and returned as ``mtp_loss``."""
    if planes.ndim == 2:
        out = apply_fn(params, planes, actions)
    else:
        out = apply_fn(params, planes)
    logits, extras = out if isinstance(out, tuple) else (out, {})
    extras = dict(extras)
    ahead = extras.pop("mtp_logits", None)
    # a model whose config weighs the module's loss says so
    mtp_weight = extras.pop("mtp_loss_weight", MTP_LOSS_WEIGHT)
    with jax.named_scope(scopes.TRAIN_LOSS):
        loss, acc = _masked_xent(logits, actions, weights)
        if ahead is not None:
            beyond = jnp.full_like(actions[:, :1], ahead.shape[-1])
            extras["mtp_loss"], _ = _masked_xent(
                ahead, jnp.concatenate([actions[:, 1:], beyond], axis=1),
                weights)
            loss = loss + mtp_weight * extras["mtp_loss"]
    return loss, acc, extras


def _moved(params, moves):
    """``params`` with ``moves`` — a part of the same tree — added."""
    if not isinstance(moves, dict):
        return params + moves
    return {k: _moved(v, moves[k]) if k in moves else v
            for k, v in params.items()}


def make_train_step(apply_fn, tx, size: int, symmetries: bool):
    """Pure (state, planes, actions) → (state, metrics) step fn.
    ``planes`` are feature planes ``[B, s, s, F]`` with moves ``[B]``,
    or a sequence policy's id rows ``[B, S]`` with next ids
    ``[B, S]``."""

    def loss_fn(params, planes, actions):
        loss, acc, extras = _policy_loss(apply_fn, params, planes,
                                         actions)
        return loss, (acc, extras)

    def train_step(state: SLState, planes, actions):
        key = unpack_rng(state.rng)
        key, sub = jax.random.split(key)
        with jax.named_scope(scopes.TRAIN_AUGMENT):
            if planes.ndim == 4:        # id rows stay integers
                planes = planes.astype(jnp.float32)
            if symmetries:
                planes, actions = random_transform_batch(
                    sub, planes, actions, size)
        (loss, (acc, extras)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params, planes, actions)
        # moves of leaves that carry no gradient (a sequence policy's
        # selection biases, by their own rule), after the optimizer's
        moves = extras.pop("no_grad_updates", None)
        with jax.named_scope(scopes.TRAIN_UPDATE):
            updates, opt_state = tx.update(
                grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            if moves is not None:
                params = _moved(params, moves)
        new = SLState(params, opt_state, state.step + 1, pack_rng(key))
        return new, {"loss": loss, "accuracy": acc, **extras}

    return train_step


def record_routing(metrics: list) -> None:
    """Add the routing counts of some steps' returned metrics (host
    values; a sequence policy's — a step without them adds nothing)
    to the process's counters, once per block of steps."""
    if not metrics or "moe_routed" not in metrics[0]:
        return
    for key, name in (("moe_routed", obs_registry.MOE_TOKENS_ROUTED),
                      ("moe_held", obs_registry.MOE_TOKENS_HELD),
                      ("moe_dropped", obs_registry.MOE_TOKENS_DROPPED),
                      ("moe_row_blocks_run",
                       obs_registry.MOE_ROW_BLOCKS_RUN),
                      ("moe_row_blocks", obs_registry.MOE_ROW_BLOCKS)):
        obs_registry.counter(name).inc(
            sum(int(m[key]) for m in metrics))
    obs_registry.gauge(obs_registry.MOE_EXPERT_LOAD_MAX).set(
        int(metrics[-1]["moe_load_max"]))
    if "mtp_loss" in metrics[-1]:
        obs_registry.gauge(obs_registry.SEQ_MTP_LOSS).set(
            float(metrics[-1]["mtp_loss"]))


def make_eval_step(apply_fn, num_points: int):
    def eval_step(params, planes, actions, weights):
        if planes.ndim == 4:            # id rows stay integers
            planes = planes.astype(jnp.float32)
        loss, acc = policy_loss_fn(apply_fn, params, planes, actions,
                                   weights)
        # effective sample count = the loss denominator (real rows
        # whose action is a board point)
        count = ((actions < num_points)
                 * _per_row(weights, actions)).sum()
        return {"loss": loss, "accuracy": acc, "count": count}
    return eval_step


def pad_batch(planes, targets, batch_size: int):
    """Pad a short final batch up to ``batch_size`` (repeating row 0)
    with a 0/1 weight vector marking the real rows — so evaluation
    keeps one compiled shape and small validation splits still
    contribute instead of being dropped."""
    k = len(targets)
    weights = np.ones(batch_size, np.float32)
    if k < batch_size:
        pad = batch_size - k
        planes = np.concatenate(
            [planes, np.repeat(planes[:1], pad, axis=0)])
        targets = np.concatenate(
            [targets, np.repeat(targets[:1], pad, axis=0)])
        weights[k:] = 0.0
    return planes, targets, weights


class SLTrainer:
    """Wires net + data + mesh + checkpointing into the train loop.

    Usable programmatically (tests drive small configs through it) or
    via the ``run_training`` CLI.
    """

    def __init__(self, cfg: SLConfig, net: NeuralNetBase | None = None):
        self.cfg = cfg
        self.net = net or NeuralNetBase.load_model(cfg.model_json)
        self.mesh = meshlib.make_mesh(cfg.num_devices)
        self.dataset = ShardedDataset(cfg.train_data)
        if self.dataset.planes != self.net.input_planes:
            raise ValueError(
                f"dataset has {self.dataset.planes} planes but the model "
                f"needs {self.net.input_planes} (0: a sequence policy's "
                "id rows, data/convert.py --sequence)")
        os.makedirs(cfg.out_dir, exist_ok=True)

        dwidth = self.mesh.shape[meshlib.DATA_AXIS]
        if cfg.minibatch % dwidth:
            raise ValueError(
                f"minibatch {cfg.minibatch} not divisible by data-parallel "
                f"width {dwidth}")

        tx = make_optimizer(cfg)
        size = self.net.board
        opt_state0 = tx.init(self.net.params)
        in_rank, target_rank = self.net.batch_ranks
        batch_sh = meshlib.data_sharding(self.mesh, rank=in_rank)
        act_sh = meshlib.data_sharding(self.mesh, rank=target_rank)
        weight_sh = meshlib.data_sharding(self.mesh, rank=1)
        rep = meshlib.replicated(self.mesh)
        state_sh = SLState(
            params=jax.tree.map(lambda _: rep, self.net.params),
            opt_state=jax.tree.map(lambda _: rep, opt_state0),
            step=rep, rng=rep)
        # compile-tracked (obs.jaxobs): a recompile mid-run — a shape
        # drifting between epochs — surfaces as a named `compile`
        # event instead of a silent throughput cliff
        self._train_step = jaxobs.track("sl.train_step", jax.jit(
            make_train_step(self.net.module.apply, tx, size, cfg.symmetries),
            in_shardings=(state_sh, batch_sh, act_sh),
            out_shardings=(state_sh, rep),
            donate_argnums=(0,)))
        self._eval_step = jaxobs.track("sl.eval_step", jax.jit(
            make_eval_step(self.net.module.apply, self.net.num_outputs),
            in_shardings=(state_sh.params, batch_sh, act_sh, weight_sh),
            out_shardings=rep))

        self.tx = tx
        # multi-host: artifact files are coordinator-only; Orbax saves
        # stay all-process (SURVEY.md §2b "Multi-host")
        self.coord = meshlib.is_coordinator()
        self.ckpt = TrainCheckpointer(
            os.path.join(cfg.out_dir, "checkpoints"))
        self.metrics = MetricsLogger(
            os.path.join(cfg.out_dir, "metrics.jsonl")
            if self.coord else None, echo=self.coord)
        # spans/compile events share the metrics stream (obs.trace)
        trace.configure(self.metrics)

        key = jax.random.key(cfg.seed)
        self.state = meshlib.replicate(self.mesh, SLState(
            params=self.net.params,
            opt_state=opt_state0,
            step=jnp.int32(0),
            rng=pack_rng(key)))

        self.train_idx, self.val_idx, self.test_idx = split_indices(
            len(self.dataset), cfg.train_val_test, seed=cfg.seed,
            path=os.path.join(cfg.out_dir, "shuffle.npz"),
            write=self.coord)
        self.start_epoch = 0
        self._resume_skip = 0
        self._maybe_resume()

    # ----------------------------------------------------------- resume

    def _maybe_resume(self):
        restored, step = self.ckpt.restore(jax.device_get(self.state))
        if restored is None:
            return
        self.state = meshlib.replicate(self.mesh, SLState(*restored))
        # the data cursor is derived, not stored: batch order within an
        # epoch is a pure function of (seed, epoch) — see run() — so
        # step % steps_per_epoch IS the number of consumed batches, and
        # a mid-epoch kill resumes at exactly the next unseen batch
        self.start_epoch, self._resume_skip = divmod(
            int(restored.step), max(self._steps_per_epoch(), 1))
        self.metrics.log("resume", step=int(restored.step),
                         epoch=self.start_epoch, skip=self._resume_skip)

    def _steps_per_epoch(self) -> int:
        if self.cfg.epoch_length:
            return self.cfg.epoch_length
        return max(len(self.train_idx) // self.cfg.minibatch, 1)

    # ------------------------------------------------------------- train

    def run(self) -> dict:
        cfg = self.cfg
        meta = MetadataWriter(
            os.path.join(cfg.out_dir, "metadata.json"),
            header={"cmd": " ".join(sys.argv),
                    "config": dataclasses.asdict(cfg),
                    "dataset_positions": len(self.dataset)},
            enabled=self.coord)
        steps_per_epoch = self._steps_per_epoch()
        jaxobs.maybe_start_profiler()      # env-gated capture
        # host wait per prefetched batch — the data-starvation probe
        # (near-zero while the input pipeline keeps up with the step)
        data_wait = obs_registry.histogram(
            "train_data_wait_seconds", trainer="sl")
        # host RNG seeded per-epoch → identical batch order on re-run
        # of the same epoch after resume (reference shuffle.npz trick)
        final = {}
        for epoch in range(self.start_epoch, cfg.epochs):
          with trace.span("sl.epoch", epoch=epoch):
            faults.barrier("sl.pre_epoch", epoch)
            skip = self._resume_skip if epoch == self.start_epoch else 0
            host_rng = np.random.default_rng(
                np.random.SeedSequence([cfg.seed, epoch]))
            it = batch_iterator(self.dataset, self.train_idx,
                                cfg.minibatch, host_rng, epochs=1,
                                skip=skip)
            it = (meshlib.shard_batch(self.mesh, b)
                  for b in it)
            t0 = time.time()
            losses, accs, routing = [], [], []
            with trace.span("sl.train"):
              for i, (planes, actions) in enumerate(obs_registry.timed(
                      device_prefetch(it, size=2), data_wait,
                      annotate="sl.data_wait")):
                if i >= steps_per_epoch - skip:
                    break
                # per-step, so on the profiler's clock only: a record
                # each would flood metrics.jsonl
                with trace.annotation("sl.step"):
                    self.state, m = self._train_step(
                        self.state, planes, actions)
                losses.append(m["loss"])
                accs.append(m["accuracy"])
                if "moe_routed" in m:       # a sequence policy's counts
                    routing.append(m)
                if cfg.save_every:
                    gstep = epoch * steps_per_epoch + skip + len(losses)
                    if gstep % cfg.save_every == 0:
                        self.ckpt.save(gstep, jax.device_get(self.state))
                        faults.barrier("sl.step_save", gstep)
            if not losses:
                raise ValueError(
                    f"train split ({len(self.train_idx)} positions) "
                    f"yields no full minibatch of {cfg.minibatch}; "
                    "convert more games or shrink the minibatch")
            record_routing(jax.device_get(routing))
            train_loss = float(jnp.mean(jnp.stack(losses)))
            train_acc = float(jnp.mean(jnp.stack(accs)))
            dt = time.time() - t0
            with trace.span("sl.eval"):
                val = self.evaluate(self.val_idx)
            step = int(jax.device_get(self.state.step))
            entry = {
                "epoch": epoch, "step": step,
                "train_loss": train_loss, "train_accuracy": train_acc,
                "val_loss": val["loss"], "val_accuracy": val["accuracy"],
                "positions_per_s": len(losses) * cfg.minibatch / max(dt, 1e-9),
            }
            self.metrics.log("epoch", **entry)
            meta.record_epoch(entry)
            # exports BEFORE the checkpoint save (the commit point): a
            # crash in between is healed by resume re-running the
            # epoch and rewriting identical artifacts atomically
            with trace.span("sl.export"):
                self._export_weights(epoch)
            with trace.span("sl.save"):
                faults.barrier("sl.pre_save", epoch)
                self.ckpt.save(step, jax.device_get(self.state))
                if faults.active():
                    # deterministic barrier: commit the async save
                    # before post_save (see training.zero)
                    self.ckpt.wait()
                faults.barrier("sl.post_save", epoch)
            final = entry
        # held-out test-split metric (BASELINE.md metric 1: top-1 move
        # accuracy) — recorded in metadata.json for tooling and
        # reportable standalone via training.evaluate
        if len(self.test_idx):
            test = self.evaluate(self.test_idx)
            final = dict(final, test_loss=test["loss"],
                         test_accuracy=test["accuracy"])
            meta.update(test_loss=test["loss"],
                        test_accuracy=test["accuracy"])
            self.metrics.log("test", **test)
        self.ckpt.wait()
        # the run's counter/histogram state, queryable by obs_report
        obs_registry.log_to(self.metrics)
        jaxobs.stop_profiler()
        return final

    def evaluate(self, indices, max_batches: int | None = None) -> dict:
        cfg = self.cfg
        max_batches = max_batches or cfg.max_validation_batches
        params = self.state.params
        rng = np.random.default_rng(0)
        loss_sum = acc_sum = count = 0.0
        it = batch_iterator(self.dataset, indices, cfg.minibatch, rng,
                            epochs=1, drop_remainder=False)
        for i, (planes, actions) in enumerate(it):
            if i >= max_batches:
                break
            planes, actions, weights = pad_batch(
                planes, actions, cfg.minibatch)
            planes, actions, weights = meshlib.shard_batch(
                self.mesh, (planes, actions, weights))
            m = self._eval_step(params, planes, actions, weights)
            c = float(m["count"])
            loss_sum += float(m["loss"]) * c
            acc_sum += float(m["accuracy"]) * c
            count += c
        if not count:
            return {"loss": float("nan"), "accuracy": float("nan")}
        return {"loss": loss_sum / count, "accuracy": acc_sum / count}

    def _export_weights(self, epoch: int) -> None:
        """Reference-parity per-epoch weight export
        (``weights.NNNNN``-style) plus ``model.json`` — a loadable
        spec always pointing at the latest weights, so downstream
        stages (RL, GTP) can consume ``out_dir/model.json`` directly."""
        if not self.coord:
            return
        self.net.params = jax.device_get(self.state.params)
        weights = os.path.join(
            self.cfg.out_dir, f"weights.{epoch:05d}.flax.msgpack")
        self.net.save_model(
            os.path.join(self.cfg.out_dir, "model.json"), weights)


def run_training(argv=None) -> dict:
    """CLI parity with the reference trainer."""
    from rocalphago_tpu.runtime.compilecache import enable_compile_cache

    # persistent compile cache before any compile (ROCALPHAGO_COMPILE_
    # CACHE): repeat/resumed runs skip the cold program compiles
    enable_compile_cache()
    # multi-host bring-up (DCN) before any backend touch; no-op for
    # single-process runs (SURVEY.md §7 step 7)
    meshlib.distributed_init()
    ap = argparse.ArgumentParser(
        description="Supervised policy training on expert games")
    ap.add_argument("model_json")
    ap.add_argument("train_data", help="npz shard prefix")
    ap.add_argument("out_dir")
    ap.add_argument("--minibatch", "-B", type=int, default=16)
    ap.add_argument("--epochs", "-E", type=int, default=10)
    ap.add_argument("--learning-rate", "-l", type=float, default=0.003)
    ap.add_argument("--decay", "-d", type=float, default=0.0)
    ap.add_argument("--momentum", type=float, default=0.0)
    ap.add_argument("--train-val-test", nargs=3, type=float,
                    default=[0.93, 0.05, 0.02])
    ap.add_argument("--no-symmetries", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--num-devices", type=int, default=None)
    ap.add_argument("--epoch-length", type=int, default=None)
    ap.add_argument("--save-every", type=int, default=None,
                    help="extra checkpoint every N steps (mid-epoch "
                         "preemption recovery)")
    a = ap.parse_args(argv)
    cfg = SLConfig(
        model_json=a.model_json, train_data=a.train_data, out_dir=a.out_dir,
        minibatch=a.minibatch, epochs=a.epochs,
        learning_rate=a.learning_rate, decay=a.decay, momentum=a.momentum,
        train_val_test=tuple(a.train_val_test),
        symmetries=not a.no_symmetries, seed=a.seed,
        num_devices=a.num_devices, epoch_length=a.epoch_length,
        save_every=a.save_every)
    return SLTrainer(cfg).run()


if __name__ == "__main__":
    run_training(sys.argv[1:])
