"""Value-network regression training, data-parallel over the mesh.

Parity: ``AlphaGo/training/reinforcement_value_trainer.py::run_training``
(MSE loss + SGD over (state, outcome z) pairs, CLI mirroring the SL
trainer, per-epoch checkpoints + ``metadata.json`` + persisted split;
SURVEY.md §2 "Value trainer"). The corpus comes from
:mod:`rocalphago_tpu.training.selfplay_data` — the de-correlated
one-position-per-game generator the reference lacks.

Same TPU shape as the SL trainer: one jitted sharded train step (batch
over the ``data`` mesh axis, XLA all-reduces gradients over ICI),
on-device dihedral augmentation (planes only — the scalar target is
rotation-invariant), Orbax checkpoints, prefetched input pipeline.
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
from rocalphago_tpu.training.sl import pad_batch
from rocalphago_tpu.training.symmetries import transform_planes


@dataclasses.dataclass
class ValueConfig:
    """Flat, JSON-serializable stage config (SURVEY.md §5 "Config")."""

    model_json: str = ""
    train_data: str = ""          # shard prefix (npz pipeline)
    out_dir: str = ""
    minibatch: int = 32
    epochs: int = 10
    learning_rate: float = 0.003
    decay: float = 0.0
    momentum: float = 0.0
    train_val_test: tuple = (0.93, 0.05, 0.02)
    symmetries: bool = True
    seed: int = 0
    num_devices: int | None = None
    max_validation_batches: int = 200
    epoch_length: int | None = None
    save_every: int | None = None     # also checkpoint every N steps


class ValueState(NamedTuple):
    params: dict
    opt_state: tuple
    step: jax.Array
    rng: jax.Array


def value_loss_fn(apply_fn, params, planes, outcomes, weights=None):
    pred = apply_fn(params, planes)
    with jax.named_scope(scopes.TRAIN_LOSS):
        z = outcomes.astype(jnp.float32)
        sq = (pred - z) ** 2
        if weights is None:
            return jnp.mean(sq)
        return (sq * weights).sum() / jnp.maximum(weights.sum(), 1.0)


def make_train_step(apply_fn, tx, symmetries: bool):
    def train_step(state: ValueState, planes, outcomes):
        key = unpack_rng(state.rng)
        key, sub = jax.random.split(key)
        with jax.named_scope(scopes.TRAIN_AUGMENT):
            planes = planes.astype(jnp.float32)
            if symmetries:
                t = jax.random.randint(sub, (planes.shape[0],), 0, 8)
                planes = jax.vmap(transform_planes)(planes, t)
        loss, grads = jax.value_and_grad(
            lambda p: value_loss_fn(apply_fn, p, planes, outcomes))(
                state.params)
        with jax.named_scope(scopes.TRAIN_UPDATE):
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
        new = ValueState(params, opt_state, state.step + 1,
                         pack_rng(key))
        return new, {"mse": loss}

    return train_step


def make_eval_step(apply_fn):
    def eval_step(params, planes, outcomes, weights):
        return {"mse": value_loss_fn(apply_fn, params,
                                     planes.astype(jnp.float32),
                                     outcomes, weights),
                "count": weights.sum()}
    return eval_step


class ValueTrainer:
    """Wires value net + data + mesh + checkpointing together."""

    def __init__(self, cfg: ValueConfig, net: NeuralNetBase | None = None):
        self.cfg = cfg
        self.net = net or NeuralNetBase.load_model(cfg.model_json)
        self.mesh = meshlib.make_mesh(cfg.num_devices)
        self.dataset = ShardedDataset(cfg.train_data)
        if self.dataset.planes != self.net.preprocess.output_dim:
            raise ValueError(
                f"dataset has {self.dataset.planes} planes but the "
                f"model needs {self.net.preprocess.output_dim}")
        if self.dataset.manifest.get("targets") != "outcome":
            raise ValueError(
                "value training needs an outcome-labelled corpus "
                "(generate one with training.selfplay_data)")
        os.makedirs(cfg.out_dir, exist_ok=True)

        dwidth = self.mesh.shape[meshlib.DATA_AXIS]
        if cfg.minibatch % dwidth:
            raise ValueError(
                f"minibatch {cfg.minibatch} not divisible by "
                f"data-parallel width {dwidth}")

        if cfg.decay:
            sched = lambda s: cfg.learning_rate / (1.0 + cfg.decay * s)  # noqa: E731
        else:
            sched = cfg.learning_rate
        tx = optax.sgd(sched, momentum=cfg.momentum or None)
        opt_state0 = tx.init(self.net.params)
        batch_sh = meshlib.data_sharding(self.mesh, rank=4)
        z_sh = meshlib.data_sharding(self.mesh, rank=1)
        rep = meshlib.replicated(self.mesh)
        state_sh = ValueState(
            params=jax.tree.map(lambda _: rep, self.net.params),
            opt_state=jax.tree.map(lambda _: rep, opt_state0),
            step=rep, rng=rep)
        # compile-tracked (obs.jaxobs): recompiles surface as named
        # `compile` events (see training.sl)
        self._train_step = jaxobs.track("value.train_step", jax.jit(
            make_train_step(self.net.module.apply, tx, cfg.symmetries),
            in_shardings=(state_sh, batch_sh, z_sh),
            out_shardings=(state_sh, rep),
            donate_argnums=(0,)))
        self._eval_step = jaxobs.track("value.eval_step", jax.jit(
            make_eval_step(self.net.module.apply),
            in_shardings=(state_sh.params, batch_sh, z_sh, z_sh),
            out_shardings=rep))

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
        self.state = meshlib.replicate(self.mesh, ValueState(
            params=self.net.params,
            opt_state=opt_state0,
            step=jnp.int32(0),
            rng=pack_rng(jax.random.key(cfg.seed))))
        self.train_idx, self.val_idx, self.test_idx = split_indices(
            len(self.dataset), cfg.train_val_test, seed=cfg.seed,
            path=os.path.join(cfg.out_dir, "shuffle.npz"),
            write=self.coord)
        self.start_epoch = 0
        self._resume_skip = 0
        self._maybe_resume()

    def _maybe_resume(self):
        restored, _ = self.ckpt.restore(jax.device_get(self.state))
        if restored is None:
            return
        self.state = meshlib.replicate(self.mesh, ValueState(*restored))
        # derived data cursor: batch order is a pure function of
        # (seed, epoch), so step % steps_per_epoch = consumed batches
        # (same scheme as SLTrainer._maybe_resume)
        self.start_epoch, self._resume_skip = divmod(
            int(restored.step), max(self._steps_per_epoch(), 1))
        self.metrics.log("resume", step=int(restored.step),
                         epoch=self.start_epoch, skip=self._resume_skip)

    def _steps_per_epoch(self) -> int:
        if self.cfg.epoch_length:
            return self.cfg.epoch_length
        return max(len(self.train_idx) // self.cfg.minibatch, 1)

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
        # host wait per prefetched batch (see training.sl)
        data_wait = obs_registry.histogram(
            "train_data_wait_seconds", trainer="value")
        final = {}
        for epoch in range(self.start_epoch, cfg.epochs):
          with trace.span("value.epoch", epoch=epoch):
            faults.barrier("value.pre_epoch", epoch)
            skip = self._resume_skip if epoch == self.start_epoch else 0
            host_rng = np.random.default_rng(
                np.random.SeedSequence([cfg.seed, epoch]))
            it = batch_iterator(self.dataset, self.train_idx,
                                cfg.minibatch, host_rng, epochs=1,
                                skip=skip)
            it = (meshlib.shard_batch(self.mesh, b) for b in it)
            t0 = time.time()
            losses = []
            with trace.span("value.train"):
              for i, (planes, z) in enumerate(obs_registry.timed(
                      device_prefetch(it, size=2), data_wait)):
                if i >= steps_per_epoch - skip:
                    break
                self.state, m = self._train_step(self.state, planes, z)
                losses.append(m["mse"])
                if cfg.save_every:
                    gstep = epoch * steps_per_epoch + skip + len(losses)
                    if gstep % cfg.save_every == 0:
                        self.ckpt.save(gstep, jax.device_get(self.state))
                        faults.barrier("value.step_save", gstep)
            if not losses:
                raise ValueError(
                    f"train split ({len(self.train_idx)} positions) "
                    f"yields no full minibatch of {cfg.minibatch}; "
                    "generate more data or shrink the minibatch")
            train_mse = float(jnp.mean(jnp.stack(losses)))
            dt = time.time() - t0
            with trace.span("value.eval"):
                val = self.evaluate(self.val_idx)
            step = int(jax.device_get(self.state.step))
            entry = {
                "epoch": epoch, "step": step,
                "train_mse": train_mse, "val_mse": val["mse"],
                "positions_per_s":
                    len(losses) * cfg.minibatch / max(dt, 1e-9),
            }
            self.metrics.log("epoch", **entry)
            meta.record_epoch(entry)
            # exports before the checkpoint save (commit point) — same
            # crash-safe ordering as SLTrainer.run
            with trace.span("value.export"):
                self._export_weights(epoch)
            with trace.span("value.save"):
                faults.barrier("value.pre_save", epoch)
                self.ckpt.save(step, jax.device_get(self.state))
                if faults.active():
                    # deterministic barrier: commit the async save
                    # before post_save (see training.zero)
                    self.ckpt.wait()
                faults.barrier("value.post_save", epoch)
            final = entry
        # held-out test-split MSE (AlphaGo paper reports train+test MSE)
        if len(self.test_idx):
            test = self.evaluate(self.test_idx)
            final = dict(final, test_mse=test["mse"])
            meta.update(test_mse=test["mse"])
            self.metrics.log("test", **test)
        self.ckpt.wait()
        # the run's counter/histogram state, queryable by obs_report
        obs_registry.log_to(self.metrics)
        jaxobs.stop_profiler()
        return final

    def evaluate(self, indices, max_batches: int | None = None) -> dict:
        cfg = self.cfg
        max_batches = max_batches or cfg.max_validation_batches
        rng = np.random.default_rng(0)
        mse_sum = count = 0.0
        it = batch_iterator(self.dataset, indices, cfg.minibatch, rng,
                            epochs=1, drop_remainder=False)
        for i, (planes, z) in enumerate(it):
            if i >= max_batches:
                break
            planes, z, weights = pad_batch(planes, z, cfg.minibatch)
            planes, z, weights = meshlib.shard_batch(
                self.mesh, (planes, z, weights))
            m = self._eval_step(self.state.params, planes, z, weights)
            c = float(m["count"])
            mse_sum += float(m["mse"]) * c
            count += c
        if not count:
            return {"mse": float("nan")}
        return {"mse": mse_sum / count}

    def _export_weights(self, epoch: int) -> None:
        if not self.coord:
            return
        self.net.params = jax.device_get(self.state.params)
        weights = os.path.join(
            self.cfg.out_dir, f"weights.{epoch:05d}.flax.msgpack")
        # model.json always points at the latest weights (GTP-loadable)
        self.net.save_model(
            os.path.join(self.cfg.out_dir, "model.json"), weights)


def run_training(argv=None) -> dict:
    """CLI parity with the reference value trainer."""
    from rocalphago_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()      # before any compile (env-tunable)
    # multi-host bring-up (DCN); no-op for single-process runs
    meshlib.distributed_init()
    ap = argparse.ArgumentParser(
        description="Value network regression on self-play outcomes")
    ap.add_argument("model_json")
    ap.add_argument("train_data", help="npz shard prefix "
                                       "(training.selfplay_data output)")
    ap.add_argument("out_dir")
    ap.add_argument("--minibatch", "-B", type=int, default=32)
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
    cfg = ValueConfig(
        model_json=a.model_json, train_data=a.train_data,
        out_dir=a.out_dir, minibatch=a.minibatch, epochs=a.epochs,
        learning_rate=a.learning_rate, decay=a.decay,
        momentum=a.momentum, train_val_test=tuple(a.train_val_test),
        symmetries=not a.no_symmetries, seed=a.seed,
        num_devices=a.num_devices, epoch_length=a.epoch_length,
        save_every=a.save_every)
    return ValueTrainer(cfg).run()


if __name__ == "__main__":
    run_training(sys.argv[1:])
