"""Supervised policy training steps through ``training/sl.py``.

The program's own step (``make_train_step`` with
``make_optimizer(SLConfig())``: SGD, categorical cross-entropy,
dihedral augmentation on the device) jitted as the trainer jits it
(state donated), fed from ``resident_batches`` synthetic batches
that live on the device — seeded 0/1 planes as ``uint8``, the type
the shard pipeline delivers, and uniform expert moves — so the cell
measures the step and not an input pipeline this PR does not have
data for. Steps are dispatched back to back and the host blocks once
per ``steps_per_block``; the window is whole blocks, and the rate is
positions over the window's wall time.
"""

from __future__ import annotations

import math
import time


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.t = ctx.traffic

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from chipbench import reference
        from chipbench.nets import build_net, random_planes
        from rocalphago_tpu.io.checkpoint import pack_rng, unpack_rng
        from rocalphago_tpu.training import sl
        from rocalphago_tpu.training.symmetries import (
            random_transform_batch,
        )

        ctx, t = self.ctx, self.t
        self.batch = int(t["batch"])
        self.block = int(t["steps_per_block"])
        seed = ctx.seed % (2 ** 31)
        with ctx.phase("weights"):
            self.net = net = build_net(ctx.config, "policy", ctx.seed)
            jax.block_until_ready(net.params)
        size, n_res = net.board, int(t["resident_batches"])
        with ctx.phase("batches"):
            planes = random_planes(
                ctx.seed + 1, n_res * self.batch, size,
                net.preprocess.output_dim, float(t["plane_density"]))
            actions = jax.random.randint(
                jax.random.key(seed + 2), (n_res * self.batch,), 0,
                size * size, jnp.int32)
            self.batches = [
                (planes[i * self.batch:(i + 1) * self.batch],
                 actions[i * self.batch:(i + 1) * self.batch])
                for i in range(n_res)]
            jax.block_until_ready(self.batches)
        tx = sl.make_optimizer(sl.SLConfig())
        sym = bool(t["symmetries"])
        self.step = jax.jit(
            sl.make_train_step(net.module.apply, tx, size, sym),
            donate_argnums=(0,))
        rng = pack_rng(jax.random.key(seed + 3))
        with ctx.phase("reference_loss"):
            # the reference's loss on the inputs the first step sees:
            # the step splits its key once and augments with the
            # second half; the augmentation (a data transform) is the
            # program's, the forward pass and loss are the reference's
            p0, a0 = self.batches[0]
            if sym:
                _, sub = jax.random.split(unpack_rng(rng))
                p0, a0 = jax.jit(
                    lambda k, p, a: random_transform_batch(
                        k, p.astype(jnp.float32), a, size))(sub, p0, a0)
            self.ref_loss = float(jax.jit(reference.policy_loss)(
                net.params, p0, a0))
        # the step donates its state: give it a copy of the weights,
        # the net keeps the seed's for the forward check
        params = jax.tree.map(jnp.copy, net.params)
        self.state = sl.SLState(params, tx.init(params), jnp.int32(0),
                                rng)
        self.steps = 0
        with ctx.phase("first_steps"):
            # two steps: the second call must find the first's program
            self.first_loss = self._block(2)[0]

    def _block(self, steps: int) -> list:
        """``steps`` steps back to back, then one block; the losses."""
        import jax

        losses = []
        with self.ctx.span("chipbench.dispatch"):
            for _ in range(steps):
                planes, actions = self.batches[
                    self.steps % len(self.batches)]
                self.state, m = self.step(self.state, planes, actions)
                losses.append(m["loss"])
                self.steps += 1
        with self.ctx.span("chipbench.block"):
            return [float(x) for x in jax.device_get(losses)]

    def window(self, seconds: float, on_start=None) -> dict:
        if on_start is not None:
            on_start()
        losses, blocks = [], 0
        with self.ctx.span("chipbench.window"):
            started_at, t0 = time.time(), time.monotonic()
            while True:
                losses.extend(self._block(self.block))
                blocks += 1
                now = time.monotonic()
                if now - t0 >= seconds:
                    break
            elapsed = now - t0
        steps = blocks * self.block
        bad = sum(1 for x in losses if not math.isfinite(x))
        return {"started_at": started_at, "elapsed_s": elapsed, "steps": steps,
                "positions": steps * self.batch, "losses": losses,
                "attempted": steps, "failed": bad}

    def end_to_end(self, raw: dict) -> dict:
        return {"train_positions_per_s":
                raw["positions"] / raw["elapsed_s"]}

    def verify(self, raw: dict) -> tuple:
        from chipbench import reference

        problems, readings = reference.check_nets(
            {"policy": self.net}, self.ctx.seed)
        rel = abs(self.first_loss - self.ref_loss) / abs(self.ref_loss)
        readings.update(first_loss=self.first_loss,
                        reference_loss=self.ref_loss,
                        first_loss_rel_err=rel,
                        last_loss=raw["losses"][-1])
        if not rel <= reference.LOSS_TOLERANCE:
            problems.append(
                f"first-step loss {self.first_loss:.6f} differs from "
                f"the reference's {self.ref_loss:.6f} by {rel:.4%} "
                f"(tolerance {reference.LOSS_TOLERANCE:.2%})")
        if raw["failed"]:
            problems.append(f"{raw['failed']} non-finite losses")
        return problems, readings

    def close(self) -> None:
        pass

