"""Supervised training steps of the move-sequence policy through
``training/sl.py`` — the step the conv policy trains by.

The program's own step (``make_train_step`` with
``make_optimizer(SLConfig())``: SGD, mean next-id cross-entropy, one
dihedral transform per row on the device) jitted as the trainer jits
it (state donated), fed from ``resident_batches`` synthetic batches
that live on the device: ``rows`` rows of ``seq_len`` ids, uniform
over the held vocabulary from the seed, a game separator every
``game_length`` tokens, the label at each token the next token. No
document mask: attention crosses game boundaries, as packed training
does. Steps are dispatched back to back and the host blocks once per
``steps_per_block``, adding the block's routing counts to the
program's counters; the window is whole blocks, a position is one
token, and the rate is positions over the window's wall time.

``correct`` compares what the timed program produced at the timed
sizes with ``chipbench/reference_laguna.py`` (float32, ``highest``,
in blocks) on the first step's augmented batch: its loss; the logits
of a seeded sample of positions, half before index 512 and half
after 4,096; and the first step's update of a sample of leaves
against ``−lr ×`` the reference's gradient. The reference runs
before the step's program is loaded, while the chip still has room
for it. Beside them it counts, and does not limit, the top-k choices
of each sparse layer's router that differ from the reference's: what
a tenth of error on a router's or an expert's gradient is made of.
"""

from __future__ import annotations

import math
import time

#: keys of a configuration file that say nothing to the network
NOT_KWARGS = ("name", "source", "stands_for", "class", "board",
              "compute_dtype", "param_dtype", "float32_parts",
              "published", "reduced", "assumed", "bytes")
#: embedding rows whose update is compared (the first ids: board
#: points, which the augmentation moves among themselves)
EMBED_ROWS = 256


def spec_kwargs(config: dict) -> dict:
    """``SeqPolicy``'s kwargs from a configuration file: the published
    keys at their published values, and what the file's reduced keys
    hold as the held share."""
    kw = {k: v for k, v in config.items() if k not in NOT_KWARGS}
    kw.update(config["published"],
              layers_held=config["num_hidden_layers"],
              experts_held=config["num_experts"],
              vocab_held=config["vocab_size"])
    return kw


def sampled_leaves(kw: dict) -> list:
    """Paths of the leaves whose first update is checked: a router,
    the held experts' three stacks (one expert of each is compared),
    ``q_proj`` of a sliding layer, the gate of a full layer, the
    embedding."""
    kinds, mlps = kw["layer_types"], kw["mlp_layer_types"]
    held = range(kw["layers_held"])
    sparse = next(i for i in held if mlps[i] == "sparse")
    sliding = next(i for i in held if kinds[i] == "sliding_attention")
    full = max(i for i in held if kinds[i] == "full_attention")
    ffn = (f"layer{sparse}", "ffn")
    return [ffn + ("router",), ffn + ("experts_gate",),
            ffn + ("experts_up",), ffn + ("experts_down",),
            (f"layer{sliding}", "attn", "q_proj"),
            (f"layer{full}", "attn", "gate_proj"), ("embed",)]


def choice_flips(chosen, masks) -> list:
    """Per sparse layer, the share of the program's token–expert
    choices (``chosen`` ``[T, top_k]`` each) that the reference did
    not make (``masks`` bool ``[layers, T, num_experts]``)."""
    import numpy as np

    return [1.0 - float(np.take_along_axis(
        mask, np.asarray(got), axis=1).mean())
        for got, mask in zip(chosen, masks)]


def cut(name: str, leaf, expert: int):
    """The part of a sampled leaf that is compared."""
    if "/experts_" in name:
        return leaf[expert]
    return leaf[:EMBED_ROWS] if name == "embed" else leaf


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.t = ctx.traffic

    # ------------------------------------------------------- set-up

    def _batches(self, vocab: int, separator: int) -> list:
        """``resident_batches`` × (ids, next ids), ``[rows, seq_len]``
        int32 on the device."""
        import jax
        import numpy as np

        t = self.t
        rng = np.random.default_rng(self.ctx.seed + 1)
        n, rows, seq = int(t["resident_batches"]), self.rows, self.seq
        tokens = rng.integers(0, vocab, (n * rows, seq + 1),
                              dtype=np.int32)
        low, high = t["game_length"]
        for row in tokens:
            at = int(rng.integers(0, high))     # mid-game at the start
            while at <= seq:
                row[at] = separator
                at += int(rng.integers(low, high + 1))
        return [(jax.device_put(tokens[i * rows:(i + 1) * rows, :-1]),
                 jax.device_put(tokens[i * rows:(i + 1) * rows, 1:]))
                for i in range(n)]

    def prepare(self) -> None:
        """Weights and batches from the seed, and what ``correct``
        compares on: the first step's augmented batch, the sampled
        positions, the sampled leaves."""
        import jax
        import numpy as np

        from rocalphago_tpu.io.checkpoint import pack_rng, unpack_rng
        from rocalphago_tpu.models.seqpolicy import SeqPolicy
        from rocalphago_tpu.training.symmetries import (
            random_transform_batch,
        )

        ctx, t = self.ctx, self.t
        self.rows, self.seq = int(t["rows"]), int(t["seq_len"])
        self.kw = kw = spec_kwargs(ctx.config)
        self.size = size = int(ctx.config["board"])
        seed = ctx.seed % (2 ** 31)
        with ctx.phase("weights"):
            self.net = SeqPolicy(board=size, seed=seed, **kw)
            jax.block_until_ready(self.net.params)
        with ctx.phase("batches"):
            self.batches = self._batches(kw["vocab_held"],
                                         size * size + 1)
        self.rng = pack_rng(jax.random.key(seed + 3))
        # the inputs the first step sees: the step splits its key once
        # and augments with the second half; the augmentation (a data
        # transform) is the program's, everything after it is compared
        ids, labels = self.batches[0]
        if t["symmetries"]:
            _, sub = jax.random.split(unpack_rng(self.rng))
            ids, labels = jax.jit(
                lambda k, a, b: random_transform_batch(k, a, b, size))(
                    sub, ids, labels)
        self.first_batch = ids, labels
        pick = np.random.default_rng(ctx.seed + 4)
        n = int(t["sample_positions"])
        near = min(512, self.seq // 2)      # inside every window
        far = self.seq // 2                 # past 4,096 at 8k
        cols = np.concatenate([
            pick.integers(0, near, n // 2),
            pick.integers(far, self.seq, n - n // 2)])
        self.sample = pick.integers(0, self.rows, n), cols
        self.expert = int(pick.integers(0, kw["experts_held"]))
        self.paths = sampled_leaves(kw)

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from chipbench import reference_laguna as reference
        from rocalphago_tpu.models.seqpolicy import chosen_experts
        from rocalphago_tpu.training import sl

        self.prepare()
        ctx, net, paths = self.ctx, self.net, self.paths
        (ids, labels), (rows, cols) = self.first_batch, self.sample
        self.block = int(self.t["steps_per_block"])
        cfg = sl.SLConfig()
        self.lr = cfg.learning_rate
        tx = sl.make_optimizer(cfg)
        self.step = jax.jit(
            sl.make_train_step(net.module.apply, tx, self.size,
                               bool(self.t["symmetries"])),
            donate_argnums=(0,))
        with ctx.phase("reference"):
            self.ref = self.reference()
        with ctx.phase("program_logits"):
            def program(p, i, r, c):
                (logits, _), kept = net.module.apply(
                    p, i, mutable=["intermediates"])
                return logits[r, c], chosen_experts(kept)

            logits, chosen = jax.jit(program)(net.params, ids, rows,
                                              cols)
            self.logits = np.asarray(logits)
            self.chosen = [np.asarray(chosen[k])
                           for k in sorted(
                               chosen, key=lambda k: int(k[5:]))]
        self.before = {
            k: np.asarray(cut(k, v, self.expert))
            for k, v in reference.pick(net.params, paths).items()}
        # the step donates its state: the net's own weights go in (a
        # copy would be 4.5 GB more), and nothing reads them after
        params, net.params = net.params, None
        self.state = sl.SLState(params, tx.init(params), jnp.int32(0),
                                self.rng)
        self.steps = 0
        with ctx.phase("first_steps"):
            first = self._block(1)[0]
            self.after = {
                k: np.asarray(cut(k, v, self.expert))
                for k, v in reference.pick(self.state.params,
                                           paths).items()}
            # a second call must find the first's program
            self.first = dict(first, second_loss=self._block(1)[0]["loss"])

    def reference(self, dtype=None) -> dict:
        """The reference's loss on the first batch, its logits at the
        sampled positions, its gradient of the sampled leaves (cut
        to what is compared) and its routers' choices, on the host.
        ``dtype`` is for ``chipbench/lowered_reading.py`` alone."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from chipbench import reference_laguna as reference

        kw, dtype = self.kw, dtype or jnp.float32
        params, (rows, cols) = self.net.params, self.sample

        # the sampled positions go in as arguments: a program that
        # held them as constants would be another program for every
        # seed, and compile (minutes, at this size) in every run
        def ref(leaves, params, ids, labels, rows, cols):
            logits, chosen = reference.forward(
                reference.put(params, leaves), ids, kw, blocks=True,
                dtype=dtype, choices=True)
            return (reference.loss_of(logits, labels),
                    (logits[rows, cols], chosen))

        (loss, (logits, chosen)), grads = jax.jit(
            jax.value_and_grad(ref, has_aux=True))(
                reference.pick(params, self.paths), params,
                *self.first_batch, rows, cols)
        return {"loss": float(loss),
                "logits": np.asarray(logits, np.float32),
                "choices": np.asarray(chosen).reshape(
                    chosen.shape[0], -1, chosen.shape[-1]),
                "grads": {k: np.asarray(cut(k, g, self.expert),
                                        np.float32)
                          for k, g in grads.items()}}

    # ------------------------------------------------------ the window

    def _block(self, steps: int) -> list:
        """``steps`` steps back to back, then one block; the steps'
        metrics on the host, their routing counts added to the
        program's counters."""
        import jax

        from rocalphago_tpu.training import sl

        metrics = []
        with self.ctx.span("chipbench.dispatch"):
            for _ in range(steps):
                ids, labels = self.batches[
                    self.steps % len(self.batches)]
                self.state, m = self.step(self.state, ids, labels)
                metrics.append(m)
                self.steps += 1
        with self.ctx.span("chipbench.block"):
            metrics = [{k: v.item() for k, v in m.items()}
                       for m in jax.device_get(metrics)]
        sl.record_routing(metrics)
        return metrics

    def window(self, seconds: float, on_start=None) -> dict:
        if on_start is not None:
            on_start()
        metrics, ends = [], []
        with self.ctx.span("chipbench.window"):
            started_at, t0 = time.time(), time.monotonic()
            while True:
                metrics.extend(self._block(self.block))
                ends.append(time.monotonic() - t0)
                if ends[-1] >= seconds:
                    break
            elapsed = ends[-1]
        blocks = len(ends)
        # a block is seconds long here, so one stall of the shared
        # host shows in the rate: say which block, for the reader of
        # a slow run
        took = [b - a for a, b in zip([0.0] + ends, ends)]
        steps = blocks * self.block
        losses = [m["loss"] for m in metrics]
        bad = sum(1 for x in losses if not math.isfinite(x))
        return {"started_at": started_at, "elapsed_s": elapsed,
                "steps": steps,
                "positions": steps * self.rows * self.seq,
                "block_s_median": sorted(took)[len(took) // 2],
                "block_s_max": max(took),
                "slowest_block": took.index(max(took)),
                "losses": losses,
                "dropped": sum(m["moe_dropped"] for m in metrics),
                "attempted": steps, "failed": bad}

    def end_to_end(self, raw: dict) -> dict:
        return {"train_positions_per_s":
                raw["positions"] / raw["elapsed_s"]}

    # ----------------------------------------------------- the checks

    def verify(self, raw: dict) -> tuple:
        import numpy as np

        from chipbench import reference_laguna as reference

        problems = []
        ref, first = self.ref, self.first
        loss_err = abs(first["loss"] - ref["loss"]) / abs(ref["loss"])
        if not loss_err <= reference.LOSS_TOLERANCE:
            problems.append(
                f"first-step loss {first['loss']:.6f} differs from the "
                f"reference's {ref['loss']:.6f} by {loss_err:.4%} "
                f"(tolerance {reference.LOSS_TOLERANCE:.2%})")
        rows = [reference.relative_error(a, b)
                for a, b in zip(self.logits, ref["logits"])]
        median, worst = float(np.median(rows)), max(rows)
        if not median <= reference.LOGITS_MEDIAN_TOLERANCE:
            problems.append(
                f"sampled logit rows: median relative error "
                f"{median:.4f} (tolerance "
                f"{reference.LOGITS_MEDIAN_TOLERANCE})")
        updates = {}
        for name, want in ref["grads"].items():
            updates[name] = u = reference.update_error(
                self.before[name], self.after[name], want, self.lr)
            limit = reference.grad_tolerance(name)
            if not u["excess"] <= limit:
                problems.append(
                    f"first update of {name}: (new − old) / −lr "
                    f"differs from the reference's gradient by "
                    f"{u['excess']:.4f} beyond float32 storage "
                    f"rounding ({u['raw']:.4f} with it; tolerance "
                    f"{limit})")
        dropped = raw["dropped"] + first["moe_dropped"]
        if dropped:
            problems.append(f"{dropped} routed pairs were dropped")
        if raw["failed"] or not math.isfinite(first["loss"]):
            problems.append(f"{raw['failed']} non-finite losses in the "
                            f"window; first loss {first['loss']}")
        readings = {
            "first_loss": first["loss"], "reference_loss": ref["loss"],
            "first_loss_rel_err": loss_err,
            "second_loss": first["second_loss"],
            "last_loss": raw["losses"][-1],
            "logit_rows": len(rows), "logit_rows_median_err": median,
            "logit_rows_max_err": worst,
            "logit_rows_err_near": float(np.median(rows[:len(rows) // 2])),
            "logit_rows_err_far": float(np.median(rows[len(rows) // 2:])),
            "update_errs": updates, "expert_compared": self.expert,
            "router_choice_flips": choice_flips(self.chosen,
                                                ref["choices"]),
            "first_step_routing": {k: v for k, v in first.items()
                                   if k.startswith("moe_")},
            "dropped_in_window": raw["dropped"]}
        return problems, readings

    def close(self) -> None:
        pass
