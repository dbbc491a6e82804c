"""Supervised training steps of the move-sequence policy built from an
``lfm2_moe`` spec (gated short convolutions in three layers of four
beside softmax attention with per-head QK-norm, a sigmoid router with
a selection bias over routed experts and no shared expert, a head
tied to the embedding) through ``training/sl.py`` —
``drivers/train_seq.py``'s window, batches and blocks, unchanged: the
same ``make_train_step`` with ``make_optimizer(SLConfig())``, state
donated, a position one token.

What differs is what ``correct`` compares, against
``chipbench/reference_lfm2.py`` (float32, ``highest``, the convolution
token by token, in blocks, run before the step's program is loaded)
on the first step's augmented batch: one short-convolution expert
layer's gated output ``C ⊙ conv(B ⊙ x̃)``, which the program computes
in float32 (the reference's from the program's own ``[B | C | x̃]``);
the first step's update of a sample of leaves — that layer's
``in_proj``, ``conv`` and ``out_proj``, the first attention layer's
``q_norm`` and ``k_norm`` scales and ``q_proj``, one held expert's
down matrix and the router of the first expert layer, and embedding
rows (the tied leaf: the lookup's gradient and the head's) — against
``−lr ×`` the reference's gradient; 0 dropped pairs; no compile in the
window (``run.py``). Beside them it reports, and does not limit, the
first step's loss, the logits of a seeded sample of positions (half
before index 512 and half after 4,096), the routers' top-k choices
that differ from the reference's, and each step's expert row blocks
that ran (``moe_row_blocks_run``): a chunk's held pairs sit on a
multiple of ``EXPERT_ROW_BLOCK`` at this cell's mean load, so the
count may swing by a block a chunk.

**The selection bias is balanced in set-up** as the ``xing4_0``
driver does it (``train_seq_xing.py``): ``BIAS_STEPS`` steps of the
published rule on the program's own choices on the first batch,
before anything is compared or timed; the reference reads the same
biases from the same tree.
"""

from __future__ import annotations

import math

from chipbench.drivers import train_seq
from chipbench.drivers.train_seq import NOT_KWARGS, choice_flips, cut
from chipbench.drivers.train_seq_xing import BIAS_STEPS

#: the reduced keys whose PUBLISHED value the spec carries, and the
#: held-share key that takes what the file holds
HELD = {"num_hidden_layers": "layers_held", "num_experts": "experts_held",
        "vocab_size": "vocab_held"}


def spec_kwargs(config: dict) -> dict:
    """``SeqPolicy``'s kwargs from a configuration file."""
    kw = {k: v for k, v in config.items() if k not in NOT_KWARGS}
    for key, held in HELD.items():
        kw[held] = config[key]
        kw[key] = config["published"][key]
    return kw


def sampled_leaves(kw: dict) -> list:
    """Paths of the leaves whose first update is checked: the first
    short-convolution layer with experts (its mixer has read an
    attention layer's output), the first attention layer, the first
    expert layer's router and a held expert, the embedding."""
    from chipbench.reference_lfm2 import is_conv, is_sparse

    held = range(kw["layers_held"])
    conv = next(i for i in held if is_conv(i, kw) and is_sparse(i, kw))
    attn = next(i for i in held if not is_conv(i, kw))
    sparse = next(i for i in held if is_sparse(i, kw))
    mixer, softmax = (f"layer{conv}", "attn"), (f"layer{attn}", "attn")
    ffn = (f"layer{sparse}", "ffn")
    return [mixer + ("in_proj",), mixer + ("conv",),
            mixer + ("out_proj",), softmax + ("q_norm", "scale"),
            softmax + ("k_norm", "scale"), softmax + ("q_proj",),
            ffn + ("experts_down",), ffn + ("router",), ("embed",)]


class Driver(train_seq.Driver):

    def prepare(self) -> None:
        """As the parent's, with this spec's kwargs and leaves, and the
        selection biases balanced."""
        import jax
        import numpy as np

        from rocalphago_tpu.io.checkpoint import pack_rng, unpack_rng
        from rocalphago_tpu.models.seqpolicy import (
            SeqPolicy,
            chosen_experts,
        )
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
        ids, labels = self.batches[0]
        if t["symmetries"]:
            _, sub = jax.random.split(unpack_rng(self.rng))
            ids, labels = jax.jit(
                lambda k, a, b: random_transform_batch(k, a, b, size))(
                    sub, ids, labels)
        self.first_batch = ids, labels
        pick = np.random.default_rng(ctx.seed + 4)
        n = int(t["sample_positions"])
        near = min(512, self.seq // 2)
        far = self.seq // 2
        cols = np.concatenate([
            pick.integers(0, near, n // 2),
            pick.integers(far, self.seq, n - n // 2)])
        self.sample = pick.integers(0, self.rows, n), cols
        self.expert = int(pick.integers(0, kw["experts_held"]))
        self.paths = sampled_leaves(kw)
        self.mixer = mixer = self.paths[0][0]
        self.row_blocks = []

        def program(p, i, r, c):
            (logits, _), kept = self.net.module.apply(
                p, i, mutable=["intermediates"])
            return logits[r, c], chosen_experts(kept)

        def mix(p, i):
            _, kept = self.net.module.apply(p, i, mutable=["intermediates"])
            return kept["intermediates"][mixer]["attn"]["mix"][0]

        # one program for the balancing and for the sampled logits
        self.forward = jax.jit(program)
        # the sampled mixer's product and float32 gated output
        self.mix = jax.jit(mix)
        with ctx.phase("balance"):
            self.balanced = self.balance()

    def balance(self) -> dict:
        """``BIAS_STEPS`` steps of the published rule on the first
        batch; per layer, busiest expert's load ÷ mean, before, after."""
        import jax.numpy as jnp
        import numpy as np

        from chipbench import reference_lfm2 as reference
        from rocalphago_tpu.models import seqpolicy

        net, experts = self.net, self.kw["num_experts"]
        account = {}
        for turn in range(BIAS_STEPS + 1):
            _, chosen = self.forward(net.params, self.first_batch[0],
                                     *self.sample)
            loads = {name: np.bincount(np.asarray(picks).ravel(),
                                       minlength=experts)
                     for name, picks in chosen.items()}
            if turn in (0, BIAS_STEPS):
                account["before" if turn == 0 else "after"] = {
                    name: float(load.max() / load.mean())
                    for name, load in loads.items()}
            if turn == BIAS_STEPS:
                return account
            paths = [(name, "ffn", "router_bias") for name in loads]
            biases = reference.pick(net.params, paths)
            net.params = reference.put(net.params, {
                key: bias + seqpolicy.bias_step(
                    jnp.asarray(loads[path[0]]))
                for path, (key, bias) in zip(paths, biases.items())})

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        import numpy as np

        from chipbench import reference_lfm2 as reference
        from rocalphago_tpu.training import sl

        self.prepare()
        ctx, net, paths = self.ctx, self.net, self.paths
        (ids, _), (rows, cols) = self.first_batch, self.sample
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
            logits, chosen = self.forward(net.params, ids, rows, cols)
            self.logits = np.asarray(logits)
            self.chosen = [np.asarray(chosen[k]) for k in sorted(
                chosen, key=lambda k: int(k[5:]))]
        with ctx.phase("mix"):
            bcx, mixed = self.mix(net.params, ids)
            self.mix_err = reference.relative_error(
                mixed, self.reference_mix(bcx))
            del bcx, mixed
        self.before = {
            k: np.asarray(cut(k, v, self.expert))
            for k, v in reference.pick(net.params, paths).items()}
        # the step donates its state: the net's own weights go in
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
            self.first = dict(first,
                              second_loss=self._block(1)[0]["loss"])
        self.row_blocks = []

    def reference(self, dtype=None) -> dict:
        """The reference's loss on the first batch, its logits at the
        sampled positions, its gradient of the sampled leaves (cut to
        what is compared) and its routers' choices, on the host.
        ``dtype`` is for ``chipbench/lowered_reading_lfm2.py``
        alone."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from chipbench import reference_lfm2 as reference

        kw, dtype = self.kw, dtype or jnp.float32
        params, (rows, cols) = self.net.params, self.sample

        # the sampled positions go in as arguments: constants would
        # make another program of every seed
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

    def reference_mix(self, bcx, dtype=None):
        """The reference's gated output of the sampled mixer from the
        program's ``[B | C | x̃]`` ``bcx``; ``dtype`` is for
        ``chipbench/lowered_reading_lfm2.py`` alone."""
        import jax
        import jax.numpy as jnp

        from chipbench import reference_lfm2 as reference

        dtype = dtype or jnp.float32
        taps = self.net.params["params"][self.mixer]["attn"]["conv"]
        return jax.jit(jax.vmap(
            lambda row, t: reference.gated_mix(row, t, dtype),
            (0, None)))(bcx, taps)

    # ------------------------------------------------------ the window

    def _block(self, steps: int) -> list:
        """The parent's block; each step's expert row blocks that ran
        are kept for the checks' account."""
        metrics = super()._block(steps)
        self.row_blocks.extend(m["moe_row_blocks_run"] for m in metrics)
        return metrics

    # ----------------------------------------------------- the checks

    def verify(self, raw: dict) -> tuple:
        import numpy as np

        from chipbench import reference_lfm2 as reference

        problems = []
        ref, first = self.ref, self.first
        if not self.mix_err <= reference.MIX_TOLERANCE:
            problems.append(
                f"{self.mixer}'s gated output differs from the "
                f"reference's on the program's own product by "
                f"{self.mix_err:.3g} (tolerance {reference.MIX_TOLERANCE})")
        loss_err = abs(first["loss"] - ref["loss"]) / abs(ref["loss"])
        rows = [reference.relative_error(a, b)
                for a, b in zip(self.logits, ref["logits"])]
        half = len(rows) // 2
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
        blocks = self.row_blocks
        readings = {
            "first_loss": first["loss"], "reference_loss": ref["loss"],
            "first_loss_rel_err": loss_err, "mix_rel_err": self.mix_err,
            "second_loss": first["second_loss"],
            "last_loss": raw["losses"][-1],
            "logit_rows": len(rows),
            "logit_rows_median_err": float(np.median(rows)),
            "logit_rows_max_err": max(rows),
            "logit_rows_err_near": float(np.median(rows[:half])),
            "logit_rows_err_far": float(np.median(rows[half:])),
            "update_errs": updates, "expert_compared": self.expert,
            "router_choice_flips": choice_flips(self.chosen,
                                                ref["choices"]),
            "busiest_over_mean_load": self.balanced,
            "first_step_routing": {k: v for k, v in first.items()
                                   if k.startswith("moe_")},
            "row_blocks_run_per_step": {
                "steps": len(blocks),
                "counts": {str(n): blocks.count(n)
                           for n in sorted(set(blocks))}},
            "dropped_in_window": raw["dropped"]}
        return problems, readings
