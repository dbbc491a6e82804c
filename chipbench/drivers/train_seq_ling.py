"""Supervised training steps of the move-sequence policy built from a
``bailing_hybrid`` spec (Kimi delta attention in five layers of six
beside latent attention, a group-limited sigmoid router with a
selection bias) through ``training/sl.py`` — ``drivers/train_seq.py``'s
window, batches and blocks, unchanged: the same ``make_train_step``
with ``make_optimizer(SLConfig())``, state donated, a position one
token. The held stage has no multi-token-prediction module (its
published loss weight is 0 and it sits on the last pipeline stage),
so there is one head and one loss.

What differs is what ``correct`` compares, against
``chipbench/reference_ling.py`` (float32, ``highest``, the delta rule
token by token, in blocks, run before the step's program is loaded)
on the first step's augmented batch: the first step's loss; the
logits of a seeded sample of positions, half before index 512 and
half after 4,096 — a state carried wrongly across chunks shows in
the second half; and the first step's update of a sample of leaves —
a router, one held expert's three matrices, one delta layer's
``f_proj``, ``A_log``, ``b_proj``, ``k_proj`` and ``k_conv``, the
latent layer's ``q_proj`` and ``gate_proj``, embedding rows — against
``−lr ×`` the reference's gradient; 0 dropped pairs; no compile in
the window (``run.py``). Beside them it counts, and does not limit,
the routers' top-k choices that differ from the reference's.

**The selection bias is balanced in set-up**, as in
``drivers/train_seq_xing.py`` and for its reason: ``BIAS_STEPS`` steps
of the step's own rule (``seqpolicy.bias_step``) over all experts of
every expert layer, from the program's own choices on the first
batch, before anything is compared or timed; the reference reads the
same biases from the same tree.
"""

from __future__ import annotations

import math

from chipbench.drivers import train_seq
from chipbench.drivers.train_seq import NOT_KWARGS, choice_flips, cut
from chipbench.drivers.train_seq_xing import BIAS_STEPS, in_order

#: the reduced keys whose PUBLISHED value the spec carries, and the
#: held-share key that takes what the file holds (``first_k_dense_
#: replace`` and ``num_nextn_predict_layers`` are reduced too, and
#: the spec carries them as held: the leading dense layers count
#: once, and the stage has no MTP module)
HELD = {"num_hidden_layers": "layers_held",
        "num_experts": "experts_held", "vocab_size": "vocab_held"}


def spec_kwargs(config: dict) -> dict:
    """``SeqPolicy``'s kwargs from a configuration file."""
    kw = {k: v for k, v in config.items() if k not in NOT_KWARGS}
    for key, held in HELD.items():
        kw[held] = config[key]
        kw[key] = config["published"][key]
    return kw


def sampled_leaves(kw: dict) -> list:
    """Paths of the leaves whose first update is checked. The delta
    layer is the second expert layer (a state that has read a routed
    layer's output); the latent layer is the first of its kind."""
    first = kw["first_k_dense_replace"]
    period = kw["layer_group_size"]
    held = range(kw["layers_held"])
    delta = next(i for i in held if i > first and (i + 1) % period)
    latent = next((i for i in held if (i + 1) % period == 0), None)
    ffn = (f"layer{first}", "ffn")
    paths = [ffn + ("router",), ffn + ("experts_gate",),
             ffn + ("experts_up",), ffn + ("experts_down",)]
    paths += [(f"layer{delta}", "attn", name) for name in
              ("f_proj", "A_log", "b_proj", "k_proj", "k_conv")]
    if latent is not None:
        paths += [(f"layer{latent}", "attn", name)
                  for name in ("q_proj", "gate_proj")]
    return paths + [("embed",)]


class Driver(train_seq.Driver):

    def prepare(self) -> None:
        """As the parent's, with this spec's kwargs and leaves, and
        the routers' biases balanced."""
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

        def program(p, i, r, c):
            (logits, _), kept = self.net.module.apply(
                p, i, mutable=["intermediates"])
            return logits[r, c], chosen_experts(kept)

        # one program for the balancing and for the sampled logits
        self.forward = jax.jit(program)
        with ctx.phase("balance"):
            self.balanced = self.balance()

    def balance(self) -> dict:
        """``BIAS_STEPS`` steps of the published rule on the first
        batch; per layer, busiest expert's load ÷ mean, before, after."""
        import jax.numpy as jnp
        import numpy as np

        from chipbench import reference_ling as reference
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

        from chipbench import reference_ling as reference
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
            self.chosen = [np.asarray(c) for c in in_order(chosen)]
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

    def reference(self, dtype=None) -> dict:
        """The reference's loss on the first batch, its logits at the
        sampled positions, its gradient of the sampled leaves (cut to
        what is compared) and its routers' choices, on the host.
        ``dtype`` is for ``chipbench/lowered_reading_ling.py`` alone."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from chipbench import reference_ling as reference

        kw, dtype = self.kw, dtype or jnp.float32
        params, (rows, cols) = self.net.params, self.sample

        # the sampled positions go in as arguments: constants would
        # make another program of every seed
        def ref(leaves, params, ids, labels, rows, cols):
            (logits, ahead), chosen = reference.forward(
                reference.put(params, leaves), ids, labels, kw,
                blocks=True, dtype=dtype, choices=True)
            total, _ = reference.loss_of(logits, ahead, labels, kw)
            return total, (logits[rows, cols], chosen)

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

    # ----------------------------------------------------- the checks

    def verify(self, raw: dict) -> tuple:
        import numpy as np

        from chipbench import reference_ling as reference

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
        half = len(rows) // 2
        median = float(np.median(rows))
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
            "logit_rows_max_err": max(rows),
            "logit_rows_err_near": float(np.median(rows[:half])),
            "logit_rows_err_far": float(np.median(rows[half:])),
            "update_errs": updates, "expert_compared": self.expert,
            "router_choice_flips": choice_flips(self.chosen,
                                                ref["choices"]),
            "busiest_over_mean_load": self.balanced,
            "first_step_routing": {k: v for k, v in first.items()
                                   if k.startswith("moe_")},
            "dropped_in_window": raw["dropped"]}
        return problems, readings
