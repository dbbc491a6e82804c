"""Supervised training steps of the move-sequence policy built from an
``xing4_0`` spec (latent attention, hyper-connections, a sigmoid
router with a selection bias, a multi-token-prediction module) through
``training/sl.py`` — ``drivers/train_seq.py``'s window, batches and
blocks, unchanged: the same ``make_train_step`` with
``make_optimizer(SLConfig())``, state donated, a position one token.

What differs is what ``correct`` compares, against
``chipbench/reference_xing.py`` (float32, ``highest``, in blocks, run
before the step's program is loaded) on the first step's augmented
batch: the first step's loss (main + λ · MTP); the logits of a seeded
sample of positions of BOTH heads, half before index 512 and half
after 4,096; and the first step's update of a sample of leaves — a
router, one held expert's three matrices, ``q_b_proj`` and
``kv_b_proj`` of the last trunk layer, one sublayer's ``phi_res``,
``mtp_eh_proj``, embedding rows — against ``−lr ×`` the reference's
gradient; 0 dropped pairs; no compile in the window (``run.py``).
Beside them it counts, and does not limit, the routers' top-k choices
that differ from the reference's, the MTP block's last.

**The selection bias is balanced in set-up.** A router of random
weights prefers some experts to others for every token alike: the
busiest expert took twice the mean, the eight held experts' load
swung 5–7 % with the seed and the rate 0.7 % with it (PERF.md §6,
PR 30). The step's own rule (``seqpolicy.bias_step``, 0.001 a step)
evens that within some sixty steps, a window's worth; a run some
hundred steps in has no such router. So set-up applies that rule
``BIAS_STEPS`` times (``b_e += 0.001 · sign(mean load − load_e)``
over all experts of every expert layer, from the program's own
choices on the first batch) before anything is compared or timed;
the reference reads the same biases from the same tree.
"""

from __future__ import annotations

import math

from chipbench.drivers import train_seq
from chipbench.drivers.train_seq import NOT_KWARGS, choice_flips, cut

#: the reduced keys whose PUBLISHED value the spec carries, and the
#: held-share key that takes what the file holds (``first_k_dense_
#: replace`` is reduced too, and the spec carries it as held: the
#: leading dense layers count once)
HELD = {"num_hidden_layers": "layers_held",
        "n_routed_experts": "experts_held", "vocab_size": "vocab_held"}


def spec_kwargs(config: dict) -> dict:
    """``SeqPolicy``'s kwargs from a configuration file."""
    kw = {k: v for k, v in config.items() if k not in NOT_KWARGS}
    for key, held in HELD.items():
        kw[held] = config[key]
        kw[key] = config["published"][key]
    return kw


def sampled_leaves(kw: dict) -> list:
    """Paths of the leaves whose first update is checked. The
    ``phi_res`` is the first expert layer's attention sublayer's: the
    streams differ by then, and it is not the last sublayer before a
    sum of the streams, whose ``H_res`` has no gradient at all."""
    first = kw["first_k_dense_replace"]
    last = kw["layers_held"] - 1
    ffn = (f"layer{first}", "ffn")
    return [ffn + ("router",), ffn + ("experts_gate",),
            ffn + ("experts_up",), ffn + ("experts_down",),
            (f"layer{last}", "attn", "q_b_proj"),
            (f"layer{last}", "attn", "kv_b_proj"),
            (f"layer{first}", "attn_hc", "phi_res"),
            ("mtp_eh_proj",), ("embed",)]


#: how many steps of the published rule (``seqpolicy.bias_step``)
#: set-up takes: a preference of 0.25 of the logits' spread is
#: evened in about sixty
BIAS_STEPS = 128


def in_order(chosen: dict) -> list:
    """``chosen_experts``' arrays, the trunk's layers in order and
    the MTP block's last — the reference's order."""
    return [chosen[k] for k in sorted(
        chosen, key=lambda k: (not k.startswith("layer"), k))]


class Driver(train_seq.Driver):

    def prepare(self) -> None:
        """As the parent's, with this spec's kwargs and leaves."""
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

        def program(p, i, nxt, r, c):
            (logits, extras), kept = self.net.module.apply(
                p, i, nxt, mutable=["intermediates"])
            return (logits[r, c], extras["mtp_logits"][r, c],
                    chosen_experts(kept))

        # one program for the balancing and for the sampled logits
        self.forward = jax.jit(program)
        with ctx.phase("balance"):
            self.balanced = self.balance()

    def balance(self) -> dict:
        """``BIAS_STEPS`` steps of the published rule on the first
        batch; per layer, busiest expert's load ÷ mean, before, after."""
        import jax.numpy as jnp
        import numpy as np

        from chipbench import reference_xing as reference
        from rocalphago_tpu.models import seqpolicy

        net, experts = self.net, self.kw["n_routed_experts"]
        account = {}
        for turn in range(BIAS_STEPS + 1):
            *_, chosen = self.forward(net.params, *self.first_batch,
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

        from chipbench import reference_xing as reference
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
            logits, ahead, chosen = self.forward(
                net.params, ids, labels, rows, cols)
            self.logits = {"main": np.asarray(logits),
                           "mtp": np.asarray(ahead)}
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
        """The reference's loss (and its MTP part) on the first
        batch, both heads' logits at the sampled positions, its
        gradient of the sampled leaves (cut to what is compared) and
        its routers' choices, on the host. ``dtype`` is for
        ``chipbench/lowered_reading_xing.py`` alone."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from chipbench import reference_xing as reference

        kw, dtype = self.kw, dtype or jnp.float32
        params, (rows, cols) = self.net.params, self.sample

        # the sampled positions go in as arguments: constants would
        # make another program of every seed
        def ref(leaves, params, ids, labels, rows, cols):
            (logits, ahead), chosen = reference.forward(
                reference.put(params, leaves), ids, labels, kw,
                blocks=True, dtype=dtype, choices=True)
            total, mtp = reference.loss_of(logits, ahead, labels)
            return total, (mtp, logits[rows, cols], ahead[rows, cols],
                           chosen)

        (loss, (mtp, logits, ahead, chosen)), grads = jax.jit(
            jax.value_and_grad(ref, has_aux=True))(
                reference.pick(params, self.paths), params,
                *self.first_batch, rows, cols)
        return {"loss": float(loss), "mtp_loss": float(mtp),
                "logits": {"main": np.asarray(logits, np.float32),
                           "mtp": np.asarray(ahead, np.float32)},
                "choices": np.asarray(chosen).reshape(
                    chosen.shape[0], -1, chosen.shape[-1]),
                "grads": {k: np.asarray(cut(k, g, self.expert),
                                        np.float32)
                          for k, g in grads.items()}}

    # ----------------------------------------------------- the checks

    def verify(self, raw: dict) -> tuple:
        import numpy as np

        from chipbench import reference_xing as reference

        problems = []
        ref, first = self.ref, self.first
        loss_err = abs(first["loss"] - ref["loss"]) / abs(ref["loss"])
        if not loss_err <= reference.LOSS_TOLERANCE:
            problems.append(
                f"first-step loss {first['loss']:.6f} differs from the "
                f"reference's {ref['loss']:.6f} by {loss_err:.4%} "
                f"(tolerance {reference.LOSS_TOLERANCE:.2%})")
        heads = {}
        for head, want in ref["logits"].items():
            rows = [reference.relative_error(a, b)
                    for a, b in zip(self.logits[head], want)]
            half = len(rows) // 2
            heads[head] = {
                "rows": len(rows), "median_err": float(np.median(rows)),
                "max_err": max(rows),
                "err_near": float(np.median(rows[:half])),
                "err_far": float(np.median(rows[half:]))}
            limit = reference.LOGITS_MEDIAN_TOLERANCE[head]
            if not heads[head]["median_err"] <= limit:
                problems.append(
                    f"sampled logit rows of the {head} head: median "
                    f"relative error {heads[head]['median_err']:.4f} "
                    f"(tolerance {limit})")
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
            "first_mtp_loss": first.get("mtp_loss"),
            "reference_mtp_loss": ref["mtp_loss"],
            "second_loss": first["second_loss"],
            "last_loss": raw["losses"][-1],
            "logit_rows": heads,
            "update_errs": updates, "expert_compared": self.expert,
            "router_choice_flips": choice_flips(self.chosen,
                                                ref["choices"]),
            "busiest_over_mean_load": self.balanced,
            "first_step_routing": {k: v for k, v in first.items()
                                   if k.startswith("moe_")},
            "dropped_in_window": raw["dropped"]}
        return problems, readings
