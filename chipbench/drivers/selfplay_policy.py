"""Raw-policy self-play through ``make_selfplay_chunked``.

The RL stage's generator (``bench.py``'s path): ``batch`` games in
lockstep, both sides the policy net, encode → forward → sample →
rules step fused in one scan segment per ``chunk`` plies, segments
driven through the cell's own ``ChunkPipeline``.

Taken over from ``bench.py``: mid-game seeding through
``initial_states`` (empty boards hide the ladder-chase cost) and a
warm-up of exactly the programs the timed reps dispatch. Here the
seeding covers a whole game's phase mix: start depths are evenly
spaced over ``start_depth_min..start_depth_max``, the positions come
from uniform random legal play on the device engine under the mix's
own ``position_seed`` — one corpus for every run — and the run's seed
deals them to the batch's slots in another order.

A rep plays ``plies_per_rep`` plies from those states under a fresh
key. The window is whole reps, closed at the first rep boundary
after ``--seconds``; the rate is live plies (plies of games not yet
ended) over the window's wall time.
"""

from __future__ import annotations

import functools
import time

import numpy as np


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.t = ctx.traffic

    # ------------------------------------------------------- set-up

    def setup(self) -> None:
        import jax

        from chipbench.nets import build_net
        from rocalphago_tpu.engine.jaxgo import GoConfig
        from rocalphago_tpu.runtime.pipeline import ChunkPipeline
        from rocalphago_tpu.search.selfplay import make_selfplay_chunked

        ctx, t = self.ctx, self.t
        self.batch = int(t["batch"])
        self.plies = int(t["plies_per_rep"])
        self.cfg = GoConfig(size=ctx.config["board"])
        with ctx.phase("weights"):
            self.net = build_net(ctx.config, "policy", ctx.seed)
            jax.block_until_ready(self.net.params)
        with ctx.phase("positions"):
            self._deal_positions()
        apply = self.net.module.apply
        self.run = make_selfplay_chunked(
            self.cfg, self.net.feature_list, apply, apply, self.batch,
            self.plies, chunk=int(t["chunk"]),
            temperature=float(t["temperature"]),
            score_on_device=False)
        self.pipe = ChunkPipeline(runner="chipbench_selfplay")
        self.rep = 0
        with ctx.phase("warm_rep"):
            # exactly the timed programs: the segment, the copy of
            # the initial states, the finish and the live-ply count
            self._rep()

    def _deal_positions(self) -> None:
        """Staggered mid-game states, and the moves that led there."""
        import jax
        import jax.numpy as jnp

        from rocalphago_tpu.engine.jaxgo import (
            legal_mask,
            new_states,
            step,
            vgroup_data,
        )

        cfg, t, batch = self.cfg, self.t, self.batch
        lo, hi = int(t["start_depth_min"]), int(t["start_depth_max"])
        depths = np.linspace(lo, hi, batch).round().astype(np.int32)
        # the positions are one corpus for every seed (dealt under the
        # mix's own key): the ladder work a rep starts from must not
        # change with the seed, or the seed changes the work. The
        # run's seed deals the corpus to batch slots in another order
        # and draws the weights and every sampled move.
        order = np.random.default_rng(self.ctx.seed).permutation(batch)
        vstep = jax.vmap(functools.partial(step, cfg))
        vlegal = jax.vmap(functools.partial(legal_mask, cfg))
        vgd = vgroup_data(cfg, with_zxor=cfg.enforce_superko)

        @jax.jit
        def deal(key, depths, order):
            def ply(carry, i):
                states, key = carry
                key, sub = jax.random.split(key)
                gd = vgd(states)
                legal = vlegal(states, gd)[:, :-1]
                action = jnp.where(
                    legal.any(-1),
                    jax.random.categorical(
                        sub, jnp.where(legal, 0.0, -1e30), axis=-1),
                    cfg.num_points).astype(jnp.int32)
                moved = vstep(states, action, gd)
                go = i < depths
                states = jax.tree.map(
                    lambda new, old: jnp.where(
                        go.reshape((-1,) + (1,) * (new.ndim - 1)),
                        new, old), moved, states)
                return (states, key), jnp.where(go, action, -1)

            (states, _), actions = jax.lax.scan(
                ply, (new_states(cfg, batch), key), jnp.arange(hi))
            return (jax.tree.map(lambda x: x[order], states),
                    actions[:, order])

        key = jax.random.key(int(t["position_seed"]))
        self.states, self.prefix = deal(key, jnp.asarray(depths),
                                        jnp.asarray(order))
        jax.block_until_ready(self.states)  # prefix: [hi, B], -1 = none

    # ------------------------------------------------------- window

    def _rep(self):
        import jax

        self.rep += 1
        key = jax.random.fold_in(
            jax.random.key(self.ctx.seed % (2 ** 31)), self.rep)
        with self.ctx.span("chipbench.dispatch"):
            res = self.run(self.net.params, self.net.params, key,
                           initial_states=self.states,
                           pipeline=self.pipe)
        with self.ctx.span("chipbench.block"):
            live = int(jax.device_get(res.num_moves.sum()))
        return res, live

    def window(self, seconds: float, on_start=None) -> dict:
        if on_start is not None:
            on_start()
        # a rep leaves its last segment registered (the runner closes
        # the accounting window without blocking); it is long done
        self.pipe.drain()
        self.pipe.reset_stats()
        reps = plies = 0
        rep_s = []
        with self.ctx.span("chipbench.window"):
            started_at, t0 = time.time(), time.monotonic()
            while True:
                t_rep = time.monotonic()
                res, live = self._rep()
                now = time.monotonic()
                rep_s.append(now - t_rep)
                reps += 1
                plies += live
                if now - t0 >= seconds:
                    break
            elapsed = now - t0
        return {"started_at": started_at, "elapsed_s": elapsed,
                "reps": reps, "live_plies": plies,
                "rep_max_s": max(rep_s), "last": res,
                "attempted": reps * self.batch, "failed": 0,
                "host_gap_frac": self.pipe.host_gap_frac}

    def end_to_end(self, raw: dict) -> dict:
        return {"selfplay_plies_per_s":
                raw["live_plies"] / raw["elapsed_s"]}

    # ------------------------------------------------------- checks

    def encode(self):
        """The encode the fused ply runs, jitted alone on the cell's
        states: ``vgroup_data`` + ``batched_encoder``. Built once;
        the correctness check and the encode span share it."""
        if getattr(self, "_encode", None) is None:
            import jax

            from rocalphago_tpu.engine.jaxgo import vgroup_data
            from rocalphago_tpu.features.planes import (
                batched_encoder,
                needs_member,
            )

            feats = self.net.feature_list
            vgd = vgroup_data(self.cfg, with_member=needs_member(feats),
                              with_zxor=self.cfg.enforce_superko)
            enc = batched_encoder(self.cfg, feats)
            self._encode = jax.jit(lambda s: enc(s, vgd(s)))
        return self._encode

    def verify(self, raw: dict) -> tuple:
        """A seeded sample of games replayed on the host engine:
        every dealt move and every self-play move of the last rep is
        legal there, the host's boards equal the device's before and
        after the rep, and the device encode of the start position
        equals the host oracle's by ``chip_smoke.py``'s rule (exact
        off the ladder planes, under 1 % of cells on them)."""
        import jax

        from chipbench.reference import check_nets
        from rocalphago_tpu.engine import pygo
        from rocalphago_tpu.features import pyfeatures

        problems, readings = check_nets({"policy": self.net},
                                        self.ctx.seed)
        size, n = self.cfg.size, self.cfg.num_points
        rng = np.random.default_rng(self.ctx.seed + 1)
        sample = sorted(rng.choice(
            self.batch, size=min(int(self.t["verify_games"]),
                                 self.batch), replace=False).tolist())
        prefix = np.asarray(jax.device_get(self.prefix))
        start_boards = np.asarray(jax.device_get(self.states.board))
        res = raw["last"]
        actions = np.asarray(jax.device_get(res.actions))
        live = np.asarray(jax.device_get(res.live))
        final_boards = np.asarray(jax.device_get(res.final.board))
        planes = np.asarray(jax.device_get(
            self.encode()(self.states)), np.float32)
        feats = self.net.feature_list
        ladder = np.concatenate([
            np.full(pyfeatures.FEATURE_PLANES[f],
                    f in pyfeatures.LADDER_FEATURES) for f in feats])
        ladder_diff = []

        def play(st, action, what):
            move = None if action == n else divmod(int(action), size)
            if not st.is_legal(move):
                problems.append(f"game {g}: {what} move {move} is "
                                "illegal on the host engine")
                return False
            st.do_move(move)
            return True

        for g in sample:
            st = pygo.GameState(size=size, komi=self.cfg.komi)
            ok = all(play(st, a, "dealt")
                     for a in prefix[:, g] if a >= 0)
            if not ok:
                continue
            if (st.board.reshape(-1) != start_boards[g]).any():
                problems.append(f"game {g}: start board differs from "
                                "the host replay")
                continue
            ora = pyfeatures.state_to_planes(st, feats)
            diff = planes[g] != ora
            if diff[..., ~ladder].any():
                bad = np.argwhere(diff[..., ~ladder])[:3].tolist()
                problems.append(f"game {g}: device encode != host "
                                f"oracle at [x, y, plane] {bad}")
            if ladder.any():
                ladder_diff.append(float(diff[..., ladder].mean()))
            ok = all(play(st, a, "self-play")
                     for a, lv in zip(actions[:, g], live[:, g]) if lv)
            if ok and (st.board.reshape(-1) != final_boards[g]).any():
                problems.append(f"game {g}: final board differs from "
                                "the host replay")
        if ladder_diff:
            rate = float(np.mean(ladder_diff))
            readings["ladder_plane_disagreement"] = rate
            if not rate < 0.01:
                problems.append(
                    f"ladder planes disagree with the host oracle on "
                    f"{rate:.2%} of cells (bound 1%)")
        readings["verified_games"] = len(sample)
        return problems, readings

    def close(self) -> None:
        pass
