"""Self-play (state, outcome) dataset generator for value training.

The reference has **no** automated generator of the de-correlated
value-net training set — the AlphaGo paper's Step-3 data generation is
left to the user (SURVEY.md §2 "Value trainer", gap [C-HIGH]). This
module fills that gap, on device: following the paper's recipe, each
game samples a random ply U, plays plies ``t < U`` with the SL policy,
plays ply ``U`` uniformly at random over sensible moves, plies
``t > U`` with the RL policy, and records exactly ONE position per
game — the state right after the random move — labelled with the final
game outcome from that position's player-to-move perspective.

TPU-native design: the whole mixed-policy game is one ``lax.scan``
(like :mod:`rocalphago_tpu.search.selfplay`), with the per-game policy
switch as a ``jnp.where`` over the three candidate actions and the
recorded position captured into a snapshot ``GoState`` carry — no
``[T, B, …]`` plane materialization. Snapshots are encoded with the
*value* feature set in one batched call after the scan and written in
the sharded-npz layout the input pipeline reads (``targets:
"outcome"``, z in the ``actions`` slot).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rocalphago_tpu.engine import jaxgo
from rocalphago_tpu.features import Preprocess
from rocalphago_tpu.models.nn_util import NeuralNetBase
from rocalphago_tpu.search.selfplay import sensible_mask


class ValueSamples(NamedTuple):
    recorded: jaxgo.GoState  # batched snapshot states (one per game)
    z: jax.Array             # int32 [B] outcome for the player to move
    valid: jax.Array         # bool  [B] game reached its sample ply
    u: jax.Array             # int32 [B] the game's random-ply index U


def _snapshot(mask: jax.Array, new, old):
    """Per-game select between two batched GoState pytrees."""
    def sel(a, b):
        m = mask.reshape((mask.shape[0],) + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)
    return jax.tree.map(sel, new, old)


def _make_value_ply(cfg: jaxgo.GoConfig, features: tuple,
                    apply_sl: Callable, apply_rl: Callable,
                    temperature: float):
    """Shared one-ply body of the mixed-policy value game (snapshot
    recording + SL/random/RL action switch), parameterized over params
    and the per-game random plies ``U`` so both the monolithic scan
    and the chunked runner trace the identical computation."""
    from rocalphago_tpu.features.planes import (
        batched_encoder,
        needs_member,
    )

    n = cfg.num_points
    vgd = jaxgo.vgroup_data(cfg, with_member=needs_member(features),
                            with_zxor=cfg.enforce_superko)
    enc = batched_encoder(cfg, features)
    vsens = jax.vmap(functools.partial(sensible_mask, cfg))
    vstep = jax.vmap(functools.partial(jaxgo.step, cfg))

    def ply(params_sl, params_rl, U, carry, t):
        states, rec, recorded, rng = carry
        rng, k_sl, k_rl, k_rand = jax.random.split(rng, 4)

        # record BEFORE stepping: ply U+1's pre-state is the position
        # right after the random move U was played
        hit = (t == U + 1) & ~states.done & ~recorded
        rec = _snapshot(hit, states, rec)
        recorded = recorded | hit

        gd = vgd(states)
        planes = enc(states, gd)
        sens = vsens(states, gd)
        neg = jnp.finfo(jnp.float32).min
        logits_sl = apply_sl(params_sl, planes)
        logits_rl = apply_rl(params_rl, planes)
        a_sl = jax.random.categorical(
            k_sl, jnp.where(sens, logits_sl / temperature, neg), axis=-1)
        a_rl = jax.random.categorical(
            k_rl, jnp.where(sens, logits_rl / temperature, neg), axis=-1)
        a_rand = jax.random.categorical(
            k_rand, jnp.where(sens, 0.0, neg), axis=-1)

        board_action = jnp.where(t < U, a_sl,
                                 jnp.where(t == U, a_rand, a_rl))
        must_pass = ~sens.any(axis=-1)
        action = jnp.where(must_pass, n, board_action).astype(jnp.int32)
        return (vstep(states, action, gd), rec, recorded, rng)

    return ply


def _value_u_cap(max_moves: int, u_max: int | None) -> int:
    return min(u_max if u_max is not None else max_moves - 2,
               max_moves - 2)


def _value_finish(cfg: jaxgo.GoConfig, final, rec, recorded,
                  U) -> ValueSamples:
    winners = jax.vmap(functools.partial(jaxgo.winner, cfg))(final)
    z = (winners.astype(jnp.int32)
         * rec.turn.astype(jnp.int32))
    return ValueSamples(rec, z, recorded, U.astype(jnp.int32))


def play_value_games(cfg: jaxgo.GoConfig, features: tuple,
                     apply_sl: Callable, params_sl,
                     apply_rl: Callable, params_rl,
                     rng: jax.Array, batch: int, max_moves: int = 500,
                     temperature: float = 1.0,
                     u_max: int | None = None) -> ValueSamples:
    """Play ``batch`` mixed-policy games, one value sample per game.

    ``features`` is the *policy* nets' feature set (used in the game
    loop); encode the returned snapshots with the value net's own
    preprocess. ``u_max`` caps the random ply U (default
    ``max_moves - 2`` so the recorded position can exist).
    """
    ply = _make_value_ply(cfg, features, apply_sl, apply_rl,
                          temperature)
    rng, u_key = jax.random.split(rng)
    U = jax.random.randint(u_key, (batch,), 0,
                           _value_u_cap(max_moves, u_max) + 1)

    states0 = jaxgo.new_states(cfg, batch)
    carry0 = (states0, states0, jnp.zeros((batch,), bool), rng)
    (final, rec, recorded, _), _ = lax.scan(
        lambda c, t: (ply(params_sl, params_rl, U, c, t), None),
        carry0, jnp.arange(max_moves))
    return _value_finish(cfg, final, rec, recorded, U)


def make_value_games_chunked(cfg: jaxgo.GoConfig, features: tuple,
                             apply_sl: Callable, apply_rl: Callable,
                             batch: int, max_moves: int = 500,
                             temperature: float = 1.0,
                             u_max: int | None = None,
                             chunk: int = 100):
    """Chunked ``(params_sl, params_rl, rng) -> ValueSamples`` — the
    same mixed-policy game as :func:`play_value_games`, but no device
    program runs longer than one ``chunk``-ply segment (the host
    regains control between segments, as in
    ``make_selfplay_chunked`` / ``make_rl_iteration_chunked``). The
    (states, snapshot, recorded, rng) carry stays device-resident
    between segments, and the host loop exits early once every game
    has ended (the remaining plies are no-ops for the snapshot and the
    outcome). Results are bit-identical to the monolithic scan —
    ``tests/test_value_path.py``."""
    ply = _make_value_ply(cfg, features, apply_sl, apply_rl,
                          temperature)
    u_cap = _value_u_cap(max_moves, u_max)

    @jax.jit
    def begin(rng):
        rng, u_key = jax.random.split(rng)
        U = jax.random.randint(u_key, (batch,), 0, u_cap + 1)
        states0 = jaxgo.new_states(cfg, batch)
        return (states0, states0, jnp.zeros((batch,), bool), rng), U

    @functools.partial(jax.jit, static_argnames=("length",))
    def segment(params_sl, params_rl, U, carry, offset, length):
        def body(c, t):
            return ply(params_sl, params_rl, U, c, t), None

        carry, _ = lax.scan(body, carry, offset + jnp.arange(length))
        return carry

    finish = jax.jit(functools.partial(_value_finish, cfg))

    def run(params_sl, params_rl, rng) -> ValueSamples:
        carry, U = begin(rng)
        for offset in range(0, max_moves, chunk):
            length = min(chunk, max_moves - offset)
            carry = segment(params_sl, params_rl, U, carry,
                            jnp.int32(offset), length)
            if bool(jax.device_get(carry[0].done.all())):
                break
        return finish(carry[0], carry[1], carry[2], U)

    return run


class ValueDataGenerator:
    """Host driver: batches of on-device games → sharded npz corpus."""

    def __init__(self, sl_net: NeuralNetBase, rl_net: NeuralNetBase,
                 value_features: tuple, batch: int = 64,
                 max_moves: int = 500, temperature: float = 1.0,
                 u_max: int | None = None, chunk: int = 0,
                 komi: float | None = None):
        if sl_net.feature_list != rl_net.feature_list or \
                sl_net.board != rl_net.board:
            raise ValueError("SL and RL nets must share features/board")
        import dataclasses

        # scoring komi: per-board-size standard unless overridden
        # (the net spec's GoConfig always carries the 19x19 value)
        self.cfg = dataclasses.replace(
            sl_net.cfg, komi=komi if komi is not None
            else jaxgo.default_komi(sl_net.cfg.size))
        self.sl = sl_net
        self.rl = rl_net
        self.pre = Preprocess(value_features, cfg=self.cfg)
        self.batch = batch

        if chunk:
            self._run = make_value_games_chunked(
                self.cfg, sl_net.feature_list, sl_net.module.apply,
                rl_net.module.apply, batch=batch, max_moves=max_moves,
                temperature=temperature, u_max=u_max, chunk=chunk)
        else:
            self._run = jax.jit(functools.partial(
                play_value_games, self.cfg, sl_net.feature_list,
                sl_net.module.apply, apply_rl=rl_net.module.apply,
                batch=batch, max_moves=max_moves,
                temperature=temperature, u_max=u_max))

    def generate(self, n_positions: int, out_prefix: str,
                 seed: int = 0, shard_size: int = 4096) -> dict:
        """Accumulate ≥ ``n_positions`` valid samples into
        ``{out_prefix}-NNNNN.npz`` shards + manifest (input-pipeline
        layout; z stored in the ``actions`` slot, ``targets:
        "outcome"``)."""
        os.makedirs(os.path.dirname(out_prefix) or ".", exist_ok=True)
        key = jax.random.key(seed)
        shard_counts: list[int] = []
        buf_s, buf_z, total = [], [], 0
        shard_id = 0

        def flush():
            nonlocal shard_id
            if not buf_s:
                return
            np.savez_compressed(
                f"{out_prefix}-{shard_id:05d}.npz",
                states=np.concatenate(buf_s),
                actions=np.concatenate(buf_z))
            shard_counts.append(sum(len(b) for b in buf_s))
            shard_id += 1
            buf_s.clear()
            buf_z.clear()

        dry_batches = 0
        while total < n_positions:
            key, sub = jax.random.split(key)
            samples = self._run(params_sl=self.sl.params,
                                params_rl=self.rl.params, rng=sub)
            planes = self.pre.states_to_tensor(samples.recorded)
            planes = np.asarray((planes > 0.5)).astype(np.uint8)
            valid = np.asarray(samples.valid)
            z = np.asarray(samples.z, np.int32)
            keep = valid & (z != 0)
            if not keep.any():
                # e.g. integer komi (all draws) or max_moves too small
                # for any game to reach its sample ply — fail loudly
                # instead of spinning forever
                dry_batches += 1
                if dry_batches >= 20:
                    raise RuntimeError(
                        "20 consecutive game batches produced no valid "
                        "value samples; check komi (draws are dropped) "
                        "and max_moves (games must reach ply U+1)")
                continue
            dry_batches = 0
            buf_s.append(planes[keep])
            buf_z.append(z[keep])
            total += int(keep.sum())
            if sum(len(b) for b in buf_s) >= shard_size:
                flush()
        flush()

        manifest = {
            "board_size": self.cfg.size,
            "komi": self.cfg.komi,
            "planes": self.pre.output_dim,
            "feature_list": list(self.pre.feature_list),
            "targets": "outcome",
            "shard_counts": shard_counts,
            "num_positions": total,
        }
        with open(f"{out_prefix}-manifest.json", "w") as f:
            json.dump(manifest, f, indent=2)
        return manifest


def run_generator(argv=None) -> dict:
    """CLI: generate the value-training corpus from saved model specs."""
    ap = argparse.ArgumentParser(
        description="Self-play value dataset generator (one "
                    "de-correlated position per game)")
    ap.add_argument("sl_model_json")
    ap.add_argument("rl_model_json")
    ap.add_argument("out_prefix")
    ap.add_argument("--n-positions", type=int, required=True)
    ap.add_argument("--value-features", nargs="*", default=None,
                    help="feature names for the recorded planes "
                         "(default: the SL net's feature list + the "
                         "'color' plane — the 49-plane value input)")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--max-moves", type=int, default=500)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=0,
                    help="plies per compiled segment (0 = monolithic "
                         "scan; use e.g. 10-60 on backends that kill "
                         "long device programs) — with early exit "
                         "once every game in the batch has ended")
    ap.add_argument("--komi", type=float, default=None,
                    help="area-scoring komi (default: the board "
                         "size's standard; engine.jaxgo.default_komi)")
    a = ap.parse_args(argv)
    sl = NeuralNetBase.load_model(a.sl_model_json)
    rl = NeuralNetBase.load_model(a.rl_model_json)
    if a.value_features:
        features = tuple(a.value_features)
    elif "color" in sl.feature_list:
        features = sl.feature_list
    else:
        features = sl.feature_list + ("color",)
    gen = ValueDataGenerator(sl, rl, features, batch=a.batch,
                             max_moves=a.max_moves,
                             temperature=a.temperature, chunk=a.chunk,
                             komi=a.komi)
    manifest = gen.generate(a.n_positions, a.out_prefix, seed=a.seed)
    print(json.dumps({k: manifest[k] for k in
                      ("num_positions", "planes", "board_size")}))
    return manifest


if __name__ == "__main__":
    run_generator(sys.argv[1:])
