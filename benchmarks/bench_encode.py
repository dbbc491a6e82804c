"""Encode-path A/B harness: gating × phase-1 depth.

The 48-plane encode is the self-play ceiling and the two ladder planes
are ~93% of it in CPU traces (on the chip: not measured) — yet until
this harness every encode knob was a platform heuristic. This measures
each configuration of the two axes that matter and records one
results.jsonl row per config, so the defaults in
``features/ladders.py`` are set from numbers (the
``jaxgo._dense_engine`` discipline):

* **gating** — ``shared`` (the pooled, gated capture+escape chase of
  ``ladders.ladder_planes``) vs ``split`` (the legacy per-plane
  chases; ``$ROCALPHAGO_LADDER_GATE``);
* **phase1** — the two-phase chase schedule's lockstep depth
  (``$ROCALPHAGO_LADDER_PHASE1``; a value ≥ ladder depth recovers the
  old single-phase FIXED-RUNG read — the baseline the gated/early-exit
  path is judged against).

Every row carries ``us_per_pos`` (per-position microseconds — the
unit ``scripts/bench_report.py``'s encode column renders) plus the
axis fields, and one ``encode_noladder`` row measures the same batch
without the ladder planes so the ladder share of encode is a recorded
number, not folklore. The env knobs are read at TRACE time, so each
config traces a fresh program — the A/B never reuses a stale cached
trace. On the chip: not measured (ROADMAP S1).

TRAJECTORY rows (``--trajectory``, PR 6): self-play and MCTS visit
SUCCESSIVE positions, so the batched mid-game measurement above is
the wrong model for the sequential hot paths — this mode replays a
real random-game tail position by position and A/Bs the incremental
encoder (``features/incremental.py``, ``encode_incr`` rows, cache
carried ply to ply) against the from-scratch encode (
``encode_scratch``), µs/pos each; ``encode_incr`` additionally
records the speedup as ``vs_baseline`` (incr rate ÷ scratch rate).
``--traj-batch`` adds the batched-lockstep pair
(``encode_incr_batched`` / ``encode_scratch_batched``) — the numbers
behind ``selfplay.incremental_default``'s CPU-measured default.
"""

from __future__ import annotations

import functools
import os
import sys

sys.path.insert(0, ".")
from benchmarks._harness import (  # noqa: E402
    random_game_states,
    report,
    std_parser,
    timed,
)


def _game_tail(cfg, skip: int, plies: int, rng_key):
    """One REAL game's successive positions (uniform random legal
    policy, the same move model as ``random_game_states``): a host
    list of ``plies`` single GoStates, positions ``skip+1 .. skip+plies``
    of the game — the sequential stream the incremental encoder is
    built for."""
    import jax
    import jax.numpy as jnp

    from rocalphago_tpu.engine.jaxgo import (
        group_data,
        legal_mask,
        new_state,
        step,
    )

    @jax.jit
    def run(rng):
        def ply(carry, _):
            state, rng = carry
            rng, sub = jax.random.split(rng)
            gd = group_data(cfg, state.board,
                            with_zxor=cfg.enforce_superko,
                            labels=state.labels)
            legal = legal_mask(cfg, state, gd)[:-1]
            logits = jnp.where(legal, 0.0, -1e30)
            action = jnp.where(
                legal.any(), jax.random.categorical(sub, logits),
                cfg.num_points).astype(jnp.int32)
            new = step(cfg, state, action, gd)
            return (new, rng), new

        _, states = jax.lax.scan(ply, (new_state(cfg), rng),
                                 length=skip + plies)
        return jax.tree.map(lambda x: x[skip:], states)

    stacked = jax.block_until_ready(run(rng_key))
    return [jax.tree.map(lambda x, i=i: x[i], stacked)
            for i in range(plies)]


def _trajectory_ab(cfg, args) -> None:
    """Sequential (and optionally batched-lockstep) trajectory A/B —
    see the module docstring's TRAJECTORY paragraph."""
    import functools

    import jax

    from rocalphago_tpu.features import incremental as incr
    from rocalphago_tpu.features.planes import encode
    from benchmarks._harness import random_game_states

    slot_kw = ({"ladder_chase_slots": args.slots}
               if args.slots is not None else {})
    plies = args.traj_plies
    states_seq = _game_tail(cfg, args.traj_skip, plies,
                            jax.random.key(0))

    enc = jax.jit(functools.partial(
        encode, cfg, ladder_depth=args.depth, **slot_kw))
    step_fn = jax.jit(lambda s, c: incr.encode_step(
        cfg, s, c, ladder_depth=args.depth, **slot_kw))
    cache0 = incr.init_cache(cfg)

    def run_scratch():
        out = None
        for st in states_seq:
            out = enc(st)
        return jax.device_get(out)

    last = {}

    def run_incr():
        # cache cold at the tail start each rep (honest: the warmup
        # ply is in the average, amortized over the tail)
        cache, out = cache0, None
        for st in states_seq:
            out, cache = step_fn(st, cache)
        last["stats"] = jax.device_get(cache.stats)
        return jax.device_get(out)

    dt_s = timed(run_scratch, reps=args.reps)
    rate_s = plies / dt_s
    report("encode_scratch", rate_s, "positions/s",
           board=args.board, plies=plies,
           us_per_pos=round(1e6 * dt_s / plies, 1))
    dt_i = timed(run_incr, reps=args.reps)
    report("encode_incr", plies / dt_i, "positions/s",
           baseline=rate_s, board=args.board, plies=plies,
           us_per_pos=round(1e6 * dt_i / plies, 1))
    # the invalidation cascade behind the incr number (one rep's
    # device-side stat vector): how many footprint hits the coarse
    # region keys let through, how many survived the cell test as
    # real invalidations, and how many chases a flipped dormant
    # verdict forced — the tentpole's tightening, as a recorded row
    s = {f: int(v) for f, v in zip(incr.STAT_FIELDS, last["stats"])}
    report("encode_incr_cascade",
           s["entries_invalidated"] / plies, "invalidations/ply",
           board=args.board, plies=plies,
           foot_hits=s["foot_hits"],
           verdict_flips=s["verdict_flips"],
           entries_revived=s["entries_revived"],
           chases_run=s["chases_run"],
           verdicts_reused=s["verdicts_reused"],
           lanes_refreshed=s["lanes_refreshed"])

    if not args.traj_batch:
        return
    from rocalphago_tpu.features.planes import batched_encoder
    from rocalphago_tpu.features import DEFAULT_FEATURES

    b = args.traj_batch
    mid = jax.block_until_ready(random_game_states(
        cfg, b, args.traj_skip, jax.random.key(1)))
    benc = jax.jit(batched_encoder(cfg, DEFAULT_FEATURES, **slot_kw))
    bdenc = jax.jit(incr.batched_delta_encoder(
        cfg, DEFAULT_FEATURES, **slot_kw))
    caches0 = incr.init_caches(cfg, b)
    actions = _random_action_stepper(cfg, b)

    def run_batch(encoder, with_cache):
        def go():
            states, caches, out = mid, caches0, None
            rng = jax.random.key(2)
            for _ in range(plies):
                if with_cache:
                    out, caches = encoder(states, caches)
                else:
                    out = encoder(states)
                states, rng = actions(states, rng)
            return jax.device_get(out)

        return go

    dt_bs = timed(run_batch(benc, False), reps=args.reps)
    rate_bs = b * plies / dt_bs
    report("encode_scratch_batched", rate_bs, "positions/s",
           batch=b, board=args.board, plies=plies,
           us_per_pos=round(1e6 * dt_bs / (b * plies), 1))
    dt_bi = timed(run_batch(bdenc, True), reps=args.reps)
    report("encode_incr_batched", b * plies / dt_bi, "positions/s",
           baseline=rate_bs, batch=b, board=args.board, plies=plies,
           us_per_pos=round(1e6 * dt_bi / (b * plies), 1))


def _random_action_stepper(cfg, batch: int):
    """Jitted ``(states, rng) -> (states', rng')`` — one uniform
    random-legal lockstep ply (the batched trajectory's move model)."""
    import functools

    import jax
    import jax.numpy as jnp

    from rocalphago_tpu.engine.jaxgo import (
        legal_mask,
        step,
        vgroup_data,
    )

    vstep = jax.vmap(functools.partial(step, cfg))
    vlegal = jax.vmap(functools.partial(legal_mask, cfg))
    vgd = vgroup_data(cfg, with_zxor=cfg.enforce_superko)

    @jax.jit
    def go(states, rng):
        rng, sub = jax.random.split(rng)
        gd = vgd(states)
        legal = vlegal(states, gd)[:, :-1]
        logits = jnp.where(legal, 0.0, -1e30)
        action = jnp.where(
            legal.any(-1), jax.random.categorical(sub, logits, axis=-1),
            cfg.num_points).astype(jnp.int32)
        return vstep(states, action, gd), rng

    return go


def main() -> None:
    import jax

    from rocalphago_tpu.engine.jaxgo import GoConfig
    from rocalphago_tpu.features import DEFAULT_FEATURES
    from rocalphago_tpu.features.planes import encode

    ap = std_parser(__doc__)
    ap.add_argument("--gating", default="shared",
                    help="comma list: shared,split")
    ap.add_argument("--phase1", default="4",
                    help="comma list of phase-1 depths (>= --depth "
                         "recovers the single-phase fixed-rung read)")
    ap.add_argument("--depth", type=int, default=40)
    ap.add_argument("--slots", type=int, default=None,
                    help="ladder_chase_slots override (default: the "
                         "encoder's measured default)")
    ap.add_argument("--skip-noladder", action="store_true")
    ap.add_argument("--trajectory", action="store_true",
                    help="sequential-trajectory A/B: encode_incr vs "
                         "encode_scratch over a real game tail "
                         "(µs/pos), instead of the batched axes sweep")
    ap.add_argument("--traj-plies", type=int, default=80,
                    help="tail length (positions encoded per rep)")
    ap.add_argument("--traj-skip", type=int, default=40,
                    help="opening plies skipped before the tail")
    ap.add_argument("--traj-batch", type=int, default=0,
                    help="also run the batched-lockstep trajectory "
                         "pair at this game batch (0 = skip)")
    args = ap.parse_args()
    batch = args.batch or (256 if jax.devices()[0].platform == "tpu"
                           else 16)
    cfg = GoConfig(size=args.board)

    if args.trajectory:
        _trajectory_ab(cfg, args)
        return

    # mid-game positions: 120 random-legal plies — dense boards with
    # real multi-ladder structure, the encode's stressed case
    states = jax.block_until_ready(
        random_game_states(cfg, batch, 120, jax.random.key(0)))

    slot_kw = ({"ladder_chase_slots": args.slots}
               if args.slots is not None else {})

    def build(features):
        # a fresh partial per config → a fresh trace, so the env
        # knobs (read at trace time) really take effect per row
        return jax.jit(jax.vmap(functools.partial(
            encode, cfg, features=features,
            ladder_depth=args.depth, **slot_kw)))

    def measure(features):
        enc = build(features)
        return timed(lambda: jax.device_get(enc(states)),
                     reps=args.reps, profile_dir=None)

    if not args.skip_noladder:
        no_ladder = tuple(f for f in DEFAULT_FEATURES
                          if not f.startswith("ladder"))
        dt = measure(no_ladder)
        report("encode_noladder", batch / dt, "positions/s",
               batch=batch, board=args.board,
               us_per_pos=round(1e6 * dt / batch, 1))
        # the same floor reached the way an operator reaches it: the
        # ROCALPHAGO_LADDER_PLANES=off feature-spec path (the
        # ladder-free self-play configuration). Must land within 1.5×
        # of the raw no-ladder row above — the knob path adds no
        # hidden tax, it just drops the planes from the spec.
        from rocalphago_tpu.features.pyfeatures import active_features

        prev = os.environ.get("ROCALPHAGO_LADDER_PLANES")
        os.environ["ROCALPHAGO_LADDER_PLANES"] = "off"
        try:
            lf = active_features(DEFAULT_FEATURES)
            dt = measure(lf)
            report("encode_noladder_net", batch / dt, "positions/s",
                   batch=batch, board=args.board,
                   ladder_planes="off", planes=len(lf),
                   us_per_pos=round(1e6 * dt / batch, 1))
        finally:
            if prev is None:
                os.environ.pop("ROCALPHAGO_LADDER_PLANES", None)
            else:
                os.environ["ROCALPHAGO_LADDER_PLANES"] = prev

    for gating in args.gating.split(","):
        for phase1 in (int(p) for p in args.phase1.split(",")):
            os.environ["ROCALPHAGO_LADDER_GATE"] = gating
            os.environ["ROCALPHAGO_LADDER_PHASE1"] = str(phase1)
            dt = measure(DEFAULT_FEATURES)
            report("encode_ab", batch / dt, "positions/s",
                   batch=batch, board=args.board,
                   gating=gating, phase1=phase1,
                   us_per_pos=round(1e6 * dt / batch, 1),
                   **({"slots": args.slots}
                      if args.slots is not None else {}))

if __name__ == "__main__":
    main()
