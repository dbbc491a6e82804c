"""On-device batched self-play: the whole game loop under one jit.

This is the rebuild of the reference's only vectorized primitive —
``ProbabilisticPolicyPlayer.get_moves`` stepping ~20 games in lockstep
on host with per-state Python featurization (SURVEY.md §2b
"environment parallelism", §3.2 HOT loops). Here the *entire* loop —
encode planes, policy forward, temperature sampling, rules step —
is a ``lax.scan`` over moves with every operand batched over games, so
thousands of games run per chip with zero host round-trips. This is
the component the ≥200 games/min north-star metric rests on.

Color handling: games in the first half of the batch have net A as
Black, the second half net B, so each scan step runs exactly one
half-batch forward through each net (a `jnp.roll` by B/2 swaps the
halves on odd plies) — no wasted double evaluation.

Move policy matches the reference's self-play players: sample from
softmax(logits/T) restricted to *sensible* moves (legal, not filling
an own true eye — the engine's sensibleness analysis); pass only when
no sensible move exists. Games end by two passes or ``max_moves``
(reference ``move_limit`` ≈ 500); unfinished games are scored as they
stand (area scoring).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rocalphago_tpu.engine.jaxgo import (
    GoConfig,
    GoState,
    group_data,
    legal_mask,
    new_states,
    step,
    vgroup_data,
    winner,
)
from rocalphago_tpu.features.incremental import (
    batched_delta_encoder,
    init_caches,
)
from rocalphago_tpu.features.planes import (
    batched_encoder,
    needs_member,
    true_eyes,
)
from rocalphago_tpu.obs import registry as obs_registry
from rocalphago_tpu.obs import scopes
from rocalphago_tpu.runtime import faults
from rocalphago_tpu.runtime.pipeline import ChunkPipeline


def incremental_default() -> bool:
    """Whether the batched self-play ply loop carries the incremental
    encode cache (``features/incremental.py``) — env knob
    ``ROCALPHAGO_ENCODE_INCR``, read at TRACE time like the ladder
    knobs so a run can A/B it per traced program.

    MEASURED DEFAULT off for the BATCHED loop: under ``vmap`` the
    delta path's gating conds lower to selects that execute both
    branches, so its win is confined to cached ladder verdicts
    shortening the batch-lockstep rung loop, against the footprint
    bookkeeping it adds every ply (a CPU A/B, CHANGES.md PR 6; on
    the chip: not measured). The SEQUENTIAL single-state paths
    (``Preprocess.advance``, the ``DeviceMCTSPlayer`` root advance)
    default ON instead — there the host-branch gating really skips
    the opening/chase blocks (~2× µs/pos on dense 19×19 random tails
    on CPU; on the chip: not measured). Results are bit-identical
    either way (``tests/test_incremental.py``)."""
    from rocalphago_tpu.features import incremental as _incr

    return _incr.enabled(default=False)


def sensible_mask(cfg: GoConfig, state: GoState,
                  gd=None) -> jax.Array:
    """bool [N]: legal board moves that do not fill an own true eye
    (the reference's ``get_legal_moves(include_eyes=False)``).
    Pass a precomputed ``gd`` to share the flood fill."""
    if gd is None:
        gd = group_data(cfg, state.board, with_zxor=cfg.enforce_superko,
                        labels=state.labels)
    legal = legal_mask(cfg, state, gd)[:-1]
    return legal & ~true_eyes(cfg, state, state.turn)


class SelfplayResult(NamedTuple):
    final: GoState       # batched end states
    actions: jax.Array   # int32 [T, B] action per ply (N = pass)
    live: jax.Array      # bool  [T, B] game was live when ply t played
    winners: jax.Array   # int32 [B]    +1 black / -1 white / 0
    num_moves: jax.Array  # int32 [B]   plies actually played


def _half_swap(x: jax.Array, swap: jax.Array) -> jax.Array:
    """Swap batch halves when ``swap`` (scalar bool) — static shapes."""
    half = x.shape[0] // 2
    return lax.cond(swap, lambda a: jnp.roll(a, half, axis=0), lambda a: a,
                    x)


def _make_ply(cfg: GoConfig, features: tuple, apply_a: Callable,
              apply_b: Callable, batch: int, temperature: float,
              incremental: bool = False):
    """Shared scan body for :func:`play_games` and
    :func:`make_selfplay_chunked`: one ply of lockstep two-net
    self-play, parameterized over net params so the chunked runner can
    trace it in a standalone jit. Owns the even-batch invariant: the
    half-batch color split slices at ``batch // 2``.

    ``incremental``: encode each ply through the delta path
    (:func:`~rocalphago_tpu.features.incremental.batched_delta_encoder`)
    with a per-game :class:`EncodeCache` threaded through the scan
    carry — bit-identical planes, cached ladder verdicts across
    successive plies. The ply then takes and returns ``caches``
    (``None`` and pass-through when off, so both runners carry one
    pytree slot either way)."""
    if batch % 2:
        raise ValueError(
            f"batch must be even (half-and-half color split), got {batch}")
    n = cfg.num_points
    vgd = vgroup_data(cfg, with_member=needs_member(features),
                      with_zxor=cfg.enforce_superko)
    enc = (batched_delta_encoder(cfg, features) if incremental
           else batched_encoder(cfg, features))
    vsens = jax.vmap(functools.partial(sensible_mask, cfg))
    vstep = jax.vmap(functools.partial(step, cfg))

    def ply(params_a, params_b, states, caches, rng, t):
        rng, sub = jax.random.split(rng)
        # one loop-free analysis per ply, shared by the encoder, the
        # sensibleness mask and the rules step
        with jax.named_scope(scopes.PLY_GROUPS):
            gd = vgd(states)
        with jax.named_scope(scopes.PLY_ENCODE):
            if incremental:
                planes, caches = enc(states, caches, gd)
            else:
                planes = enc(states, gd)
        with jax.named_scope(scopes.PLY_FORWARD):
            # which half faces net A this ply (see module docstring)
            swap = (t % 2) == 1
            rolled = _half_swap(planes, swap)
            half = batch // 2
            logits_a = apply_a(params_a, rolled[:half])
            logits_b = apply_b(params_b, rolled[half:])
            logits = _half_swap(
                jnp.concatenate([logits_a, logits_b], axis=0), swap)

        with jax.named_scope(scopes.PLY_SAMPLE):
            sens = vsens(states, gd)                      # bool [B, N]
            neg = jnp.finfo(logits.dtype).min
            masked = jnp.where(sens, logits / temperature, neg)
            board_action = jax.random.categorical(sub, masked, axis=-1)
            must_pass = ~sens.any(axis=-1)
            action = jnp.where(must_pass, n,
                               board_action).astype(jnp.int32)

        live = ~states.done
        with jax.named_scope(scopes.PLY_STEP):
            new = vstep(states, action, gd)
        return new, caches, rng, action, live

    return ply


def _scan_plies(ply, params_a, params_b, states, caches, rng, ts):
    """Scan ``ply`` over the ply indices ``ts``; returns
    ``(states, caches, rng, actions [T,B], live [T,B])``."""
    def body(carry, t):
        states, caches, rng = carry
        new, caches, rng, action, live = ply(
            params_a, params_b, states, caches, rng, t)
        return (new, caches, rng), (action, live)

    (states, caches, rng), (actions, live) = lax.scan(
        body, (states, caches, rng), ts)
    return states, caches, rng, actions, live


def _finish(cfg: GoConfig, final, actions, live,
            score_on_device: bool, batch: int) -> SelfplayResult:
    """Shared result assembly for both runners."""
    if score_on_device:
        winners = jax.vmap(functools.partial(winner, cfg))(final)
    else:
        # caller scores the final boards on host (:func:`host_winners`);
        # sentinel 2 (impossible winner value) so accidentally reading
        # .winners fails loudly instead of looking like all-draws
        winners = jnp.full((batch,), 2, jnp.int32)
    return SelfplayResult(final, actions, live, winners,
                          live.sum(axis=0, dtype=jnp.int32))


def play_games(cfg: GoConfig, features: tuple,
               apply_a: Callable, params_a,
               apply_b: Callable, params_b,
               rng: jax.Array, batch: int, max_moves: int = 500,
               temperature: float = 1.0,
               score_on_device: bool = True,
               incremental: bool | None = None) -> SelfplayResult:
    """Play ``batch`` lockstep games of net A vs net B.

    First half of the batch: A is Black; second half: B is Black
    (callers average both colors for unbiased win-rates, as the
    reference's RL trainer does). ``apply_*`` map (params, planes
    [B',s,s,F]) → logits [B', N]. Fully jit-compatible; wrap in
    ``jax.jit`` with static ``cfg/features/batch/max_moves``.

    ``incremental`` (default: the ``ROCALPHAGO_ENCODE_INCR`` knob,
    :func:`incremental_default`): thread the delta-encode cache
    through the ply scan — bit-identical results, ladder-chase
    verdicts reused across successive plies.
    """
    if incremental is None:
        incremental = incremental_default()
    states = new_states(cfg, batch)
    caches = init_caches(cfg, batch) if incremental else None
    ply = _make_ply(cfg, features, apply_a, apply_b, batch,
                    temperature, incremental=incremental)
    final, _, _, actions, live = _scan_plies(
        ply, params_a, params_b, states, caches, rng,
        jnp.arange(max_moves))
    return _finish(cfg, final, actions, live, score_on_device, batch)


def make_selfplay(cfg: GoConfig, features: tuple, apply_a: Callable,
                  apply_b: Callable, batch: int, max_moves: int = 500,
                  temperature: float = 1.0,
                  incremental: bool | None = None):
    """Jitted ``(params_a, params_b, rng) -> SelfplayResult`` closure."""

    @jax.jit
    def run(params_a, params_b, rng):
        return play_games(cfg, features, apply_a, params_a, apply_b,
                          params_b, rng, batch, max_moves, temperature,
                          incremental=incremental)

    return run


def make_selfplay_chunked(cfg: GoConfig, features: tuple,
                          apply_a: Callable, apply_b: Callable,
                          batch: int, max_moves: int = 500,
                          chunk: int = 100, temperature: float = 1.0,
                          score_on_device: bool = True,
                          mesh=None,
                          incremental: bool | None = None):
    """Chunked variant of :func:`make_selfplay`: the host regains
    control between segments.

    A monolithic ``max_moves``-ply scan is one device program — the
    host can check no deadline, drain request or all-games-done flag
    until it returns. This runner jits ONE ``chunk``-ply scan segment
    and drives it from a host loop, carrying the batched
    :class:`GoState` **device-resident** between calls — those checks
    run between segments, host↔device traffic is one tiny dispatch per
    segment, and a single compile serves any ``max_moves`` (the
    segment program
    takes the ply offset as a traced scalar, so odd/even color phases
    share the compile too).

    Returns ``(params_a, params_b, rng) -> SelfplayResult`` with
    bit-identical move selection to :func:`play_games` given the same
    rng (the per-ply ``random.split`` chain is preserved across the
    segment boundary by threading the rng through the carry).

    PIPELINED DISPATCH (``runtime.pipeline``): segments are driven
    through a :class:`ChunkPipeline` (``depth`` in-flight segments,
    default env/1; ``depth=0`` = fully synchronous pacing) and each
    segment program DONATES its input ``GoState`` slab, so the
    device-resident carry never exists twice. The ``stop_when_done``
    done-poll never syncs the fresh dispatch at ANY depth: every
    segment's done-scalar is computed on device at dispatch and the
    host reads it from a RETIRED segment (already materialized). At
    ``depth>=1`` the poll runs one segment behind, so up to ``depth``
    extra segments may be dispatched onto all-done states — a proven
    no-op (the engine freezes finished games; asserted in
    ``tests/test_pipeline.py``) whose recorded rows are replaced by
    the same zero padding the sync path writes. Results are therefore
    bit-identical to the sync path at any depth.

    Pass ``mesh`` (a ``parallel.mesh.make_mesh`` mesh) to shard the
    game batch over the mesh's ``data`` axis — environment parallelism
    ACROSS devices, the multi-chip extension of the reference's
    lockstep ``get_moves`` batching (SURVEY.md §2b): initial states
    are placed batch-split, params replicated, and XLA propagates the
    shardings through the whole scan segment (the odd-ply color-swap
    ``roll`` becomes an ICI collective permute). Results are
    bit-identical to the unsharded runner; ``batch`` must be a
    multiple of 2× the data-axis width (even per-device shares keep
    the color-split halves aligned to devices).
    """
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    import time as _time
    if incremental is None:
        incremental = incremental_default()
    meshlib = None
    if mesh is not None:
        from rocalphago_tpu.parallel import mesh as meshlib

        data_width = mesh.shape[meshlib.DATA_AXIS]
        if batch % (2 * data_width):
            raise ValueError(
                f"batch {batch} must be a multiple of 2x the data-axis "
                f"width ({data_width})")
    ply = _make_ply(cfg, features, apply_a, apply_b, batch,
                    temperature, incremental=incremental)

    def _segment_impl(params_a, params_b, states, caches, rng, offset,
                      length):
        return _scan_plies(ply, params_a, params_b, states, caches,
                           rng, offset + jnp.arange(length))

    # the chunk loop's program: the input GoState slab (and the
    # incremental-encode cache slab riding with it) is DONATED so
    # pipelined dispatch (runtime.pipeline) never holds two copies of
    # the device-resident carry. The loop below owns every states
    # value it passes (fresh/sharded/copied), so donation never eats
    # a caller's buffers; donates_buffers marks the program
    # unretryable (runtime.retries refuses to wrap it — retry the
    # whole runner instead, which re-derives everything).
    segment = functools.partial(
        jax.jit, static_argnames=("length",),
        donate_argnums=(2, 3))(_segment_impl)
    segment.donates_buffers = True

    # tiny per-segment done-reduction, dispatched WITH the segment so
    # the host can later read it without syncing anything fresh
    done_flag = jax.jit(lambda s: s.done.all())
    copy_states = jax.jit(lambda s: jax.tree.map(jnp.copy, s))

    finish = jax.jit(functools.partial(
        _finish, cfg, score_on_device=score_on_device, batch=batch))

    # per-segment host wall time (~real segment time when the
    # pipeline paces the loop — each push waits for the previous
    # segment — pure dispatch latency at depth>=1 only for the first
    # segments) + total plies dispatched
    _seg_h = obs_registry.histogram("selfplay_segment_seconds")
    _plies_c = obs_registry.counter("selfplay_plies_total")

    def run(params_a, params_b, rng,
            initial_states: GoState | None = None,
            deadline: float | None = None,
            stop_when_done: bool = False,
            depth: int | None = None,
            pipeline: ChunkPipeline | None = None) -> SelfplayResult:
        """``initial_states`` (batched, defaults to fresh games) lets
        callers continue play from arbitrary positions — e.g. the
        benchmark's mid-game probe segments (the runner copies them
        once before the first segment: segments donate their input
        slab, and the caller keeps ownership of what it passed).

        ``deadline`` (absolute ``time.time()`` value): stop issuing
        further segments once the clock passes it — the in-flight
        segment always completes (a device program is never killed
        mid-flight); the result then has
        ``actions.shape[0] < max_moves`` and possibly-unfinished
        games. ``stop_when_done``: stop early once every game has
        ended (two passes) — the done-scalar is computed on device
        per segment and read from a RETIRED segment (one segment
        behind at ``depth>=1``, already materialized at any depth —
        the host never blocks on the fresh dispatch); rows recorded
        past the all-done segment are replaced by the ZERO padding
        the sync path writes, so the result keeps the full
        ``[max_moves, B]`` shape and stays bit-identical at every
        depth. Callers distinguish a deadline truncation from a
        done-exit via ``final.done.all()``. Both default off, which
        preserves the bit-identical-to-monolithic contract (under
        ``stop_when_done`` the action rows after every game has
        ended are zeros where the monolithic scan would have recorded
        sampled-then-ignored moves; ``live``/``num_moves``/``final``
        are unaffected).

        ``depth``/``pipeline``: the dispatch window (see
        :class:`~rocalphago_tpu.runtime.pipeline.ChunkPipeline`);
        pass ``pipeline`` to share one across calls (bench A/Bs read
        its ``host_gap_frac``)."""
        states = (new_states(cfg, batch) if initial_states is None
                  else initial_states)
        # delta-encode carry: cold per run (the runner owns it — the
        # first segment's encodes all refresh, which IS the
        # from-scratch read; warm reuse accrues across segments)
        caches = init_caches(cfg, batch) if incremental else None
        if mesh is not None:
            states = meshlib.shard_batch(mesh, states)
            if caches is not None:
                caches = meshlib.shard_batch(mesh, caches)
            params_a = meshlib.replicate(mesh, params_a)
            params_b = meshlib.replicate(mesh, params_b)
        elif initial_states is not None:
            # segments donate their input slab; the caller keeps its
            # states, so the first donation must eat OUR copy
            states = copy_states(states)
        pipe = pipeline if pipeline is not None else ChunkPipeline(
            depth, runner="selfplay")
        acts = [jnp.zeros((0, batch), jnp.int32)]   # max_moves=0 parity
        lives = [jnp.zeros((0, batch), bool)]
        plies = 0
        done_plies = None      # plies recorded when all games done

        def _first_done(retired):
            """Earliest retired segment whose done-scalar is True
            (retire order = dispatch order; done is monotonic). Each
            entry is ``(payload=plies, handle=done-scalar)``; the
            handle is materialized — the fetch cannot sync anything
            still in flight."""
            for seg_plies, handle in retired:
                if bool(jax.device_get(handle)):
                    return seg_plies
            return None

        for offset in range(0, max_moves, chunk):
            if deadline is not None and _time.time() > deadline:
                # deliberately NOT zero-padded (unlike the
                # stop_when_done exit): the short actions shape IS the
                # caller's truncation signal, and a deadline stop ends
                # the caller's whole measurement anyway, so the one
                # odd-shape finish compile happens at most once per
                # process — inside the 2x backstop slack
                break
            # exact remainder segment (one extra compile at most) so
            # no ply beyond max_moves ever runs — results stay
            # bit-identical to the monolithic scan
            faults.barrier("selfplay.chunk", offset)
            length = min(chunk, max_moves - offset)
            t0 = _time.monotonic()
            states, caches, rng, actions, live = segment(
                params_a, params_b, states, caches, rng,
                jnp.int32(offset), length)
            acts.append(actions)
            lives.append(live)
            plies = offset + length
            _plies_c.inc(length)
            handle = done_flag(states) if stop_when_done else rng
            retired = pipe.push(handle, payload=plies)
            _seg_h.observe(_time.monotonic() - t0)
            if stop_when_done:
                done_plies = _first_done(retired)
                if done_plies is not None:
                    break
        if stop_when_done:
            # drain both exits: the lagged extras are no-op segments
            # (the result fetch would sync them anyway) and a shared
            # pipeline must not leak this run's done-handles into the
            # next run's retire stream
            retired = pipe.drain()
            if done_plies is None:
                done_plies = _first_done(retired)
        else:
            pipe.finish()
        if done_plies is not None:
            # zero-pad from the first all-done segment (see
            # docstring): rows recorded by lagged extra segments are
            # dropped — those segments stepped frozen games (a no-op
            # on `states`) and the sync path writes zeros here. Fixed
            # output shapes keep the finish program at one compile.
            actions_all = jnp.concatenate(acts)[:done_plies]
            lives_all = jnp.concatenate(lives)[:done_plies]
            pad = max_moves - done_plies
            return finish(
                states,
                jnp.concatenate(
                    [actions_all, jnp.zeros((pad, batch), jnp.int32)]),
                jnp.concatenate(
                    [lives_all, jnp.zeros((pad, batch), bool)]))
        return finish(states, jnp.concatenate(acts),
                      jnp.concatenate(lives))

    def warmup(params_a, params_b):
        """Compile-and-once-execute the EXACT programs a full
        ``run()`` dispatches — the chunk-length segment, the
        remainder segment (when ``max_moves % chunk``), the
        done-scalar reduction and the full-shape finish program — so
        a subsequent timed rep pays zero compiles (the headline
        bench's untimed-warmup discipline, at a couple of segments'
        cost instead of a whole game's; a full-rep warmup once ate
        the budget the timed rep needed).
        Returns the measured post-compile wall seconds of one
        chunk-length segment (the caller's rep-time estimator)."""
        states = new_states(cfg, batch)
        caches = init_caches(cfg, batch) if incremental else None
        rng = jax.random.key(0)
        lengths = [min(chunk, max_moves)]
        rem = max_moves % chunk
        if max_moves > chunk and rem:
            lengths.append(rem)
        seg_s = None
        for length in lengths:
            # compile pass, then one timed pass for the estimator
            states, caches, rng, actions, live = segment(
                params_a, params_b, states, caches, rng,
                jnp.int32(0), length)
            jax.block_until_ready(actions)
            if length == lengths[0]:
                t0 = _time.monotonic()
                states, caches, rng, actions, live = segment(
                    params_a, params_b, states, caches, rng,
                    jnp.int32(0), length)
                jax.block_until_ready(actions)
                seg_s = _time.monotonic() - t0
        jax.device_get(done_flag(states))
        jax.device_get(finish(
            states, jnp.zeros((max_moves, batch), jnp.int32),
            jnp.zeros((max_moves, batch), bool)).winners)
        return seg_s

    # the compiled per-segment program, exposed for benchmarks (flops
    # accounting via .lower().compile().cost_analysis()) — signature
    # (params_a, params_b, states, caches, rng, offset, length=K).
    # NOTE: it donates its `states`/`caches` arguments when executed.
    run.segment = segment
    run.warmup = warmup
    return run


def host_winners(cfg: GoConfig, boards: np.ndarray) -> np.ndarray:
    """Area-score final boards on HOST: int32 [B] (+1/-1/0).

    Equivalent to ``vmap(winner)`` but in numpy (the oracle's
    :func:`pygo.score_board` per board) — benchmarks use it to keep
    whole-board region labeling out of the compiled program (scoring
    happens once per game; a host BFS is microseconds and shrinks the
    XLA graph the experimental TPU backend must handle).
    """
    from rocalphago_tpu.engine.pygo import score_board

    size = cfg.size
    boards = np.asarray(boards, np.int8).reshape(-1, size, size)
    out = np.zeros(len(boards), np.int32)
    for b, board in enumerate(boards):
        black, white = score_board(board, cfg.komi)
        diff = black - white
        out[b] = 0 if diff == 0 else (1 if diff > 0 else -1)
    return out


def make_device_rollout(cfg: GoConfig, features: tuple, apply_fn: Callable,
                        rollout_limit: int = 500,
                        temperature: float = 1.0):
    """Jitted ``(params, states, rng) -> winners`` rollout-to-terminal.

    The MCTS λ-mix's rollout leg, fully on device (SURVEY.md §3.3
    rebuild note): play a *batched* :class:`GoState` — e.g. a wave of
    leaves bridged via :func:`jaxgo.from_pygo` — to the end of the game
    (≤ ``rollout_limit`` further plies) with one rollout net playing
    both colors, then area-score. Finished or padded entries stay
    frozen (``step`` is a no-op on done games). Returns int32 ``[B]``
    winners (+1 black / -1 white / 0 draw); callers translate to the
    entry player's perspective.

    Same ply body as :func:`play_games`, minus the two-net color
    split: rollouts use a single policy, so every ply is exactly one
    full-batch forward. The loop is a ``while_loop`` that EXITS as
    soon as every game in the wave has ended (two passes) — typical
    games finish far before ``rollout_limit``, and a fixed-length
    scan would make every rollout pay the worst case (measured 10×
    on 9×9 with the default limit of 500).
    """
    n = cfg.num_points
    vgd = vgroup_data(cfg, with_member=needs_member(features),
                      with_zxor=cfg.enforce_superko)
    enc = batched_encoder(cfg, features)
    vsens = jax.vmap(functools.partial(sensible_mask, cfg))
    vstep = jax.vmap(functools.partial(step, cfg))

    @jax.jit
    def run(params, states: GoState, rng: jax.Array) -> jax.Array:
        def ply(carry):
            states, rng, t = carry
            rng, sub = jax.random.split(rng)
            gd = vgd(states)
            planes = enc(states, gd)
            logits = apply_fn(params, planes)
            sens = vsens(states, gd)
            neg = jnp.finfo(logits.dtype).min
            masked = jnp.where(sens, logits / temperature, neg)
            action = jax.random.categorical(sub, masked, axis=-1)
            must_pass = ~sens.any(axis=-1)
            action = jnp.where(must_pass, n, action).astype(jnp.int32)
            return vstep(states, action, gd), rng, t + 1

        def cond(carry):
            states, _, t = carry
            return ~states.done.all() & (t < rollout_limit)

        final, _, _ = lax.while_loop(cond, ply,
                                     (states, rng, jnp.int32(0)))
        return jax.vmap(functools.partial(winner, cfg))(final)

    return run
