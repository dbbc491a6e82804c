"""AlphaZero-style training iteration over the on-device search.

Beyond the reference's scope (its RL trainer REINFORCEs the raw
policy against a past self; ``AlphaGo/training/
reinforcement_policy_trainer.py``, SURVEY.md §3.2): this closes the
modern loop the device search makes possible — self-play games where
EVERY move comes from the batched on-device MCTS
(:func:`search.device_mcts.make_mcts_selfplay`), then one update that
trains the policy toward the search's visit distributions and the
value net toward the game outcomes:

    loss = CE(policy(s_t), π_t) + MSE(value(s_t), z_t)

with π_t the root visit distribution at ply t and z_t the final
outcome from ply t's player-to-move perspective.

TPU-native structure (same watchdog discipline as the chunked RL
iteration): the game phase is the chunk-driven search self-play; the
training phase REPLAYS the recorded actions through the engine in
compiled segments, accumulating both nets' gradients in a
params-shaped carry — constant memory in game length, no
``[T, B, 19, 19, F]`` plane materialization; only the visit targets
``[T, B, A]`` are kept (a few MB). One optimizer step per net per
iteration.

Policy targets and the pass action: the policy net's head covers the
N board points (pass is an agent-layer decision, reference parity —
``models/policy.py``), while the search's visit distribution includes
pass. Pass gets visits only when nothing sensible exists (its prior
is 0 otherwise), and those plies carry no board signal — so each
ply's target is the board slice of π renormalized, and plies whose
board mass is zero (forced passes, finished games) get weight 0.
"""

from __future__ import annotations

import functools
import os
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import optax
from jax import lax

from rocalphago_tpu.data.replay import ZeroGames
from rocalphago_tpu.engine import jaxgo
from rocalphago_tpu.features import pyfeatures
from rocalphago_tpu.features.planes import batched_encoder, needs_member
from rocalphago_tpu.features.pyfeatures import output_planes
from rocalphago_tpu.io.checkpoint import pack_rng, unpack_rng
from rocalphago_tpu.obs import jaxobs, trace
from rocalphago_tpu.parallel import mesh as meshlib
from rocalphago_tpu.runtime.pipeline import ChunkPipeline
from rocalphago_tpu.search.device_mcts import make_mcts_selfplay
from rocalphago_tpu.search.selfplay import sensible_mask


class ZeroState(NamedTuple):
    policy_params: dict
    value_params: dict
    opt_policy: tuple
    opt_value: tuple
    iteration: jax.Array   # int32 []
    rng: jax.Array         # uint32 key data


def next_keys(rng_bits):
    """Step the zero rng chain one iteration: ``(rng_bits) ->
    (next_rng_bits, game_key)``.

    EXACTLY the split ``iteration`` performs: the game key sequence
    depends only on the seed rng, never on game content or params —
    which is what lets a detached self-play actor walk the chain
    locally and reproduce the synchronous loop's games bit-for-bit
    (docs/SCALE.md)."""
    key, game_key = jax.random.split(unpack_rng(rng_bits))
    return pack_rng(key), game_key


def make_zero_iteration(cfg: jaxgo.GoConfig, policy_features: tuple,
                        value_features: tuple, policy_apply: Callable,
                        value_apply: Callable, tx_policy, tx_value,
                        batch: int, move_limit: int, n_sim: int,
                        max_nodes: int | None = None,
                        temperature: float = 1.0,
                        sim_chunk: int = 8, replay_chunk: int = 10,
                        gumbel: bool = False, m_root: int = 16,
                        gumbel_sample: bool = False,
                        dirichlet_alpha: float = 0.0,
                        noise_frac: float = 0.25, mesh=None,
                        cap_p: float | None = None,
                        cap_cheap: int | None = None,
                        cap_per_row: bool = False,
                        forced_k: float = 0.0,
                        aux_weight: float | None = None,
                        value_apply_aux: Callable | None = None):
    """``(ZeroState) -> (ZeroState, metrics)`` — one full iteration:
    search self-play, replay-gradient accumulation for both nets, one
    optimizer step each. Host-driven (chunk-compiled throughout); the
    search phase and every replay segment stay under the TPU worker
    watchdog.

    Self-play-economics knobs (KataGo; docs/PERFORMANCE.md "Self-play
    economics"; all default OFF and the OFF path is pinned
    bit-identical): ``cap_p``/``cap_cheap``/``cap_per_row`` and
    ``forced_k`` pass through to :func:`make_mcts_selfplay` (env
    defaults ``ROCALPHAGO_CAP_P``/``ROCALPHAGO_CAP_CHEAP`` resolve
    HERE so the recorder and the loss masking agree on whether cap
    randomization is live). With cap randomization on, only
    full-searched plies carry policy-loss weight — cheap plies still
    train the value (and aux) heads, which is the economics bet: a
    cheap search is a fine move-picker and a fine value label, just
    not a policy target.

    ``aux_weight`` (> 0, env default ``ROCALPHAGO_AUX_WEIGHT``)
    enables the auxiliary ownership/score regression against the
    engine's terminal labels, weighted into the value-net loss;
    requires ``value_apply_aux`` (an apply returning
    ``(value, {"ownership", "score"})`` — build the net with
    ``aux_heads=("ownership", "score")``, see ``models/value.py``).
    Aux terms are masked exactly like the value loss (live plies of
    FINISHED games: a move-capped game's terminal labels describe a
    half-played board).
    """
    n = cfg.num_points
    if cap_p is None:
        cap_p = float(os.environ.get("ROCALPHAGO_CAP_P", "") or 0.0)
    if cap_cheap is None:
        cap_cheap = int(os.environ.get("ROCALPHAGO_CAP_CHEAP", "")
                        or max(1, n_sim // 4))
    cheap = max(1, min(int(cap_cheap), n_sim))
    econ = cap_p > 0 and cheap < n_sim
    if aux_weight is None:
        aux_weight = float(
            os.environ.get("ROCALPHAGO_AUX_WEIGHT", "") or 0.0)
    aux = aux_weight > 0
    if aux and value_apply_aux is None:
        raise ValueError(
            "aux_weight > 0 needs value_apply_aux — an apply "
            "returning (value, aux dict); build the value net with "
            "aux_heads=('ownership', 'score')")
    selfplay = make_mcts_selfplay(
        cfg, policy_features, value_features, policy_apply,
        value_apply, batch, move_limit, n_sim, max_nodes,
        temperature=temperature, sim_chunk=sim_chunk,
        record_visits=True, gumbel=gumbel, m_root=m_root,
        gumbel_sample=gumbel_sample,
        dirichlet_alpha=dirichlet_alpha, noise_frac=noise_frac,
        mesh=mesh, cap_p=cap_p, cap_cheap=cheap,
        cap_per_row=cap_per_row, forced_k=forced_k)
    vlabels = jax.jit(jax.vmap(
        functools.partial(jaxgo.terminal_labels, cfg))) if aux else None

    n_policy_planes = output_planes(policy_features)
    vgd = jax.vmap(lambda s: jaxgo.group_data(
        cfg, s.board, with_member=needs_member(value_features),
        with_zxor=cfg.enforce_superko, labels=s.labels))
    venc = batched_encoder(cfg, value_features)
    vsens = jax.vmap(functools.partial(sensible_mask, cfg))
    vstep = jax.vmap(functools.partial(jaxgo.step, cfg))

    def ply(policy_params, value_params, winners, finished,
            aux_labels, carry, xs):
        states, grads_p, grads_v, stats = carry
        if econ:
            actions_t, live_t, visits_t, full_t = xs
        else:
            actions_t, live_t, visits_t = xs
            full_t = None
        if mesh is not None:
            # anchor the replayed game batch on the data axis (same
            # pattern as the RL iteration); the batch-summed losses
            # and grads then all-reduce via XLA-inserted collectives
            states = lax.with_sharding_constraint(
                states, meshlib.data_sharding(mesh))

        gd = vgd(states)
        planes = venc(states, gd)
        sens = vsens(states, gd)
        # search-policy target: board slice of the per-ply target
        # distribution (root visit counts, or π' under gumbel),
        # renormalized (see module docstring). Visit counts are
        # integers so mass>0 implies mass>=1; π' is a probability
        # vector whose board mass can be any positive fraction —
        # normalize by the actual mass and skip plies where almost
        # everything sat on pass
        board_counts = visits_t[:, :n].astype(jnp.float32)
        mass = board_counts.sum(axis=-1)
        pi = board_counts / jnp.maximum(mass, 1e-6)[:, None]
        w = live_t * (mass > 1e-3)                   # f32-able [B]
        wf = w.astype(jnp.float32)
        if full_t is not None:
            # playout-cap randomization: cheap-searched plies carry
            # no policy target (their visit distribution is too
            # shallow to teach), but still replay into the value/aux
            # losses below
            wf = wf * full_t
        # outcome from ply t's player-to-move perspective
        z = (winners * states.turn).astype(jnp.float32)
        turn_f = states.turn.astype(jnp.float32)

        def loss_fn(pp, vp):
            # nested layout: the policy reads the prefix slice of the
            # value planes (one encode serves both nets, as in search)
            logits = policy_apply(pp, planes[..., :n_policy_planes])
            neg = jnp.finfo(logits.dtype).min
            logp = jax.nn.log_softmax(
                jnp.where(sens, logits, neg), axis=-1)
            ce = -(pi * logp).sum(axis=-1)
            if aux_labels is None:
                v = value_apply(vp, planes)
            else:
                v, aux_out = value_apply_aux(vp, planes)
            mse = (v - z) ** 2
            lp = (wf * ce).sum() / batch
            # value targets only from games that actually ENDED (two
            # passes): a move-capped game's area score labels a
            # half-played board (the round-4 run trained 267
            # iterations of value net exclusively on such labels —
            # VERDICT r4 weak #2). Policy targets stay per-ply (the
            # visit distribution is valid however the game ends).
            livef = live_t.astype(jnp.float32) * finished
            lv = (livef * mse).sum() / batch
            # win-prediction accuracy (VERDICT r3 #7): the learning
            # signal the paper reports — live non-draw plies where
            # the value head's SIGN matches the game's outcome
            decided = livef * (z != 0)
            correct = (decided * ((v > 0) == (z > 0))).sum()
            aux_stats = ()
            total = lp + lv
            if aux_labels is not None:
                # terminal ownership/score, rotated to the player to
                # move like z (the labels are black-positive) and
                # masked exactly like the value loss — a half-played
                # board's "terminal" labels teach nothing
                own_l, score_l = aux_labels
                own_t = own_l.astype(jnp.float32) * turn_f[:, None]
                l_own = (livef * ((aux_out["ownership"] - own_t) ** 2
                                  ).mean(axis=-1)).sum() / batch
                sc_t = score_l * turn_f
                l_sc = (livef * ((aux_out["score"] - sc_t) ** 2
                                 )).sum() / batch
                total = total + aux_weight * (l_own + l_sc)
                aux_stats = (l_own, l_sc)
            return total, (lp, lv, correct, decided.sum(),
                           livef.sum()) + aux_stats

        (gp, gv), st = jax.grad(
            loss_fn, argnums=(0, 1), has_aux=True)(
                policy_params, value_params)
        grads_p = jax.tree.map(jnp.add, grads_p, gp)
        grads_v = jax.tree.map(jnp.add, grads_v, gv)
        stats = tuple(s + d for s, d in zip(stats, st))
        # share the ply's one group analysis with the rules step
        return (vstep(states, actions_t, gd), grads_p, grads_v, stats)

    # Explicit in/out shardings (not just internal constraints) when a
    # mesh is supplied: params/opt-state/grads replicated, the game
    # batch sharded on `data` (batch-leading for [B]/GoState leaves,
    # axis 1 for the time-major [T, B, ...] histories). Shardings are
    # pytree prefixes, so one NamedSharding covers a whole subtree.
    # This is what lets the detached learner compile ONE program whose
    # inputs arrive from the replay buffer (host numpy) and land
    # directly in the right placement — and it makes the collective
    # layout part of the program's signature instead of an inference.
    if mesh is None:
        _replay_jit = functools.partial(jax.jit, donate_argnums=(4,))
        _update_jit = jax.jit
    else:
        _rep = meshlib.replicated(mesh)
        _dat = meshlib.data_sharding(mesh)
        _tmaj = meshlib.axis_sharding(mesh, 1)
        _carry_sh = (_dat, _rep, _rep, _rep)
        _replay_jit = functools.partial(
            jax.jit, donate_argnums=(4,),
            in_shardings=(_rep, _rep, _dat, _dat, _carry_sh,
                          _tmaj, _tmaj, _tmaj, _tmaj, _dat),
            out_shardings=_carry_sh)
        _update_jit = functools.partial(
            jax.jit,
            in_shardings=(_rep, _rep, _rep, _rep, _dat, _dat, _dat,
                          _rep),
            out_shardings=(_rep, _rep))

    @jaxobs.track("zero.replay_segment")
    @_replay_jit
    def replay_segment(policy_params, value_params, winners, finished,
                       carry, actions, live, visits, full, aux_labels):
        # segment length rides the xs shapes (one compile per distinct
        # segment length — the fixed chunk plus at most one remainder).
        # The carry (replay states + BOTH nets' grad accumulators) is
        # DONATED: it is loop-internal (built fresh per iteration, so
        # the iteration-level retry wrapper stays valid) and donating
        # it keeps pipelined dispatch from doubling the params-shaped
        # accumulators. ``full``/``aux_labels`` are None with the
        # economics flags off — empty pytrees that leave the traced
        # program (and the donation indices) exactly as before.
        def body(c, xs):
            return ply(policy_params, value_params, winners, finished,
                       aux_labels, c, xs), None

        xs = ((actions, live, visits) if full is None
              else (actions, live, visits, full))
        carry, _ = lax.scan(body, carry, xs)
        return carry

    replay_segment.donates_buffers = True

    @jaxobs.track("zero.apply_updates")
    @_update_jit
    def apply_updates(state: ZeroState, grads_p, grads_v, stats,
                      winners, finished, num_moves, key):
        up, opt_p = tx_policy.update(grads_p, state.opt_policy,
                                     state.policy_params)
        uv, opt_v = tx_value.update(grads_v, state.opt_value,
                                    state.value_params)
        metrics = {
            "policy_loss": stats[0],
            "value_loss": stats[1],
            # normalized value diagnostics: mean squared error per
            # live ply (comparable across batch/move-limit configs —
            # AlphaGo paper baseline 0.226/0.234; draws count in the
            # MSE but not the accuracy) and win-prediction sign
            # accuracy over decided plies (0.5 = uninformative)
            "value_mse": stats[1] * batch / jnp.maximum(stats[4], 1.0),
            "value_acc": stats[2] / jnp.maximum(stats[3], 1.0),
            "black_win_rate": (winners > 0).mean(),
            "draw_rate": (winners == 0).mean(),
            "mean_moves": num_moves.astype(jnp.float32).mean(),
            # fraction of games that ended by two passes within the
            # move limit; a low value means the move limit is starving
            # the value net (its loss is masked to finished games)
            "finished_rate": finished.mean(),
        }
        if aux:
            metrics["aux_loss_ownership"] = stats[5]
            metrics["aux_loss_score"] = stats[6]
        return ZeroState(
            optax.apply_updates(state.policy_params, up),
            optax.apply_updates(state.value_params, uv),
            opt_p, opt_v, state.iteration + 1, pack_rng(key)), metrics

    def play(policy_params, value_params, game_key) -> ZeroGames:
        """The ACTOR half: search self-play only — no optimizer
        state, no gradients. Returns the raw game record the replay
        buffer stores; any params snapshot can play (the gated
        best pair, a stale actor copy) without touching the learner.

        The self-play span is honest host wall time (the chunk loop
        syncs per done-poll — see docs/OBSERVABILITY.md)."""
        with trace.span("zero.selfplay", plies=move_limit):
            out = selfplay(policy_params, value_params, game_key)
            if econ:
                final, actions, live, visits, full = out
            else:
                (final, actions, live, visits), full = out, None
            winners = jax.vmap(
                functools.partial(jaxgo.winner, cfg))(final)
            ownership = score = None
            if aux:
                # terminal aux labels off the final position (the
                # loss masks to finished games, so labels from
                # move-capped boards are recorded but never weighted)
                ownership, score = vlabels(final)
        return ZeroGames(actions, live, visits, winners, final.done,
                         full, ownership, score)

    def learn(state: ZeroState, games: ZeroGames):
        """The LEARNER half: replay-gradient accumulation + one
        optimizer step per net, from a recorded :class:`ZeroGames`
        (device arrays or host numpy — the buffer round-trip is
        bit-exact because the record keeps raw recorder dtypes).

        Steps ``state.rng`` exactly as the synchronous iteration
        does (re-deriving the same split ``play``'s caller used), so
        ``learn(state, play(..., game_key))`` ==
        ``iteration(state)`` bit-for-bit."""
        key = unpack_rng(state.rng)
        key, _ = jax.random.split(key)   # the slot play's key used

        actions = jnp.asarray(games.actions)
        live = jnp.asarray(games.live)
        visits = jnp.asarray(games.visits)
        winners = jnp.asarray(games.winners)
        wf = winners.astype(jnp.float32)
        finished = jnp.asarray(games.finished).astype(jnp.float32)
        live_f = live.astype(jnp.float32)
        num_moves = live.sum(axis=0, dtype=jnp.int32)
        full_f = None
        if econ:
            # a v1/flags-off record fed to an economics learner has
            # no mask: every ply was a full search
            full_f = (jnp.ones_like(live_f) if games.full is None
                      else jnp.asarray(games.full).astype(jnp.float32))
        aux_labels = None
        if aux:
            if games.ownership is None or games.score is None:
                raise ValueError(
                    "aux_weight > 0 but the game record carries no "
                    "ownership/score labels — the actor must play "
                    "with aux labelling on (schema v2)")
            aux_labels = (jnp.asarray(games.ownership),
                          jnp.asarray(games.score))

        states = jaxgo.new_states(cfg, batch)
        if mesh is not None:
            # commit every game array to the placement the jitted
            # programs declare (device_put reshards legally even for
            # committed arrays; letting jit see a mismatched
            # committed sharding would error instead)
            states = meshlib.shard_batch(mesh, states)
            winners, wf, finished, num_moves = (
                jax.device_put(x, _dat)
                for x in (winners, wf, finished, num_moves))
            actions, live_f, visits = (
                jax.device_put(x, _tmaj)
                for x in (actions, live_f, visits))
            if full_f is not None:
                full_f = jax.device_put(full_f, _tmaj)
            if aux_labels is not None:
                aux_labels = jax.device_put(aux_labels, _dat)
        grads_p = jax.tree.map(jnp.zeros_like, state.policy_params)
        grads_v = jax.tree.map(jnp.zeros_like, state.value_params)
        # DISTINCT zero arrays, not one repeated: the replay
        # segment donates the carry, and XLA rejects donating the
        # same buffer twice (5 stats; +2 aux-loss slots when on)
        stats = tuple(jnp.float32(0) for _ in range(7 if aux else 5))
        if mesh is not None:
            # commit them to the mesh like every other carry leaf: a
            # fresh scalar's type carries no mesh, the segment's
            # OUTPUT stats do, so the second segment would miss the
            # trace cache and re-trace the whole replay program (a
            # ~37 s re-trace and re-lower per run at 19x19 on the
            # v5e, PR 21)
            stats = jax.device_put(stats, _rep)
        plies = actions.shape[0]
        carry = (states, grads_p, grads_v, stats)
        # pipelined dispatch (runtime.pipeline): the pipeline paces
        # the host to `depth` in-flight segments (device never idle,
        # host never queueing unboundedly) and records the dispatch
        # gap/occupancy telemetry
        pipe = ChunkPipeline(runner="zero.replay")
        with trace.span("zero.replay", plies=plies):
            for offset in range(0, plies, replay_chunk):
                sl = slice(offset, offset + replay_chunk)
                carry = replay_segment(
                    state.policy_params, state.value_params, wf,
                    finished, carry, actions[sl], live_f[sl],
                    visits[sl],
                    None if full_f is None else full_f[sl],
                    aux_labels)
                # fresh handle (the next segment donates the carry,
                # deleting its leaves out from under a retire)
                pipe.push(carry[3][0] + 0.0)
            pipe.finish()
        _, grads_p, grads_v, stats = carry

        with trace.span("zero.update"):
            return apply_updates(state, grads_p, grads_v, stats,
                                 winners, finished, num_moves, key)

    def iteration(state: ZeroState, sp_policy_params=None,
                  sp_value_params=None):
        """One iteration. ``sp_*_params`` override which nets PLAY the
        self-play games (the gated "best"/incumbent pair — AlphaGo's
        evaluator discipline: the data generator only changes when a
        candidate demonstrably beats it); gradients always update
        ``state``'s candidate nets. Default: state's own nets play
        (ungated self-play).

        Composed as ``learn(state, play(...))`` — the synchronous
        path and the actor/learner split (docs/SCALE.md) run the
        same two halves, so the A/B stays bit-exact for free."""
        _, game_key = jax.random.split(unpack_rng(state.rng))
        games = play(
            state.policy_params if sp_policy_params is None
            else sp_policy_params,
            state.value_params if sp_value_params is None
            else sp_value_params, game_key)
        return learn(state, games)

    # the halves ARE the public actor/learner API (training/actor.py
    # and training/learner.py consume them); expose on the composed fn
    iteration.play = play
    iteration.learn = learn
    iteration.batch = batch
    return iteration


def init_zero_state(policy_params, value_params, tx_policy, tx_value,
                    seed: int = 0) -> ZeroState:
    return ZeroState(policy_params, value_params,
                     tx_policy.init(policy_params),
                     tx_value.init(value_params),
                     jnp.int32(0), pack_rng(jax.random.key(seed)))


class ZeroGate:
    """Evaluator gating + best-pair pool for the zero loop.

    Round-4 measured WHY this exists: ungated zero self-play cycles —
    iteration 260 of the 267-iteration 9×9 run LOSES to iteration 80
    raw 25–75 (``results/zero_scale_r4/strength_*.jsonl``; VERDICT r4
    missing #5). The fix is the reference pipeline's own discipline
    (AlphaGo's evaluator; the same past-self mechanism as
    :class:`rocalphago_tpu.training.rl.OpponentPool`): self-play data
    comes from the gated "best" pair, and a training candidate is
    promoted to best only after beating the incumbent in an N-game
    raw-policy match. Promoted pairs snapshot to ``out_dir/pool`` so
    a resumed run keeps its incumbent and a strength ladder can be
    replayed offline.

    Matches are raw-policy (no search): cheap — a gate costs about
    one search-free self-play batch — and it targets exactly the
    regression round 4 measured, which was in *raw* strength (the
    search-backed 260-vs-80 match was level at 4–4). Promotion is
    statistically honest (:meth:`decide`): besides the point-estimate
    ``threshold``, the candidate's decided-game win rate must carry a
    Wilson 95% lower bound ≥ 0.5 — marginal 64-game results
    (0.56–0.62, most of round 5's recorded promotions) no longer
    promote on noise.

    Multi-host: ``pool_dir`` must live on a filesystem shared by all
    processes (the same requirement ``rl.OpponentPool`` documents).
    Snapshots are written by the coordinator only (``write``); every
    process replays identical match programs with identical keys, so
    gate/promotion decisions agree — but resume and ladder sampling
    READ the pool listing, which must therefore be the same
    everywhere.
    """

    def __init__(self, cfg: jaxgo.GoConfig, features: tuple,
                 policy_apply: Callable, pool_dir: str, games: int,
                 threshold: float, temperature: float,
                 move_limit: int, chunk: int = 20, write: bool = True):
        from rocalphago_tpu.search.selfplay import make_selfplay_chunked

        if games % 2:
            raise ValueError(f"gate games must be even, got {games}")
        self.pool_dir = pool_dir
        self.games = games
        self.threshold = threshold
        self.write = write
        self._runner = make_selfplay_chunked(
            cfg, features, policy_apply, policy_apply, games,
            max_moves=move_limit, chunk=chunk,
            temperature=temperature)

    def match(self, params_a, params_b, key) -> dict:
        """N games of A vs B (colors split half/half by the runner);
        returns A's win rate over decided games plus the tally."""
        import numpy as np

        res = self._runner(params_a, params_b, key,
                           stop_when_done=True)
        w = np.asarray(jax.device_get(res.winners))
        half = self.games // 2
        wins_a = int((w[:half] > 0).sum() + (w[half:] < 0).sum())
        draws = int((w == 0).sum())
        decided = self.games - draws
        return {"wins_a": wins_a, "wins_b": decided - wins_a,
                "draws": draws,
                "win_rate_a": wins_a / max(decided, 1)}

    def decide(self, result: dict) -> tuple:
        """``(promoted, wilson_lb)`` from a :meth:`match` result —
        the statistically honest promotion rule (VERDICT r5 #4): the
        candidate needs BOTH the point-estimate threshold AND a
        Wilson 95% lower bound ≥ 0.5 on its decided-game win rate.
        At the default 64-game budget the bound refuses exactly the
        coin-flip promotions round 5 recorded (a 0.59 observed rate
        has lb ≈ 0.47; clearing 0.5 needs ~0.625+). Gate events log
        the bound so every promotion carries its confidence."""
        from rocalphago_tpu.interface.elo import wilson_lower_bound

        decided = result["wins_a"] + result["wins_b"]
        lb = wilson_lower_bound(result["wins_a"], decided)
        return (result["win_rate_a"] >= self.threshold
                and lb >= 0.5), lb

    # ---- best-pair snapshots ------------------------------------

    def _paths(self, iteration: int) -> tuple:
        import os

        return tuple(os.path.join(
            self.pool_dir, f"best.{iteration:05d}.{kind}.msgpack")
            for kind in ("policy", "value"))

    def snapshots(self) -> list:
        """Sorted ``(iteration, policy_path, value_path)`` triples."""
        import glob
        import os
        import re

        out = []
        for p in sorted(glob.glob(os.path.join(
                self.pool_dir, "best.*.policy.msgpack"))):
            m = re.search(r"best\.(\d+)\.policy\.msgpack$", p)
            v = p.replace(".policy.", ".value.")
            if m and os.path.exists(v):
                out.append((int(m.group(1)), p, v))
        return out

    def promote(self, policy_params, value_params,
                iteration: int) -> None:
        if not self.write:
            return
        from flax import serialization

        from rocalphago_tpu.runtime import atomic, faults, retries

        # atomic per-file writes + policy-before-value order: a crash
        # mid-promotion leaves either a complete pair or a policy file
        # whose missing value sibling keeps it OUT of snapshots() —
        # never a torn incumbent. Transient write failures (flaky
        # shared filesystem) retry with backoff; the promotion is
        # idempotent (same params → same bytes).
        @retries.retry(max_attempts=3, base_delay=0.2)
        def write_pair():
            for path, params in zip(self._paths(iteration),
                                    (policy_params, value_params)):
                faults.barrier("zero.promote", iteration)
                atomic.atomic_write_bytes(
                    path, serialization.to_bytes(
                        jax.device_get(params)))

        write_pair()
        # pointer AFTER the pair: a rollout watcher reading the spill
        # always finds the files it names (docs/ROLLOUT.md)
        from rocalphago_tpu.training.actor import write_spill

        ppath, vpath = self._paths(iteration)
        write_spill(self.pool_dir, version=iteration,
                    policy_path=ppath, value_path=vpath)

    def load(self, entry, policy_template, value_template) -> tuple:
        from flax import serialization

        _, ppath, vpath = entry
        out = []
        for path, template in ((ppath, policy_template),
                               (vpath, value_template)):
            with open(path, "rb") as f:
                out.append(serialization.from_bytes(
                    template, f.read()))
        return tuple(out)

    def sample(self, seed: int, iteration: int):
        """Stateless uniform draw over the pool for ladder matches
        (same (seed, iteration) discipline as ``OpponentPool``). The
        LATEST snapshot — the current incumbent — is excluded: a
        ladder probe exists to compare the incumbent against its
        *past* selves, and best-vs-best is 64 games of noise. Returns
        ``None`` until the pool has a past entry."""
        import numpy as np

        snaps = self.snapshots()[:-1]
        if not snaps:
            return None
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, iteration]))
        return snaps[rng.integers(len(snaps))]


def run_training(argv=None) -> dict:
    """CLI: ``python -m rocalphago_tpu.training.zero policy.json
    value.json out_dir [...]`` — the sibling trainers' operational
    surface (argparse, Orbax checkpoint/resume, JSONL metrics +
    metadata.json, per-save model.json exports loadable by
    GTP/tournament).

    Multi-chip/multi-host wired like the sibling trainers:
    ``distributed_init`` (no-op single-process), a ``(data, model)``
    mesh with the game batch sharded over ``data`` (the search shards
    by root placement; the replay's batch-summed grads all-reduce via
    XLA collectives), replicated net/optimizer state, and
    coordinator-only artifact writes (Orbax saves participate on
    every process)."""
    import argparse
    import dataclasses
    import json
    import os
    import sys
    import time

    from rocalphago_tpu.io.checkpoint import (
        MetadataWriter,
        TrainCheckpointer,
    )
    from rocalphago_tpu.io.metrics import MetricsLogger
    from rocalphago_tpu.models.nn_util import NeuralNetBase
    from rocalphago_tpu.obs import registry as obs_registry
    from rocalphago_tpu.runtime import faults, retries
    from rocalphago_tpu.runtime.compilecache import enable_compile_cache
    from rocalphago_tpu.runtime.watchdog import Watchdog

    enable_compile_cache()      # before any compile
    ap = argparse.ArgumentParser(
        description="AlphaZero-style training: device-MCTS self-play "
                    "+ visit-distribution policy targets")
    ap.add_argument("policy_json")
    ap.add_argument("value_json")
    ap.add_argument("out_dir")
    ap.add_argument("--learning-rate", type=float, default=0.001)
    ap.add_argument("--game-batch", type=int, default=8)
    ap.add_argument("--iterations", type=int, default=10)
    ap.add_argument("--save-every", type=int, default=5)
    ap.add_argument("--move-limit", type=int, default=500)
    ap.add_argument("--sims", type=int, default=64)
    ap.add_argument("--max-nodes", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--sim-chunk", type=int, default=8)
    ap.add_argument("--replay-chunk", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gumbel", action="store_true",
                    help="Gumbel root search self-play with improved-"
                         "policy (π') targets instead of PUCT + "
                         "visit counts. Plays each ply's halving "
                         "winner (--temperature does not apply); "
                         "NOTE the halving schedule visits every "
                         "candidate at least once per phase, so at "
                         "small --sims the real per-ply simulation "
                         "count is max(sims, schedule total) — "
                         "lower --m-root accordingly")
    ap.add_argument("--m-root", type=int, default=16,
                    help="gumbel root candidate count (top-k of the "
                         "gumbel-perturbed logits)")
    ap.add_argument("--gumbel-sample-moves", action="store_true",
                    help="with --gumbel: SAMPLE each move from the "
                         "improved policy (temperature applies) "
                         "instead of playing the halving winner — "
                         "decouples the pi' target from the play "
                         "distribution (VERDICT r4 #9 experiment)")
    ap.add_argument("--dirichlet-alpha", type=float, default=0.0,
                    help="AlphaZero root-noise Dir(α) for PUCT "
                         "self-play (0 = off; paper: 0.03 on 19x19; "
                         "incompatible with --gumbel)")
    ap.add_argument("--noise-frac", type=float, default=0.25,
                    help="root-noise mix fraction ε")
    ap.add_argument("--cap-p", type=float, default=None,
                    help="playout-cap randomization: probability a "
                         "ply gets the FULL --sims search (cheap cap "
                         "otherwise; only full plies emit policy "
                         "targets). Default $ROCALPHAGO_CAP_P or 0 "
                         "= off")
    ap.add_argument("--cap-cheap", type=int, default=None,
                    help="cheap-search sim cap (default "
                         "$ROCALPHAGO_CAP_CHEAP or --sims // 4)")
    ap.add_argument("--cap-per-row", action="store_true",
                    help="draw the cap per GAME instead of per ply-"
                         "batch (iid rows; masked-slab budgets — see "
                         "docs/PERFORMANCE.md before using: lockstep "
                         "batches only save wall-clock with the "
                         "shared draw)")
    ap.add_argument("--forced-k", type=float, default=0.0,
                    help="forced-playout coefficient k at the PUCT "
                         "root (KataGo sqrt(k*P*n) visit floors; "
                         "recorded policy targets have the forced "
                         "visits pruned back out; 0 = off, "
                         "incompatible with --gumbel)")
    ap.add_argument("--aux-weight", type=float, default=None,
                    help="weight of the auxiliary ownership/score "
                         "losses (value net needs aux_heads; default "
                         "$ROCALPHAGO_AUX_WEIGHT or 0 = off)")
    ap.add_argument("--num-devices", type=int, default=None,
                    help="mesh width (default: every device whose "
                         "count divides --game-batch)")
    ap.add_argument("--komi", type=float, default=None,
                    help="area-scoring komi (default: the board "
                         "size's standard — 7.5 at 13x13+, 7.0 below;"
                         " engine.jaxgo.default_komi)")
    ap.add_argument("--no-gating", action="store_true",
                    help="train WITHOUT the evaluator gate (round-4 "
                         "evidence says this cycles: iter-260 lost "
                         "25-75 raw to iter-80)")
    ap.add_argument("--gate-every", type=int, default=0,
                    help="iterations between candidate-vs-best gate "
                         "matches (0 = --save-every)")
    ap.add_argument("--gate-games", type=int, default=64,
                    help="games per gate match (raw policy, colors "
                         "split)")
    ap.add_argument("--gate-threshold", type=float, default=0.55,
                    help="decided-game win rate the candidate needs "
                         "to be promoted to self-play duty (a Wilson "
                         "95%% lower bound >= 0.5 is additionally "
                         "required — marginal wins don't promote)")
    ap.add_argument("--gate-temperature", type=float, default=1.0,
                    help="sampling temperature for gate/ladder match "
                         "play")
    ap.add_argument("--actor-learner", action="store_true",
                    help="decouple self-play from the update "
                         "(docs/SCALE.md): in-process actor threads "
                         "stream finished games into a bounded "
                         "replay buffer, and the learner consumes "
                         "them at its own cadence. With --actors 1 "
                         "the run is BIT-IDENTICAL to the "
                         "synchronous loop (lockstep pacing); more "
                         "actors free-run against the freshest "
                         "published params")
    ap.add_argument("--actors", type=int, default=1,
                    help="self-play actor threads (--actor-learner)")
    ap.add_argument("--replay-connect", default=None,
                    metavar="HOST:PORT",
                    help="consume games from a networked replay "
                         "service (docs/REPLAYNET.md) instead of "
                         "in-process actors: implies "
                         "--actor-learner with zero local actor "
                         "threads — self-play comes from actor "
                         "PROCESSES (rocalphago_tpu.replaynet"
                         ".actor) shipping to the service")
    ap.add_argument("--replay-capacity", type=int, default=None,
                    help="replay buffer capacity in game batches "
                         "(default $ROCALPHAGO_REPLAY_CAPACITY or 8)")
    ap.add_argument("--replay-sample", action="store_true",
                    help="learner draws prioritized-recency samples "
                         "instead of FIFO batches (breaks the "
                         "bit-exact A/B; actors evict instead of "
                         "pacing)")
    ap.add_argument("--iteration-deadline", type=float, default=0.0,
                    help="watchdog: seconds one iteration may take "
                         "before a 'stall' event is logged and the "
                         "run aborts with the last completed "
                         "checkpoint (0 = off); resume picks up at "
                         "the aborted iteration")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the run "
                         "into this directory (also via "
                         "ROCALPHAGO_JAX_PROFILE; default off)")
    a = ap.parse_args(argv)
    if a.gumbel and a.dirichlet_alpha > 0:
        raise SystemExit("--dirichlet-alpha is PUCT-mode root noise; "
                         "--gumbel explores via the gumbel draw")
    if a.gumbel_sample_moves and not a.gumbel:
        raise SystemExit("--gumbel-sample-moves requires --gumbel")
    if a.gumbel and a.forced_k:
        raise SystemExit("--forced-k is a PUCT-root knob; gumbel "
                         "search visits candidates by schedule")
    aux_weight = (a.aux_weight if a.aux_weight is not None else
                  float(os.environ.get("ROCALPHAGO_AUX_WEIGHT", "")
                        or 0.0))
    if a.gumbel and a.temperature != 1.0 and not a.gumbel_sample_moves:
        print("zero: --temperature is ignored with --gumbel (the "
              "per-ply gumbel draw is the exploration; with "
              "--gumbel-sample-moves it applies to the pi' draw)",
              file=sys.stderr)

    policy = NeuralNetBase.load_model(a.policy_json)
    value = NeuralNetBase.load_model(a.value_json)
    if policy.board != value.board:
        raise SystemExit(
            f"policy is {policy.board}x{policy.board} but value is "
            f"{value.board}x{value.board} — the nets must share a "
            "board size")
    # ladder-free configuration (docs/PERFORMANCE.md "Ladder-free
    # encode"): the feature list lives in the NET SPECS — the env knob
    # shapes new specs at models/specs.py, not a trained net's input
    # layer. Surface the mismatch loudly instead of silently paying
    # the ladder tax the operator thought they turned off.
    ladder_free = not any(f in pyfeatures.LADDER_FEATURES
                          for f in (policy.feature_list
                                    + value.feature_list))
    if not pyfeatures.ladder_planes_enabled() and not ladder_free:
        print("zero: ROCALPHAGO_LADDER_PLANES=off has no effect on "
              "nets whose saved specs include the ladder planes — "
              "rebuild the specs under the knob "
              "(python -m rocalphago_tpu.models.specs ...) to get "
              "the ladder-free encode", file=sys.stderr)
    # scoring komi: per-board-size default (VERDICT r4 weak #2 — the
    # nets' own GoConfig carries the 19x19 value whatever the board)
    game_cfg = dataclasses.replace(
        policy.cfg, komi=a.komi if a.komi is not None
        else jaxgo.default_komi(policy.board))
    a.komi = game_cfg.komi      # metadata records the resolved value
    # multi-host/multi-chip bring-up, same wiring as the sibling
    # trainers: DCN init (no-op single-process), a (data, model)
    # mesh, the game batch sharded over data, state replicated,
    # artifact writes on the coordinator only
    meshlib.distributed_init()
    requested = a.num_devices or len(jax.devices())
    # the game batch shards over the data axis — use the largest
    # device count that divides it (a 2-game smoke run on an 8-device
    # mesh must not die on divisibility)
    n_dev = requested
    while a.game_batch % n_dev:
        n_dev -= 1
    if n_dev < requested:
        print(f"zero: using {n_dev}/{requested} devices "
              f"(--game-batch {a.game_batch} must divide evenly; "
              "raise it to use the full mesh)", file=sys.stderr)
    mesh = meshlib.make_mesh(n_dev)
    coord = meshlib.is_coordinator()

    value_apply_aux = None
    if aux_weight > 0:
        if not getattr(value.module, "aux_heads", ()):
            raise SystemExit(
                "--aux-weight needs a value net built with "
                "aux_heads=('ownership', 'score') — rebuild the "
                "value spec (models/value.py) or graft heads onto "
                "the checkpoint with models.value.with_aux_heads")
        value_apply_aux = functools.partial(value.module.apply,
                                            with_aux=True)

    tx_p = optax.sgd(a.learning_rate)
    tx_v = optax.sgd(a.learning_rate)
    iteration = make_zero_iteration(
        game_cfg, policy.feature_list, value.feature_list,
        policy.module.apply, value.module.apply, tx_p, tx_v,
        batch=a.game_batch, move_limit=a.move_limit, n_sim=a.sims,
        max_nodes=a.max_nodes or None,   # 0 = auto (CLI convention)
        temperature=a.temperature, sim_chunk=a.sim_chunk,
        replay_chunk=a.replay_chunk, gumbel=a.gumbel,
        m_root=a.m_root, gumbel_sample=a.gumbel_sample_moves,
        dirichlet_alpha=a.dirichlet_alpha,
        noise_frac=a.noise_frac, mesh=mesh,
        cap_p=a.cap_p, cap_cheap=a.cap_cheap,
        cap_per_row=a.cap_per_row, forced_k=a.forced_k,
        aux_weight=aux_weight, value_apply_aux=value_apply_aux)
    state = meshlib.replicate(mesh, init_zero_state(
        policy.params, value.params, tx_p, tx_v, seed=a.seed))

    os.makedirs(a.out_dir, exist_ok=True)
    ckpt = TrainCheckpointer(os.path.join(a.out_dir, "checkpoints"))
    metrics = MetricsLogger(
        os.path.join(a.out_dir, "metrics.jsonl") if coord else None,
        echo=coord)
    # observability: spans/compile events share the metrics stream;
    # opt-in profiler capture (--profile-dir / env) brackets the run
    trace.configure(metrics)
    jaxobs.maybe_start_profiler(a.profile_dir)
    device = jaxobs.device_record()
    metrics.log("device", **device)
    meta = MetadataWriter(
        os.path.join(a.out_dir, "metadata.json"),
        header={"cmd": " ".join(sys.argv), "config": vars(a),
                "ladder_free": ladder_free, "device": device},
        enabled=coord)
    start = 0
    restored, _ = ckpt.restore(jax.device_get(state))
    if restored is not None:
        # re-replicate over the mesh (rl.py does the same): the
        # restore yields host arrays, but the iteration's sharding
        # contract is replicated state next to data-sharded batches
        state = meshlib.replicate(mesh, ZeroState(*restored))
        start = int(state.iteration)
        metrics.log("resume", iteration=start)
    final = {}

    # evaluator gating (VERDICT r4 missing #5): self-play data comes
    # from the gated BEST pair; the trained candidate must beat it in
    # a raw match to take over self-play duty
    gate = None
    best_p = best_v = None
    gate_every = a.gate_every or a.save_every
    if not a.no_gating:
        gate = ZeroGate(
            game_cfg, policy.feature_list, policy.module.apply,
            os.path.join(a.out_dir, "pool"), games=a.gate_games,
            threshold=a.gate_threshold,
            temperature=a.gate_temperature, move_limit=a.move_limit,
            write=coord)
        # only snapshots at-or-before the restored checkpoint count:
        # a crash between a promotion and its checkpoint save leaves a
        # "future" pool entry, and resuming with it as incumbent would
        # diverge from the uninterrupted run (the re-run iteration
        # re-promotes deterministically, overwriting it with identical
        # bytes)
        snaps = [s for s in gate.snapshots() if s[0] <= start]
        if restored is not None and snaps:
            # a resumed run keeps its incumbent (the candidate in the
            # checkpoint may be mid-losing-streak)
            bp, bv = gate.load(snaps[-1], jax.device_get(
                state.policy_params), jax.device_get(
                state.value_params))
            best_p = meshlib.replicate(mesh, bp)
            best_v = meshlib.replicate(mesh, bv)
            metrics.log("gate_resume", incumbent=snaps[-1][0])
        else:
            best_p, best_v = state.policy_params, state.value_params
            if not snaps:
                gate.promote(best_p, best_v, start)
    gate_root = jax.random.key(a.seed ^ 0x9A7E)

    def export(it):
        if not coord:
            return
        for net, params, name in ((policy, state.policy_params,
                                   "policy"),
                                  (value, state.value_params,
                                   "value")):
            net.params = jax.device_get(params)
            weights = os.path.join(
                a.out_dir, f"{name}.{it:05d}.flax.msgpack")
            net.save_model(
                os.path.join(a.out_dir, f"{name}.json"), weights)

    # transient device/XLA failures re-dispatch the whole iteration:
    # it is functional (state in, new state out), so a retry
    # recomputes the identical result from the same state. The
    # iteration's chunk programs donate their loop-internal carries,
    # but those are rebuilt from `state` — which is never donated —
    # on every invocation, so iteration-level retry stays valid
    # (retries.retry refuses to wrap the donating chunk programs
    # themselves; see runtime/retries.py)
    run_iteration = retries.retry(
        max_attempts=3, base_delay=1.0, logger=metrics.log)(iteration)

    # watchdog: a wedged device program must not hang a nohup run
    # forever — log a stall and abort with the last COMPLETED
    # iteration durably checkpointed; resume picks up exactly there
    last_done = {"state": None, "step": -1}

    def _stall_abort():
        st = last_done["state"]
        if st is not None and last_done["step"] != ckpt.latest_step():
            ckpt.save(last_done["step"], st, wait=True)

    watchdog = None
    if a.iteration_deadline > 0:
        watchdog = Watchdog(a.iteration_deadline, metrics=metrics,
                            abort_fn=_stall_abort, name="zero").start()

    # actor/learner composition (docs/SCALE.md): actors walk the SAME
    # rng chain the synchronous loop would (next_keys depends only on
    # the seed rng, never on game content), play against the published
    # best pair, and stream host copies into the buffer; the learner
    # half consumes at its own cadence. Lockstep (1 actor, FIFO) is
    # bit-identical to the synchronous path — the A/B the acceptance
    # test pins.
    rig = None
    sup = None
    publisher = None
    if a.actor_learner or a.replay_connect:
        from rocalphago_tpu.data.replay import ReplayBuffer
        from rocalphago_tpu.runtime import supervisor as superv
        from rocalphago_tpu.training.actor import (
            DispatchGang,
            ParamsPublisher,
            SelfplayActor,
        )
        from rocalphago_tpu.training.learner import ZeroLearner

        lockstep = (a.actors == 1 and not a.replay_sample
                    and not a.replay_connect)
    if a.replay_connect:
        # the wire rig: the learner consumes a remote replay service
        # over RemoteReplayBuffer (FIFO over the wire; reconnect with
        # backoff inside the client); actor processes ship to the
        # service, so there is no in-process publisher — actors pin
        # their own params version
        from rocalphago_tpu.replaynet.client import (
            RemoteReplayBuffer,
            ReplayClient,
        )

        rhost, _, rport = a.replay_connect.rpartition(":")
        buffer = RemoteReplayBuffer(
            ReplayClient(rhost or "127.0.0.1", int(rport)))
        gang = DispatchGang()
        sup = superv.Supervisor(metrics=metrics)
        learner = ZeroLearner(iteration.learn, buffer, gang=gang,
                              sample=a.replay_sample, metrics=metrics)
        sup.install_sigterm()
        sup.start()
        rig = (buffer, publisher, sup, learner)
        metrics.log("actor_learner", actors=0, lockstep=False,
                    remote=a.replay_connect, sample=a.replay_sample,
                    supervised=True)
    elif a.actor_learner:
        buffer = ReplayBuffer(
            capacity=a.replay_capacity,
            spill_dir=(os.path.join(a.out_dir, "replay")
                       if coord else None))
        # spill left by a drained/killed predecessor: the lockstep
        # actor replays its games bit-identically from the
        # checkpointed rng chain, so restoring leftovers would
        # double-insert them — discard; free-run has no replay to
        # lean on, so it restores what survived
        if coord:
            n_spill = (buffer.discard_spill() if lockstep
                       else buffer.restore())
            if n_spill:
                metrics.log("replay_spill_discarded" if lockstep
                            else "replay_restored", entries=n_spill)
        publisher = ParamsPublisher()
        # one gang shared by every device-section owner: concurrent
        # play/learn SPMD programs over the same mesh can deadlock at
        # their collective rendezvous (training.actor.DispatchGang)
        gang = DispatchGang()
        sup = superv.Supervisor(metrics=metrics)
        base_rng = state.rng

        def _actor_factory(i):
            def make(attempt, beat):
                # free-run restarts branch a FRESH key per attempt —
                # the in-flight game is discarded, never replayed;
                # lockstep never reaches attempt > 0 (the handle is
                # restartable=False)
                if lockstep:
                    rng = base_rng
                else:
                    key = jax.random.fold_in(unpack_rng(base_rng),
                                             i + 1)
                    if attempt:
                        key = jax.random.fold_in(key, attempt)
                    rng = pack_rng(key)
                return SelfplayActor(
                    iteration.play, publisher, buffer, rng,
                    name=f"a{i}", lockstep=lockstep,
                    start_index=start,
                    games=((a.iterations - start) if lockstep
                           else None),
                    pace=not a.replay_sample, gang=gang,
                    metrics=metrics, on_progress=beat)
            return make

        for i in range(a.actors):
            sup.add(_actor_factory(i), name=f"actor:{i}",
                    restartable=not lockstep)
        learner = ZeroLearner(iteration.learn, buffer, gang=gang,
                              sample=a.replay_sample, metrics=metrics)
        publisher.publish(
            best_p if best_p is not None else state.policy_params,
            best_v if best_v is not None else state.value_params,
            version=start)
        # SIGTERM (the preemption notice) → graceful drain: exit at
        # the next iteration boundary with a committed checkpoint
        sup.install_sigterm()
        sup.start()
        rig = (buffer, publisher, sup, learner)
        metrics.log("actor_learner", actors=a.actors,
                    lockstep=lockstep, capacity=buffer.capacity,
                    sample=a.replay_sample, supervised=True)

    def _learner_iteration(state, it):
        # finite waits so a dead fleet surfaces as an error instead
        # of an indefinite hang (the watchdog would fire anyway, but
        # with less to say). A learner death FAILS OVER (free-run
        # only): restore the last committed checkpoint and re-step
        # until iteration it+1 is consumed again — the consumed-but-
        # unlearned entry is simply re-learned from older state.
        # Lockstep refuses the ride: its FIFO entries are gone once
        # taken, so a failover could not replay them bit-identically.
        fell_back = False
        while True:
            try:
                out = learner.step(state, timeout=5.0)
            except Exception as e:
                if lockstep:
                    raise
                restored2, _ = ckpt.restore(jax.device_get(state))
                if restored2 is not None:
                    state = meshlib.replicate(mesh,
                                              ZeroState(*restored2))
                step_now = int(state.iteration)
                metrics.log("learner_failover",
                            error=f"{type(e).__name__}: {e}",
                            restored_step=step_now, target=it + 1)
                obs_registry.counter(
                    "supervisor_restarts_total", worker="learner",
                    reason=("transient" if retries.is_transient(e)
                            else "error")).inc()
                fell_back = True
                continue
            if out is None:
                parked = sup.parked()
                if parked:
                    raise RuntimeError(
                        f"self-play worker {parked[0].name} parked; "
                        "learner starved") from parked[0].error
                if buffer.closed:
                    raise RuntimeError("replay buffer closed mid-run")
                continue
            state, m, _ = out
            if not fell_back or int(state.iteration) >= it + 1:
                return state, m

    drained = False
    try:
        for it in range(start, a.iterations):
            if sup is not None and sup.draining:
                # preemption drain: stop at the iteration boundary —
                # everything up to `it` is complete and (below) gets
                # committed, so a resumed run replays from exactly
                # here, byte-identical to never having been drained
                metrics.log("drain", phase="loop_exit", iteration=it,
                            reason=sup.drain_reason)
                drained = True
                break
            with trace.span("zero.iteration", iteration=it):
                faults.barrier("zero.pre_iteration", it)
                t0 = time.time()
                if rig is None:
                    state, m = run_iteration(state, best_p, best_v)
                    # the fetch below syncs the iteration's device
                    # programs, so zero.iteration is real end-to-end
                    # wall time and the replay spans' async remainder
                    # lands inside this span, not outside it
                    m = {k: float(jax.device_get(v))
                         for k, v in m.items()}
                else:
                    # actors produced the games; learn + fetch only
                    # (the fetch inside learner.step is the sync)
                    state, m = _learner_iteration(state, it)
                if watchdog is not None:
                    watchdog.beat()
                    last_done["state"] = jax.device_get(state)
                    last_done["step"] = it + 1
                faults.barrier("zero.post_iteration", it)
                if "aux_loss_ownership" in m:
                    # per-head gauges mirror the metrics stream so
                    # obs_report can trend the aux losses next to the
                    # economics counters
                    obs_registry.gauge(
                        "aux_loss", head="ownership").set(
                            m["aux_loss_ownership"])
                    obs_registry.gauge("aux_loss", head="score").set(
                        m["aux_loss_score"])
                entry = {"iteration": it, **m,
                         "games_per_min": a.game_batch * 60.0
                         / max(time.time() - t0, 1e-9)}
                metrics.log("iteration", **entry)
                meta.record_epoch(entry)
                final = entry
                if gate and ((it + 1) % gate_every == 0
                             or it + 1 == a.iterations):
                    with trace.span("zero.gate", iteration=it):
                        gkey, lkey = jax.random.split(
                            jax.random.fold_in(gate_root, it))
                        r = gate.match(state.policy_params, best_p, gkey)
                        promoted, wilson_lb = gate.decide(r)
                        if promoted:
                            best_p, best_v = (state.policy_params,
                                              state.value_params)
                            gate.promote(best_p, best_v, it + 1)
                        metrics.log("gate", iteration=it,
                                    promoted=promoted,
                                    wilson_lb=round(wilson_lb, 4), **r)
                        # ladder probe: the (possibly new) incumbent vs a
                        # sampled past best — the monotonicity evidence
                        # round 4 lacked
                        snap = gate.sample(a.seed, it)
                        if snap is not None:
                            lp, _ = gate.load(snap, jax.device_get(
                                state.policy_params), jax.device_get(
                                state.value_params))
                            lr = gate.match(
                                best_p, meshlib.replicate(mesh, lp), lkey)
                            metrics.log("ladder", iteration=it,
                                        opponent=snap[0], **lr)
                        faults.barrier("zero.post_gate", it)
                if rig is not None and publisher is not None:
                    # version it+1 = exactly the pair the synchronous
                    # loop would hand iteration it+1 (post-gate best,
                    # or the fresh candidate without gating)
                    publisher.publish(
                        best_p if best_p is not None
                        else state.policy_params,
                        best_v if best_v is not None
                        else state.value_params, version=it + 1)
                if (it + 1) % a.save_every == 0 or it + 1 == a.iterations:
                    # exports BEFORE the checkpoint save: everything
                    # written before the save that commits step it+1 is
                    # reproduced by a resume from the previous
                    # checkpoint, so a crash at any point leaves
                    # artifacts a resume makes identical to the
                    # uninterrupted run (the save is the commit point)
                    with trace.span("zero.export", iteration=it):
                        export(it + 1)
                        faults.barrier("zero.post_export", it)
                    with trace.span("zero.save", iteration=it):
                        faults.barrier("zero.pre_save", it)
                        ckpt.save(it + 1, jax.device_get(state))
                        if faults.active():
                            # barriers are DETERMINISTIC points: under an
                            # active fault plan the async save commits
                            # before post_save, so crash@pre_save/
                            # post_save cleanly separate uncommitted from
                            # committed (a real crash can land anywhere —
                            # the chaos sweep covers that too)
                            ckpt.wait()
                        faults.barrier("zero.post_save", it)
    finally:
        if rig is not None:
            buffer.close()          # unblocks paced/waiting actors
            sup.stop()              # joins monitor, stops workers
            metrics.log(
                "actor_learner_done",
                learner_idle_frac=round(learner.idle_frac, 4),
                learner_steps=learner.steps,
                restarts=sum(h.restarts for h in sup.handles()),
                games_played=sum(
                    h.worker.games_played for h in sup.handles()
                    if h.worker is not None))
    if drained:
        # commit the drain point: the last completed iteration's
        # state, saved through the normal checkpointer (no export —
        # exports happen at save boundaries, which the resumed run
        # reproduces identically). Exit 0 follows: a drain is a
        # success, not a failure.
        step_now = int(state.iteration)
        if step_now != ckpt.latest_step():
            ckpt.save(step_now, jax.device_get(state))
        metrics.log("drain", phase="checkpoint", step=step_now,
                    reason=sup.drain_reason)
    ckpt.wait()
    if watchdog is not None:
        watchdog.stop()
    # the run's counter/histogram state, queryable by obs_report
    obs_registry.log_to(metrics)
    jaxobs.stop_profiler()
    print(json.dumps(final))
    return final


if __name__ == "__main__":
    run_training()
