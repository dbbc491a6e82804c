"""Fully on-device batched MCTS: the whole search is ONE jitted program.

The reference's search (``AlphaGo/mcts.py`` — host tree, batch-1 NN
evals) and its rebuild :class:`~rocalphago_tpu.search.mcts.ParallelMCTS`
(host tree, batched leaf waves) both pay a host↔device round trip per
evaluation wave. This module removes the host from the loop entirely,
mctx-style: the tree itself lives in fixed-shape device arrays (a
``max_nodes`` slab per game), and select → expand → evaluate → backup
is a ``lax.fori_loop`` over simulations, with each simulation stepping
ALL games in lockstep — so every policy/value forward runs at the full
game batch, and the only host↔device traffic for an entire search is
the root states in and the visit counts out.

Search semantics match the host tree (λ=0 APV — PUCT select, policy
priors over sensible moves, value-net leaf evaluation, sign-alternating
backup; same ``c_puct`` formula), with two deliberate differences:
simulations are strictly sequential per game (no virtual loss — the
batch axis provides the parallelism), and the tree is capacity-bounded
(``max_nodes``; a full slab keeps evaluating leaves but stops
allocating, so extra simulations still improve Q estimates).
:func:`make_gumbel_mcts` swaps the ROOT rule for Gumbel-top-k
candidate sampling + sequential halving (the mctx pattern) — the
stronger decision procedure at the low simulation budgets this search
serves at; selection below the root stays PUCT.

Layout notes (TPU): per game the slab holds the node states (a stacked
:class:`GoState` pytree), edge stats ``P/N/W [M, A]`` and the child
index table ``[M, A]`` — all static shapes; descend and backup are
``while_loop``s over int32 scalars with array gathers, and the
per-simulation NN evaluation uses the same nested-feature fusion as
the host waves (value planes encoded once; the policy forward reads
the prefix slice when ``value_features == policy_features + color``).

Multi-chip: the search shards over a device mesh BY PLACEMENT ALONE —
every per-game slab is independent, so passing root states sharded
over the ``data`` axis (``parallel.mesh.shard_batch``) with replicated
params shards the whole search, bit-identically
(``tests/test_device_mcts.py``); no search-code mesh plumbing needed.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rocalphago_tpu.engine.jaxgo import (
    GoConfig,
    GoState,
    area_scores,
    eval_signature,
    group_data,
    new_states,
    step,
    winner,
)
from rocalphago_tpu.features.incremental import (
    batched_delta_encoder,
    init_caches,
)
from rocalphago_tpu.features.planes import batched_encoder, needs_member
from rocalphago_tpu.features.pyfeatures import output_planes
from rocalphago_tpu.obs import jaxobs, scopes
from rocalphago_tpu.obs import registry as obs_registry
from rocalphago_tpu.runtime import faults
from rocalphago_tpu.runtime.pipeline import ChunkPipeline
from rocalphago_tpu.search.clock import MoveClock
from rocalphago_tpu.search.selfplay import sensible_mask


class SimStep(NamedTuple):
    """One simulation's device-side context between SELECT/EXPAND and
    EVALUATE — the seam the serving subsystem's cross-game leaf
    batching cuts the search at (``rocalphago_tpu/serve``):
    ``prepare_sim`` descends + steps and returns this (with
    ``eval_states`` = the leaf states to evaluate), an EXTERNAL
    evaluator produces ``(priors, values)`` for those states — for
    serving, coalesced with other games' leaves into one device batch
    — and ``apply_sim`` writes the node + backs the value up. The
    fused in-search path composes the same two halves around its own
    ``eval_batch``, so the split path is the fused path by
    construction, not a re-implementation."""

    node: jax.Array         # i32 [B] node the descent ended on
    safe_action: jax.Array  # i32 [B] selected edge (pass where none)
    expanding: jax.Array    # bool [B] True = a new leaf was stepped
    eval_states: GoState    # [B, ...] states the evaluator must
    #   score. Where ``expanding`` these ARE the stepped children
    #   (the only rows the apply half writes), so one materialized
    #   GoState serves both the evaluator and the node write.
    eval_keys: jax.Array    # u32 [B, 2] eval signature of each
    #   ``eval_states`` row (``jaxgo.eval_signature``): the external
    #   evaluator's transposition-cache key, computed on device where
    #   the carried hash already lives. Unused by ``apply_sim`` and
    #   dead-code-eliminated out of the fused in-search path.


class DeviceTree(NamedTuple):
    """Per-game search slab (leading axis = game batch B).

    ``A = N + 1`` actions (last = pass); ``M = max_nodes``.
    """

    states: GoState      # node states, arrays shaped [B, M, ...]
    prior: jax.Array     # f32 [B, M, A]
    visits: jax.Array    # i32 [B, M, A]
    value_sum: jax.Array  # f32 [B, M, A] — from the node player's view
    child: jax.Array     # i32 [B, M, A]  node index, -1 = unexpanded
    parent: jax.Array    # i32 [B, M]     -1 at the root
    paction: jax.Array   # i32 [B, M]
    n_nodes: jax.Array   # i32 [B]
    root: jax.Array      # i32 [B]  current root node (0 at init;
    #   advance_root moves it down a child edge for subtree reuse —
    #   backups above it waste a few adds but root_stats never reads
    #   them, and allocation keeps appending to the shared slab)


def _state_at(states: GoState, idx) -> GoState:
    """Node ``idx``'s state out of a [M, ...]-stacked GoState."""
    return jax.tree.map(lambda x: x[idx], states)


def _set_state(states: GoState, idx, st: GoState) -> GoState:
    return jax.tree.map(lambda buf, v: buf.at[idx].set(v), states, st)


def _where_rows(active, new, old):
    """Per-game pytree select: row ``b`` takes ``new`` where
    ``active[b]`` else keeps ``old`` — the per-row budget mask of the
    playout-cap programs (every field's leading axis is the game
    batch)."""
    return jax.tree.map(
        lambda a, b: jnp.where(
            active.reshape((-1,) + (1,) * (a.ndim - 1)), a, b),
        new, old)


def _terminal_value(cfg: GoConfig, st: GoState) -> jax.Array:
    """Outcome in {-1, 0, 1} from the player to move's perspective."""
    w = winner(cfg, st)
    return (w * st.turn).astype(jnp.float32)


def _terminal_value_komi(cfg: GoConfig, st: GoState,
                         komi: jax.Array) -> jax.Array:
    """:func:`_terminal_value` rescored under a per-game ``komi`` (f32
    scalar) instead of the static ``cfg.komi``. ``area_scores`` bakes
    ``cfg.komi`` into white's total, so the rescore just shifts the
    margin by the komi delta — at ``komi == cfg.komi`` the shift is
    exactly ``0.0`` and the result is identical to the pinned path."""
    b, w = area_scores(cfg, st)
    margin = (b - w) + (jnp.float32(cfg.komi) - komi)
    return (jnp.sign(margin) * st.turn).astype(jnp.float32)


def make_device_mcts(cfg: GoConfig, policy_features: tuple,
                     value_features: tuple,
                     policy_apply: Callable, value_apply: Callable,
                     n_sim: int, max_nodes: int | None = None,
                     c_puct: float = 5.0, forced_k: float = 0.0):
    """Build the jitted searcher.

    Returns ``search(params_p, params_v, root_states) ->
    (root_visits i32 [B, A], root_q f32 [B, A])`` where ``root_states``
    is a batched :class:`GoState` (leading axis B) and ``root_q`` is
    the mean backed-up value per root action from the root player's
    perspective (0 where unvisited). ``value_features`` must be
    ``policy_features + ("color",)`` (the canonical nested 48/49
    layout) so one encode serves both nets. ``max_nodes=None`` sizes
    the slab to ``2 * n_sim`` (root + every expanded leaf fit).

    ``forced_k > 0`` enables FORCED PLAYOUTS at the root ("Accelerating
    Self-Play Learning in Go", PAPERS.md): any prior-supported root
    child with fewer than ``sqrt(forced_k · p(c) · N)`` visits (N =
    total root visits so far) is selected ahead of PUCT — cheap
    guaranteed exploration for self-play roots. The matching training
    target prunes those forced visits back out
    (``search.pruned_targets``); serving keeps the default ``0.0``
    (bit-identical programs).
    """
    if max_nodes is None:
        max_nodes = 2 * n_sim
    if tuple(value_features[:-1]) != tuple(policy_features) or \
            value_features[-1] != "color":
        raise ValueError(
            "device MCTS requires the nested feature layout: "
            "value_features == policy_features + ('color',); got "
            f"{policy_features} / {value_features}")
    n = cfg.num_points
    num_actions = n + 1
    m = max_nodes
    n_policy_planes = output_planes(policy_features)

    vgd = jax.vmap(lambda s: group_data(
        cfg, s.board, with_member=needs_member(value_features),
        with_zxor=cfg.enforce_superko, labels=s.labels))
    venc = batched_encoder(cfg, value_features)
    denc = batched_delta_encoder(cfg, value_features)
    vsens = jax.vmap(functools.partial(sensible_mask, cfg))
    vstep = jax.vmap(functools.partial(step, cfg))
    vterm = jax.vmap(functools.partial(_terminal_value, cfg))
    vterm_komi = jax.vmap(functools.partial(_terminal_value_komi, cfg))

    def _analyse(states: GoState):
        """Group analysis + value-feature planes of a batch, each
        under its scope (the from-scratch encode)."""
        with jax.named_scope(scopes.EVAL_GROUPS):
            gd = vgd(states)
        with jax.named_scope(scopes.EVAL_ENCODE):
            planes = venc(states, gd)                  # [B, s, s, Fv]
        return gd, planes

    def _eval_from(params_p, params_v, states: GoState, gd, planes,
                   komi=None):
        """The NN half of :func:`eval_batch`, on precomputed analysis
        + planes (shared with the delta-encode root path)."""
        with jax.named_scope(scopes.EVAL_POLICY):
            sens = vsens(states, gd)                   # [B, N]
            logits = policy_apply(params_p,
                                  planes[..., :n_policy_planes])
            neg = jnp.finfo(logits.dtype).min
            masked = jnp.where(sens, logits, neg)
            board_p = jax.nn.softmax(masked, axis=-1)
            any_sens = sens.any(axis=-1, keepdims=True)
            board_p = jnp.where(any_sens, board_p, 0.0)
            pass_p = jnp.where(any_sens[:, 0], 0.0, 1.0)
            priors = jnp.concatenate(
                [board_p, pass_p[:, None]],
                axis=-1).astype(jnp.float32)
        with jax.named_scope(scopes.EVAL_VALUE):
            values = value_apply(params_v, planes).astype(jnp.float32)
            term = vterm(states) if komi is None \
                else vterm_komi(states, komi)
            values = jnp.where(states.done, term, values)
        return priors, values

    def eval_batch(params_p, params_v, states: GoState):
        """One fused NN evaluation of a [B]-batched GoState:
        ``(priors f32 [B, A], values f32 [B])``. Priors are a masked
        softmax over sensible moves; the pass action gets probability
        1 exactly when no sensible move exists. Values are the value
        net's output where live, the terminal outcome where done."""
        gd, planes = _analyse(states)
        return _eval_from(params_p, params_v, states, gd, planes)

    def eval_batch_komi(params_p, params_v, states: GoState, komi):
        """:func:`eval_batch` with a PER-ROW komi (f32 [B]): terminal
        rows are rescored as if the game were played under
        ``komi[i]`` instead of the static ``cfg.komi``. The serving
        layer uses this to give each session its own komi without a
        per-komi recompile — one program per batch size serves every
        komi, and rows at the default komi score identically to the
        pinned :func:`eval_batch` path."""
        gd, planes = _analyse(states)
        return _eval_from(params_p, params_v, states, gd, planes,
                          komi=komi)

    def _assemble_tree(roots: GoState, root_priors) -> DeviceTree:
        batch = roots.board.shape[0]
        # node-state slab: every slot starts as a fresh state (cheap,
        # valid shapes), root state written into slot 0
        slab = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (batch,) + x.shape),
            new_states(cfg, m))
        slab = jax.vmap(_set_state, in_axes=(0, None, 0))(
            slab, 0, roots)
        prior = jnp.zeros((batch, m, num_actions), jnp.float32) \
            .at[:, 0, :].set(root_priors)
        return DeviceTree(
            states=slab,
            prior=prior,
            visits=jnp.zeros((batch, m, num_actions), jnp.int32),
            value_sum=jnp.zeros((batch, m, num_actions), jnp.float32),
            child=jnp.full((batch, m, num_actions), -1, jnp.int32),
            parent=jnp.full((batch, m), -1, jnp.int32),
            paction=jnp.zeros((batch, m), jnp.int32),
            n_nodes=jnp.ones((batch,), jnp.int32),
            root=jnp.zeros((batch,), jnp.int32),
        )

    def init_tree(params_p, params_v, roots: GoState) -> DeviceTree:
        root_priors, _ = eval_batch(params_p, params_v, roots)
        return _assemble_tree(roots, root_priors)

    def init_tree_cached(params_p, params_v, roots: GoState, caches):
        """:func:`init_tree` with the root planes through the
        incremental encoder (``features/incremental.py``): serving
        advances the root ONE move per ``get_move``, so successive
        root encodes reuse the previous move's ladder-chase verdicts.
        Bit-identical priors (the delta path's contract); returns
        ``(tree, caches')`` — the caller carries the cache across
        moves (``DeviceMCTSPlayer._enc_cache``)."""
        with jax.named_scope(scopes.EVAL_GROUPS):
            gd = vgd(roots)
        with jax.named_scope(scopes.EVAL_ENCODE):
            planes, caches = denc(roots, caches, gd)
        priors, _ = _eval_from(params_p, params_v, roots, gd, planes)
        return _assemble_tree(roots, priors), caches

    def _select_action(prior_n, visits_n, value_n):
        """PUCT argmax over one node's edges ([A] arrays).

        ``sqrt(sum(edge visits) + 1)`` IS the host tree's
        ``sqrt(parent node visits)``: in the host ``TreeNode`` the
        parent's visit count equals the sum of its edge visits plus
        the one evaluation that ended at the parent itself when it was
        expanded — so the two formulas agree at every node, not just
        asymptotically."""
        nv = visits_n.astype(jnp.float32)
        q = jnp.where(visits_n > 0, value_n / jnp.maximum(nv, 1.0), 0.0)
        u = (c_puct * prior_n * jnp.sqrt(nv.sum() + 1.0) / (1.0 + nv))
        score = jnp.where(prior_n > 0, q + u, -jnp.inf)
        return jnp.argmax(score).astype(jnp.int32)

    def _select_action_root(prior_n, visits_n, value_n):
        """Root selection under forced playouts: a prior-supported
        child short of its visit floor ``sqrt(forced_k · p · N)`` is
        taken first (largest deficit); PUCT otherwise. At N = 0 every
        floor is 0, so the first simulation is plain PUCT."""
        nv = visits_n.astype(jnp.float32)
        floor = jnp.sqrt(jnp.float32(forced_k) * prior_n * nv.sum())
        deficit = jnp.where(prior_n > 0, floor - nv, -jnp.inf)
        a_puct = _select_action(prior_n, visits_n, value_n)
        return jnp.where(jnp.max(deficit) > 0,
                         jnp.argmax(deficit).astype(jnp.int32),
                         a_puct)

    def _descend_one(prior, visits, value_sum, child, done_m,
                     root_action, root):
        """Single-game descend ([M, ...] arrays): walk existing child
        pointers from ``root`` until an unexpanded edge or a terminal
        node. Returns ``(node, action)``; ``action`` = -1 when the
        walk ended ON a terminal node (evaluate that node itself).

        ``root_action >= 0`` forces the FIRST edge out of the root
        (the Gumbel searcher's scheduled candidate); selection below
        the root is PUCT either way. ``-1`` = free PUCT from the root.
        """
        def cond(carry):
            node, action, stop = carry
            return ~stop

        def body(carry):
            node, _, _ = carry
            at_term = done_m[node]
            sel = _select_action(prior[node], visits[node],
                                 value_sum[node])
            if forced_k:
                # trace-time gate: serving/default searchers (0.0)
                # compile exactly the pre-forced-playout program
                sel = jnp.where(
                    node == root,
                    _select_action_root(prior[node], visits[node],
                                        value_sum[node]), sel)
            action = jnp.where(at_term, -1, sel)
            nxt = jnp.where(action >= 0, child[node, action], -1)
            stop = at_term | (nxt < 0)
            return (jnp.where(stop, node, nxt), action, stop)

        # pre-execute the root step with the forced action (if any):
        # the carry then starts at the forced edge's child — or stops
        # on the root edge itself when it is unexpanded/terminal
        at_term0 = done_m[root]
        forced = (root_action >= 0) & ~at_term0
        nxt0 = jnp.where(forced, child[root, root_action], -1)
        stop0 = at_term0 | (forced & (nxt0 < 0))
        init = (jnp.where(stop0 | ~forced, root, nxt0)
                .astype(jnp.int32),
                jnp.where(at_term0, -1,
                          jnp.where(forced, root_action, -1))
                .astype(jnp.int32),
                stop0)
        node, action, _ = lax.while_loop(cond, body, init)
        return node, action

    def _backup_one(visits, value_sum, parent, paction, start_node,
                    start_action, v_child):
        """Single-game backup: add the evaluation along the path back
        to the root, alternating sign each level. ``v_child`` is from
        the evaluated state's player-to-move perspective, so the edge
        into it scores ``-v_child`` for its chooser."""
        def cond(carry):
            node, *_ = carry
            return node >= 0

        def body(carry):
            node, action, v, visits, value_sum = carry
            visits = visits.at[node, action].add(1)
            value_sum = value_sum.at[node, action].add(v)
            return (parent[node], paction[node], -v, visits, value_sum)

        _, _, _, visits, value_sum = lax.while_loop(
            cond, body,
            (start_node, start_action, -v_child, visits, value_sum))
        return visits, value_sum

    def prepare_sim(tree: DeviceTree, root_actions) -> SimStep:
        """SELECT + EXPAND half of one lockstep simulation: descend,
        step the selected edge, and return the :class:`SimStep` whose
        ``eval_states`` an evaluator must score. ``root_actions``
        (i32 [B], -1 = free) forces each game's first edge — the
        Gumbel searcher's scheduled candidates."""
        with jax.named_scope(scopes.MCTS_SELECT):
            node, action = jax.vmap(_descend_one)(
                tree.prior, tree.visits, tree.value_sum, tree.child,
                tree.states.done, root_actions, tree.root)

        with jax.named_scope(scopes.MCTS_EXPAND):
            # candidate child states: step the selected edge
            # (terminal descends step a no-op pass on an already-done
            # state — the result is discarded for those games)
            parent_states = jax.vmap(_state_at)(tree.states, node)
            safe_action = jnp.where(action >= 0, action, n)
            new_states_b = vstep(parent_states, safe_action)

            expanding = action >= 0                   # bool [B]

            # evaluate: expanded games evaluate the new child state;
            # terminal descends evaluate the terminal node's own state
            eval_states = jax.tree.map(
                lambda a, b: jnp.where(
                    expanding.reshape((-1,) + (1,) * (a.ndim - 1)),
                    a, b),
                new_states_b, parent_states)
            # transposition key per eval row — a handful of XOR lanes
            # off the carried hash; dead-code-eliminated in the fused
            # ``simulate`` path (where no external evaluator reads it)
            eval_keys = jax.vmap(
                functools.partial(eval_signature, cfg))(eval_states)
        return SimStep(node=node, safe_action=safe_action,
                       expanding=expanding, eval_states=eval_states,
                       eval_keys=eval_keys)

    def apply_sim(tree: DeviceTree, ctx: SimStep, priors,
                  values) -> DeviceTree:
        """WRITE + BACKUP half of one simulation: store the evaluated
        leaf (where expanding & slab not full) and back ``values`` up
        the path. ``(priors, values)`` must be the evaluation of
        ``ctx.eval_states`` — from the in-search ``eval_batch`` or an
        external (cross-game batching) evaluator; the two compose to
        exactly the fused ``simulate``."""
        with jax.named_scope(scopes.MCTS_EXPAND):
            node, safe_action = ctx.node, ctx.safe_action
            # the written rows are exactly the expanding ones, where
            # eval_states IS the stepped child (SimStep docstring)
            expanding, new_states_b = ctx.expanding, ctx.eval_states
            full = tree.n_nodes >= m
            idx = jnp.where(expanding & ~full,
                            jnp.minimum(tree.n_nodes, m - 1), 0)

            # write the new node (only where expanding & not full)
            write = expanding & ~full

            def write_state(slab, i, st, w):
                return jax.tree.map(
                    lambda buf, v: jnp.where(w, buf.at[i].set(v), buf),
                    slab, st)

            states = jax.vmap(write_state)(tree.states, idx, new_states_b,
                                           write)
            prior = jax.vmap(
                lambda p, i, row, w: jnp.where(w, p.at[i].set(row), p))(
                    tree.prior, idx, priors, write)
            child = jax.vmap(
                lambda c, nd, a, i, w: jnp.where(
                    w, c.at[nd, a].set(i), c))(
                    tree.child, node, safe_action, idx, write)
            parent = jax.vmap(
                lambda p, i, nd, w: jnp.where(w, p.at[i].set(nd), p))(
                    tree.parent, idx, node, write)
            paction = jax.vmap(
                lambda p, i, a, w: jnp.where(w, p.at[i].set(a), p))(
                    tree.paction, idx, safe_action, write)
            n_nodes = tree.n_nodes + write.astype(jnp.int32)

        with jax.named_scope(scopes.MCTS_BACKUP):
            # backup start: the edge INTO the evaluated state — (node,
            # action) for expansions (stored or capacity-skipped alike),
            # the terminal node's own parent edge otherwise. A terminal
            # ROOT (parent -1) skips the backup loop entirely.
            start_node = jnp.where(expanding, node,
                                   jax.vmap(lambda p, nd: p[nd])(
                                       tree.parent, node))
            start_action = jnp.where(
                expanding, safe_action,
                jax.vmap(lambda p, nd: p[nd])(tree.paction, node))
            visits, value_sum = jax.vmap(_backup_one)(
                tree.visits, tree.value_sum, parent, paction,
                start_node, start_action, values)

        return DeviceTree(states, prior, visits, value_sum, child,
                          parent, paction, n_nodes, tree.root)

    def simulate(params_p, params_v, tree: DeviceTree,
                 root_actions=None) -> DeviceTree:
        """One lockstep simulation across the whole game batch —
        :func:`prepare_sim` → :func:`eval_batch` → :func:`apply_sim`
        fused into the caller's trace."""
        if root_actions is None:
            root_actions = jnp.full(
                (tree.n_nodes.shape[0],), -1, jnp.int32)
        ctx = prepare_sim(tree, root_actions)
        priors, values = eval_batch(params_p, params_v,
                                    ctx.eval_states)
        return apply_sim(tree, ctx, priors, values)

    def advance_sim(tree: DeviceTree, ctx: SimStep, priors, values,
                    root_actions):
        """Serving's steady-state program: APPLY this simulation and
        PREPARE the next in ONE compiled call — halves the
        per-simulation dispatch count of the split path and lets XLA
        fuse the node write into the next descent's reads. Returns
        ``(tree', ctx')``."""
        tree = apply_sim(tree, ctx, priors, values)
        return tree, prepare_sim(tree, root_actions)

    def _root_stats(tree: DeviceTree):
        idx = tree.root[:, None, None]
        root_visits = jnp.take_along_axis(tree.visits, idx,
                                          axis=1)[:, 0, :]
        root_vsum = jnp.take_along_axis(tree.value_sum, idx,
                                        axis=1)[:, 0, :]
        root_q = jnp.where(
            root_visits > 0,
            root_vsum
            / jnp.maximum(root_visits.astype(jnp.float32), 1.0),
            0.0)
        return root_visits, root_q

    @jax.jit
    def advance_root(tree: DeviceTree, actions):
        """Move each game's root down the ``actions`` edge (subtree
        reuse after a move is played). Returns ``(tree, ok bool [B])``
        — where the edge is unexpanded (``ok`` False) the root is
        unchanged and the caller must rebuild with :func:`init`."""
        nxt = jax.vmap(lambda c, r, a: c[r, a])(
            tree.child, tree.root, actions.astype(jnp.int32))
        ok = nxt >= 0
        return tree._replace(
            root=jnp.where(ok, nxt, tree.root).astype(jnp.int32)), ok

    @functools.partial(jax.jit, static_argnames=("k",))
    def run_sims(params_p, params_v, tree: DeviceTree, k: int):
        """``k`` simulations as one compiled program (tree in/out) —
        the chunking unit for watchdog-limited backends: drive
        ``init`` + repeated ``run_sims`` from a host loop, with the
        tree device-resident between calls, then ``root_stats``."""
        return lax.fori_loop(
            0, k, lambda _, t: simulate(params_p, params_v, t), tree)

    @jax.jit
    def search(params_p, params_v, roots: GoState):
        tree = init_tree(params_p, params_v, roots)
        tree = run_sims(params_p, params_v, tree, n_sim)
        return _root_stats(tree)

    # the chunk loop's program: same trace as run_sims, but the tree
    # slab is DONATED into the program so a pipelined loop (one chunk
    # in flight while the next is prepared) never holds two slabs.
    # Callers that keep their tree use `run_sims` (non-donating);
    # the loop below protects a non-owned input with one copy.
    run_sims_donated = functools.partial(
        jax.jit, static_argnames=("k",), donate_argnums=(2,))(
        lambda params_p, params_v, tree, k: lax.fori_loop(
            0, k, lambda _, t: simulate(params_p, params_v, t), tree))

    def _run_sims_budget_impl(params_p, params_v, tree, budget, j0,
                              k: int):
        """``k`` simulations with a PER-GAME sim budget (i32 [B]):
        global sim index ``j0 + i`` runs only on rows still under
        their budget — retired rows keep their slab bit-for-bit (the
        playout-cap randomization mask; the chunk loop's early exit
        at ``max(budget)`` is where the wall-clock saving is)."""
        def body(i, t):
            t2 = simulate(params_p, params_v, t)
            return _where_rows((j0 + i) < budget, t2, t)

        return lax.fori_loop(0, k, body, tree)

    copy_tree = jax.jit(lambda t: jax.tree.map(jnp.copy, t))

    def run_sims_chunked(params_p, params_v, tree: DeviceTree,
                         chunk: int, n: int | None = None,
                         deadline=None, depth: int | None = None,
                         pipeline: ChunkPipeline | None = None,
                         owned: bool = False, budget=None):
        """The one owner of the watchdog chunk schedule: ``n``
        (default ``n_sim``; a game clock may ask for fewer)
        simulations as ``chunk``-sized compiled programs, tree
        device-resident in between. Returns ``(tree, ran)`` — the
        simulations actually dispatched.

        PIPELINED (``runtime.pipeline``): the loop dispatches through
        a :class:`ChunkPipeline` (``depth`` in-flight chunks; default
        env/1, ``depth=0`` = the old fully-sync behavior; pass
        ``pipeline`` to share one across calls, e.g. a bench A/B) and
        DONATES the tree slab into each chunk program so pipelining
        never doubles slab memory. The input ``tree`` is treated as
        caller-owned and copied once before the first donation —
        callers that hand the tree over (the player, the self-play
        loop) pass ``owned=True`` to skip the copy. Results are
        bit-identical to the sync path at any depth: same programs,
        same operands, same order.

        ``deadline`` (a :class:`~rocalphago_tpu.runtime.deadline.
        Deadline` or None) is the hard wall-clock enforcer: it is
        checked before every chunk AFTER the first (the anytime floor
        — an already-expired deadline still yields one searched
        chunk). The pipeline paces the host to real device completion
        lagged by ``depth`` chunks, so on expiry at most ``depth``
        chunks (one, at the default) are still in flight — they
        complete, their simulations count, and argmax of the returned
        tree's visits is the anytime answer; the hard-stop overshoot
        is bounded by those in-flight chunks (docs/RESILIENCE.md).

        Observability: per-chunk latency is recorded only at
        ``depth=0`` (the only mode that can attribute wall time to
        one chunk); the pipeline records ``dispatch_gap_s`` /
        ``device_occupancy`` at any depth, and sims-per-sec plus the
        deadline-margin gauge are recorded while a deadline is armed
        (the enforced path drains, so the numbers are real execution
        time)."""
        n = n_sim if n is None else n
        if budget is not None:
            # per-row budgets (i32 [B], playout-cap randomization):
            # the caller usually passes n = host-known max(budget) so
            # the loop early-exits; without it the mask alone keeps
            # results right at full-loop cost
            budget = budget.astype(jnp.int32)
        enforce = deadline is not None and not deadline.unlimited
        pipe = pipeline if pipeline is not None else ChunkPipeline(
            depth, runner="device_mcts")
        if not owned and n > 0:
            tree = copy_tree(tree)   # first donation eats our copy,
            #                          never the caller's buffers
        ran = 0
        t_start = time.monotonic()
        for done in range(0, n, chunk):
            if ran and enforce and deadline.expired():
                break
            faults.barrier("search.chunk", done // chunk)
            k = min(chunk, n - done)
            # the chunk program is read off the ``search`` attribute
            # (not the closure) so tests/instrumentation can wrap it
            t0 = time.monotonic()
            if budget is None:
                tree = search.run_sims_donated(params_p, params_v,
                                               tree, k=k)
            else:
                tree = search.run_sims_budget_donated(
                    params_p, params_v, tree, budget,
                    jnp.int32(done), k=k)
            # the pipeline handle must be a FRESH array: the next
            # chunk donates the tree itself, which would delete
            # n_nodes out from under the retire's block
            pipe.push(tree.n_nodes + 0)
            if enforce and pipe.depth == 0:
                _chunk_h.observe(time.monotonic() - t0)
            ran += k
        _sims_c.inc(ran)
        if enforce:
            pipe.drain()
            elapsed = time.monotonic() - t_start
            if elapsed > 0:
                _rate_h.observe(ran / elapsed)
            rem = deadline.remaining()
            if rem is not None:
                _margin_g.set(rem)
        else:
            pipe.finish()
        return tree, ran

    def run_chunked(params_p, params_v, roots: GoState, chunk: int,
                    tree: DeviceTree | None = None, deadline=None,
                    depth: int | None = None,
                    pipeline: ChunkPipeline | None = None,
                    owned: bool = False, n: int | None = None,
                    budget=None):
        """Full search as ``chunk``-simulation compiled programs with
        the tree device-resident in between — THE way to drive this
        on watchdog-limited backends (the ~40s TPU worker limit);
        identical results to :func:`search` (deterministic, the tree
        carry is the entire state) unless a ``deadline`` expires
        mid-search, in which case the stats reflect the simulations
        that fit. Pass ``tree`` to resume from a prepared tree (e.g.
        root priors mixed with exploration noise, or a reused
        subtree) instead of ``init(roots)``; ``depth``/``pipeline``/
        ``owned`` thread through to :func:`run_sims_chunked` (the
        loop donates the tree slab — ``owned=True`` hands a passed
        tree over). ``n``/``budget`` are the playout-cap seam: ``n``
        caps the sims this search runs (host-known, so the chunk loop
        early-exits), ``budget`` adds per-row i32 [B] masking for a
        mixed-budget batch."""
        if tree is None:
            tree = search.init(params_p, params_v, roots)
            owned = True             # init's output is loop-internal
        tree, ran = run_sims_chunked(params_p, params_v, tree, chunk,
                                     n=n, deadline=deadline,
                                     depth=depth, pipeline=pipeline,
                                     owned=owned, budget=budget)
        search.last_ran = ran
        return search.root_stats(tree)

    def _pruned_targets(tree: DeviceTree):
        """Policy target with forced playouts PRUNED back out (the
        KataGo policy-target-pruning rule, vectorized in-jit): per
        root child except the most-visited, subtract its forced-visit
        floor ``sqrt(forced_k · p · N)``, zero children left below one
        real visit (forced-only exploration must not teach the
        policy), keep the most-visited child whole, renormalize.
        Returns ``(target f32 [B, A] summing to 1 per searched row,
        pruned i32 [B] visits removed)``. With ``forced_k == 0`` the
        floor is 0 and the target is exactly the normalized visit
        distribution."""
        visits, _ = _root_stats(tree)
        idx = tree.root[:, None, None]
        prior = jnp.take_along_axis(tree.prior, idx, axis=1)[:, 0, :]
        nv = visits.astype(jnp.float32)
        total = nv.sum(axis=-1, keepdims=True)
        floor = jnp.sqrt(jnp.float32(forced_k) * prior * total)
        on_best = (jnp.arange(nv.shape[-1])[None, :]
                   == jnp.argmax(nv, axis=-1)[:, None])
        kept = jnp.maximum(nv - floor, 0.0)
        kept = jnp.where(kept < 1.0, 0.0, kept)
        kept = jnp.where(on_best, nv, kept)
        norm = kept.sum(axis=-1, keepdims=True)
        target = jnp.where(norm > 0, kept / jnp.maximum(norm, 1.0),
                           0.0)
        pruned = (total - norm)[:, 0].astype(jnp.int32)
        return target, pruned

    # serving-path telemetry (obs.registry): hoisted once per searcher
    # so the chunk loop pays a method call, not a registry lookup
    _chunk_h = obs_registry.histogram("device_mcts_chunk_seconds")
    _rate_h = obs_registry.histogram("device_mcts_sims_per_s",
                                     edges=obs_registry.RATE_EDGES)
    _margin_g = obs_registry.gauge("device_mcts_deadline_margin_s")
    _sims_c = obs_registry.counter("device_mcts_sims_total")

    # chunk-driving surface (same convention as the chunked runners):
    # search.init → DeviceTree, search.run_sims(…, k=) → DeviceTree,
    # search.root_stats(tree) → (visits, q); search.run_chunked =
    # all three composed. init/run_sims are compile-tracked
    # (obs.jaxobs): an unexpected recompile — a new chunk size, a new
    # komi — surfaces as a named `compile` event.
    # run_sims_donated is the chunk loop's program (tree slab donated
    # in — see run_sims_chunked); wrap THAT attribute to intercept
    # the loop's chunks. Its donates_buffers marks it unretryable
    # (runtime.retries refuses to wrap it).
    search.init = jaxobs.track("device_mcts.init", jax.jit(init_tree))
    # incremental-root sibling: (params_p, params_v, roots, caches) →
    # (tree, caches') — the GTP/DeviceMCTSPlayer root advance carries
    # the cache across moves; make_caches builds the cold carry
    search.init_cached = jaxobs.track(
        "device_mcts.init_cached", jax.jit(init_tree_cached))
    search.make_caches = functools.partial(init_caches, cfg)
    search.run_sims = jaxobs.track("device_mcts.run_sims", run_sims)
    search.run_sims_donated = jaxobs.track(
        "device_mcts.run_sims", run_sims_donated)
    search.run_sims_donated.donates_buffers = True
    # playout-cap sibling of run_sims_donated: per-row sim budgets
    # masked in-program (same donation discipline; budget/j0 traced
    # so one program serves every draw)
    search.run_sims_budget_donated = jaxobs.track(
        "device_mcts.run_sims_budget",
        functools.partial(jax.jit, static_argnames=("k",),
                          donate_argnums=(2,))(_run_sims_budget_impl))
    search.run_sims_budget_donated.donates_buffers = True
    # forced-playout training target (f32 distribution); the plain
    # visit-count target when forced_k == 0
    search.pruned_targets = jax.jit(_pruned_targets)
    search.run_sims_chunked = run_sims_chunked
    search.root_stats = jax.jit(_root_stats)
    search.run_chunked = run_chunked
    search.simulate = simulate          # forced-root hook (Gumbel)
    # injectable-evaluator surface (rocalphago_tpu/serve): the serving
    # subsystem drives prepare_sim → [shared cross-game evaluator] →
    # apply_sim per simulation, with eval_batch as the evaluator's
    # compiled program (padded to a few fixed batch sizes). The fused
    # paths above compose the SAME two halves around the in-trace
    # eval, so the split path cannot drift from the fused one.
    search.prepare_sim = jax.jit(prepare_sim)
    search.apply_sim = jax.jit(apply_sim)
    search.advance_sim = jax.jit(advance_sim)
    search.assemble_tree = jax.jit(_assemble_tree)
    search.eval_batch = jaxobs.track("device_mcts.eval_batch",
                                     jax.jit(eval_batch))
    # per-session komi variant (rocalphago_tpu/serve): the evaluator
    # switches to this program only when a custom-komi request is in
    # the batch, so default-komi traffic stays on eval_batch bit-for-
    # bit. Compiled lazily, once per batch size, for ALL komi values.
    search.eval_batch_komi = jaxobs.track(
        "device_mcts.eval_batch_komi", jax.jit(eval_batch_komi))
    # transposition key of a batch of states (uint32 [B, 2]) — the
    # serving evaluator's cache key program for rows that don't come
    # through prepare_sim (root evals); SimStep.eval_keys covers the
    # in-search rows without a second dispatch.
    search.eval_key = jaxobs.track(
        "device_mcts.eval_key",
        jax.jit(jax.vmap(functools.partial(eval_signature, cfg))))
    search.advance_root = advance_root  # subtree reuse across moves
    search.max_nodes = max_nodes        # the slab size actually built
    search.last_ran = None              # sims the last chunked run ran
    return search


def _halving_schedule(n_sim: int, m: int) -> list[tuple[int, int]]:
    """Sequential-halving plan: ``[(k_candidates, visits_per_cand)]``.

    Candidate count halves each phase (m, m//2, …, 2); the simulation
    budget is split evenly across phases, and whatever the integer
    division leaves over goes to the final (2-candidate) phase, where
    extra visits sharpen exactly the comparison that decides the move.
    Every phase visits each surviving candidate at least once, so for
    tiny ``n_sim`` the actual total can exceed ``n_sim`` (documented
    in :func:`make_gumbel_mcts`)."""
    ks, k = [], m
    while k >= 2:
        ks.append(k)
        k //= 2
    p = len(ks)
    sched = [(k, max(1, n_sim // (p * k))) for k in ks]
    used = sum(k * v for k, v in sched)
    leftover = n_sim - used
    if leftover >= ks[-1]:
        k, v = sched[-1]
        sched[-1] = (k, v + leftover // k)
    return sched


def gumbel_plan_sims(n_sim: int, m_root: int, num_actions: int) -> int:
    """Real simulation count of a Gumbel search's halving plan.

    Every halving phase must visit each surviving candidate at least
    once, so for small ``n_sim`` the plan total exceeds the nominal
    budget (e.g. n_sim=8, m_root=16 → 30). Slabs sized from nominal
    ``n_sim`` silently saturate; size them from THIS instead."""
    m = max(2, min(m_root, num_actions))
    return sum(k * v for k, v in _halving_schedule(n_sim, m))


def make_gumbel_mcts(cfg: GoConfig, policy_features: tuple,
                     value_features: tuple,
                     policy_apply: Callable, value_apply: Callable,
                     n_sim: int, max_nodes: int | None = None,
                     m_root: int = 16,
                     c_visit: float = 50.0, c_scale: float = 0.1,
                     c_puct: float = 5.0):
    """Gumbel root search over the device tree (Danihelka et al. 2022,
    the mctx pattern): the move decision at low simulation budgets.

    PUCT spends its root budget proportionally to priors + optimism —
    at 16–64 sims/move (the regime the on-device search serves in) it
    often never tries the 2nd-best prior twice. Gumbel instead:

    1. samples ``m_root`` root candidates without replacement via
       Gumbel-top-k on the masked policy logits (``g(a) = logits(a) +
       Gumbel noise``) — a principled exploration draw;
    2. runs SEQUENTIAL HALVING over the candidates: every survivor
       gets the same number of simulations per phase (scheduled by
       :func:`_halving_schedule`; below the root, selection stays
       PUCT), then the worse half is dropped by the score
       ``g(a) + σ(q̂(a))``, where σ min–max-rescales the completed q̂
       to [0, 1] and scales by ``(c_visit + max_N)·c_scale`` (see
       :func:`_sigma_completed`);
    3. returns the last survivor as ``best`` — the action the player
       should take (argmax root visits is the PUCT convention; under
       a halving schedule visit counts reflect the schedule, not the
       conclusion, so callers must use ``best``).

    Returns ``search(params_p, params_v, roots, rng) ->
    (root_visits [B, A], root_q [B, A], best [B], pi [B, A])`` — with
    ``pi`` the improved policy ``softmax(logits + σ(completed q̂))``,
    the Gumbel MuZero training target — plus the same chunk-driving
    surface as :func:`make_device_mcts`
    (``init/run_phase/rerank/root_stats/improved_policy/
    run_chunked``). For tiny
    ``n_sim`` (< one visit per candidate per phase) the actual
    simulation count can exceed ``n_sim`` — every phase must visit
    each survivor once to have a score to halve on.
    """
    num_actions = cfg.num_points + 1
    m = max(2, min(m_root, num_actions))
    schedule = _halving_schedule(n_sim, m)
    if max_nodes is None:
        # the halving plan's REAL simulation count, not nominal n_sim
        # — a 2*n_sim slab silently saturates small-budget searches
        max_nodes = 2 * gumbel_plan_sims(n_sim, m_root, num_actions)
    base = make_device_mcts(cfg, policy_features, value_features,
                            policy_apply, value_apply, n_sim=n_sim,
                            max_nodes=max_nodes, c_puct=c_puct)
    neg = jnp.float32(jnp.finfo(jnp.float32).min)

    def _root_draw(tree: DeviceTree, rng):
        """Gumbel-top-k root candidate draw off an initialized tree:
        ``(tree, g, cand, logits)`` — shared by the from-scratch and
        incremental-root inits."""
        root_prior = tree.prior[:, 0, :]
        logits = jnp.where(root_prior > 0, jnp.log(
            jnp.maximum(root_prior, 1e-38)), neg)
        gumbel = jax.random.gumbel(rng, logits.shape, jnp.float32)
        g = jnp.where(root_prior > 0, logits + gumbel, neg)
        _, cand = lax.top_k(g, m)
        return tree, g, cand.astype(jnp.int32), logits

    def init(params_p, params_v, roots: GoState, rng):
        """-> (tree, g f32 [B, A], cand i32 [B, m], logits f32 [B, A])
        — the tree with root priors, the gumbel-perturbed root logits,
        the ranked candidate actions, and the raw (noise-free) masked
        logits the improved-policy target is built from."""
        tree = base.init(params_p, params_v, roots)
        return _root_draw(tree, rng)

    def init_cached(params_p, params_v, roots: GoState, rng, caches):
        """:func:`init` with the root encode through the incremental
        path (``base.init_cached``) → ``(tree, g, cand, logits,
        caches')``. Gumbel rebuilds its tree every move by design, so
        the root encode is per-move serving cost — exactly the
        successive-positions pattern the delta cache pays for."""
        tree, caches = base.init_cached(params_p, params_v, roots,
                                        caches)
        return _root_draw(tree, rng) + (caches,)

    def _sigma_completed(tree: DeviceTree):
        """σ(completed q̂) over every root action — the Gumbel value
        transform shared by halving ranking and the π' target
        (mctx's ``qtransform_completed_by_mix_value`` shape):

        1. complete: unvisited actions take the visit-weighted mean
           of the visited q̂ (a no-extra-eval simplification of
           mctx's prior-weighted mixed value);
        2. rescale completed q̂ to [0, 1] per state (min–max over the
           prior-supported actions) — without this, raw q ∈ [-1, 1]
           times (c_visit + maxN) swamps the logits and π' collapses
           to argmax-of-value-noise (observed: a π'-target zero run
           whose policy loss would not fall);
        3. scale by ``(c_visit + max_N) · c_scale`` (mctx defaults:
           50.0 / 0.1), growing value weight as evidence accumulates.

        Returns ``(visits, sigma)``.
        """
        visits, q = base.root_stats(tree)
        nv = visits.astype(jnp.float32)
        total = nv.sum(axis=-1, keepdims=True)
        q_bar = (nv * q).sum(axis=-1, keepdims=True) \
            / jnp.maximum(total, 1.0)
        completed = jnp.where(visits > 0, q, q_bar)
        valid = tree.prior[:, 0, :] > 0
        lo = jnp.min(jnp.where(valid, completed, jnp.inf),
                     axis=-1, keepdims=True)
        hi = jnp.max(jnp.where(valid, completed, -jnp.inf),
                     axis=-1, keepdims=True)
        rescaled = (completed - lo) / jnp.maximum(hi - lo, 1e-8)
        rescaled = jnp.where(valid & (hi > lo), rescaled, 0.0)
        maxn = visits.max(axis=-1, keepdims=True).astype(jnp.float32)
        return visits, (c_visit + maxn) * c_scale * rescaled

    def _scores(tree: DeviceTree, g):
        visits, sigma = _sigma_completed(tree)
        return jnp.where(visits > 0, g + sigma, g)

    def improved_policy(tree: DeviceTree, logits):
        """π' = softmax(logits + σ(completed q̂)) — the Gumbel MuZero
        training target (see :func:`_sigma_completed`)."""
        _, sigma = _sigma_completed(tree)
        masked = jnp.where(logits > neg / 2, logits + sigma, neg)
        return jax.nn.softmax(masked, axis=-1)

    def rerank(tree: DeviceTree, g, cand, k: int):
        """Sort the first ``k`` candidates by ``g + σ(q̂)`` descending
        (the halving step: the next phase reads the first k//2)."""
        s = jnp.take_along_axis(_scores(tree, g), cand[:, :k], axis=-1)
        order = jnp.argsort(-s, axis=-1)
        head = jnp.take_along_axis(cand[:, :k], order, axis=-1)
        return jnp.concatenate([head, cand[:, k:]], axis=-1)

    def _forced_candidate(g, cand, slot):
        """Root candidate forced by schedule slot ``slot`` (i32
        scalar): candidates beyond the sensible set (possible when
        fewer than m moves are sensible) carry ``-inf`` g — those
        slots redirect to the top candidate instead of forcing an
        unreachable edge."""
        forced = jnp.take_along_axis(
            cand, jnp.broadcast_to(slot, (cand.shape[0], 1)),
            axis=-1)[:, 0]
        g_f = jnp.take_along_axis(g, forced[:, None], axis=-1)[:, 0]
        return jnp.where(g_f > neg / 2, forced, cand[:, 0])

    def _run_phase_impl(params_p, params_v, tree: DeviceTree, g, cand,
                        j0, count: int, k: int):
        """``count`` scheduled simulations (one compiled program):
        sim ``j`` forces root candidate ``(j0 + j) % k`` (see
        :func:`_forced_candidate` for the -inf-slot redirect)."""
        def body(i, t):
            forced = _forced_candidate(g, cand, (j0 + i) % k)
            return base.simulate(params_p, params_v, t, forced)

        return lax.fori_loop(0, count, body, tree)

    def _run_phase_budget_impl(params_p, params_v, tree: DeviceTree,
                               g, cand, j0, ran0, budget, count: int,
                               k: int):
        """:func:`_run_phase_impl` under per-game sim budgets
        (playout-cap randomization): the budget counts GLOBAL sims
        across the whole halving plan (``ran0`` = sims already run),
        and a row past its budget keeps its slab bit-for-bit — the
        between-phase rerank then ranks whatever evidence that row
        gathered, the same anytime rule a deadline expiry applies."""
        def body(i, t):
            forced = _forced_candidate(g, cand, (j0 + i) % k)
            t2 = base.simulate(params_p, params_v, t, forced)
            return _where_rows((ran0 + i) < budget, t2, t)

        return lax.fori_loop(0, count, body, tree)

    run_phase = functools.partial(
        jax.jit, static_argnames=("count", "k"))(_run_phase_impl)

    def search_impl(params_p, params_v, roots: GoState, rng):
        tree, g, cand, logits = init(params_p, params_v, roots, rng)
        for k, v in schedule:        # static plan — unrolls into jit
            tree = run_phase(params_p, params_v, tree, g, cand,
                             jnp.int32(0), count=k * v, k=k)
            cand = rerank(tree, g, cand, k)
        visits, q = base.root_stats(tree)
        return visits, q, cand[:, 0], improved_policy(tree, logits)

    search = jax.jit(search_impl)

    def run_chunked(params_p, params_v, roots: GoState, rng,
                    chunk: int, deadline=None,
                    depth: int | None = None,
                    pipeline: ChunkPipeline | None = None,
                    caches=None, n: int | None = None, budget=None):
        """Phase-by-phase, ``chunk``-simulation compiled programs with
        the tree device-resident in between (the ~40s TPU worker
        watchdog); identical results to :func:`search` unless a
        ``deadline`` (:class:`~rocalphago_tpu.runtime.deadline.
        Deadline`) expires mid-plan. On expiry the halving stops
        where it is, the SURVIVING candidates are reranked by the
        evidence gathered so far, and ``best`` is the anytime answer
        (``g + σ(q̂)`` argmax — the same rule a completed phase
        applies, on a truncated schedule). The first chunk always
        runs; ``search.last_ran`` reports the real simulation count.

        Pipelined like the PUCT loop (``runtime.pipeline``): the host
        dispatches through a :class:`ChunkPipeline` (``depth`` chunks
        in flight, default env/1) and each phase-chunk program
        DONATES the tree slab (the tree is loop-internal — ``init``'s
        output — so no defensive copy is needed; ``g``/``cand`` are
        reused across phases and stay un-donated). The between-phase
        rerank is a device-side dependency of the next phase, so it
        needs no host sync; deadline expiry may leave up to ``depth``
        chunks in flight — they complete and count, the overshoot
        bound (docs/RESILIENCE.md).

        ``n``/``budget`` are the playout-cap seam: ``n`` (host int)
        truncates the halving plan at that many sims — the loop stops
        dispatching, the surviving candidates are reranked on the
        evidence so far and ``best``/π' are the anytime answer, the
        SAME rule a deadline expiry applies; ``budget`` (i32 [B])
        additionally masks per-row for a mixed-budget batch (rows
        past their budget freeze; sims count globally across
        phases)."""
        if caches is None:
            tree, g, cand, logits = init_j(params_p, params_v, roots,
                                           rng)
        else:
            # incremental root encode; the refreshed carry comes back
            # on search.last_caches (same convention as last_ran) —
            # the return tuple stays (visits, q, best, pi)
            tree, g, cand, logits, caches = init_cached_j(
                params_p, params_v, roots, rng, caches)
        search.last_caches = caches
        enforce = deadline is not None and not deadline.unlimited
        pipe = pipeline if pipeline is not None else ChunkPipeline(
            depth, runner="gumbel")
        ran, out_of_time, chunk_i = 0, False, 0
        t_start = time.monotonic()
        if budget is not None:
            budget = budget.astype(jnp.int32)
        for k, v in schedule:
            total = k * v
            for j0 in range(0, total, chunk):
                if ran and enforce and deadline.expired():
                    out_of_time = True
                    break
                if n is not None and ran >= n:
                    # playout cap reached: stop dispatching — the
                    # rerank below is the anytime answer
                    out_of_time = True
                    break
                faults.barrier("search.chunk", chunk_i)
                chunk_i += 1
                count = min(chunk, total - j0)
                if n is not None:
                    count = min(count, n - ran)
                # read off the attribute (not the closure) so tests/
                # instrumentation can wrap the compiled phase program
                t0 = time.monotonic()
                if budget is None:
                    tree = search.run_phase_donated(
                        params_p, params_v, tree, g, cand,
                        jnp.int32(j0), count=count, k=k)
                else:
                    tree = search.run_phase_budget_donated(
                        params_p, params_v, tree, g, cand,
                        jnp.int32(j0), jnp.int32(ran), budget,
                        count=count, k=k)
                # fresh handle: the next chunk donates the tree (see
                # the PUCT loop)
                pipe.push(tree.n_nodes + 0)
                if enforce and pipe.depth == 0:
                    _chunk_h.observe(time.monotonic() - t0)
                ran += count
            # rerank even a truncated phase: the anytime ``best`` is
            # the top candidate under whatever evidence exists
            cand = rerank_j(tree, g, cand, k)
            if out_of_time:
                break
        _sims_c.inc(ran)
        if enforce:
            pipe.drain()
            elapsed = time.monotonic() - t_start
            if elapsed > 0:
                _rate_h.observe(ran / elapsed)
            rem = deadline.remaining()
            if rem is not None:
                _margin_g.set(rem)
        else:
            pipe.finish()
        search.last_ran = ran
        visits, q = base.root_stats(tree)
        return visits, q, cand[:, 0], improved_j(tree, logits)

    init_j = jax.jit(init)
    init_cached_j = jaxobs.track("device_mcts.init_cached",
                                 jax.jit(init_cached))
    rerank_j = jax.jit(rerank, static_argnames=("k",))
    improved_j = jax.jit(improved_policy)

    # same serving-path telemetry as the PUCT chunk loop (shared
    # metric names — one histogram serves both searchers)
    _chunk_h = obs_registry.histogram("device_mcts_chunk_seconds")
    _rate_h = obs_registry.histogram("device_mcts_sims_per_s",
                                     edges=obs_registry.RATE_EDGES)
    _margin_g = obs_registry.gauge("device_mcts_deadline_margin_s")
    _sims_c = obs_registry.counter("device_mcts_sims_total")

    search.init = init_j
    search.init_cached = init_cached_j
    search.make_caches = base.make_caches
    search.last_caches = None   # refreshed carry from run_chunked
    search.rerank = rerank_j
    search.run_phase = jaxobs.track("device_mcts.run_phase", run_phase)
    # the chunk loop's program: run_phase with the tree slab donated
    # in (g/cand are NOT donated — they live across phases); wrap
    # THIS attribute to intercept the loop's chunks
    search.run_phase_donated = jaxobs.track(
        "device_mcts.run_phase",
        functools.partial(jax.jit, static_argnames=("count", "k"),
                          donate_argnums=(2,))(_run_phase_impl))
    search.run_phase_donated.donates_buffers = True
    # playout-cap sibling: per-row GLOBAL sim budgets masked into the
    # phase program (budget/ran0 traced — one program per (count, k))
    search.run_phase_budget_donated = jaxobs.track(
        "device_mcts.run_phase_budget",
        functools.partial(jax.jit, static_argnames=("count", "k"),
                          donate_argnums=(2,))(_run_phase_budget_impl))
    search.run_phase_budget_donated.donates_buffers = True
    search.root_stats = base.root_stats
    search.improved_policy = improved_j
    search.run_chunked = run_chunked
    search.schedule = schedule
    search.m_root = m
    search.max_nodes = max_nodes        # the slab size actually built
    search.last_ran = None              # sims the last chunked run ran
    return search


class DeviceMCTSPlayer:
    """GTP/tournament-facing agent over the on-device search.

    ``get_move(pygo.GameState) -> move | None`` (None = pass): the
    host state is bridged once (:func:`jaxgo.from_pygo`), the whole
    search runs on device (chunk-driven under the worker watchdog),
    and the argmax-visits move comes back — two host↔device transfers
    per move, total.

    PUCT serving REUSES the previous move's subtree: the tree is
    carried across ``get_move`` calls and its root walked down the
    moves actually played (``advance_root``), so the new search
    starts from the visits the old one already spent below that child
    — the host-tree player's ``update_with_move`` economy, in slab
    form. Falls back to a fresh tree on komi/board change, undo, an
    unexpanded edge, a near-full slab, or any position mismatch
    (handicap stones placed outside the history); ``reuse=False``
    disables, ``.reuses`` counts engagements. Gumbel mode always
    rebuilds (its root draw is per-move by design).

    TIME CONTROL: ``set_move_time(seconds)`` (wired from GTP
    ``time_settings``/``time_left`` by the engine) caps the next
    searches' simulation count at ``seconds × measured sims/sec``
    (EMA over past searches; the first timed move runs the full
    budget and seeds the estimate). PUCT shrinks to any chunk
    multiple — only already-compiled chunk programs run; gumbel
    quantizes to halvings of ``n_sim`` so at most log₂ tiers ever
    compile. ``last_n_sim`` reports what the last search really ran.

    DEADLINE: the clock plan is predictive; the same ``seconds``
    budget also arms a hard :class:`~rocalphago_tpu.runtime.deadline.
    Deadline` checked between compiled chunks — a mispredicted
    sims/sec rate or a slow chunk stops the search where it is and
    the ANYTIME answer (argmax visits so far; the gumbel rerank of
    the surviving candidates) goes out instead of blowing the wall
    clock. The floor is one chunk; under the default pipelined
    dispatch (``runtime.pipeline``, one chunk in flight while the
    host decides) the hard stop may additionally let that one
    in-flight chunk complete — its simulations count toward the
    anytime answer and the overshoot is bounded by one chunk's wall
    time (``ROCALPHAGO_PIPELINE_DEPTH=0`` restores the fully-sync
    check). ``last_deadline_hit`` / ``deadline_hits`` report
    enforcement; ``last_n_sim`` then shows the truncated count.

    ``sim_limit`` (int or None) caps the next searches' budget
    regardless of the clock — the degradation ladder's reduced-sims
    retry rung (:class:`~rocalphago_tpu.interface.resilient.
    ResilientPlayer`) sets it for its one cheap re-dispatch after a
    transient device error.
    """

    def __init__(self, value_net, policy_net, n_sim: int = 100,
                 max_nodes: int | None = None, c_puct: float = 5.0,
                 sim_chunk: int = 8, gumbel: bool = False,
                 m_root: int = 16, seed: int = 0,
                 reuse: bool = True,
                 incremental: bool | None = None):
        self.policy = policy_net
        self.value = value_net
        self.board = policy_net.board
        self._cfg = policy_net.cfg
        self._chunk = sim_chunk
        self._n_sim = n_sim
        # None → the factory's own default (2*n_sim for PUCT, 2× the
        # halving plan's real sim count for gumbel — advisor r3);
        # read back from the built searcher below so the reuse
        # check's capacity bound always matches the real slab
        self._max_nodes = max_nodes
        self._c_puct = c_puct
        self._gumbel = gumbel
        self._m_root = m_root
        self._rng = jax.random.key(seed)
        # subtree reuse (PUCT only — gumbel redraws its root noise
        # every move, so its tree is rebuilt by design): the previous
        # move's tree + the (komi, turns_played) it was searched at;
        # get_move walks the actual history delta down child pointers
        # and resumes the search from the shifted root when possible
        self._reuse = reuse and not gumbel
        self._carry = None
        self.reuses = 0     # observability: # of reused searches
        # incremental ROOT encode (features/incremental.py): serving
        # advances the root one move per get_move, so the root
        # planes' ladder chases are re-run only where the one-move
        # board delta touched their recorded footprints. Default ON
        # for this sequential path (env ROCALPHAGO_ENCODE_INCR
        # forces either way); bit-identical priors, so search results
        # never depend on the cache. The cache rides across komi
        # changes (planes don't read komi) and any position jump
        # (board-diff invalidation is the correctness mechanism);
        # reset() drops it per game for honest reuse stats.
        from rocalphago_tpu.features import incremental as _incr

        self._incr = (_incr.enabled(default=True)
                      if incremental is None else incremental)
        self._enc_cache = None
        self._enc_stats = None
        # GTP time control (see class docstring): shared clock, rate
        # samples keyed per searcher so each key's compile-bearing
        # first run never pollutes the sims/sec EMA
        self._clock = MoveClock()
        self.last_n_sim = None      # sims the last get_move ran
        # hard-deadline enforcement stats (class docstring DEADLINE)
        self.last_deadline_hit = False
        self.deadline_hits = 0
        # external per-search sim cap (degradation ladder's reduced
        # rung); None = uncapped
        self.sim_limit: int | None = None
        # per-move telemetry (obs.registry): get_move is fully synced
        # (the visit fetch), so these are real wall numbers
        self._move_h = obs_registry.histogram(
            "device_mcts_get_move_seconds")
        self._rate_h = obs_registry.histogram(
            "device_mcts_sims_per_s", edges=obs_registry.RATE_EDGES)
        # searchers are cached PER KOMI: the search's terminal-node
        # evaluations score with its GoConfig's komi, and GTP can set
        # any komi per game — same handling as the host MCTSPlayer's
        # per-komi rollout programs (search/mcts.py)
        self._searchers: dict = {}
        # build the default-komi searcher NOW: feature-layout
        # validation must fail at construction (like build_player's
        # missing-value guard), not on the first genmove
        self._max_nodes = self._searcher_for(
            self._cfg.komi)[1].max_nodes

    @property
    def n_sim(self) -> int:
        """Nominal per-move simulation budget (uncapped)."""
        return self._n_sim

    def reset(self, reason: str = "new_game") -> None:
        """Forget cross-move search state (new game): the carried
        subtree and the incremental-encode cache (counted per
        ``reason`` — ``encode_cache_resets_total{reason=...}``)."""
        self._carry = None
        if self._enc_cache is not None:
            from rocalphago_tpu.features.api import count_cache_reset

            count_cache_reset(reason)
        self._enc_cache = None
        self._enc_stats = None

    def set_move_time(self, seconds) -> None:
        """Per-move wall budget in seconds (None = no clock). The GTP
        engine calls this before every genmove from the game clock."""
        self._clock.set_move_time(seconds)

    def _effective_sims(self) -> int:
        """Simulation budget for the next search under the clock.

        ``move_time × measured sims/sec``, floored at one chunk and
        capped at nominal ``n_sim``. No clock, or no measurement yet
        (the very first search — which pays the compiles anyway and
        seeds the estimate): full budget."""
        allowed = self._clock.allowed_units()
        if self.sim_limit is not None:
            allowed = (self.sim_limit if allowed is None
                       else min(allowed, self.sim_limit))
        if allowed is None:
            return self._n_sim
        if self._gumbel:
            # halving tiers only: each distinct n_sim compiles its
            # own phase programs, so at most log2(n_sim) tiers exist.
            # The plan has a floor (every phase visits each survivor
            # once) — stop when halving no longer shrinks it, or a
            # starved clock would burn compiles on identical plans
            tier = self._n_sim
            num_actions = self._cfg.num_points + 1
            plan = gumbel_plan_sims(tier, self._m_root, num_actions)
            while tier > 2 and plan > allowed:
                nxt = max(2, tier // 2)
                nxt_plan = gumbel_plan_sims(nxt, self._m_root,
                                            num_actions)
                if nxt_plan >= plan:
                    break               # plan floor reached
                tier, plan = nxt, nxt_plan
            return tier
        # PUCT shrinks to any chunk multiple: only the already-
        # compiled chunk-sized program runs, never a new compile
        return min(self._n_sim,
                   max(self._chunk,
                       allowed // self._chunk * self._chunk))

    def _searcher_for(self, komi: float, n_sim: int | None = None):
        key = (komi, n_sim or self._n_sim)
        if key not in self._searchers:
            import dataclasses

            cfg = dataclasses.replace(self._cfg, komi=komi)
            make = (functools.partial(make_gumbel_mcts,
                                      m_root=self._m_root)
                    if self._gumbel else make_device_mcts)
            self._searchers[key] = (cfg, make(
                cfg, self.policy.feature_list, self.value.feature_list,
                self.policy.module.apply, self.value.module.apply,
                n_sim=key[1], max_nodes=self._max_nodes,
                c_puct=self._c_puct))
        return self._searchers[key]

    def _reused_tree(self, search, state, komi, bridged):
        """Walk the carried tree's root down the moves actually played
        since it was searched; None when a rebuild is needed (no
        carry, komi/board changed, undo, unexpanded edge, the shared
        slab is nearly full, or the walked-to position does not match
        the real one — e.g. free handicap stones placed outside the
        move history)."""
        import numpy as np

        from rocalphago_tpu.utils.coords import flatten_idx

        if self._carry is None:
            return None
        ck, csize, cturns, tree = self._carry
        if (ck != komi or csize != state.size
                or state.turns_played < cturns):
            return None
        n = csize * csize
        for mv in state.history[cturns:]:
            a = n if mv is None else flatten_idx(mv, csize)
            tree, ok = search.advance_root(
                tree, jnp.array([a], jnp.int32))
            if not bool(jax.device_get(ok)[0]):
                return None
        if int(jax.device_get(tree.n_nodes)[0]) \
                > 0.75 * self._max_nodes:
            return None                # slab nearly full: rebuild
        # identity check: the reused root must BE the position we
        # were asked to search (board + turn + ko) — anything the
        # history walk can't see (handicap placement, clear_board)
        # falls back to a fresh tree instead of searching a stale one
        r = int(jax.device_get(tree.root)[0])
        rs = jax.device_get(jax.tree.map(
            lambda x: x[0, r], tree.states))
        ok_pos = (np.array_equal(np.asarray(rs.board),
                                 np.asarray(jax.device_get(
                                     bridged.board)))
                  and int(rs.turn) == int(jax.device_get(bridged.turn))
                  and int(rs.ko) == int(jax.device_get(bridged.ko)))
        return tree if ok_pos else None

    def get_move(self, state):
        import numpy as np

        from rocalphago_tpu.engine import jaxgo as _jaxgo
        from rocalphago_tpu.utils.coords import unflatten_idx

        from rocalphago_tpu.runtime.deadline import Deadline

        komi = float(state.komi)
        eff = self._effective_sims()
        skey = (komi, eff if self._gumbel else self._n_sim)
        cfg, search = self._searcher_for(
            komi, eff if self._gumbel else None)
        root = _jaxgo.from_pygo(cfg, state)
        roots = jax.tree.map(lambda x: x[None], root)
        # the clock PLANNED eff sims; the deadline ENFORCES the wall
        # budget between chunks (anytime answer on expiry). The first
        # search per komi pays the compiles — no rate estimate exists
        # yet and no deadline would be meaningful through a compile —
        # so enforcement starts once the clock is warmed.
        deadline = Deadline.after(
            self._clock.move_time if self._clock.rate is not None
            else None)
        t0 = time.monotonic()
        if self._gumbel:
            self._rng, sub = jax.random.split(self._rng)
            if self._incr and self._enc_cache is None:
                self._enc_cache = search.make_caches(1)
            visits, _, best, _ = search.run_chunked(
                self.policy.params, self.value.params, roots, sub,
                self._chunk, deadline=deadline,
                caches=self._enc_cache if self._incr else None)
            if self._incr:
                self._enc_cache = search.last_caches
            action = int(jax.device_get(best)[0])
            counts = np.asarray(jax.device_get(visits))[0]
            # a halving plan really runs its schedule total, not eff
            planned = sum(k * v for k, v in search.schedule)
            ran = search.last_ran if search.last_ran is not None \
                else planned
        else:
            tree = (self._reused_tree(search, state, komi, root)
                    if self._reuse else None)
            if tree is not None:
                self.reuses += 1
            elif self._incr:
                # incremental root encode: one move past the last
                # encoded root in serving, so the cached ladder
                # verdicts mostly survive the one-stone board delta
                if self._enc_cache is None:
                    self._enc_cache = search.make_caches(1)
                tree, self._enc_cache = search.init_cached(
                    self.policy.params, self.value.params, roots,
                    self._enc_cache)
            else:
                tree = search.init(self.policy.params,
                                   self.value.params, roots)
            # hand the tree over to the donating chunk loop
            # (owned=True): a reused tree shares buffers with the
            # carry, so the carry is dropped FIRST — if a transient
            # fault aborts the search mid-loop (the resilient
            # ladder's retry path), the next get_move must rebuild
            # instead of walking a donated-away slab
            self._carry = None
            # the clock owns the sim count: eff ≤ n_sim simulations
            # in chunk-sized compiled programs (same programs the
            # full budget runs — shrinking never recompiles)
            tree, ran = search.run_sims_chunked(
                self.policy.params, self.value.params, tree,
                self._chunk, n=eff, deadline=deadline, owned=True)
            planned = eff
            visits, _ = search.root_stats(tree)
            counts = np.asarray(jax.device_get(visits))[0]
            action = int(counts.argmax())
            if self._reuse:
                self._carry = (komi, state.size, state.turns_played,
                               tree)
        if self._incr and self._enc_cache is not None:
            from rocalphago_tpu.features.api import observe_incremental

            # get_move is fully synced by the visits fetch above, so
            # the 6-int stats snapshot costs one tiny transfer
            self._enc_stats = observe_incremental(
                self._enc_stats, self._enc_cache.stats)
        self.last_deadline_hit = ran < planned
        self.deadline_hits += int(self.last_deadline_hit)
        dt = time.monotonic() - t0
        self._clock.note(skey, ran, dt)
        self._move_h.observe(dt)
        if dt > 0:
            self._rate_h.observe(ran / dt)
        self.last_n_sim = ran
        if action >= cfg.num_points or counts[action] == 0:
            return None                              # pass
        return unflatten_idx(action, cfg.size)


def make_mcts_selfplay(cfg: GoConfig, policy_features: tuple,
                       value_features: tuple, policy_apply: Callable,
                       value_apply: Callable, batch: int,
                       max_moves: int, n_sim: int,
                       max_nodes: int | None = None,
                       c_puct: float = 5.0, temperature: float = 1.0,
                       sim_chunk: int = 8,
                       record_visits: bool = False,
                       gumbel: bool = False, m_root: int = 16,
                       gumbel_sample: bool = False,
                       dirichlet_alpha: float = 0.0,
                       noise_frac: float = 0.25, mesh=None,
                       cap_p: float | None = None,
                       cap_cheap: int | None = None,
                       cap_per_row: bool = False,
                       forced_k: float = 0.0):
    """Search-driven self-play: every move of every game comes from a
    fresh on-device search over the batch — PUCT
    (:func:`make_device_mcts`, move sampled from root visit counts by
    ``temperature``) or, with ``gumbel=True``,
    :func:`make_gumbel_mcts` (each ply plays the halving winner;
    ``temperature`` does not apply — see the return-contract note).

    This is the AlphaZero-shaped generation loop the reference never
    had (its RL self-play samples the raw policy; SURVEY.md §3.2) —
    here each ply runs ``n_sim`` lockstep simulations for ALL games in
    one set of compiled programs and then samples the move from root
    visit counts (``∝ visits^(1/temperature)``; argmax at
    ``temperature=0``; forced pass when only pass was visited). Games
    that end are frozen by the engine; the host loop carries only the
    batched :class:`GoState` and per-ply actions.

    ``sim_chunk`` bounds the simulations per compiled program (the
    ~40s TPU worker watchdog). Trees are rebuilt per ply (no subtree
    reuse — the standard trade of slab-array search; priors/values are
    recomputed where a host tree would reuse ~1/A of the subtree).

    Returns ``run(params_p, params_v, rng) -> (final GoState,
    actions i32 [T, B], live bool [T, B])`` — with
    ``record_visits=True``, ``(..., targets [T, B, A])``: the
    search-policy targets an AlphaZero-style trainer
    (``training.zero``) learns from — raw root visit counts (i32)
    under PUCT, the improved policy π' (f32, the Gumbel MuZero
    target) under ``gumbel=True``. Gumbel self-play plays each ply's
    halving winner directly: the per-ply fresh Gumbel draw is the
    exploration, so no visit-count temperature sampling applies.

    ``dirichlet_alpha > 0`` (PUCT mode only) mixes AlphaZero root
    exploration noise into each ply's root priors before the
    simulations: ``p ← (1−ε)·p + ε·Dir(α)`` over the prior-supported
    actions, with ``ε = noise_frac`` (paper values: α=0.03, ε=0.25
    for 19×19). Self-play generation only — serving
    (:class:`DeviceMCTSPlayer`) never adds noise. Gumbel mode
    rejects the knob: the gumbel draw is already the root
    exploration mechanism.

    **Self-play economics** (KataGo, "Accelerating Self-Play
    Learning in Go"; all default OFF, each an independent flag):

    - ``cap_p`` — playout-cap randomization. Each ply draws its sim
      budget from the game rng chain: the full ``n_sim`` with
      probability ``cap_p``, else the cheap ``cap_cheap``
      (default ``n_sim // 4``). The draw is SHARED across the batch
      by default — the games run lockstep, so one full-searched row
      would make the whole batch pay full price; a correlated draw
      converts the cheap plies into real wall-clock
      (``E[sims/ply] = p·full + (1−p)·cheap``). ``cap_per_row=True``
      draws iid per game instead and leans on the per-row budget
      masking in the chunk programs (rows at their cap retire sim
      steps as no-ops) — same E[sims] but chunk count follows the
      batch MAX, so it only pays off once per-row early-exit
      matters more than lockstep (e.g. under cross-game batching).
      With ``record_visits=True`` the run appends a
      ``full bool [T, B]`` mask — only full-searched plies should
      emit policy targets (the trainer masks with it); cheap plies
      still train the value/aux heads.
    - ``forced_k`` — forced playouts + policy-target pruning at the
      root (PUCT only): selection floors each root child at
      ``sqrt(forced_k · prior · n_total)`` visits, and the recorded
      target has the forced visits pruned back out
      (:func:`search.pruned_targets`) so exploration doesn't leak
      into the policy target. Targets become f32 (normalized).

    Env defaults: ``ROCALPHAGO_CAP_P`` / ``ROCALPHAGO_CAP_CHEAP``
    seed ``cap_p`` / ``cap_cheap`` when the caller passes ``None``.
    """
    if gumbel and dirichlet_alpha > 0:
        raise ValueError(
            "dirichlet_alpha is a PUCT-mode knob; gumbel self-play's "
            "root exploration is the gumbel draw itself")
    if cap_p is None:
        cap_p = float(os.environ.get("ROCALPHAGO_CAP_P", "") or 0.0)
    if not 0.0 <= cap_p <= 1.0:
        raise ValueError(f"cap_p must be in [0, 1], got {cap_p}")
    if cap_cheap is None:
        cap_cheap = int(os.environ.get("ROCALPHAGO_CAP_CHEAP", "")
                        or max(1, n_sim // 4))
    cheap = max(1, min(int(cap_cheap), n_sim))
    econ = cap_p > 0 and cheap < n_sim
    if gumbel and forced_k:
        raise ValueError(
            "forced_k is a PUCT-root knob; gumbel search visits "
            "candidates by schedule, not PUCT selection")
    if gumbel:
        search = make_gumbel_mcts(cfg, policy_features,
                                  value_features, policy_apply,
                                  value_apply, n_sim, max_nodes,
                                  m_root=m_root, c_puct=c_puct)
    else:
        search = make_device_mcts(cfg, policy_features,
                                  value_features, policy_apply,
                                  value_apply, n_sim, max_nodes,
                                  c_puct, forced_k=forced_k)
    n = cfg.num_points
    vstep = jax.vmap(functools.partial(step, cfg))

    def sample_weighted(weights, sub):
        """Draw an action per game from non-negative weights
        ``∝ w^(1/temperature)``; exact argmax at temperature 0.
        Shared by the visit-count and π' move rules so the two
        cannot drift."""
        if temperature > 0:
            logits = jnp.where(
                weights > 0, jnp.log(jnp.maximum(weights, 1e-9))
                / temperature, -jnp.inf)
            action = jax.random.categorical(sub, logits, axis=-1)
        else:
            action = jnp.argmax(weights, axis=-1)
        return action.astype(jnp.int32)

    @jax.jit
    def pick_and_step(states: GoState, visits, rng):
        rng, sub = jax.random.split(rng)
        action = sample_weighted(visits.astype(jnp.float32), sub)
        live = ~states.done
        return vstep(states, action), rng, action, live

    @jax.jit
    def step_best(states: GoState, best):
        """Gumbel move rule: play the halving winner — the per-ply
        fresh Gumbel draw already IS the exploration (sampling from
        the policy via the Gumbel-max trick), so no visit-count
        temperature sampling on top."""
        live = ~states.done
        return vstep(states, best), best, live

    @jax.jit
    def add_root_noise(tree: DeviceTree, rng):
        """AlphaZero root exploration: mix Dir(α) into the root
        priors over the prior-supported actions."""
        p0 = tree.prior[:, 0, :]
        valid = p0 > 0
        gam = jnp.where(valid, jax.random.gamma(
            rng, dirichlet_alpha, p0.shape, jnp.float32), 0.0)
        dirichlet = gam / jnp.maximum(
            gam.sum(axis=-1, keepdims=True), 1e-12)
        mixed = jnp.where(
            valid, (1.0 - noise_frac) * p0 + noise_frac * dirichlet,
            0.0)
        return tree._replace(prior=tree.prior.at[:, 0, :].set(mixed))

    def puct_search_noisy(params_p, params_v, states, rng):
        """init → noise → the searcher's own chunk loop (the noisy
        tree is ours alone — hand it over for donation)."""
        tree = search.init(params_p, params_v, states)
        tree = add_root_noise(tree, rng)
        return search.run_chunked(params_p, params_v, states,
                                  sim_chunk, tree=tree, owned=True)

    @jax.jit
    def draw_budget(sub):
        """One Bernoulli(cap_p) per ply: shared across the batch by
        default (lockstep games — see the docstring), iid per row
        with ``cap_per_row``."""
        if cap_per_row:
            full = jax.random.bernoulli(sub, cap_p, (batch,))
        else:
            full = jnp.broadcast_to(
                jax.random.bernoulli(sub, cap_p), (batch,))
        return full, jnp.where(full, n_sim, cheap).astype(jnp.int32)

    def puct_search(params_p, params_v, states, noise_rng, n_ply,
                    budget):
        """The economics PUCT ply: same program sequence as
        :func:`run_chunked` (init → [noise] → donated chunk loop →
        root stats), but with the ply's sim count / per-row budget
        threaded through and the pruned policy target read off the
        final tree when ``forced_k`` is on."""
        tree = search.init(params_p, params_v, states)
        if dirichlet_alpha > 0:
            tree = add_root_noise(tree, noise_rng)
        tree, ran = search.run_sims_chunked(
            params_p, params_v, tree, sim_chunk, n=n_ply,
            budget=budget, owned=True)
        visits, _ = search.root_stats(tree)
        if forced_k:
            target, pruned = search.pruned_targets(tree)
        else:
            target, pruned = visits, None
        return visits, target, pruned, ran

    # per-ply wall time of search self-play (the done-fetch below
    # syncs each ply, so the numbers are real)
    _ply_h = obs_registry.histogram("selfplay_ply_seconds")
    _sims_h = obs_registry.histogram("selfplay_sims_per_move",
                                     edges=obs_registry.COUNT_EDGES)
    _full_g = obs_registry.gauge("selfplay_fullsearch_frac")
    _pruned_c = obs_registry.counter("policy_targets_pruned_total")

    def run(params_p, params_v, rng):
        states = new_states(cfg, batch)
        if mesh is not None:
            # the search shards by placement alone (module docstring):
            # sharding the game batch here shards every per-ply search
            # and the engine steps; params stay replicated
            from rocalphago_tpu.parallel import mesh as meshlib

            states = meshlib.shard_batch(mesh, states)
        actions, lives, visit_seq, full_seq = [], [], [], []
        pruned_acc, full_frac, n_plies = [], 0.0, 0
        for _ in range(max_moves):
            t_ply = time.monotonic()
            if econ:
                # the budget draw is a separate split so the OFF
                # path's rng chain (and everything downstream of it)
                # stays bit-identical
                rng, sub_b = jax.random.split(rng)
                full, budget = draw_budget(sub_b)
                fh = np.asarray(jax.device_get(full))
                n_ply = int(n_sim if fh.any() else cheap)
                budget_arg = budget if cap_per_row else None
                full_frac += float(fh.mean())
                n_plies += 1
            if gumbel:
                rng, sub = jax.random.split(rng)
                if econ:
                    visits, _, best, pi = search.run_chunked(
                        params_p, params_v, states, sub, sim_chunk,
                        n=n_ply, budget=budget_arg)
                    _sims_h.observe(search.last_ran
                                    if search.last_ran is not None
                                    else n_ply)
                else:
                    visits, _, best, pi = search.run_chunked(
                        params_p, params_v, states, sub, sim_chunk)
                if gumbel_sample:
                    # ``gumbel_sample`` move rule (VERDICT r4 #9
                    # experiment): sample the move from the improved
                    # policy π' instead of playing the halving
                    # winner — keeps the π' TRAINING target while
                    # restoring PUCT-style stochastic play (the
                    # round-4 rerun measured play-the-winner
                    # narrowing the game distribution off the value
                    # manifold, results/zero_scale_r4/target_compare)
                    states, rng, action, live = pick_and_step(
                        states, pi, rng)
                else:
                    states, action, live = step_best(states, best)
                target = pi
            elif econ or forced_k:
                sub = None
                if dirichlet_alpha > 0:
                    rng, sub = jax.random.split(rng)
                visits, target, pruned, ran = puct_search(
                    params_p, params_v, states, sub,
                    n_ply if econ else None,
                    budget_arg if econ else None)
                if econ:
                    _sims_h.observe(ran)
                if pruned is not None:
                    pruned_acc.append(pruned.sum())
                # the move is always sampled from the RAW visit
                # counts — pruning reshapes only the recorded target
                states, rng, action, live = pick_and_step(
                    states, visits, rng)
            elif dirichlet_alpha > 0:
                rng, sub = jax.random.split(rng)
                visits, _ = puct_search_noisy(params_p, params_v,
                                              states, sub)
                states, rng, action, live = pick_and_step(
                    states, visits, rng)
                target = visits
            else:
                visits, _ = search.run_chunked(params_p, params_v,
                                               states, sim_chunk)
                states, rng, action, live = pick_and_step(
                    states, visits, rng)
                target = visits
            actions.append(action)
            lives.append(live)
            if record_visits:
                visit_seq.append(target)
                if econ:
                    full_seq.append(full)
            done = bool(jax.device_get(states.done.all()))
            _ply_h.observe(time.monotonic() - t_ply)
            if done:
                break
        if econ and n_plies:
            _full_g.set(full_frac / n_plies)
        if pruned_acc:
            _pruned_c.inc(int(jax.device_get(sum(pruned_acc))))
        n_act = cfg.num_points + 1
        out = (states,
               jnp.stack(actions) if actions
               else jnp.zeros((0, batch), jnp.int32),
               jnp.stack(lives) if lives
               else jnp.zeros((0, batch), bool))
        if record_visits:
            tdtype = (jnp.float32 if (gumbel or forced_k)
                      else jnp.int32)
            out += (jnp.stack(visit_seq) if visit_seq
                    else jnp.zeros((0, batch, n_act), tdtype),)
            if econ:
                out += (jnp.stack(full_seq) if full_seq
                        else jnp.zeros((0, batch), bool),)
        return out

    return run
