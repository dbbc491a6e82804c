"""Self-play economics (docs/PERFORMANCE.md "Self-play economics"):
playout-cap randomization, forced-playout policy-target pruning, and
the auxiliary ownership/score labels — plus the flags-OFF bit-identity
guarantees the whole layer is gated behind.

Same fake-backend strategy as tests/test_device_mcts.py: injected
jittable policy/value callables, tiny boards, so every path runs as
the compiled programs it is in production with no trained nets.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocalphago_tpu.engine import jaxgo
from rocalphago_tpu.engine.jaxgo import GoConfig, new_states
from rocalphago_tpu.search.device_mcts import (
    make_device_mcts,
    make_mcts_selfplay,
)

SIZE = 5
N = SIZE * SIZE
FEATS = ("board", "ones")
VFEATS = FEATS + ("color",)
CFG = GoConfig(size=SIZE)


def fake_policy(params, planes):
    return jnp.zeros((planes.shape[0], N))


def fake_value(params, planes):
    mine = planes[..., 0].sum(axis=(1, 2))
    theirs = planes[..., 1].sum(axis=(1, 2))
    return (mine - theirs) / N


# ------------------------------------------------ masked budget runs


def test_full_budget_matches_plain_run():
    """A budget of n_sim on every row must be the plain chunked run
    bit-for-bit — the masked program is the SAME search with rows
    switched off, so all-on is the identity."""
    s = make_device_mcts(CFG, FEATS, VFEATS, fake_policy, fake_value,
                         n_sim=16, max_nodes=32)
    roots = new_states(CFG, 2)
    t1 = s.init(None, None, roots)
    t1, ran1 = s.run_sims_chunked(None, None, t1, 4, owned=True)
    v1, q1 = jax.device_get(s.root_stats(t1))
    t2 = s.init(None, None, roots)
    t2, ran2 = s.run_sims_chunked(None, None, t2, 4, owned=True,
                                  budget=jnp.full((2,), 16, jnp.int32))
    v2, q2 = jax.device_get(s.root_stats(t2))
    assert ran1 == ran2 == 16
    np.testing.assert_array_equal(v1, v2)
    np.testing.assert_array_equal(q1, q2)


def test_mixed_budget_rows_stop_at_cap():
    """Mixed per-row budgets in ONE slab program: each cheap row's
    root visits total exactly its budget, and a full-budget row is
    bit-identical to the same row of an unmasked run (rows are
    independent per-game trees — masking a neighbor must not leak)."""
    s = make_device_mcts(CFG, FEATS, VFEATS, fake_policy, fake_value,
                         n_sim=16, max_nodes=32)
    roots = new_states(CFG, 3)
    budget = jnp.array([4, 16, 9], jnp.int32)
    tree = s.init(None, None, roots)
    tree, _ = s.run_sims_chunked(None, None, tree, 5, owned=True,
                                 budget=budget)
    v, q = jax.device_get(s.root_stats(tree))
    np.testing.assert_array_equal(v.sum(axis=1), np.asarray(budget))
    plain = s.init(None, None, roots)
    plain, _ = s.run_sims_chunked(None, None, plain, 5, owned=True)
    vp, qp = jax.device_get(s.root_stats(plain))
    np.testing.assert_array_equal(v[1], vp[1])
    np.testing.assert_array_equal(q[1], qp[1])


# ------------------------------------- forced playouts + pruning


def test_pruned_targets_sum_to_one_and_zero_forced_only():
    """KataGo target pruning: the recorded distribution sums to 1,
    keeps the most-visited child whole, and zeroes children whose
    visits don't clear the forced floor — forced-only exploration
    must not teach the policy."""
    s = make_device_mcts(CFG, FEATS, VFEATS, fake_policy, fake_value,
                         n_sim=32, max_nodes=64, forced_k=2.0)
    roots = new_states(CFG, 2)
    tree = s.init(None, None, roots)
    tree = s.run_sims(None, None, tree, k=32)
    visits, _ = jax.device_get(s.root_stats(tree))
    target, pruned = jax.device_get(s.pruned_targets(tree))
    np.testing.assert_allclose(target.sum(axis=-1), 1.0, rtol=1e-5)
    assert (target >= 0).all()
    assert ((target > 0) <= (visits > 0)).all(), (
        "target puts mass on an unvisited child")
    # uniform priors at 32 sims: floor = sqrt(2·32/25) ≈ 1.6, so
    # 1-visit children are forced-only and must be zeroed
    assert ((visits > 0) & (target == 0)).any()
    assert (pruned > 0).all()
    best = visits.argmax(axis=-1)
    assert (target[np.arange(2), best] > 0).all()
    np.testing.assert_array_equal(target.argmax(axis=-1), best)


def test_pruned_targets_reduce_to_visits_without_forcing():
    """forced_k=0: the floor is 0 and the target is exactly the
    normalized visit distribution with nothing pruned."""
    s = make_device_mcts(CFG, FEATS, VFEATS, fake_policy, fake_value,
                         n_sim=16, max_nodes=32)
    roots = new_states(CFG, 2)
    tree = s.init(None, None, roots)
    tree = s.run_sims(None, None, tree, k=16)
    visits, _ = jax.device_get(s.root_stats(tree))
    target, pruned = jax.device_get(s.pruned_targets(tree))
    np.testing.assert_array_equal(pruned, 0)
    np.testing.assert_allclose(
        target, visits / visits.sum(axis=-1, keepdims=True), rtol=1e-6)


# ------------------------------------------------ self-play gating


def _selfplay_kwargs(**over):
    kw = dict(batch=2, max_moves=6, n_sim=8, max_nodes=16, sim_chunk=4,
              record_visits=True)
    kw.update(over)
    return kw


def test_selfplay_flags_off_identity():
    """Explicitly-disabled economics kwargs must be the default path
    bit-for-bit — actions, live mask, targets, and the rng chain all
    untouched (the OFF path never splits the game rng)."""
    base = make_mcts_selfplay(CFG, FEATS, VFEATS, fake_policy,
                              fake_value, **_selfplay_kwargs())
    off = make_mcts_selfplay(CFG, FEATS, VFEATS, fake_policy,
                             fake_value,
                             **_selfplay_kwargs(cap_p=0.0, cap_cheap=2,
                                                forced_k=0.0))
    out_b = jax.device_get(base(None, None, jax.random.key(5)))
    out_o = jax.device_get(off(None, None, jax.random.key(5)))
    assert len(out_b) == len(out_o) == 4       # no full mask when OFF
    for a, b in zip(jax.tree.leaves(out_b), jax.tree.leaves(out_o)):
        np.testing.assert_array_equal(a, b)
    assert np.asarray(out_b[3]).dtype == np.int32


def test_selfplay_cap_correlated_draw_and_budget_sums():
    """Correlated (default) cap draw: every row of a ply shares one
    Bernoulli, the returned full mask matches, and each ply's target
    visit total is exactly the drawn budget — cheap plies stop at the
    cap, full plies run the whole n_sim."""
    run = make_mcts_selfplay(CFG, FEATS, VFEATS, fake_policy,
                             fake_value,
                             **_selfplay_kwargs(batch=4, cap_p=0.5,
                                                cap_cheap=2))
    final, actions, live, targets, full = run(None, None,
                                              jax.random.key(0))
    f = np.asarray(full)
    lv = np.asarray(live)
    t = np.asarray(targets)
    assert f.dtype == np.bool_ and f.shape == lv.shape
    assert t.dtype == np.int32
    assert (f == f[:, :1]).all(), "correlated draw differs in-batch"
    sums = t.sum(axis=-1)
    np.testing.assert_array_equal(
        sums, np.where(lv, np.where(f, 8, 2), 0))


def test_selfplay_cap_per_row_budgets():
    """Per-row (iid) draw: rows of one ply may differ, and each row's
    visit total still matches its own draw."""
    run = make_mcts_selfplay(CFG, FEATS, VFEATS, fake_policy,
                             fake_value,
                             **_selfplay_kwargs(batch=4, cap_p=0.5,
                                                cap_cheap=2,
                                                cap_per_row=True))
    _, _, live, targets, full = run(None, None, jax.random.key(2))
    f = np.asarray(full)
    lv = np.asarray(live)
    sums = np.asarray(targets).sum(axis=-1)
    np.testing.assert_array_equal(
        sums, np.where(lv, np.where(f, 8, 2), 0))


def test_selfplay_forced_k_records_pruned_distribution():
    """forced_k on its own: moves still come from RAW visits, but the
    recorded target is the pruned float distribution."""
    run = make_mcts_selfplay(CFG, FEATS, VFEATS, fake_policy,
                             fake_value,
                             **_selfplay_kwargs(forced_k=1.0))
    _, actions, live, targets = run(None, None, jax.random.key(1))
    t = np.asarray(targets)
    assert t.dtype == np.float32
    lv = np.asarray(live)
    np.testing.assert_allclose(t.sum(axis=-1)[lv], 1.0, rtol=1e-5)
    acts = np.asarray(actions)
    assert ((acts >= 0) & (acts <= N)).all()


# ------------------------------------------------ terminal labels


@pytest.mark.parametrize("size,moves", [(9, 70), (19, 320)])
def test_terminal_labels_parity_with_engine_scoring(
        size, moves, random_game_states):
    """jaxgo.terminal_labels must agree with the engine's area
    scoring exactly: score == black − white_plus_komi, sign(score) ==
    jaxgo.winner, and the per-point ownership counts reproduce the
    score (ownership IS the area verdict per point)."""
    cfg = GoConfig(size=size)
    states = random_game_states(cfg, 8, moves, jax.random.key(2))
    own, score = jax.device_get(
        jax.vmap(lambda s: jaxgo.terminal_labels(cfg, s))(states))
    b, w = jax.device_get(
        jax.vmap(lambda s: jaxgo.area_scores(cfg, s))(states))
    np.testing.assert_allclose(
        score, np.asarray(b, np.float32) - np.asarray(w, np.float32))
    winners = jax.device_get(
        jax.vmap(lambda s: jaxgo.winner(cfg, s))(states))
    np.testing.assert_array_equal(
        np.sign(score).astype(np.int32), winners)
    assert own.dtype == np.int8
    assert set(np.unique(own)) <= {-1, 0, 1}
    np.testing.assert_allclose(
        (own == 1).sum(axis=-1) - (own == -1).sum(axis=-1) - cfg.komi,
        score)


# ------------------------------------------------ aux value heads


def test_aux_heads_graft_keeps_value_bit_identical():
    """with_aux_heads: the grown net's value output is the trained
    net's bit-for-bit (trunk + value head copied by value); the new
    heads predict with the right shapes."""
    from rocalphago_tpu.models import CNNValue
    from rocalphago_tpu.models.value import with_aux_heads

    val = CNNValue(VFEATS, board=SIZE, layers=1, filters_per_layer=4)
    grown = with_aux_heads(val, seed=3)
    assert grown.module.aux_heads == ("ownership", "score")
    from rocalphago_tpu.engine import pygo

    st = pygo.GameState(size=SIZE)
    st.do_move((1, 1), pygo.BLACK)
    v0 = val.batch_eval_state([st])
    v1 = grown.batch_eval_state([st])
    np.testing.assert_array_equal(v0, v1)
    planes = grown._states_to_planes([st])
    v, aux = jax.device_get(grown.forward_aux(planes))
    np.testing.assert_array_equal(np.asarray(v), v1)
    assert aux["ownership"].shape == (1, N)
    assert (np.abs(aux["ownership"]) <= 1.0).all()
    assert aux["score"].shape == (1,)
    # unknown head names rejected up front
    with pytest.raises(ValueError, match="aux heads"):
        CNNValue.create_network(board=SIZE, aux_heads=("bogus",))


# ------------------------------------------------ zero iteration


def _make_iteration(pol, val, **over):
    import optax
    from rocalphago_tpu.training.zero import make_zero_iteration

    tx_p, tx_v = optax.sgd(0.01), optax.sgd(0.01)
    kw = dict(batch=2, move_limit=6, n_sim=4, max_nodes=8, sim_chunk=2,
              replay_chunk=6)
    kw.update(over)
    return (make_zero_iteration(
        CFG, FEATS, VFEATS, pol.module.apply, val.module.apply,
        tx_p, tx_v, **kw), tx_p, tx_v)


def _state_fingerprint(state):
    import hashlib

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(jax.device_get(state)):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def test_zero_iteration_flags_off_identity():
    """One full zero iteration with every economics kwarg explicitly
    disabled must produce the SAME state (params, opt state, rng) as
    the default build — the gate is trace-time, so OFF means the
    pre-economics programs run unchanged."""
    from rocalphago_tpu.models import CNNPolicy, CNNValue
    from rocalphago_tpu.training.zero import init_zero_state

    pol = CNNPolicy(FEATS, board=SIZE, layers=1, filters_per_layer=4)
    val = CNNValue(VFEATS, board=SIZE, layers=1, filters_per_layer=4)
    it0, tx_p, tx_v = _make_iteration(pol, val)
    it1, _, _ = _make_iteration(pol, val, cap_p=0.0, cap_cheap=1,
                                forced_k=0.0, aux_weight=0.0)
    s0 = init_zero_state(pol.params, val.params, tx_p, tx_v, seed=0)
    new0, _ = it0(s0)
    s1 = init_zero_state(pol.params, val.params, tx_p, tx_v, seed=0)
    new1, _ = it1(s1)
    assert _state_fingerprint(new0) == _state_fingerprint(new1)


@pytest.mark.slow
def test_zero_iteration_econ_aux_end_to_end():
    """Everything ON at once (cap + forcing + aux heads): the
    iteration runs end-to-end, aux losses are finite, the record
    carries the full mask and labels, and a v1-shaped record (full
    stripped) still learns — the learner synthesizes all-full."""
    from rocalphago_tpu.models import CNNPolicy, CNNValue
    from rocalphago_tpu.models.value import with_aux_heads
    from rocalphago_tpu.training.zero import init_zero_state

    pol = CNNPolicy(FEATS, board=SIZE, layers=1, filters_per_layer=4)
    val = with_aux_heads(
        CNNValue(VFEATS, board=SIZE, layers=1, filters_per_layer=4))
    import functools

    it, tx_p, tx_v = _make_iteration(
        pol, val, move_limit=8, cap_p=0.5, cap_cheap=2, forced_k=1.0,
        aux_weight=0.5,
        value_apply_aux=functools.partial(val.module.apply,
                                          with_aux=True))
    state = init_zero_state(pol.params, val.params, tx_p, tx_v, seed=1)
    import jax.random as jrandom

    from rocalphago_tpu.io.checkpoint import unpack_rng

    _, game_key = jrandom.split(unpack_rng(state.rng))
    games = jax.device_get(it.play(state.policy_params,
                                   state.value_params, game_key))
    assert games.full is not None and games.full.dtype == np.bool_
    assert games.ownership is not None and games.score is not None
    new, m = it.learn(state, games)
    for key in ("policy_loss", "value_loss", "aux_loss_ownership",
                "aux_loss_score"):
        assert np.isfinite(float(jax.device_get(m[key]))), key
    # v1-shaped record: the full mask absent -> treated as all-full
    v1_games = games._replace(full=None)
    new2, m2 = it.learn(state, v1_games)
    assert np.isfinite(float(jax.device_get(m2["policy_loss"])))
