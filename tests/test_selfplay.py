"""On-device self-play loop + host agents.

Mirrors the reference's agent behavior contracts (``ai.py``:
legal/sensible move selection, lockstep ``get_moves``; SURVEY.md §2
"Agents") and validates the rebuild's scaling primitive: the fully
jitted batched game loop terminates, scores, and respects rules.
"""

import jax
import numpy as np
import pytest

from rocalphago_tpu.engine import pygo
from rocalphago_tpu.engine.jaxgo import GoConfig
from rocalphago_tpu.models import CNNPolicy, CNNValue
from rocalphago_tpu.search.players import (
    GreedyPolicyPlayer,
    ProbabilisticPolicyPlayer,
    ValuePlayer,
)
from rocalphago_tpu.search.selfplay import (
    make_selfplay,
    make_selfplay_chunked,
)

SIZE = 5
FEATURES = ("board", "ones")


@pytest.fixture(scope="module")
def policy():
    return CNNPolicy(FEATURES, board=SIZE, layers=2, filters_per_layer=4)


@pytest.fixture(scope="module")
def result(policy):
    cfg = GoConfig(size=SIZE)
    run = make_selfplay(cfg, FEATURES, policy.module.apply,
                        policy.module.apply, batch=8, max_moves=80)
    return run(policy.params, policy.params, jax.random.key(0))


def test_selfplay_terminates_and_scores(result):
    assert np.asarray(result.final.done).all()
    winners = np.asarray(result.winners)
    assert set(np.unique(winners)).issubset({-1, 0, 1})
    moves = np.asarray(result.num_moves)
    assert (moves > 2).all() and (moves <= 80).all()


def test_host_winners_matches_device_scoring(result):
    """The host scorer benchmarks rely on must agree with the device
    winner() on real final boards."""
    from rocalphago_tpu.search.selfplay import host_winners

    cfg = GoConfig(size=SIZE)
    device = np.asarray(result.winners)
    host = host_winners(cfg, np.asarray(result.final.board))
    np.testing.assert_array_equal(device, host)


def test_selfplay_trajectories_replay_legally(result):
    """Replaying the recorded actions through the host oracle engine
    must raise no IllegalMove and reproduce the final boards."""
    actions = np.asarray(result.actions)      # [T, B]
    live = np.asarray(result.live)
    boards = np.asarray(result.final.board)
    for g in range(actions.shape[1]):
        st = pygo.GameState(size=SIZE)
        for t in range(actions.shape[0]):
            if not live[t, g]:
                continue
            a = actions[t, g]
            mv = None if a == SIZE * SIZE else (a // SIZE, a % SIZE)
            st.do_move(mv)   # raises IllegalMove on any rules violation
        np.testing.assert_array_equal(
            np.asarray(st.board, np.int8).reshape(-1), boards[g],
            err_msg=f"game {g} board mismatch")


def test_selfplay_deterministic_given_key(policy):
    cfg = GoConfig(size=SIZE)
    run = make_selfplay(cfg, FEATURES, policy.module.apply,
                        policy.module.apply, batch=4, max_moves=40)
    a = run(policy.params, policy.params, jax.random.key(7))
    b = run(policy.params, policy.params, jax.random.key(7))
    np.testing.assert_array_equal(np.asarray(a.actions),
                                  np.asarray(b.actions))


@pytest.mark.slow
def test_chunked_selfplay_bit_identical(policy):
    """The chunked runner (TPU watchdog workaround) must reproduce the
    monolithic scan exactly — including a non-divisible remainder
    segment (25 plies in chunks of 10 → segments of 10/10/5)."""
    cfg = GoConfig(size=SIZE)
    mono = make_selfplay(cfg, FEATURES, policy.module.apply,
                         policy.module.apply, batch=4, max_moves=25)
    chunked = make_selfplay_chunked(cfg, FEATURES, policy.module.apply,
                                    policy.module.apply, batch=4,
                                    max_moves=25, chunk=10)
    a = mono(policy.params, policy.params, jax.random.key(3))
    b = chunked(policy.params, policy.params, jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(a.actions),
                                  np.asarray(b.actions))
    np.testing.assert_array_equal(np.asarray(a.live), np.asarray(b.live))
    np.testing.assert_array_equal(np.asarray(a.winners),
                                  np.asarray(b.winners))
    np.testing.assert_array_equal(np.asarray(a.final.board),
                                  np.asarray(b.final.board))
    np.testing.assert_array_equal(np.asarray(a.num_moves),
                                  np.asarray(b.num_moves))


@pytest.mark.slow
def test_sharded_selfplay_bit_identical_and_distributed(policy):
    """Game-batch sharding over the mesh's data axis (env parallelism
    across devices, SURVEY.md §2b) must not change a single move, and
    must actually distribute the state across the 8 virtual devices
    the conftest provides."""
    from rocalphago_tpu.parallel.mesh import make_mesh

    cfg = GoConfig(size=SIZE)
    mesh = make_mesh()       # all 8 virtual CPU devices
    plain = make_selfplay_chunked(cfg, FEATURES, policy.module.apply,
                                  policy.module.apply, batch=16,
                                  max_moves=20, chunk=8)
    sharded = make_selfplay_chunked(cfg, FEATURES, policy.module.apply,
                                    policy.module.apply, batch=16,
                                    max_moves=20, chunk=8, mesh=mesh)
    a = plain(policy.params, policy.params, jax.random.key(11))
    b = sharded(policy.params, policy.params, jax.random.key(11))
    np.testing.assert_array_equal(np.asarray(a.actions),
                                  np.asarray(b.actions))
    np.testing.assert_array_equal(np.asarray(a.winners),
                                  np.asarray(b.winners))
    assert len(b.final.board.sharding.device_set) == 8

    with pytest.raises(ValueError, match="data-axis"):
        make_selfplay_chunked(cfg, FEATURES, policy.module.apply,
                              policy.module.apply, batch=6,
                              max_moves=20, mesh=mesh)


def test_greedy_player_moves_are_sensible(policy):
    st = pygo.GameState(size=SIZE)
    player = GreedyPolicyPlayer(policy)
    mv = player.get_move(st)
    assert mv in st.get_legal_moves(include_eyes=False)


def test_probabilistic_player_lockstep_batch(policy):
    states = [pygo.GameState(size=SIZE) for _ in range(3)]
    states[1].do_move((2, 2))
    player = ProbabilisticPolicyPlayer(policy, temperature=0.5, seed=0)
    moves = player.get_moves(states)
    assert len(moves) == 3
    for st, mv in zip(states, moves):
        assert mv in st.get_legal_moves(include_eyes=False)


def test_probabilistic_player_respects_move_limit(policy):
    st = pygo.GameState(size=SIZE)
    player = ProbabilisticPolicyPlayer(policy, move_limit=0)
    assert player.get_move(st) is None


def test_value_player_picks_legal_move():
    value = CNNValue(FEATURES, board=SIZE, layers=2, filters_per_layer=4,
                     dense_units=8)
    st = pygo.GameState(size=SIZE)
    player = ValuePlayer(value)
    assert player.get_move(st) in st.get_legal_moves(include_eyes=False)


def test_warmup_compiles_exactly_the_timed_programs():
    """run.warmup must leave a subsequent full rep with ZERO segment
    compiles — the exact-program warmup discipline: a timed rep
    after it pays no compile."""
    cfg = GoConfig(size=5)
    net = CNNPolicy(("board", "ones"), board=5, layers=1,
                    filters_per_layer=2)
    # chunk deliberately not a divisor: the remainder segment is its
    # own compile and warmup must cover it too
    run = make_selfplay_chunked(
        cfg, net.feature_list, net.module.apply, net.module.apply,
        batch=4, max_moves=10, chunk=4, score_on_device=False)
    seg_s = run.warmup(net.params, net.params)
    assert seg_s is not None and seg_s > 0
    n0 = run.segment._cache_size()
    assert n0 == 2          # chunk-length + remainder programs
    res = run(net.params, net.params, jax.random.key(1),
              stop_when_done=True)
    jax.device_get(res.actions)
    assert run.segment._cache_size() == n0   # zero compile growth
