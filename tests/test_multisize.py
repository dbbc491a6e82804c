"""Multi-size subsystem: FCN heads applying one checkpoint at every
board size, the MultiSizePool serving ladder + GTP boardsize
re-routing, per-session komi as data, and the progressive-size
curriculum driver.

Tiny nets and small boards throughout; the board-size PARAMETRIZATION
is the point — the same param pytree must apply and stay
symmetry-honest at every size.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rocalphago_tpu.engine import jaxgo, pygo
from rocalphago_tpu.models import CNNPolicy, CNNValue

SIZE = 5
FEATS = ("board", "ones")
VFEATS = FEATS + ("color",)


@pytest.fixture(scope="module")
def fcn_nets():
    pol = CNNPolicy(FEATS, board=SIZE, layers=2, filters_per_layer=4)
    val = CNNValue(VFEATS, board=SIZE, layers=2, filters_per_layer=4)
    return pol, val


def _dense_value():
    os.environ["ROCALPHAGO_VALUE_HEAD"] = "dense"
    try:
        return CNNValue(VFEATS, board=SIZE, layers=2,
                        filters_per_layer=4)
    finally:
        del os.environ["ROCALPHAGO_VALUE_HEAD"]


# ------------------------------------------------------ FCN heads


def test_policy_fcn_vs_bias_head_ab_fixed_seed(fcn_nets):
    """A fresh net is bit-identical under either policy head: the
    legacy per-position bias initializes to zeros, so head='fcn'
    (which omits it) changes nothing until training moves it."""
    pol, _ = fcn_nets
    legacy = CNNPolicy(FEATS, board=SIZE, layers=2,
                       filters_per_layer=4, head="bias")
    planes = jnp.zeros((2, SIZE, SIZE, pol.preprocess.output_dim))
    planes = planes.at[0, 2, 2, 0].set(1.0)
    a = np.asarray(pol.forward(planes))
    b = np.asarray(legacy.forward(planes))
    np.testing.assert_array_equal(a, b)
    assert pol.size_generic() and not legacy.size_generic()


def test_value_head_env_knob_and_size_lock(fcn_nets):
    _, val = fcn_nets
    dense = _dense_value()
    assert val.size_generic() and not dense.size_generic()
    with pytest.raises(ValueError, match="MULTISIZE"):
        dense.at_board(9)
    # the facade at the native size is the net itself
    assert val.at_board(SIZE) is val


@pytest.mark.parametrize("size", [7, 9, 13])
def test_one_checkpoint_applies_at_every_size(tmp_path, fcn_nets,
                                              size):
    """Save at 5, load, apply at 7/9/13: same param pytree (shared by
    reference), right output shapes, finite values."""
    pol, val = fcn_nets
    pj = os.path.join(tmp_path, "policy.json")
    vj = os.path.join(tmp_path, "value.json")
    pol.save_model(pj)
    val.save_model(vj)
    from rocalphago_tpu.models.nn_util import NeuralNetBase

    for src, loaded in ((pol, NeuralNetBase.load_model(pj)),
                        (val, NeuralNetBase.load_model(vj))):
        facade = loaded.at_board(size)
        assert facade.board == size
        assert facade.params is loaded.params
        planes = jnp.zeros(
            (1, size, size, facade.preprocess.output_dim))
        out = np.asarray(facade.forward(planes))
        want = (1, size * size) if src is pol else (1,)
        assert out.shape == want
        assert np.isfinite(out).all()
    # loaded weights match the saved net bit-for-bit
    for a, b in zip(jax.tree.leaves(pol.params),
                    jax.tree.leaves(
                        NeuralNetBase.load_model(pj).params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("size", [5, 9, 13])
def test_value_symmetric_invariant_across_sizes(fcn_nets, size):
    """The dihedral-ensembled value is invariant under any board
    transform AT EVERY SIZE the facade serves — the invariance audit
    the multi-size pool leans on."""
    from rocalphago_tpu.training.symmetries import transform_planes

    _, val = fcn_nets
    net = val.at_board(size)
    rng = np.random.default_rng(size)
    planes = jnp.asarray(rng.standard_normal(
        (1, size, size, net.preprocess.output_dim)), jnp.float32)
    base = np.asarray(net.forward_symmetric(planes))
    for t in range(8):
        tp = jax.vmap(lambda x: transform_planes(x, t))(planes)
        np.testing.assert_allclose(
            np.asarray(net.forward_symmetric(tp)), base,
            rtol=0, atol=1e-5)


@pytest.mark.parametrize("size", [5, 9, 13, 19])
def test_symmetry_transforms_round_trip(size):
    """transform/inverse_transform are exact inverses at every
    supported size (the action map has its own test, below)."""
    from rocalphago_tpu.training.symmetries import (
        inverse_transform_planes,
        transform_planes,
    )

    rng = np.random.default_rng(size)
    x = jnp.asarray(rng.standard_normal((size, size, 2)), jnp.float32)
    for t in range(8):
        rt = inverse_transform_planes(transform_planes(x, t), t)
        np.testing.assert_array_equal(np.asarray(rt), np.asarray(x))


@pytest.mark.parametrize("t", range(8))
@pytest.mark.parametrize("size", [5, 9, 13, 19])
def test_transform_action_follows_the_planes(size, t):
    """Every point, every group element: the action map sends a point
    to where ``transform_planes`` puts that point's stone, it is a
    permutation of the board, and pass stays pass."""
    from rocalphago_tpu.training.symmetries import (
        transform_action,
        transform_planes,
    )

    n = size * size
    moved = np.asarray(transform_action(
        jnp.arange(n + 1, dtype=jnp.int32), jnp.int32(t), size))
    # row a of `boards` is the one-hot board of action a
    boards = jnp.eye(n, dtype=jnp.float32).reshape(n, size, size)
    landed = np.asarray(jax.vmap(
        lambda b: transform_planes(b, jnp.int32(t)))(boards))
    landed = landed.reshape(n, n)
    assert (landed.sum(axis=1) == 1).all()
    np.testing.assert_array_equal(moved[:n], landed.argmax(axis=1))
    assert sorted(moved[:n]) == list(range(n))
    assert moved[n] == n


def test_random_transform_batch_matches_numpy_oracle():
    """The draw of ``t`` from the key and the pairing of each sample's
    planes with its action, against ``np.rot90``/``np.flip`` on one-hot
    boards — what the benchmark's reference inputs rely on."""
    from rocalphago_tpu.training.symmetries import random_transform_batch

    size, batch = 19, 16
    n = size * size
    key = jax.random.key(25)
    rng = np.random.default_rng(25)
    planes = rng.standard_normal((batch, size, size, 3)).astype(
        np.float32)
    actions = rng.integers(0, n + 1, batch).astype(np.int32)
    actions[0], actions[1] = n, 0        # a pass and a corner for sure
    got_p, got_a = random_transform_batch(
        key, jnp.asarray(planes), jnp.asarray(actions), size)

    ts = np.asarray(jax.random.randint(key, (batch,), 0, 8))
    assert len(set(ts)) > 4              # the key exercises the group

    def oracle(a, t):
        if t >= 4:
            a = np.flip(a, axis=1)
        return np.rot90(a, t % 4)

    for i, t in enumerate(ts):
        np.testing.assert_array_equal(
            np.asarray(got_p[i]), oracle(planes[i], t))
        if actions[i] == n:
            assert int(got_a[i]) == n
            continue
        onehot = np.zeros((size, size), np.float32)
        onehot[actions[i] // size, actions[i] % size] = 1.0
        assert int(got_a[i]) == int(
            np.flatnonzero(oracle(onehot, t).reshape(n))[0])


# ------------------------------------------------- per-session komi


@pytest.fixture(scope="module")
def komi_search(fcn_nets):
    from rocalphago_tpu.search.device_mcts import make_device_mcts

    pol, val = fcn_nets
    return make_device_mcts(pol.cfg, pol.feature_list,
                            val.feature_list, pol.module.apply,
                            val.module.apply, n_sim=6)


def _done_pair(cfg):
    """[live, done-by-two-passes] batch of empty-board states."""
    live = jaxgo.from_pygo(cfg, pygo.GameState(size=cfg.size,
                                               komi=cfg.komi))
    g = pygo.GameState(size=cfg.size, komi=cfg.komi)
    g.do_move(None)
    g.do_move(None)
    done = jaxgo.from_pygo(cfg, g)
    return jax.tree.map(lambda a, b: jnp.stack([a, b]), live, done)


def test_eval_batch_komi_default_is_bit_compat(fcn_nets, komi_search):
    pol, val = fcn_nets
    states = _done_pair(pol.cfg)
    p0, v0 = komi_search.eval_batch(pol.params, val.params, states)
    p1, v1 = komi_search.eval_batch_komi(
        pol.params, val.params, states,
        jnp.full((2,), pol.cfg.komi, jnp.float32))
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))


def test_eval_batch_komi_flips_terminal_sign(fcn_nets, komi_search):
    """Empty board, two passes: white wins by komi at the default;
    at komi=-25 the margin flips, so the terminal value flips."""
    pol, val = fcn_nets
    states = _done_pair(pol.cfg)
    _, v0 = komi_search.eval_batch(pol.params, val.params, states)
    _, v2 = komi_search.eval_batch_komi(
        pol.params, val.params, states,
        jnp.array([pol.cfg.komi, -25.0], jnp.float32))
    assert float(v2[1]) == -float(v0[1]) != 0.0


def test_pool_komi_session_and_pinned_default_path(fcn_nets):
    from rocalphago_tpu.serve.sessions import ServePool

    pol, val = fcn_nets
    pool = ServePool(val, pol, n_sim=4, batch_sizes=(1, 2, 4))
    try:
        sess = pool.open_session(resilient=False, komi=0.5)
        mv = sess.get_move(pygo.GameState(size=SIZE, komi=0.5))
        assert mv is None or isinstance(mv, tuple)
        st = pool.stats()
        assert st["evaluator"]["komi_batches"] >= 1
        assert st["board"] == SIZE
        assert st["komi_default"] == float(pol.cfg.komi)
        # a default-komi session stays on the pinned program
        before = pool.evaluator.komi_batches
        s2 = pool.open_session(resilient=False)
        s2.get_move(pygo.GameState(size=SIZE, komi=pol.cfg.komi))
        assert pool.evaluator.komi_batches == before
        # komi re-threads live (the GTP komi command's path)
        s2.set_komi(0.5)
        assert s2.komi == 0.5
    finally:
        pool.close()


# --------------------------------------------------- MultiSizePool


@pytest.fixture(scope="module")
def msize_pool(fcn_nets):
    from rocalphago_tpu.multisize import MultiSizePool

    pol, val = fcn_nets
    pool = MultiSizePool(val, pol, sizes=(5, 7), n_sim=4,
                         batch_sizes=(1, 2, 4))
    yield pool
    pool.close()


def test_multisize_routing_shares_one_checkpoint(fcn_nets,
                                                 msize_pool):
    pol, val = fcn_nets
    assert msize_pool.sizes == (5, 7)
    assert msize_pool.default_size == 5
    p7 = msize_pool.pool_for(7)
    assert p7.policy.params is pol.params
    assert p7.value.params is val.params
    s5 = msize_pool.open_session(resilient=False)
    s7 = msize_pool.open_session(size=7, resilient=False)
    try:
        assert s5.raw.board == 5 and s7.raw.board == 7
        s5.get_move(pygo.GameState(size=5))
        s7.get_move(pygo.GameState(size=7))
        with pytest.raises(ValueError, match="one board size"):
            msize_pool.driver([s5, s7])
    finally:
        s5.close()
        s7.close()


def test_multisize_probe_schema_and_add_size(msize_pool):
    st = msize_pool.stats()
    assert st["multisize"] is True
    assert st["default_board"] == 5
    assert set(st["boards"]) == {str(s) for s in msize_pool.sizes}
    for size, row in st["boards"].items():
        assert row["board"] == int(size)
        assert "komi_batches" in row["evaluator"]
    assert st["sessions_live"] == sum(
        b["sessions"]["live"] for b in st["boards"].values())
    with pytest.raises(KeyError, match="add_size"):
        msize_pool.pool_for(11)
    msize_pool.add_size(11)
    assert 11 in msize_pool.sizes


def test_multisize_refuses_size_locked_heads(fcn_nets):
    from rocalphago_tpu.multisize import MultiSizePool

    pol, _ = fcn_nets
    with pytest.raises(ValueError, match="MULTISIZE"):
        MultiSizePool(_dense_value(), pol, sizes=(5, 7))


def test_gtp_boardsize_reroutes_and_carries_komi(msize_pool):
    from rocalphago_tpu.interface.gtp import GTPEngine

    sess = msize_pool.open_session(resilient=True)
    eng = GTPEngine(sess.player, serve_pool=msize_pool,
                    serve_session=sess)
    assert eng.size == 5
    r, _ = eng.handle("1 komi 6.5\n")
    assert r.startswith("=1")
    r, _ = eng.handle("2 boardsize 7\n")
    assert r.startswith("=2"), r
    assert eng.size == 7
    assert eng._serve_session is not sess
    assert eng._serve_session.raw.board == 7
    assert eng._serve_session.komi == 6.5
    r, _ = eng.handle("3 genmove b\n")
    assert r.startswith("=3"), r
    # a size the ladder does not serve is still refused
    r, _ = eng.handle("4 boardsize 17\n")
    assert r.startswith("?4"), r
    eng._serve_session.close()


# ------------------------------------------------------ curriculum


def _save_pair(tmp_path, pol, val):
    pj = os.path.join(tmp_path, "policy.json")
    vj = os.path.join(tmp_path, "value.json")
    pol.save_model(pj)
    val.save_model(vj)
    return pj, vj


def test_curriculum_stages_hand_off_checkpoints(tmp_path, fcn_nets,
                                                monkeypatch):
    """Fast plumbing test: run_training stubbed out — proves the
    stage sequencing, at_board checkpoint handoff, per-stage argv
    (iterations/seed appended last so they win), span + event
    emission into the CURRICULUM stream."""
    from rocalphago_tpu.models.nn_util import NeuralNetBase
    from rocalphago_tpu.training import curriculum, zero

    calls = []

    def fake_run_training(argv):
        calls.append(list(argv))
        p_json, v_json, out_dir = argv[0], argv[1], argv[2]
        os.makedirs(out_dir, exist_ok=True)
        for name, src in (("policy", p_json), ("value", v_json)):
            net = NeuralNetBase.load_model(src)
            net.save_model(os.path.join(out_dir, f"{name}.json"))
        return {"iteration": 0, "policy_loss": 1.0}

    monkeypatch.setattr(zero, "run_training", fake_run_training)
    pol, val = fcn_nets
    pj, vj = _save_pair(tmp_path, pol, val)
    out = os.path.join(tmp_path, "run")
    summary = curriculum.run_curriculum(
        [pj, vj, out, "--stages", "5:1,7:2", "--seed", "3",
         "--sims", "4"])

    assert [s["board"] for s in summary["stages"]] == [5, 7]
    assert len(calls) == 2
    for argv, iters, seed in zip(calls, ("1", "2"), ("3", "4")):
        assert argv[argv.index("--iterations") + 1] == iters
        assert argv[argv.index("--seed") + 1] == seed
        assert "--sims" in argv          # passthrough forwarded
    # stage 1 trained on stage 0's export re-boarded to 7
    s1_in = NeuralNetBase.load_model(calls[1][0])
    assert s1_in.board == 7
    s0_out = NeuralNetBase.load_model(
        os.path.join(out, "stage00_b5", "policy.json"))
    for a, b in zip(jax.tree.leaves(s0_out.params),
                    jax.tree.leaves(s1_in.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert summary["final_policy"].endswith(
        os.path.join("stage01_b7", "policy.json"))

    events = [json.loads(line)
              for line in open(os.path.join(out, "metrics.jsonl"))]
    kinds = [e["event"] for e in events]
    assert kinds.count("curriculum_stage") == 2
    spans = [e for e in events if e["event"] == "span"
             and e.get("name") == "curriculum.stage"]
    assert {s["board"] for s in spans} == {5, 7}


def test_parse_stages_rejects_malformed():
    from rocalphago_tpu.training.curriculum import parse_stages

    assert parse_stages("9:30,13:20") == [(9, 30), (13, 20)]
    for bad in ("9x30", "9:", "", "1:5", "9:0"):
        with pytest.raises(ValueError):
            parse_stages(bad)


@pytest.mark.slow
def test_curriculum_two_stage_real(tmp_path, fcn_nets):
    """The real thing, tiny: two zero stages 5x5 -> 7x7 plus the
    Wilson-gated transferred-vs-fresh match at 7x7."""
    from rocalphago_tpu.training.curriculum import run_curriculum

    pol, val = fcn_nets
    pj, vj = _save_pair(tmp_path, pol, val)
    out = os.path.join(tmp_path, "run")
    summary = run_curriculum(
        [pj, vj, out, "--stages", "5:1,7:1", "--game-batch", "2",
         "--sims", "4", "--move-limit", "12", "--save-every", "1",
         "--no-gating", "--transfer-games", "4",
         "--transfer-move-limit", "20"])
    assert os.path.exists(
        os.path.join(out, "stage01_b7", "policy.json"))
    tr = summary["transfer"]
    assert tr["board"] == 7 and isinstance(tr["transfer"], bool)
    assert 0.0 <= tr["wilson_lb"] <= 1.0
