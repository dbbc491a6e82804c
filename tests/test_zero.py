"""AlphaZero-style iteration (``training.zero``) smoke + sanity.

Tiny nets, tiny search — the point is that the full loop (device-MCTS
self-play with recorded visit targets → chunked replay gradients for
both nets → one optimizer step each) runs compiled end-to-end and
moves both nets' parameters with finite losses.
"""

import jax
import jax.flatten_util  # noqa: F401 — used as jax.flatten_util
import numpy as np
import optax
import pytest

from rocalphago_tpu.engine.jaxgo import GoConfig
from rocalphago_tpu.models import CNNPolicy, CNNValue
from rocalphago_tpu.training.zero import (
    init_zero_state,
    make_zero_iteration,
)

SIZE = 5
FEATS = ("board", "ones")
VFEATS = FEATS + ("color",)


@pytest.fixture(scope="module")
def nets():
    pol = CNNPolicy(FEATS, board=SIZE, layers=1, filters_per_layer=4)
    val = CNNValue(VFEATS, board=SIZE, layers=1, filters_per_layer=4)
    return pol, val


@pytest.mark.slow
def test_zero_iteration_trains_both_nets(nets):
    pol, val = nets
    cfg = GoConfig(size=SIZE)
    tx_p, tx_v = optax.sgd(0.01), optax.sgd(0.01)
    # move_limit must cover natural 5x5 game length (~47 plies): the
    # value loss is masked to games that END by two passes, so a
    # too-small cap leaves the value net untrained (by design —
    # capped-game area scores label half-played boards)
    iteration = make_zero_iteration(
        cfg, FEATS, VFEATS, pol.module.apply, val.module.apply,
        tx_p, tx_v, batch=2, move_limit=60, n_sim=8, max_nodes=16,
        sim_chunk=4, replay_chunk=7)
    state = init_zero_state(pol.params, val.params, tx_p, tx_v, seed=3)

    new, metrics = iteration(state)
    assert int(jax.device_get(new.iteration)) == 1
    for key in ("policy_loss", "value_loss", "black_win_rate",
                "draw_rate", "mean_moves", "value_mse", "value_acc",
                "finished_rate"):
        assert np.isfinite(float(jax.device_get(metrics[key]))), key
    assert 0.0 <= float(jax.device_get(metrics["value_acc"])) <= 1.0
    # 60 plies cover natural 5x5 endings — games must actually end
    # (otherwise the masked value loss trains on nothing)
    assert float(jax.device_get(metrics["finished_rate"])) > 0

    def delta(a, b):
        fa, _ = jax.flatten_util.ravel_pytree(jax.device_get(a))
        fb, _ = jax.flatten_util.ravel_pytree(jax.device_get(b))
        return float(np.abs(np.asarray(fa) - np.asarray(fb)).max())

    assert delta(state.policy_params, new.policy_params) > 0
    assert delta(state.value_params, new.value_params) > 0

    # a second iteration continues from the new state (rng threads on)
    newer, _ = iteration(new)
    assert int(jax.device_get(newer.iteration)) == 2
    assert not np.array_equal(np.asarray(new.rng),
                              np.asarray(newer.rng))


@pytest.mark.slow
def test_zero_cli_trains_saves_and_resumes(tmp_path, nets):
    """The trainer CLI end to end on tiny specs: metrics written,
    GTP-loadable exports, and a rerun with a higher --iterations
    resumes from the checkpoint instead of restarting."""
    import json

    from rocalphago_tpu.training.zero import run_training

    pol, val = nets
    pj, vj = str(tmp_path / "p.json"), str(tmp_path / "v.json")
    pol.save_model(pj)
    val.save_model(vj)
    out = str(tmp_path / "out")
    args = [pj, vj, out, "--game-batch", "2", "--iterations", "1",
            "--move-limit", "16", "--sims", "4", "--sim-chunk", "2",
            "--save-every", "1"]
    final = run_training(args)
    assert final["iteration"] == 0

    from rocalphago_tpu.models.nn_util import NeuralNetBase

    exported = NeuralNetBase.load_model(str(tmp_path / "out"
                                            / "policy.json"))
    assert exported.board == SIZE

    args[args.index("--iterations") + 1] = "2"
    final = run_training(args)
    assert final["iteration"] == 1          # resumed, ran only iter 1
    lines = [json.loads(ln) for ln in
             (tmp_path / "out" / "metrics.jsonl").read_text()
             .splitlines()]
    assert any(e["event"] == "resume" and e["iteration"] == 1
               for e in lines)
    # evaluator gating ran (default-on): a gate match was logged and
    # the pool holds the iteration-0 incumbent snapshot
    gates = [e for e in lines if e["event"] == "gate"]
    assert gates and all(0.0 <= g["win_rate_a"] <= 1.0 for g in gates)
    assert (tmp_path / "out" / "pool"
            / "best.00000.policy.msgpack").exists()


def test_zero_gate_decide_requires_wilson_bound():
    """Promotion needs BOTH the point-estimate threshold AND a Wilson
    95% lower bound >= 0.5 on the decided-game win rate (VERDICT r5
    #4). ``decide`` reads only ``self.threshold``, so the rule is
    testable without building the match machinery."""
    from rocalphago_tpu.training.zero import ZeroGate

    g = object.__new__(ZeroGate)
    g.threshold = 0.55

    def result(wa, wb):
        return {"wins_a": wa, "wins_b": wb,
                "win_rate_a": wa / max(wa + wb, 1)}

    promoted, lb = g.decide(result(38, 26))     # 0.594 at 64 games:
    assert not promoted and lb < 0.5            # round 5's coin flip
    promoted, lb = g.decide(result(45, 19))     # 0.703: decisive
    assert promoted and lb >= 0.5
    g.threshold = 0.75                          # the point threshold
    promoted, _ = g.decide(result(45, 19))      # still gates on top
    assert not promoted


def test_zero_gate_match_and_promotion(tmp_path, nets):
    """ZeroGate mechanics: an even match reports a sane tally; a
    promotion writes a loadable best-pair snapshot; sample() draws
    from the pool statelessly."""
    from rocalphago_tpu.training.zero import ZeroGate

    pol, val = nets
    cfg = GoConfig(size=SIZE, komi=7.0)
    gate = ZeroGate(cfg, FEATS, pol.module.apply,
                    str(tmp_path / "pool"), games=8, threshold=0.55,
                    temperature=1.0, move_limit=60, chunk=20)
    r = gate.match(pol.params, pol.params, jax.random.key(0))
    assert r["wins_a"] + r["wins_b"] + r["draws"] == 8
    assert 0.0 <= r["win_rate_a"] <= 1.0

    gate.promote(pol.params, val.params, 3)
    snaps = gate.snapshots()
    assert [s[0] for s in snaps] == [3]
    lp, lv = gate.load(snaps[0], pol.params, val.params)
    flat0, _ = jax.flatten_util.ravel_pytree(pol.params)
    flat1, _ = jax.flatten_util.ravel_pytree(lp)
    np.testing.assert_array_equal(np.asarray(flat0),
                                  np.asarray(flat1))
    # the sole snapshot IS the incumbent — nothing past to ladder
    assert gate.sample(7, 11) is None
    gate.promote(pol.params, val.params, 5)
    # with a past entry the draw is stateless and never the incumbent
    assert gate.sample(7, 11) == gate.sample(7, 11)
    assert gate.sample(7, 11)[0] == 3


@pytest.mark.slow
@pytest.mark.parametrize("sample_moves", [False, True])
def test_zero_iteration_gumbel_targets(nets, sample_moves):
    """The Gumbel variant: self-play plays halving winners (or, with
    ``gumbel_sample``, samples moves from pi' — VERDICT r4 #9) and
    the policy learns from pi' (improved policy) float targets - one
    iteration must move both nets with finite losses."""
    pol, val = nets
    cfg = GoConfig(size=SIZE)
    tx_p, tx_v = optax.sgd(0.01), optax.sgd(0.01)
    iteration = make_zero_iteration(
        cfg, FEATS, VFEATS, pol.module.apply, val.module.apply,
        tx_p, tx_v, batch=2, move_limit=60, n_sim=8, max_nodes=16,
        sim_chunk=4, replay_chunk=8, gumbel=True,
        gumbel_sample=sample_moves)
    state = init_zero_state(pol.params, val.params, tx_p, tx_v,
                            seed=3)
    new_state, metrics = iteration(state)
    for k in ("policy_loss", "value_loss"):
        assert np.isfinite(float(metrics[k])), (k, metrics[k])
    flat0, _ = jax.flatten_util.ravel_pytree(state.policy_params)
    flat1, _ = jax.flatten_util.ravel_pytree(new_state.policy_params)
    assert not np.allclose(np.asarray(flat0), np.asarray(flat1))
    vflat0, _ = jax.flatten_util.ravel_pytree(state.value_params)
    vflat1, _ = jax.flatten_util.ravel_pytree(new_state.value_params)
    assert not np.allclose(np.asarray(vflat0), np.asarray(vflat1))


@pytest.mark.slow
def test_zero_actor_learner_lockstep_bit_exact(nets):
    """The acceptance pin (docs/SCALE.md): one lockstep actor + FIFO
    learner reproduce the synchronous iteration BIT-identically —
    same keys (the actor walks ``next_keys`` locally), same games
    (host round-trip through the buffer keeps raw dtypes), same
    params/opt-state/rng after two steps."""
    import optax as _optax

    from rocalphago_tpu.data.replay import ReplayBuffer
    from rocalphago_tpu.training.actor import (
        ParamsPublisher,
        SelfplayActor,
    )
    from rocalphago_tpu.training.learner import ZeroLearner

    pol, val = nets
    cfg = GoConfig(size=SIZE)
    tx_p, tx_v = _optax.sgd(0.01), _optax.sgd(0.01)
    iteration = make_zero_iteration(
        cfg, FEATS, VFEATS, pol.module.apply, val.module.apply,
        tx_p, tx_v, batch=2, move_limit=16, n_sim=4, max_nodes=16,
        sim_chunk=2, replay_chunk=5)
    state = init_zero_state(pol.params, val.params, tx_p, tx_v,
                            seed=5)

    s_sync = state
    sync_metrics = []
    for _ in range(2):
        s_sync, m = iteration(s_sync)
        sync_metrics.append(
            {k: float(jax.device_get(v)) for k, v in m.items()})

    buf = ReplayBuffer(capacity=4)
    pub = ParamsPublisher()
    actor = SelfplayActor(iteration.play, pub, buf, state.rng,
                          lockstep=True, games=2, poll_s=0.05)
    learner = ZeroLearner(iteration.learn, buf)
    pub.publish(state.policy_params, state.value_params, version=0)
    actor.start()
    s_al = state
    try:
        for it in range(2):
            s_al, m, entry = learner.step(s_al, timeout=120.0)
            assert entry.version == it       # FIFO, in lockstep order
            # the learner adds replay_version/replay_staleness_s on
            # top of the iteration metrics — those aside, identical
            assert {k: m[k] for k in sync_metrics[it]} \
                == sync_metrics[it]
            pub.publish(s_al.policy_params, s_al.value_params,
                        version=it + 1)
    finally:
        buf.close()
        actor.stop()
    assert actor.error is None

    def flat(tree):
        f, _ = jax.flatten_util.ravel_pytree(jax.device_get(tree))
        return np.asarray(f)

    for attr in ("policy_params", "value_params", "opt_policy",
                 "opt_value"):
        np.testing.assert_array_equal(
            flat(getattr(s_sync, attr)), flat(getattr(s_al, attr)),
            err_msg=attr)
    np.testing.assert_array_equal(np.asarray(s_sync.rng),
                                  np.asarray(s_al.rng))
    assert int(jax.device_get(s_al.iteration)) == 2


@pytest.mark.slow
def test_zero_actor_learner_cli_bit_exact(tmp_path, nets):
    """`run_training --actor-learner` (1 actor) vs the synchronous
    CLI: exported params bit-identical, iteration metrics equal."""
    from rocalphago_tpu.models.nn_util import NeuralNetBase
    from rocalphago_tpu.training.zero import run_training

    pol, val = nets
    pj, vj = str(tmp_path / "p.json"), str(tmp_path / "v.json")
    pol.save_model(pj)
    val.save_model(vj)
    base = [pj, vj, "", "--game-batch", "2", "--iterations", "2",
            "--move-limit", "12", "--sims", "4", "--sim-chunk", "2",
            "--save-every", "2", "--seed", "5"]

    def run(out, extra):
        args = list(base)
        args[2] = str(tmp_path / out)
        return run_training(args + extra)

    f_sync = run("sync", [])
    f_al = run("al", ["--actor-learner"])
    for k in ("policy_loss", "value_loss", "mean_moves",
              "finished_rate"):
        assert f_sync[k] == f_al[k], k
    for name in ("policy", "value"):
        pa = NeuralNetBase.load_model(
            str(tmp_path / "sync" / f"{name}.json")).params
        pb = NeuralNetBase.load_model(
            str(tmp_path / "al" / f"{name}.json")).params
        fa, _ = jax.flatten_util.ravel_pytree(pa)
        fb, _ = jax.flatten_util.ravel_pytree(pb)
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb),
                                      err_msg=name)


@pytest.mark.slow
def test_zero_iteration_sharded_matches_unsharded(nets):
    """Mesh wiring is placement + constraints only: one iteration on
    the virtual 8-device mesh must match the unsharded run
    bit-for-bit (same rng, same math; XLA inserts the collectives).
    And the replay is traced once per distinct segment LENGTH, not
    once per segment: every carry leaf enters the first segment
    already committed to the mesh, so the second segment's inputs
    (the first one's outputs) have the same types (on the v5e a
    re-trace of the 19x19 replay program cost ~37 s per run, PR 21)."""
    from rocalphago_tpu.io.checkpoint import unpack_rng
    from rocalphago_tpu.parallel import mesh as meshlib

    pol, val = nets
    cfg = GoConfig(size=SIZE)
    tx_p, tx_v = optax.sgd(0.01), optax.sgd(0.01)
    kw = dict(batch=4, move_limit=20, n_sim=8, max_nodes=16,
              sim_chunk=4, replay_chunk=8)
    base = make_zero_iteration(
        cfg, FEATS, VFEATS, pol.module.apply, val.module.apply,
        tx_p, tx_v, **kw)
    mesh = meshlib.make_mesh(4)
    traces = []                 # the Python body runs once per trace

    def counted_value_apply(*a, **k):
        traces.append(1)
        return val.module.apply(*a, **k)

    sharded = make_zero_iteration(
        cfg, FEATS, VFEATS, pol.module.apply, counted_value_apply,
        tx_p, tx_v, mesh=mesh, **kw)
    s0 = init_zero_state(pol.params, val.params, tx_p, tx_v, seed=7)
    s0m = meshlib.replicate(mesh, init_zero_state(
        pol.params, val.params, tx_p, tx_v, seed=7))
    _, m1 = base(s0)
    # sharded(s0m), taken apart at its own play/learn seam
    _, game_key = jax.random.split(unpack_rng(s0m.rng))
    games = sharded.play(s0m.policy_params, s0m.value_params, game_key)
    del traces[:]
    _, m2 = sharded.learn(s0m, games)
    assert len(traces) == 2     # segments of 8, 8 and 4 plies
    for k in m1:
        np.testing.assert_allclose(
            float(jax.device_get(m1[k])), float(jax.device_get(m2[k])),
            rtol=1e-5, err_msg=k)
