"""Live rollout subsystem (``rocalphago_tpu/rollout``): hot-swap
serving, the Wilson-gated canary, and the federated gateway router
(docs/ROLLOUT.md).

Fast tier (all of this file): version pinning and single-version
batching in the evaluator (fake eval — no device), staged versions
and retirement, the spill pointer roundtrip (publisher + gate →
SpillWatcher), canary gating on a fake pool (strong promotes, weak
rolls back, exact fractional assignment), the gateway's canary arm
wiring, a live game surviving repeated hot swaps with ZERO compile
growth, and the router's sticky/spillover/failover behavior over two
in-process gateway replicas — including the client-side
``ResilientGatewayClient`` mid-game reconnect regression.
"""

import threading
import time

import numpy as np
import pytest

from rocalphago_tpu.engine import pygo
from rocalphago_tpu.gateway.client import (
    GatewayClient,
    GatewayRefused,
    ResilientGatewayClient,
)
from rocalphago_tpu.gateway.server import GatewayServer
from rocalphago_tpu.obs import registry as obs_registry
from rocalphago_tpu.rollout import (
    CanaryController,
    HotSwapper,
    Replica,
    RolloutRouter,
    SpillWatcher,
)
from rocalphago_tpu.runtime import faults
from rocalphago_tpu.serve import BatchingEvaluator, ServePool

SIZE = 5


@pytest.fixture(autouse=True)
def _clean_fault_plan():
    yield
    faults.install(None)


@pytest.fixture(scope="module")
def nets():
    from rocalphago_tpu.models import CNNPolicy, CNNValue

    pol = CNNPolicy(("board", "ones"), board=SIZE, layers=1,
                    filters_per_layer=2)
    val = CNNValue(("board", "ones", "color"), board=SIZE, layers=1,
                   filters_per_layer=2)
    return pol, val


@pytest.fixture(scope="module")
def pool(nets):
    """One warm 5×5 pool shared by the module (XLA compiles
    dominate); extra pools share its compiled searcher."""
    pol, val = nets
    p = ServePool(val, pol, n_sim=6, max_sessions=4,
                  batch_sizes=(1, 2, 4), max_wait_us=2000)
    p.warm()
    yield p
    p.close()


# ------------------------------------------------- versioned evaluator

def _fake_states(rows: int = 1):
    return {"board": np.zeros((rows, SIZE, SIZE), np.float32)}


def _tag_eval(pp, pv, states):
    b = states["board"].shape[0]
    tag = float(np.asarray(pp["tag"]))
    return np.full((b, 4), tag, np.float32), \
        np.full((b,), tag, np.float32)


def _fake_evaluator(**kw):
    return BatchingEvaluator(
        _tag_eval, {"tag": np.float32(0.0)}, {"tag": np.float32(0.0)},
        batch_sizes=(1, 2, 4), start=False, **kw)


def test_pinned_request_is_served_on_its_submit_version():
    """A queued request holds its version across a swap: the swap
    cannot retire the net the request was submitted against, and the
    answer comes from THAT net — one genmove never sees two nets."""
    ev = _fake_evaluator()
    try:
        before = ev.submit(_fake_states(), rows=1)
        v1 = ev.set_params({"tag": np.float32(1.0)},
                           {"tag": np.float32(1.0)})
        after = ev.submit(_fake_states(), rows=1)
        ev.drain_once()   # the v0 request (version edge splits)
        ev.drain_once()   # the v1 request
        priors0, _ = before.result(timeout=5)
        priors1, _ = after.result(timeout=5)
        assert float(priors0[0, 0]) == 0.0
        assert float(priors1[0, 0]) == 1.0
        st = ev.stats()
        assert st["params_version"] == v1 and st["swaps"] == 1
        # with its last pin released by the dispatch, v0 is retired
        with pytest.raises(KeyError):
            ev.acquire(0)
    finally:
        ev.close()


def test_batches_never_coalesce_across_a_version_edge():
    """Mixed-version pendings split into per-version batches: one
    device batch = one net."""
    ev = _fake_evaluator()
    try:
        reqs = [ev.submit(_fake_states(), rows=1)]
        ev.set_params({"tag": np.float32(1.0)},
                      {"tag": np.float32(1.0)})
        reqs += [ev.submit(_fake_states(), rows=1) for _ in range(2)]
        ev.drain_once()
        assert ev.batches == 1 and ev.rows_total == 1
        ev.drain_once()
        # the two same-version requests DID coalesce
        assert ev.batches == 2 and ev.rows_total == 3
        tags = [float(r.result(timeout=5)[0][0, 0]) for r in reqs]
        assert tags == [0.0, 1.0, 1.0]
    finally:
        ev.close()


def test_staged_version_promotes_or_retires():
    """The canary's evaluator contract: ``add_version`` stages a pair
    pinned (not current); promoting by version flips the pointer and
    retires the old one; releasing an unpromoted stage retires it."""
    ev = _fake_evaluator()
    try:
        staged = ev.add_version({"tag": np.float32(2.0)},
                                {"tag": np.float32(2.0)})
        assert ev.params_version == 0        # pointer untouched
        assert ev.acquire(staged) == staged  # pinnable while staged
        ev.release(staged)
        ev.set_params(version=staged)        # promote
        assert ev.params_version == staged
        with pytest.raises(KeyError):
            ev.acquire(0)                    # incumbent retired
        # stage another and DISCARD it instead
        dead = ev.add_version({"tag": np.float32(3.0)},
                              {"tag": np.float32(3.0)})
        ev.release(dead)                     # drop the stage pin
        with pytest.raises(KeyError):
            ev.acquire(dead)
        with pytest.raises(KeyError):
            ev.set_params(version=dead)
    finally:
        ev.close()


def test_session_falls_back_when_its_pin_is_rolled_back(pool):
    """Mid-game rollback continuity: a session pinned to a canary
    version keeps playing after the version retires — the next
    genmove lands on the current pointer instead of erroring."""
    import jax

    staged = pool.stage_params(
        jax.tree.map(lambda x: x * 1.5, pool.policy.params),
        jax.tree.map(lambda x: x * 0.5, pool.value.params))
    with pool.open_session() as sess:
        sess.pin_version(staged)
        game = pygo.GameState(size=SIZE)
        mv = sess.get_move(game)
        assert mv is None or game.is_legal(mv)
        assert sess.params_version == staged
        game.do_move(mv)
        pool.discard_version(staged)         # instant rollback
        mv = sess.get_move(game)
        assert mv is None or game.is_legal(mv)
        assert sess.params_version == pool.params_version


def test_game_survives_hot_swaps_with_zero_compile_growth(pool):
    """The zero-downtime core claim: a live game plays through
    repeated hot swaps — every move legal, every search on exactly
    one version, and ``jax_compiles_total`` flat (params are jit
    arguments at fixed shapes; a swap is a pointer flip)."""
    import jax

    def total_compiles():
        return sum(v for k, v in obs_registry.REGISTRY.snapshot()
                   ["counters"].items()
                   if k.startswith("jax_compiles_total"))

    compiles0 = total_compiles()
    swaps0 = pool.evaluator.stats()["swaps"]
    with pool.open_session() as sess:
        game = pygo.GameState(size=SIZE)
        for i in range(3):
            mv = sess.get_move(game)
            assert mv is None or game.is_legal(mv)
            game.do_move(mv)
            scale = 1.0 + 0.01 * (i + 1)
            pool.set_params(
                jax.tree.map(lambda x: x * scale, pool.policy.params),
                jax.tree.map(lambda x: x * scale, pool.value.params))
        mv = sess.get_move(game)             # one move on the last net
        assert mv is None or game.is_legal(mv)
        assert sess.params_version == pool.params_version
    assert game.turns_played == 3
    assert pool.evaluator.stats()["swaps"] == swaps0 + 3
    assert total_compiles() == compiles0, \
        "a hot swap recompiled something"
    # the probe block carries the swap trail
    st = pool.stats()
    assert st["params"]["swaps"] == swaps0 + 3


# ------------------------------------------------------ spill pointer

def test_publisher_spill_roundtrip_and_pruning(tmp_path, nets):
    """``ParamsPublisher(spill_dir)`` mirrors each publish to disk
    (pair first, pointer last); a ``SpillWatcher`` applies exactly
    the newer-than-served versions, and older pairs are pruned."""
    import jax

    from rocalphago_tpu.training.actor import ParamsPublisher, \
        read_spill

    pol, val = nets

    class Target:
        def __init__(self):
            self.sets = []

        def set_params(self, pp, pv):
            self.sets.append((pp, pv))

    pub = ParamsPublisher(spill_dir=str(tmp_path))
    v0 = pub.publish(pol.params, val.params)
    assert read_spill(str(tmp_path))["version"] == v0

    target = Target()
    watcher = SpillWatcher(str(tmp_path), HotSwapper(target),
                           pol.params, val.params)
    assert watcher.poll_once() is True
    assert watcher.poll_once() is False      # nothing newer
    assert watcher.swapper.version == v0 and len(target.sets) == 1
    # the deserialized pair is bit-equal to what was published
    got, want = target.sets[0][0], jax.device_get(pol.params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    v1 = pub.publish(jax.tree.map(lambda x: x * 2.0, pol.params),
                     val.params)
    assert watcher.poll_once() is True
    assert watcher.swapper.version == v1
    # only the latest pair survives the prune
    spills = sorted(p.name for p in tmp_path.glob("spill.*.msgpack"))
    assert spills == [f"spill.{v1:05d}.policy.msgpack",
                      f"spill.{v1:05d}.value.msgpack"]


def test_zero_gate_promotion_writes_the_spill_pointer(tmp_path, nets):
    """``ZeroGate.promote`` leaves ``rollout.json`` at its best pair:
    the cross-process hook a rollout watcher (or a restarted serving
    process) picks the gated version up from."""
    from rocalphago_tpu.training.actor import read_spill
    from rocalphago_tpu.training.zero import ZeroGate

    pol, val = nets
    gate = ZeroGate(pol.cfg, pol.feature_list, pol.module.apply,
                    str(tmp_path), games=2, threshold=0.55,
                    temperature=1.0, move_limit=4, chunk=2)
    gate.promote(pol.params, val.params, iteration=3)
    spill = read_spill(str(tmp_path))
    assert spill["version"] == 3
    assert spill["policy"] == "best.00003.policy.msgpack"

    target_p, target_v = [], []

    class Pool:
        def set_params(self, pp, pv):
            target_p.append(pp)
            target_v.append(pv)

    watcher = SpillWatcher(str(tmp_path), HotSwapper(Pool()),
                           pol.params, val.params)
    assert watcher.poll_once() is True
    assert watcher.swapper.version == 3 and len(target_p) == 1


# ------------------------------------------------------------- canary

class FakePool:
    """Records the pool calls the controller makes."""

    def __init__(self):
        self.version = 1
        self._next = 2
        self.staged: list = []
        self.promoted: list = []
        self.discarded: list = []

    @property
    def params_version(self):
        return self.version

    def stage_params(self, pp, pv, version=None):
        v = self._next if version is None else int(version)
        self._next = v + 1
        self.staged.append(v)
        return v

    def promote_version(self, v):
        self.promoted.append(v)
        self.version = v

    def discard_version(self, v):
        self.discarded.append(v)


def test_canary_strong_candidate_promotes():
    fp = FakePool()
    canary = CanaryController(fp, fraction=0.5, min_games=6)
    v = canary.stage({"p": 1}, {"v": 1})
    assert fp.staged == [v] and canary.state == "running"
    for _ in range(6):
        state = canary.record("candidate", won=True)
    assert state == "promoted"
    assert fp.promoted == [v] and fp.discarded == []
    st = canary.stats()
    assert st["wilson_lb"] >= 0.5 and st["promotions"] == 1
    assert st["games"]["candidate_wins"] == 6


def test_canary_weak_candidate_rolls_back_instantly():
    fp = FakePool()
    canary = CanaryController(fp, fraction=0.5, min_games=6)
    v = canary.stage({"p": 1}, {"v": 1})
    for won in (True, False, False, False, False, False):
        state = canary.record("candidate", won=won)
    assert state == "rolled_back"
    assert fp.discarded == [v] and fp.promoted == []
    st = canary.stats()
    assert st["wilson_lb"] < 0.5 and st["rollbacks"] == 1
    # a rolled-back controller is re-stageable
    v2 = canary.stage({"p": 2}, {"v": 2})
    assert canary.state == "running" and v2 != v


def test_canary_gate_waits_for_candidate_games():
    """Incumbent games inform the record but never trip the gate —
    only DECIDED CANDIDATE games count toward ``min_games``."""
    fp = FakePool()
    canary = CanaryController(fp, fraction=0.5, min_games=4)
    canary.stage({"p": 1}, {"v": 1})
    for _ in range(10):
        assert canary.record("incumbent", won=True) == "running"
    for won in (True, True, True):
        assert canary.record("candidate", won=won) == "running"
    assert canary.record("candidate", won=True) == "promoted"


def test_canary_fractional_assignment_is_exact():
    fp = FakePool()
    canary = CanaryController(fp, fraction=0.25, min_games=4)
    v = canary.stage({"p": 1}, {"v": 1})
    arms = [canary.assign() for _ in range(8)]
    assert arms.count(v) == 2                # exactly 25%
    st = canary.stats()
    assert st["assigned"] == {"candidate": 2, "incumbent": 6}
    with pytest.raises(RuntimeError):
        canary.stage({"p": 2}, {"v": 2})     # one canary at a time
    with pytest.raises(ValueError):
        canary.record("blue", won=True)


def test_gateway_routes_the_canary_slice(pool):
    """The gateway arm wiring: with a staged canary at fraction 1.0
    every new session is pinned to the candidate version."""
    import jax

    canary = CanaryController(pool, fraction=1.0, min_games=64)
    staged = canary.stage(
        jax.tree.map(lambda x: x * 1.1, pool.policy.params),
        jax.tree.map(lambda x: x * 1.1, pool.value.params))
    srv = GatewayServer(pool, max_conns=4, canary=canary).start()
    try:
        client = GatewayClient("127.0.0.1", srv.port)
        client.new_game(board=SIZE)
        client.genmove("b")
        client.close()
        st = canary.stats()
        assert st["assigned"]["candidate"] == 1
        assert st["candidate_version"] == staged
    finally:
        srv.close()
        canary.rollback(reason="test_teardown")


# ------------------------------------------------------------- router

@pytest.fixture()
def replicas(pool, nets):
    """Two gateway replicas: ``a`` over a 1-session pool (the
    spillover victim), ``b`` over the module pool — both sharing the
    module pool's compiled searcher (no recompiles)."""
    pol, val = nets
    small = ServePool(val, pol, n_sim=6, max_sessions=1,
                      batch_sizes=(1, 2, 4), max_wait_us=2000,
                      searcher=pool.search)
    srv_a = GatewayServer(small, max_conns=4).start()
    srv_b = GatewayServer(pool, max_conns=4).start()
    reps = [Replica("127.0.0.1", srv_a.port, gateway=srv_a, name="a"),
            Replica("127.0.0.1", srv_b.port, gateway=srv_b, name="b")]
    yield reps, srv_a, srv_b
    srv_a.close()
    srv_b.close()
    small.close()


def test_router_sticky_sessions_and_routing_share(replicas):
    reps, _a, _b = replicas
    with RolloutRouter(reps, max_conns=8).start() as router:
        c1 = GatewayClient("127.0.0.1", router.port)
        c2 = GatewayClient("127.0.0.1", router.port)
        try:
            c1.new_game(board=SIZE)
            c2.new_game(board=SIZE)
            for _ in range(2):               # sticky: same backend
                assert "move" in c1.genmove("b")
                assert "move" in c2.genmove("b")
            st = router.stats()
            assert st["routed"] == 2
            shares = {n: r["routed"]
                      for n, r in st["replicas"].items()}
            # least-loaded routing spread the two conns apart
            assert shares == {"a": 1, "b": 1}
        finally:
            c1.close()
            c2.close()


def test_router_spills_over_a_full_replica(replicas):
    """Replica ``a`` holds one session; a second game refused there
    lands on ``b`` without the client seeing the refusal."""
    reps, _a, _b = replicas
    with RolloutRouter(reps, max_conns=8).start() as router:
        clients = [GatewayClient("127.0.0.1", router.port)
                   for _ in range(3)]
        try:
            for c in clients:
                c.new_game(board=SIZE)
                assert "move" in c.genmove("b")
            st = router.stats()
            # 3 conns over a 1-session replica + the big one: at
            # least one new_game spilled over, none surfaced
            assert st["spillovers"] >= 1
            assert sum(r["routed"]
                       for r in st["replicas"].values()) >= 3
        finally:
            for c in clients:
                c.close()


def test_router_failover_replays_a_mid_drain_game(replicas):
    """The mid-game replica drain regression: the backend dies
    between moves; the router reconnects elsewhere, replays the game
    log, and re-serves the move — ≤1 retried genmove, the client
    never sees an error."""
    reps, srv_a, srv_b = replicas
    with RolloutRouter(reps, max_conns=8).start() as router:
        client = GatewayClient("127.0.0.1", router.port)
        try:
            client.new_game(board=SIZE)
            moved = client.genmove("b")["move"]
            client.play("w", "C3" if moved != "C3" else "C2")
            # kill whichever replica holds the session
            holder = srv_a if router.stats()["replicas"]["a"][
                "sessions"] else srv_b
            holder.drain(timeout=1.0)
            reply = client.genmove("b")      # transparent failover
            assert "move" in reply
            st = router.stats()
            assert st["failovers"] == 1
            assert st["retried_genmoves"] <= 1
            # the replayed game kept its history: the next move is
            # served against a 3-stone board, still legal
            assert "move" in client.genmove("w")
        finally:
            client.close()


def test_router_health_and_version_convergence(replicas, pool, nets):
    """Health polling reads each replica's serve probe; a fleet-wide
    hot swap converges every replica's params version."""
    import jax

    reps, _a, _b = replicas
    pol, val = nets
    with RolloutRouter(reps, max_conns=8).start() as router:
        router.poll_health_once()
        assert all(r.healthy for r in reps)
        # coordinated fan-out: ONE version number across the fleet
        target = max(r.gateway.pool.params_version
                     for r in reps) + 1
        for r in reps:
            r.gateway.pool.set_params(
                jax.tree.map(lambda x: x * 1.02, pol.params),
                jax.tree.map(lambda x: x * 1.02, val.params),
                version=target)
        router.poll_health_once()
        assert router.await_convergence(target, timeout=5)
        assert all((r.params_version or 0) >= target for r in reps)


def test_router_refuses_with_retry_hint_when_fleet_is_down(replicas):
    reps, srv_a, srv_b = replicas
    with RolloutRouter(reps, max_conns=8).start() as router:
        srv_a.drain(timeout=0.5)
        srv_b.drain(timeout=0.5)
        router.poll_health_once()
        # with no backend to pair with, the router refuses at the
        # hello handshake — GatewayClient surfaces it on construction
        with pytest.raises(GatewayRefused) as exc:
            GatewayClient("127.0.0.1", router.port)
        assert exc.value.code == "overload"
        assert exc.value.retry_after_s is not None


# ------------------------------------------------- resilient client

def test_resilient_client_reconnects_and_replays_midgame(pool):
    """The ``--connect`` bridge's client survives a mid-game server
    restart: reconnect with backoff, replay the game log, re-serve
    the move — the caller sees an unbroken session."""
    srv = GatewayServer(pool, max_conns=4).start()
    port = srv.port
    client = ResilientGatewayClient("127.0.0.1", port, attempts=8,
                                    base_delay=0.05, max_delay=0.2)
    try:
        client.new_game(board=SIZE)
        first = client.genmove("b")["move"]
        client.play("w", "C3" if first != "C3" else "C2")
        srv.close()                          # the mid-game drop
        srv = GatewayServer(pool, port=port, max_conns=4).start()
        reply = client.genmove("b")          # reconnect + replay
        assert "move" in reply
        assert client.reconnects >= 1
        assert "move" in client.genmove("w")
    finally:
        client.close()
        srv.close()


def test_resilient_client_passes_game_errors_through(pool):
    """Typed in-game errors are NOT transport failures: an illegal
    move surfaces immediately, with no reconnect churn."""
    from rocalphago_tpu.gateway.client import GatewayError

    srv = GatewayServer(pool, max_conns=4).start()
    client = ResilientGatewayClient("127.0.0.1", srv.port)
    try:
        client.new_game(board=SIZE)
        client.play("b", "C3")
        with pytest.raises(GatewayError) as exc:
            client.play("w", "C3")           # occupied point
        assert exc.value.code == "illegal_move"
        assert client.reconnects == 0
        assert "move" in client.genmove("w")  # session intact
    finally:
        client.close()
        srv.close()


# ----------------------------------------------------------------- soak


def run_soak(tmp_path, extra):
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_dir = str(tmp_path / "soak")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts",
                                      "rollout_soak.py"),
         "--out", out_dir, *extra],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=repo, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, \
        f"soak failed:\n{proc.stdout}\n{proc.stderr}"
    with open(os.path.join(out_dir, "summary.json")) as f:
        summary = json.load(f)
    assert all(summary["checks"].values()), summary["checks"]
    return summary


@pytest.mark.slow
def test_rollout_soak_smoke(tmp_path):
    """The zero-downtime proof, sized for the full tier (suite wall-time): one
    mid-storm promotion through the spill pipe, one replica bounce
    with transparent failover, kills inside the fault wall, the weak
    canary rolled back, compiles flat, SIGTERM drain exit 0."""
    summary = run_soak(tmp_path, ["--min-kills", "1", "--swaps", "1",
                                  "--moves", "3", "--p-kill", "0.3",
                                  "--deadline-s", "150"])
    assert summary["kills"] >= 1
    assert summary["storm_swaps"] >= 1
    assert summary["failovers"] >= 1
    assert summary["compiles_delta"] == 0
    assert summary["canary"]["state"] == "rolled_back"


@pytest.mark.slow
def test_rollout_soak_full(tmp_path):
    summary = run_soak(tmp_path, [])
    assert summary["kills"] >= 3
    assert summary["storm_swaps"] >= 2
