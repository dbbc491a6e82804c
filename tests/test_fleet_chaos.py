"""Fleet supervision + chaos soak (docs/RESILIENCE.md "Fleet
supervision").

Tier-1 units: the randomized-kill plan grammar (``kill`` kind,
``random`` wildcard, ``:p=``/``:seed=`` determinism), the
supervisor's restart / crash-loop-park / lockstep-refusal / drain
semantics against fake workers, the supervised serving dispatcher
resurrecting across an injected kill, and the stale-worker
``waiting_on`` tagging that names wedged fleet members in watchdog
stall events.

Tier-1 subprocesses: a lockstep run under an actor kill FAILS (park
with ``restart_refused`` — the bit-identity pin forbids resurrection,
this test enforces the refusal), a SIGTERM drain exits 0 at the
iteration boundary and the resumed run is byte-identical to an
uninterrupted one, and a short ``scripts/chaos_soak.py`` smoke runs
green. The @slow soak runs the full randomized storm (>= 6 kills
across actors, learner and dispatcher).
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from rocalphago_tpu.runtime import retries
from rocalphago_tpu.runtime.faults import (
    InjectedFault,
    InjectedKill,
    barrier,
    install,
    parse_plan,
)
from rocalphago_tpu.runtime.jsonl import read_jsonl
from rocalphago_tpu.runtime.supervisor import RestartPolicy, Supervisor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------- kill grammar


def kill_schedule(plan, name="actor.game", n=200):
    """Barrier indices where ``plan`` injects a kill over n hits."""
    install(plan)
    hits = []
    try:
        for i in range(n):
            try:
                barrier(name, iteration=i)
            except InjectedKill:
                hits.append(i)
    finally:
        install(None)
    return hits


def test_kill_spec_parses_p_and_seed_comma_form():
    (spec,) = parse_plan("kill@actor.game:p=0.05,seed=7")
    assert (spec.kind, spec.barrier) == ("kill", "actor.game")
    assert spec.p == 0.05 and spec.seed == 7
    # mixed plan: the param fragment binds to ITS spec, not the next
    a, b = parse_plan("kill@random:p=0.5,seed=3,error@zero.post_save")
    assert a.barrier == "random" and a.seed == 3
    assert (b.kind, b.p, b.seed) == ("error", None, 0)


def test_kill_spec_rejects_bad_probabilities():
    with pytest.raises(ValueError):
        parse_plan("kill@random")          # wildcard needs a p
    with pytest.raises(ValueError):
        parse_plan("kill@actor.game:p=1.5")


def test_kill_schedule_deterministic_by_seed():
    plan = "kill@actor.game:p=0.2,seed=5"
    first = kill_schedule(plan)
    assert first, "p=0.2 over 200 hits produced no kills"
    assert kill_schedule(plan) == first          # replayable
    assert kill_schedule("kill@actor.game:p=0.2,seed=6") != first
    assert kill_schedule("kill@actor.game:p=1") == list(range(200))
    assert kill_schedule("kill@actor.game:p=0") == []


def test_kill_spec_scoping_and_wildcard():
    assert kill_schedule("kill@actor.game:p=1",
                         name="learner.step") == []
    assert kill_schedule("kill@random:p=1",
                         name="serve.dispatch", n=3) == [0, 1, 2]


def test_injected_kill_bypasses_retries():
    """The kill kind models worker DEATH: the PR-1 retry layer must
    re-raise it (non-transient) so it reaches the supervisor."""
    assert not retries.is_transient(InjectedKill("x"))
    assert retries.is_transient(InjectedFault("x"))


# ------------------------------------------------- supervisor units


class Cap:
    """MetricsLogger-shaped event capture."""

    def __init__(self):
        self.events = []

    def log(self, event, **fields):
        self.events.append((event, fields))

    def named(self, event):
        return [f for e, f in self.events if e == event]


class FakeWorker:
    """Worker-protocol stub: optionally dies the moment it starts."""

    def __init__(self, die_with=None, beat=None):
        self.error = None
        self._alive = False
        self._die_with = die_with
        self._beat = beat

    def start(self):
        if self._die_with is not None:
            self.error = self._die_with
            self._alive = False
        else:
            self._alive = True
            if self._beat is not None:
                self._beat()

    def stop(self, timeout=None):
        self._alive = False

    def alive(self):
        return self._alive


def wait_for(pred, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


def quick_policy(max_deaths=3):
    return RestartPolicy(max_deaths=max_deaths, window_s=60.0,
                         base_delay=0.01, max_delay=0.05)


def test_supervisor_restarts_dead_worker_and_stamps_mttr():
    cap = Cap()
    sup = Supervisor(metrics=cap, policy=quick_policy(), poll_s=0.01)

    def factory(attempt, beat):
        die = InjectedKill("boom") if attempt == 0 else None
        return FakeWorker(die_with=die, beat=beat)

    h = sup.add(factory, name="actor:0")
    try:
        sup.start()
        wait_for(lambda: h.restarts == 1 and h.alive(),
                 msg="restarted worker")
        wait_for(lambda: h.last_mttr_s is not None, msg="recovery")
    finally:
        sup.stop()
    (restart,) = cap.named("worker_restart")
    assert restart["worker"] == "actor:0"
    assert restart["reason"] == "error"        # InjectedKill: fatal
    assert "InjectedKill" in restart["error"]
    (rec,) = cap.named("worker_recovered")
    assert rec["mttr_s"] == pytest.approx(h.last_mttr_s, abs=1e-3)
    assert not h.parked


def test_supervisor_parks_crash_loop():
    cap = Cap()
    sup = Supervisor(metrics=cap, policy=quick_policy(max_deaths=2),
                     poll_s=0.01)
    h = sup.add(
        lambda attempt, beat: FakeWorker(die_with=RuntimeError("x")),
        name="actor:1")
    try:
        sup.start()
        wait_for(lambda: h.parked, msg="crash-loop park")
    finally:
        sup.stop()
    assert h.restarts == 1                     # 2nd death parks
    (park,) = cap.named("worker_parked")
    assert park["reason"] == "crash_loop" and park["deaths"] == 2


def test_supervisor_refuses_lockstep_restart():
    """ISSUE 14: a lockstep actor is registered restartable=False —
    its death must PARK (reason restart_refused), never resurrect: a
    restarted lockstep actor would replay games the FIFO consumer
    already ate, breaking the bit-identity pin."""
    cap = Cap()
    sup = Supervisor(metrics=cap, policy=quick_policy(), poll_s=0.01)
    h = sup.add(
        lambda attempt, beat: FakeWorker(die_with=InjectedKill("k")),
        name="actor:0", restartable=False)
    try:
        sup.start()
        wait_for(lambda: h.parked, msg="refused restart")
    finally:
        sup.stop()
    assert h.restarts == 0                     # never resurrected
    (park,) = cap.named("worker_parked")
    assert park["reason"] == "restart_refused"
    assert not cap.named("worker_restart")


def test_supervisor_drain_stops_restarts():
    cap = Cap()
    sup = Supervisor(metrics=cap, policy=quick_policy(), poll_s=0.01)
    worker = FakeWorker()
    h = sup.add(lambda attempt, beat: worker, name="actor:0")
    try:
        sup.start()
        assert not sup.draining
        sup.request_drain(reason="test")
        sup.request_drain(reason="test")       # idempotent
        assert sup.draining and sup.drain_reason == "test"
        # a death during the drain is final — no resurrection
        worker.error = RuntimeError("died mid-drain")
        worker._alive = False
        time.sleep(0.1)
        assert h.restarts == 0 and not h.parked
    finally:
        sup.stop()
    assert [f for f in cap.named("drain")] == [
        {"phase": "requested", "reason": "test"}]


def test_supervisor_tags_stale_worker_for_watchdog():
    """Satellite: an alive-but-silent worker gets named in the
    watchdog's waiting_on registry, so a stall event says WHICH fleet
    member wedged."""
    from rocalphago_tpu.runtime import watchdog

    cap = Cap()
    sup = Supervisor(metrics=cap, policy=quick_policy(),
                     poll_s=0.01, heartbeat_s=0.05)
    h = sup.add(lambda attempt, beat: FakeWorker(), name="actor:9")
    wd = watchdog.Watchdog(0.05, metrics=cap, exit=False,
                           poll_s=0.01, name="fleet")
    try:
        sup.start()
        wait_for(lambda: "actor:9" in watchdog.waiting_phases(),
                 msg="stale tag")
        wd.start()
        wait_for(lambda: cap.named("stall"), msg="stall event")
        stall = cap.named("stall")[0]
        assert "actor:9" in (stall["waiting_on"] or "")
        h.beat()                               # progress: tag clears
        wait_for(lambda: "actor:9" not in watchdog.waiting_phases(),
                 msg="tag cleared")
    finally:
        wd.stop()
        sup.stop()
    assert "actor:9" not in watchdog.waiting_phases()


# ------------------------------------- supervised dispatcher


def fake_eval(_pp, _vv, states):
    b = states.shape[0]
    return (np.full((b, 26), 1.0 / 26, np.float32),
            np.zeros((b,), np.float32))


def test_dispatcher_resurrects_and_serves_across_kill():
    from rocalphago_tpu.serve.evaluator import BatchingEvaluator

    cap = Cap()
    install("kill@serve.dispatch:2")
    ev = BatchingEvaluator(fake_eval, None, None, batch_sizes=(2,),
                           max_wait_us=100.0, metrics=cap,
                           restart_policy=quick_policy())
    try:
        states = np.zeros((2, 4), np.float32)
        p1, _ = ev.evaluate(states, rows=2, timeout=10.0)
        # the next loop wake is the 2nd serve.dispatch hit: the kill
        # takes the THREAD down with the queue intact
        p2, _ = ev.evaluate(states, rows=2, timeout=10.0)
        assert np.array_equal(p1, p2)
        wait_for(lambda: ev._thread.restarts == 1, msg="restart")
    finally:
        install(None)
        ev.close()
    (restart,) = cap.named("worker_restart")
    assert restart["worker"] == "serve:dispatcher"
    assert not ev._thread.parked


def test_dispatcher_park_fails_pending_requests():
    from rocalphago_tpu.serve.evaluator import BatchingEvaluator

    cap = Cap()
    install("kill@serve.dispatch:p=1")         # dies on every wake
    ev = BatchingEvaluator(fake_eval, None, None, batch_sizes=(2,),
                           max_wait_us=100.0, metrics=cap,
                           restart_policy=quick_policy(max_deaths=2))
    try:
        req = ev.submit(np.zeros((2, 4), np.float32), rows=2)
        with pytest.raises(RuntimeError, match="parked"):
            req.result(timeout=10.0)
        assert ev._thread.parked
        (park,) = cap.named("worker_parked")
        assert park["reason"] == "crash_loop"
    finally:
        install(None)
        ev.close()


# --------------------------------------- subprocess: the real loop

SIZE = 5
ARGS = ["--game-batch", "2", "--iterations", "2", "--move-limit", "8",
        "--sims", "2", "--sim-chunk", "2", "--replay-chunk", "4",
        "--save-every", "1", "--gate-games", "2", "--num-devices", "1",
        "--seed", "3"]


@pytest.fixture(scope="module")
def specs(tmp_path_factory):
    from rocalphago_tpu.models import CNNPolicy, CNNValue

    d = tmp_path_factory.mktemp("fleet_specs")
    pol = CNNPolicy(("board", "ones"), board=SIZE, layers=1,
                    filters_per_layer=2)
    val = CNNValue(("board", "ones", "color"), board=SIZE, layers=1,
                   filters_per_layer=2)
    pj, vj = str(d / "p.json"), str(d / "v.json")
    pol.save_model(pj)
    val.save_model(vj)
    return pj, vj


def zero_env(fault_plan=None):
    return dict(os.environ, JAX_PLATFORMS="cpu",
                ROCALPHAGO_FAULT_PLAN=fault_plan or "")


def run_zero(specs, out_dir, fault_plan=None, extra=()):
    pj, vj = specs
    return subprocess.run(
        [sys.executable, "-m", "rocalphago_tpu.training.zero",
         pj, vj, str(out_dir), *ARGS, *extra],
        env=zero_env(fault_plan), cwd=REPO,
        capture_output=True, text=True, timeout=600)


def events_of(out_dir):
    return list(read_jsonl(os.path.join(str(out_dir),
                                        "metrics.jsonl")))


def final_stats(out_dir):
    rows = {}
    for r in events_of(out_dir):
        if r.get("event") == "iteration":
            # wall-time fields (incl. the learner's replay-staleness
            # stamp) differ run-to-run by design — drop them
            rows[r["iteration"]] = {
                k: v for k, v in r.items()
                if k not in ("time", "games_per_min",
                             "replay_staleness_s")}
    return rows


def assert_same_run(baseline_dir, resumed_dir):
    assert final_stats(baseline_dir) == final_stats(resumed_dir), (
        "drained+resumed training stats diverge from baseline")
    names = sorted(n for n in os.listdir(str(baseline_dir))
                   if n.endswith(".msgpack") or n.endswith(".json"))
    for name in names:
        if name == "metadata.json":
            continue            # wall_time fields differ by design
        with open(os.path.join(str(baseline_dir), name), "rb") as f:
            want = f.read()
        with open(os.path.join(str(resumed_dir), name), "rb") as f:
            assert f.read() == want, f"{name} differs after drain"
    bpool = os.path.join(str(baseline_dir), "pool")
    if os.path.isdir(bpool):
        bsnaps = sorted(os.listdir(bpool))
        assert sorted(os.listdir(
            os.path.join(str(resumed_dir), "pool"))) == bsnaps


def test_lockstep_kill_parks_and_fails_loudly(specs, tmp_path):
    """The enforcement test: an injected actor kill in LOCKSTEP mode
    must park (restart_refused) and fail the run — never silently
    resurrect into a bitstream the A/B pin could not reproduce."""
    out = tmp_path / "lockstep_kill"
    proc = run_zero(specs, out, fault_plan="kill@actor.game",
                    extra=("--actor-learner",))
    assert proc.returncode != 0, (
        "lockstep run under an actor kill must fail, not heal:\n"
        + proc.stderr[-2000:])
    assert "parked" in proc.stderr
    parks = [r for r in events_of(out)
             if r.get("event") == "worker_parked"]
    assert parks and parks[0]["reason"] == "restart_refused"
    assert not [r for r in events_of(out)
                if r.get("event") == "worker_restart"]


@pytest.mark.slow
def test_sigterm_drain_resume_bit_identical(specs, tmp_path):
    """The preemption-drain proof: SIGTERM → stop at the iteration
    boundary, commit a checkpoint, exit 0 — and the rerun converges
    byte-identically to a never-drained run."""
    pj, vj = specs
    extra = ("--actor-learner", "--iterations", "3")
    baseline = tmp_path / "baseline"
    proc = run_zero(specs, baseline, extra=extra)
    assert proc.returncode == 0, proc.stderr[-2000:]

    drained = tmp_path / "drained"
    proc = subprocess.Popen(
        [sys.executable, "-m", "rocalphago_tpu.training.zero",
         pj, vj, str(drained), *ARGS, *extra],
        env=zero_env(), cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        # wait for the first completed iteration, then preempt
        metrics_path = os.path.join(str(drained), "metrics.jsonl")
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if os.path.exists(metrics_path) and any(
                    r.get("event") == "iteration"
                    for r in read_jsonl(metrics_path)):
                break
            assert proc.poll() is None, proc.stderr.read()[-2000:]
            time.sleep(0.1)
        else:
            raise AssertionError("no iteration completed in 300s")
        proc.send_signal(signal.SIGTERM)
        _, stderr = proc.communicate(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, (
        f"drain must exit 0, got {proc.returncode}\n{stderr[-2000:]}")
    phases = [r["phase"] for r in events_of(drained)
              if r.get("event") == "drain"]
    assert phases[:2] == ["requested", "loop_exit"]
    assert "checkpoint" in phases
    reasons = {r.get("reason") for r in events_of(drained)
               if r.get("event") == "drain" and "reason" in r}
    assert reasons == {"sigterm"}

    # resume: same command runs to completion, byte-identical
    proc2 = run_zero(specs, drained, extra=extra)
    assert proc2.returncode == 0, proc2.stderr[-2000:]
    assert any(r.get("event") == "resume" for r in events_of(drained))
    assert_same_run(baseline, drained)


# -------------------------------------------------- the chaos soak


def run_soak(out_dir, *extra):
    return subprocess.run(
        [sys.executable, "scripts/chaos_soak.py",
         "--out", str(out_dir), *extra],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=REPO, capture_output=True, text=True, timeout=600)


def check_soak(proc, out_dir, min_kills):
    assert proc.returncode == 0, (
        f"soak failed rc={proc.returncode}\n{proc.stdout[-2000:]}\n"
        f"{proc.stderr[-2000:]}")
    with open(os.path.join(str(out_dir), "summary.json")) as f:
        summary = json.load(f)
    assert all(summary["checks"].values()), summary["checks"]
    assert summary["kills_total"] >= min_kills
    assert summary["monotonic"] and summary["stall_events"] == 0
    events = {r.get("event") for r in read_jsonl(
        os.path.join(str(out_dir), "metrics.jsonl"))}
    assert "worker_restart" in events
    assert "worker_recovered" in events or "stall" not in events
    return summary


@pytest.mark.slow
def test_chaos_soak_smoke(tmp_path):
    """Full tier (suite wall-time): a short randomized storm — at least 2 kills across
    the fleet, supervised progress to 3 learner steps, clean gate."""
    out = tmp_path / "soak"
    proc = run_soak(out, "--steps", "3", "--min-kills", "2",
                    "--serve-requests", "10")
    check_soak(proc, out, min_kills=2)


@pytest.mark.slow
def test_chaos_soak_full(tmp_path):
    """The headline soak: >= 6 randomized kills across actors,
    learner steps and the serving dispatcher; monotonic learner
    progress, zero stalls, zero parks, green fault-free gate."""
    out = tmp_path / "soak_full"
    proc = run_soak(out)                       # defaults: 12 steps,
    summary = check_soak(proc, out, min_kills=6)   # min 6 kills
    assert summary["learner_steps"] >= 12
    assert summary["serve_ok"] > 0
