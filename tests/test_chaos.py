"""Chaos tests: kill the zero trainer at every declared fault
barrier, resume, and prove the exact-resume docstring in
``io/checkpoint.py`` — final training stats and saved params must be
IDENTICAL to an uninterrupted run, and no injected crash may leave a
torn artifact anywhere in the run directory.

Mechanics: the trainer runs in a subprocess with
``ROCALPHAGO_FAULT_PLAN=crash@<barrier>`` (``runtime.faults`` calls
``os._exit`` — the honest model of SIGKILL/OOM/preemption: no atexit,
no finally blocks, async checkpoint writes die mid-flight). The
resumed run restores the last COMMITTED Orbax step, replays the
killed iteration from identical state (rng, incumbent, gate keys all
live in or derive from the checkpoint), and rewrites every artifact
atomically — so the equality assertions below are exact, not
approximate.

The smoke test (tier-1, not slow) does one kill/resume cycle; the
slow test sweeps every barrier including mid-promotion kills.
"""

import json
import os
import subprocess
import sys

import pytest

from rocalphago_tpu.runtime.faults import FAULT_EXIT_CODE
from rocalphago_tpu.runtime.jsonl import read_jsonl

SIZE = 5
# the chaos configuration: 2 iterations, checkpoint+gate every
# iteration, tiny 5x5 search self-play
ARGS = ["--game-batch", "2", "--iterations", "2", "--move-limit", "8",
        "--sims", "2", "--sim-chunk", "2", "--replay-chunk", "4",
        "--save-every", "1", "--gate-games", "2", "--num-devices", "1",
        "--seed", "3"]

# every fault barrier the zero loop declares (docs/RESILIENCE.md);
# the smoke test uses the first, the slow sweep runs them all.
# iter0-qualified so each crash lands mid-run with work left to do.
ZERO_BARRIERS = [
    "crash@iter0.zero.post_save",
    "crash@iter0.zero.pre_iteration",
    "crash@iter0.zero.post_iteration",
    "crash@iter0.zero.post_gate",
    "crash@iter0.zero.post_export",
    "crash@iter0.zero.pre_save",
    "crash@zero.promote",            # first promote: torn-pair check
    "crash@zero.promote:2",          # mid-pair: policy without value
    "crash@iter1.zero.post_iteration",
]


@pytest.fixture(scope="module")
def specs(tmp_path_factory):
    """Tiny policy/value spec JSONs shared by every run."""
    from rocalphago_tpu.models import CNNPolicy, CNNValue

    d = tmp_path_factory.mktemp("chaos_specs")
    pol = CNNPolicy(("board", "ones"), board=SIZE, layers=1,
                    filters_per_layer=2)
    val = CNNValue(("board", "ones", "color"), board=SIZE, layers=1,
                   filters_per_layer=2)
    pj, vj = str(d / "p.json"), str(d / "v.json")
    pol.save_model(pj)
    val.save_model(vj)
    return pj, vj


def run_zero(specs, out_dir, fault_plan=None, extra=()):
    pj, vj = specs
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               ROCALPHAGO_FAULT_PLAN=fault_plan or "")
    return subprocess.run(
        [sys.executable, "-m", "rocalphago_tpu.training.zero",
         pj, vj, str(out_dir), *ARGS, *extra],
        env=env, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=600)


def final_stats(out_dir):
    """Last record per iteration index, wall-time fields dropped —
    everything else must be bit-identical across resume."""
    rows = {}
    for r in read_jsonl(os.path.join(str(out_dir), "metrics.jsonl")):
        if r.get("event") == "iteration":
            rows[r["iteration"]] = {
                k: v for k, v in r.items()
                if k not in ("time", "games_per_min")}
    return rows


def assert_no_torn_artifacts(out_dir):
    """Atomicity sweep after a kill: no temp litter, every JSON
    parses, every pool policy snapshot has its value sibling."""
    out_dir = str(out_dir)
    for root, _, names in os.walk(out_dir):
        if "checkpoints" in os.path.relpath(root, out_dir).split(
                os.sep):
            continue            # Orbax manages its own tmp lifecycle
        for name in names:
            path = os.path.join(root, name)
            assert not name.endswith(".tmp"), f"torn write: {path}"
            if name.endswith(".json"):
                with open(path) as f:
                    json.load(f)        # complete JSON or it raises
            if name.endswith(".policy.msgpack"):
                sibling = path.replace(".policy.", ".value.")
                # a mid-promotion kill may leave the policy file
                # alone — then snapshots() must not list the pair
                if not os.path.exists(sibling):
                    from rocalphago_tpu.training.zero import ZeroGate

                    listed = [p for _, p, _ in
                              ZeroGate.snapshots(
                                  type("G", (), {"pool_dir": root}))]
                    assert path not in listed, (
                        f"incomplete pair {path} visible to resume")


def assert_same_run(baseline_dir, resumed_dir):
    base, res = final_stats(baseline_dir), final_stats(resumed_dir)
    assert base == res, "resumed training stats diverge from baseline"
    names = sorted(n for n in os.listdir(str(baseline_dir))
                   if n.endswith(".msgpack") or n.endswith(".json"))
    for name in names:
        if name == "metadata.json":
            continue            # wall_time fields differ by design
        with open(os.path.join(str(baseline_dir), name), "rb") as f:
            want = f.read()
        with open(os.path.join(str(resumed_dir), name), "rb") as f:
            got = f.read()
        assert got == want, f"{name} differs after crash+resume"
    # promotion pools match snapshot-for-snapshot
    bpool = os.path.join(str(baseline_dir), "pool")
    if os.path.isdir(bpool):
        bsnaps = sorted(os.listdir(bpool))
        assert sorted(os.listdir(
            os.path.join(str(resumed_dir), "pool"))) == bsnaps
        for name in bsnaps:
            with open(os.path.join(bpool, name), "rb") as f:
                want = f.read()
            with open(os.path.join(
                    str(resumed_dir), "pool", name), "rb") as f:
                assert f.read() == want, f"pool/{name} differs"


def crash_and_resume(specs, out_dir, plan):
    """One cycle: run under ``plan`` until the injected kill, assert
    artifact atomicity, then resume to completion."""
    proc = run_zero(specs, out_dir, fault_plan=plan)
    assert proc.returncode == FAULT_EXIT_CODE, (
        f"{plan}: expected injected crash, got rc={proc.returncode}\n"
        f"{proc.stderr[-2000:]}")
    assert_no_torn_artifacts(out_dir)
    proc = run_zero(specs, out_dir)
    assert proc.returncode == 0, (
        f"{plan}: resume failed rc={proc.returncode}\n"
        f"{proc.stderr[-2000:]}")
    return proc


@pytest.mark.slow
def test_chaos_smoke_single_kill_resume(specs, tmp_path):
    """Full-tier smoke (suite wall-time; the faster lockstep-kill
    rig keeps a chaos subprocess in the fast tier): one injected
    kill right after the first
    checkpoint commit, resume, and the run is indistinguishable from
    one that never crashed."""
    baseline = tmp_path / "baseline"
    proc = run_zero(specs, baseline)
    assert proc.returncode == 0, proc.stderr[-2000:]

    crashed = tmp_path / "crashed"
    crash_and_resume(specs, crashed, ZERO_BARRIERS[0])
    assert_same_run(baseline, crashed)
    # the resume actually happened (not a silent from-scratch rerun)
    events = [r["event"] for r in read_jsonl(
        os.path.join(str(crashed), "metrics.jsonl"))]
    assert "resume" in events


@pytest.mark.slow
def test_chaos_every_zero_barrier(specs, tmp_path):
    """The headline proof: crash at EVERY declared barrier in the
    zero loop (including mid-promotion), resume each time, and every
    resumed run's final stats, exports, and promotion pool are
    byte-identical to the uninterrupted baseline."""
    baseline = tmp_path / "baseline"
    proc = run_zero(specs, baseline)
    assert proc.returncode == 0, proc.stderr[-2000:]

    for plan in ZERO_BARRIERS[1:]:
        out = tmp_path / plan.replace("@", "_").replace(
            ":", "_").replace(".", "_")
        crash_and_resume(specs, out, plan)
        assert_same_run(baseline, out)


def test_selfplay_chunk_barrier_once_per_chunk_under_pipelining():
    """ISSUE 4: pipelined dispatch (one segment in flight) must not
    move the fault-injection points — ``selfplay.chunk`` still fires
    exactly once per dispatched segment, host-side, in dispatch
    order. A 12-ply/chunk-4 run has exactly three chunk barriers: a
    spec on hit 3 fires (the loop reached the third chunk with the
    first two already dispatched), a spec on hit 4 never does."""
    import jax
    import jax.numpy as jnp

    from rocalphago_tpu.engine.jaxgo import GoConfig
    from rocalphago_tpu.runtime import faults
    from rocalphago_tpu.runtime.faults import InjectedFault
    from rocalphago_tpu.search.selfplay import make_selfplay_chunked

    def fake_policy(params, planes):
        return jnp.zeros((planes.shape[0], 25))

    cfg = GoConfig(size=5)
    run = make_selfplay_chunked(cfg, ("board", "ones"), fake_policy,
                                fake_policy, batch=2, max_moves=12,
                                chunk=4)
    key = jax.random.key(0)
    try:
        faults.install("io_error@selfplay.chunk:3")
        with pytest.raises(InjectedFault):
            run(None, None, key)
        faults.install("io_error@selfplay.chunk:4")
        run(None, None, key)        # only 3 chunks: never fires
    finally:
        faults.install(None)


@pytest.mark.slow
def test_chaos_io_error_retried_in_run(specs, tmp_path):
    """A transient (injected) io_error during promotion is absorbed
    by the retry layer: the run completes in ONE process with a
    'retry' event logged, and artifacts match the clean baseline."""
    baseline = tmp_path / "baseline"
    proc = run_zero(specs, baseline)
    assert proc.returncode == 0, proc.stderr[-2000:]

    out = tmp_path / "io_error"
    proc = run_zero(specs, out, fault_plan="io_error@zero.promote")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "retrying" in proc.stderr     # the backoff path ran
    assert_same_run(baseline, out)
