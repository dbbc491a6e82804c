"""Multi-host wiring tests (single-process simulation).

The reference has no distributed layer (SURVEY.md §2c); the rebuild's
multi-host story is ``jax.distributed`` bring-up + coordinator-only
artifact writes. Real DCN needs multiple processes, so these tests
exercise the seams: ``distributed_init`` dispatch, and that a
non-coordinator trainer process writes NO artifact files while still
training (checkpoint saves stay all-process for Orbax).
"""

import json
import os

import numpy as np
import pytest

from rocalphago_tpu.parallel import mesh as meshlib
from rocalphago_tpu.training.sl import SLTrainer

from tests.test_sl_trainer import small_cfg, small_net, write_dataset


@pytest.fixture()
def corpus(tmp_path):
    prefix = str(tmp_path / "data" / "corpus")
    os.makedirs(tmp_path / "data")
    write_dataset(prefix)
    return prefix


def test_distributed_init_noop_single_process(monkeypatch):
    calls = []
    monkeypatch.setattr(
        meshlib.jax.distributed, "initialize",
        lambda *a, **k: calls.append((a, k)))
    meshlib.distributed_init()          # no coordinator, 1 process
    assert calls == []


def test_distributed_init_dispatches_multiprocess(monkeypatch):
    calls = []
    monkeypatch.setattr(
        meshlib.jax.distributed, "initialize",
        lambda *a, **k: calls.append(k))
    meshlib.distributed_init(coordinator="host0:1234",
                             num_processes=2, process_id=1)
    assert calls and calls[0]["num_processes"] == 2
    calls.clear()
    monkeypatch.setenv("JAX_NUM_PROCESSES", "4")
    meshlib.distributed_init()          # env-driven pod bring-up
    assert len(calls) == 1


def test_coordinator_is_true_single_process():
    assert meshlib.is_coordinator()


def test_non_coordinator_writes_no_artifacts(corpus, tmp_path,
                                             monkeypatch):
    """A process with ``is_coordinator() == False`` must train (Orbax
    checkpoints land — every process participates in multi-host saves)
    but never touch metadata/metrics/weights/shuffle files."""
    monkeypatch.setattr(meshlib, "is_coordinator", lambda: False)
    out = tmp_path / "out"
    trainer = SLTrainer(small_cfg(corpus, out, epochs=1),
                        net=small_net())
    result = trainer.run()
    trainer.ckpt.close()
    assert result["step"] > 0
    assert not (out / "metadata.json").exists()
    assert not (out / "metrics.jsonl").exists()
    assert not (out / "shuffle.npz").exists()
    assert not (out / "model.json").exists()
    assert (out / "checkpoints").is_dir()
    assert os.listdir(out / "checkpoints")


def test_non_coordinator_split_matches_coordinator(corpus, tmp_path,
                                                   monkeypatch):
    """The shuffle split is a pure function of the seed, so a
    non-coordinator (which never reads or writes shuffle.npz on a cold
    start) computes the identical split."""
    out_a = tmp_path / "a"
    t_coord = SLTrainer(small_cfg(corpus, out_a, epochs=1),
                        net=small_net())
    monkeypatch.setattr(meshlib, "is_coordinator", lambda: False)
    out_b = tmp_path / "b"
    t_worker = SLTrainer(small_cfg(corpus, out_b, epochs=1),
                         net=small_net())
    np.testing.assert_array_equal(t_coord.train_idx, t_worker.train_idx)
    np.testing.assert_array_equal(t_coord.test_idx, t_worker.test_idx)
    t_coord.ckpt.close()
    t_worker.ckpt.close()


def _run_two_workers(tmp_path, mode=None, timeout=180):
    """Spawn coordinator + worker ``multihost_worker.py`` processes
    over a free loopback port; return their JSON results by pid."""
    import socket
    import subprocess
    import sys as _sys

    with socket.socket() as s:          # free loopback port
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = os.path.join(repo, "tests", "multihost_worker.py")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)          # 1 real CPU device/process —
    # the parent's 8-virtual-device flag must not leak into children
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")

    procs = [subprocess.Popen(
        [_sys.executable, worker, str(i), "2", str(port),
         str(tmp_path)] + ([mode] if mode else []),
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True) for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=timeout)
            assert p.returncode == 0, (out, err)
            outs.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return {o["process"]: o for o in outs}


def test_two_process_distributed_dp_step(tmp_path):
    """REAL 2-process ``jax.distributed`` bring-up (VERDICT r3 #8):
    localhost coordinator, CPU backend, one local device per process.
    Both processes must complete one data-parallel step, agree on the
    replicated result, and only the coordinator may write artifacts.
    The CPU processes talk over jax's default CPU collectives (gloo
    over TCP)."""
    by_pid = _run_two_workers(tmp_path)
    assert set(by_pid) == {0, 1}
    # the DP step saw the GLOBAL device set and agreed on the result
    assert all(o["n_global_devices"] == 2 for o in by_pid.values())
    assert by_pid[0]["loss"] == pytest.approx(by_pid[1]["loss"])
    assert by_pid[0]["w"] == by_pid[1]["w"]
    # coordinator-only artifact discipline held over real processes
    assert by_pid[0]["coordinator"] is True
    assert by_pid[1]["coordinator"] is False
    assert os.path.exists(tmp_path / "result.json")
    assert os.listdir(tmp_path) == ["result.json"]


@pytest.mark.slow
def test_two_process_sharded_learner_step(tmp_path):
    """One SHARDED zero learner step over real 2-process gloo DCN
    (the actor/learner split's consumer — docs/SCALE.md): both
    processes ingest the identical host-side game record, ``learn``
    commits it to its declared shardings (batch on ``data``, params
    replicated), and the replicated post-update params must be
    bit-consistent across hosts — the checksum and losses each
    process reports from its OWN addressable shards agree."""
    by_pid = _run_two_workers(tmp_path, mode="zero_learner",
                              timeout=300)

    assert set(by_pid) == {0, 1}
    assert all(o["n_global_devices"] == 2 for o in by_pid.values())
    # params consistent across hosts after the sharded update
    assert by_pid[0]["params_checksum"] == by_pid[1]["params_checksum"]
    assert by_pid[0]["policy_loss"] == by_pid[1]["policy_loss"]
    assert by_pid[0]["value_loss"] == by_pid[1]["value_loss"]
    # the artifact-write discipline holds in this mode too
    assert by_pid[0]["coordinator"] is True
    assert by_pid[1]["coordinator"] is False
    assert os.listdir(tmp_path) == ["result.json"]
