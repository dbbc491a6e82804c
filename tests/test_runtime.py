"""Crash-safe runtime layer (``rocalphago_tpu.runtime``) unit tests:
atomic artifact writes, retry classification/backoff, the fault-plan
grammar and barrier semantics, the watchdog, the line-buffered
``MetricsLogger`` crash contract with its tolerant reader, metadata
resume-overwrite semantics, and the ladder-script satellite fixes."""

import json
import os
import time

import pytest

from rocalphago_tpu.runtime import atomic, faults, retries
from rocalphago_tpu.runtime.jsonl import read_jsonl
from rocalphago_tpu.runtime.watchdog import Watchdog


@pytest.fixture(autouse=True)
def _no_fault_plan():
    """Isolate every test from the env plan and reset fired specs."""
    faults.install("")
    yield
    faults.install(None)


# ---------------------------------------------------------- atomic

def test_atomic_write_roundtrip(tmp_path):
    p = str(tmp_path / "a" / "b.json")
    atomic.atomic_write_json(p, {"x": 1})
    with open(p) as f:
        assert json.load(f) == {"x": 1}
    atomic.atomic_write_bytes(p, b"v2")
    with open(p, "rb") as f:
        assert f.read() == b"v2"


def test_atomic_write_failure_preserves_old(tmp_path, monkeypatch):
    """A failure at the rename leaves the previous complete file and
    no temp litter — the whole point of the dance."""
    p = str(tmp_path / "f.bin")
    atomic.atomic_write_bytes(p, b"old")

    def boom(*a, **k):
        raise OSError("injected replace failure")

    monkeypatch.setattr(os, "replace", boom)
    with pytest.raises(OSError):
        atomic.atomic_write_bytes(p, b"new")
    monkeypatch.undo()
    with open(p, "rb") as f:
        assert f.read() == b"old"
    assert os.listdir(tmp_path) == ["f.bin"]   # tmp cleaned up


# --------------------------------------------------------- retries

def test_retry_transient_then_success():
    calls = []

    @retries.retry(max_attempts=3, base_delay=0.0, sleep=lambda s: None)
    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert flaky() == "ok"
    assert len(calls) == 3


def test_retry_gives_up_after_max_attempts():
    calls = []

    @retries.retry(max_attempts=2, base_delay=0.0, sleep=lambda s: None)
    def always():
        calls.append(1)
        raise OSError("still down")

    with pytest.raises(OSError):
        always()
    assert len(calls) == 2


def test_retry_programming_error_not_retried():
    calls = []

    @retries.retry(max_attempts=5, base_delay=0.0, sleep=lambda s: None)
    def broken():
        calls.append(1)
        raise ValueError("shape mismatch")

    with pytest.raises(ValueError):
        broken()
    assert len(calls) == 1


def test_transient_classification():
    class XlaRuntimeError(Exception):
        pass

    assert retries.is_transient(OSError("disk"))
    assert retries.is_transient(faults.InjectedFault("io"))
    assert retries.is_transient(
        XlaRuntimeError("UNAVAILABLE: socket closed"))
    assert retries.is_transient(
        XlaRuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    # an XlaRuntimeError wrapping a programming error is NOT transient
    assert not retries.is_transient(
        XlaRuntimeError("INVALID_ARGUMENT: dimension mismatch"))
    assert not retries.is_transient(TypeError("bad arg"))
    assert not retries.is_transient(KeyboardInterrupt())


def test_backoff_deterministic_and_bounded():
    a = [retries.backoff_delay(i, 0.5, 8.0, seed=7, key="f")
         for i in range(6)]
    b = [retries.backoff_delay(i, 0.5, 8.0, seed=7, key="f")
         for i in range(6)]
    assert a == b                       # same seed → same schedule
    assert a != [retries.backoff_delay(i, 0.5, 8.0, seed=8, key="f")
                 for i in range(6)]
    for i, d in enumerate(a):
        envelope = min(8.0, 0.5 * 2 ** i)
        assert envelope * 0.5 <= d <= envelope


# ---------------------------------------------------------- faults

def test_fault_plan_grammar():
    specs = faults.parse_plan(
        "crash@iter3.post_save, io_error@promote:2, sleep@chunk=0.25")
    assert [s.kind for s in specs] == ["crash", "io_error", "sleep"]
    assert specs[0].iteration == 3 and specs[0].barrier == "post_save"
    assert specs[1].hit == 2
    assert specs[2].arg == 0.25
    with pytest.raises(ValueError, match="bad fault spec"):
        faults.parse_plan("crash")
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.parse_plan("explode@save")
    with pytest.raises(ValueError, match="needs a duration"):
        faults.parse_plan("sleep@save")


def test_fault_barrier_iteration_and_hit_count():
    faults.install("io_error@iter2.zero.post_save:2")
    faults.barrier("zero.post_save", 0)      # wrong iteration
    faults.barrier("zero.post_save", 2)      # hit 1 of 2
    with pytest.raises(faults.InjectedFault):
        faults.barrier("zero.post_save", 2)  # hit 2 → fires
    faults.barrier("zero.post_save", 2)      # fired → spent


def test_fault_barrier_suffix_match():
    faults.install("io_error@post_save")
    with pytest.raises(faults.InjectedFault):
        faults.barrier("sl.post_save", 0)
    faults.install("io_error@zero.post_save")
    faults.barrier("sl.post_save", 0)        # qualified: no match
    with pytest.raises(faults.InjectedFault):
        faults.barrier("zero.post_save", 0)


def test_fault_sleep_kind():
    faults.install("sleep@tick=0.05")
    t0 = time.monotonic()
    faults.barrier("loop.tick")
    assert time.monotonic() - t0 >= 0.05


def test_injected_fault_is_retryable_and_one_shot():
    """The designed interplay: one injected io_error costs one retry
    attempt, then the run proceeds — fault plans exercise the backoff
    path without killing the run."""
    faults.install("io_error@write:1")

    @retries.retry(max_attempts=3, base_delay=0.0, sleep=lambda s: None)
    def write():
        faults.barrier("artifact.write")
        return "written"

    assert write() == "written"


# -------------------------------------------------------- watchdog

def test_watchdog_beat_keeps_quiet():
    events = []

    class Log:
        def log(self, event, **kw):
            events.append((event, kw))

    with Watchdog(0.2, metrics=Log(), poll_s=0.02) as wd:
        for _ in range(10):
            wd.beat()
            time.sleep(0.02)
    assert events == []


def test_watchdog_stall_logs_and_aborts():
    events, aborted = [], []

    class Log:
        def log(self, event, **kw):
            events.append((event, kw))

    wd = Watchdog(0.05, metrics=Log(), poll_s=0.01,
                  abort_fn=lambda: aborted.append(1), exit=False,
                  name="t")
    wd.start()
    time.sleep(0.3)                      # no beats → stall
    wd.stop()
    assert aborted == [1]
    assert events and events[0][0] == "stall"
    assert events[0][1]["watchdog"] == "t"
    assert events[0][1]["elapsed_s"] >= 0.05


# ------------------------------------- MetricsLogger crash contract

def test_metrics_logger_line_buffered_no_close(tmp_path):
    """Every log() is durably a whole line immediately (buffering=1):
    a kill between events loses nothing, a kill mid-write loses at
    most the in-flight line. Read WITHOUT closing the logger — a
    crashed process never calls close()."""
    from rocalphago_tpu.io.metrics import MetricsLogger

    path = str(tmp_path / "m.jsonl")
    log = MetricsLogger(path, echo=False)
    for i in range(5):
        log.log("iteration", iteration=i)
    recs = read_jsonl(path)
    assert [r["iteration"] for r in recs] == list(range(5))


def test_read_jsonl_skips_torn_final_line(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"event": "a", "i": 0}) + "\n")
        f.write(json.dumps({"event": "a", "i": 1}) + "\n")
        f.write('{"event": "a", "i": 2, "tru')   # torn mid-record
    recs = read_jsonl(path)
    assert [r["i"] for r in recs] == [0, 1]
    with pytest.raises(ValueError):
        read_jsonl(path, on_error="raise")


# -------------------------------------- MetadataWriter resume paths

def test_metadata_resume_overwrites_reran_epoch(tmp_path):
    from rocalphago_tpu.io.checkpoint import MetadataWriter

    path = str(tmp_path / "metadata.json")
    meta = MetadataWriter(path, header={"cmd": "x"})
    meta.record_epoch({"iteration": 0, "loss": 1.0})
    meta.record_epoch({"iteration": 1, "loss": 0.9})
    # crashed-and-resumed run re-records iteration 1
    meta2 = MetadataWriter(path)
    meta2.record_epoch({"iteration": 1, "loss": 0.9})
    with open(path) as f:
        epochs = json.load(f)["epochs"]
    assert [e["iteration"] for e in epochs] == [0, 1]


def test_metadata_corrupt_file_starts_fresh(tmp_path):
    from rocalphago_tpu.io.checkpoint import MetadataWriter

    path = str(tmp_path / "metadata.json")
    with open(path, "w") as f:
        f.write('{"epochs": [{"iteration":')    # legacy torn write
    meta = MetadataWriter(path, header={"cmd": "x"})
    meta.record_epoch({"iteration": 0})
    with open(path) as f:
        data = json.load(f)
    assert data["cmd"] == "x" and len(data["epochs"]) == 1


# ------------------------------------------- ladder script (ADVICE)

def _load_ladder():
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "zero_ladder_matches.py")
    spec = importlib.util.spec_from_file_location("zero_ladder", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ladder_pool_snapshots_missing_dir_is_usage_error(tmp_path):
    mod = _load_ladder()
    with pytest.raises(SystemExit, match="does not exist"):
        mod.pool_snapshots(str(tmp_path / "no_such_run"))


def test_ladder_pool_snapshots_numeric_sort(tmp_path):
    mod = _load_ladder()
    pool = tmp_path / "run" / "pool"
    pool.mkdir(parents=True)
    # zero-padding narrower than the largest iteration: lexicographic
    # order would yield 10 < 5
    for it in (5, 10, 100):
        (pool / f"best.{it}.policy.msgpack").write_bytes(b"")
    snaps = mod.pool_snapshots(str(tmp_path / "run"))
    assert [it for it, _ in snaps] == [5, 10, 100]


def test_ladder_write_spec_never_clobbers_pool(tmp_path):
    mod = _load_ladder()
    pool = tmp_path / "run" / "pool"
    pool.mkdir(parents=True)
    weights = pool / "best.00005.policy.msgpack"
    weights.write_bytes(b"w")
    tracked = pool / "best.00005.policy.json"
    tracked.write_text('{"tracked": true}')     # git-tracked artifact
    spec_src = tmp_path / "spec.json"
    spec_src.write_text(json.dumps({"class": "CNNPolicy"}))
    out_dir = tmp_path / "specs"
    out_dir.mkdir()
    out = mod.write_spec(str(spec_src), str(weights), str(out_dir))
    assert os.path.dirname(out) == str(out_dir)
    assert tracked.read_text() == '{"tracked": true}'   # untouched
    with open(out) as f:
        spec = json.load(f)
    assert spec["weights_file"] == os.path.abspath(str(weights))


# -------------------------------------------------- compile cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_config_updates(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


METADATA_IN_KEY = ("jax_compilation_cache_include_metadata_in_key", True)


def test_compile_cache_placed_from_outside_sets_no_directory(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set → JAX's own handling of it
    places the cache: the helper reports the directory and sets none.
    What it does set, always: metadata in the cache key, so a loaded
    executable carries THIS program's scope names (obs/scopes.py)."""
    from rocalphago_tpu.runtime.compilecache import enable_compile_cache

    calls = _recorded_config_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
    assert enable_compile_cache() == "/placed/outside"
    assert calls == [METADATA_IN_KEY]


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    """Unset → ``<checkout>/.jax_cache``: one fixed, git-ignored path
    (never from tempfile, a pid or the clock), and nothing but the
    directory and the metadata-in-key rule is configured."""
    from rocalphago_tpu.runtime.compilecache import enable_compile_cache

    calls = _recorded_config_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert enable_compile_cache() == want
    assert enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want),
                     METADATA_IN_KEY] * 2
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_rule_has_one_home():
    """The suite itself runs under the rule (conftest), no other file
    configures a cache directory, and the repo's old knob is gone
    from code and docs/KNOBS.md."""
    import jax

    from rocalphago_tpu.runtime.compilecache import cache_dir

    assert jax.config.jax_compilation_cache_dir == cache_dir()
    home = os.path.join("rocalphago_tpu", "runtime", "compilecache.py")
    setters, old_knob = [], []
    for top in ("rocalphago_tpu", "scripts", "tests", "docs",
                "chip_smoke.py", "__graft_entry__.py"):
        path = os.path.join(REPO, top)
        files = [path] if os.path.isfile(path) else [
            os.path.join(d, f) for d, _, fs in os.walk(path)
            for f in fs if f.endswith((".py", ".md", ".sh"))]
        for fp in files:
            rel = os.path.relpath(fp, REPO)
            with open(fp, errors="replace") as f:
                text = f.read()
            if '"jax_compilation_cache_dir"' in text and rel not in (
                    home, os.path.join("tests", "test_runtime.py")):
                setters.append(rel)
            if "ROCALPHAGO_" + "COMPILE_CACHE" in text:
                old_knob.append(rel)
    assert setters == [] and old_knob == []


# -------------------------------------------------------- deadline

def test_deadline_semantics():
    from rocalphago_tpu.runtime.deadline import Deadline

    d = Deadline.after(None)
    assert d.unlimited
    assert not d.expired()
    assert d.remaining() is None
    d0 = Deadline.after(0)
    assert d0.expired()
    assert d0.remaining() == 0.0
    assert Deadline.after(-5).expired()      # negative budgets clamp
    d1 = Deadline.after(60)
    assert not d1.expired()
    assert 0 < d1.remaining() <= 60
    assert "unlimited" in repr(d)


def test_deadline_expires_with_wall_clock():
    from rocalphago_tpu.runtime.deadline import Deadline

    d = Deadline.after(0.05)
    assert not d.expired()
    time.sleep(0.08)
    assert d.expired()
    assert d.remaining() == 0.0


# ---------------------------------------- checkpoint restore fallback

def test_checkpoint_restore_falls_back_past_torn_step(tmp_path,
                                                      capsys):
    """Satellite (ISSUE 2): a finalized-then-damaged newest Orbax
    step must not kill the resume — restore warns and falls back to
    the next-older retained step. An EXPLICITLY requested step still
    raises."""
    import shutil

    import numpy as np

    from rocalphago_tpu.io.checkpoint import TrainCheckpointer

    d = str(tmp_path / "ckpt")
    ckpt = TrainCheckpointer(d, max_to_keep=3)
    template = {"w": np.zeros(4, np.float32), "step": 0}
    for s in (1, 2):
        ckpt.save(s, {"w": np.full(4, float(s), np.float32),
                      "step": s}, wait=True)
    ckpt.wait()
    assert ckpt.latest_step() == 2
    # tear the newest step AFTER finalize: rip out its item payload
    # (the torn-directory model — rename already happened, contents
    # later damaged by the flaky filesystem)
    item_dir = os.path.join(d, "2", "default")
    assert os.path.isdir(item_dir)
    shutil.rmtree(item_dir)

    restored, step = ckpt.restore(template)
    assert step == 1
    assert restored["step"] == 1
    assert restored["w"][0] == 1.0
    err = capsys.readouterr().err
    assert "falling back to step 1" in err

    with pytest.raises(Exception):
        ckpt.restore(template, step=2)       # asked-for step: honest
    ckpt.close()
