"""Observability subsystem (``rocalphago_tpu.obs``) tests: span
nesting/exception paths, registry snapshot determinism, histogram
bucket edges, compile-tracking first-vs-second call, the watchdog
span-context satellite, the ``obs_report`` render path, and the
tier-1 zero-trainer smoke asserting the per-phase span records land
in ``metrics.jsonl`` with <2% instrumentation overhead."""

import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from rocalphago_tpu.io.metrics import MetricsLogger
from rocalphago_tpu.obs import jaxobs, trace
from rocalphago_tpu.obs import registry as obs_registry
from rocalphago_tpu.obs.registry import (
    Registry,
    quantile_from_buckets,
)
from rocalphago_tpu.runtime.jsonl import read_jsonl
from rocalphago_tpu.runtime.watchdog import Watchdog


@pytest.fixture(autouse=True)
def _detached_trace():
    """Every test starts and ends with no process sink installed."""
    trace.configure(None)
    yield
    trace.configure(None)


def _records(path):
    return read_jsonl(str(path))


# ------------------------------------------------------------ trace

def test_span_nesting_paths_parents_and_tags(tmp_path):
    path = tmp_path / "m.jsonl"
    with MetricsLogger(str(path), echo=False) as log:
        trace.configure(log)
        with trace.span("outer", iteration=3):
            with trace.span("inner"):
                pass
            with trace.span("sibling"):
                pass
    spans = {r["path"]: r for r in _records(path)
             if r["event"] == "span"}
    assert set(spans) == {"outer", "outer/inner", "outer/sibling"}
    assert spans["outer"]["parent"] is None
    assert spans["outer"]["depth"] == 0
    assert spans["outer"]["iteration"] == 3
    assert spans["outer/inner"]["parent"] == "outer"
    assert spans["outer/inner"]["depth"] == 1
    for r in spans.values():
        assert r["ok"] is True
        assert r["dur_s"] >= 0
        assert r["start"] > 0
    # children emit before their parent (exit order), and the parent
    # duration covers the children
    assert spans["outer"]["dur_s"] >= spans["outer/inner"]["dur_s"]


def test_span_exception_path_records_and_propagates(tmp_path):
    path = tmp_path / "m.jsonl"
    with MetricsLogger(str(path), echo=False) as log:
        trace.configure(log)
        with pytest.raises(ValueError, match="boom"):
            with trace.span("phase"):
                raise ValueError("boom")
    (rec,) = [r for r in _records(path) if r["event"] == "span"]
    assert rec["ok"] is False
    assert rec["error"] == "ValueError: boom"
    # the stack healed: nothing is left open
    assert trace.current_path() is None
    assert trace.open_spans() == {}


def test_span_without_sink_tracks_but_emits_nothing():
    with trace.span("a"):
        with trace.span("b"):
            assert trace.current_path() == "a/b"
            assert trace.open_spans() == {"MainThread": "a/b"}
    assert trace.current_path() is None
    assert trace.open_spans() == {}


def test_where_prefers_deepest_span_across_threads():
    started, release = threading.Event(), threading.Event()

    def worker():
        with trace.span("deep"):
            with trace.span("deeper"):
                started.set()
                release.wait(5.0)

    with trace.span("outer"):
        t = threading.Thread(target=worker, name="w1")
        t.start()
        try:
            assert started.wait(5.0)
            assert trace.where() == "deep/deeper"
        finally:
            release.set()
            t.join()
        # worker gone: the main thread's span is the answer again
        assert trace.where() == "outer"
    assert trace.where() is None


# --------------------------------------------------------- registry

def test_registry_get_or_create_and_label_identity():
    reg = Registry()
    c = reg.counter("serve_rung_total", rung="policy")
    c.inc()
    assert reg.counter("serve_rung_total", rung="policy") is c
    assert reg.counter("serve_rung_total", rung="search") is not c
    reg.gauge("margin").set(1.5)
    assert reg.snapshot()["gauges"]["margin"] == 1.5
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("serve_rung_total", rung="policy")


def test_histogram_bucket_edges_are_le_inclusive():
    reg = Registry()
    h = reg.histogram("lat", edges=(0.1, 1.0))
    for v in (0.05, 0.1, 0.2, 1.0, 1.5):
        h.observe(v)
    snap = h.snapshot()
    # le semantics: 0.05+0.1 ≤ 0.1; 0.2+1.0 land in le=1; 1.5 → +Inf
    assert snap["buckets"] == {"0.1": 2, "1": 4, "+Inf": 5}
    assert snap["count"] == 5
    assert abs(snap["sum"] - 2.85) < 1e-9
    with pytest.raises(ValueError, match="ascending"):
        reg.histogram("bad", edges=(1.0, 1.0))


def test_registry_snapshot_deterministic_across_insert_order():
    a, b = Registry(), Registry()
    a.counter("x").inc(2)
    a.histogram("h", edges=(1.0,)).observe(0.5)
    a.gauge("g", k="v").set(3.0)
    # same metrics, reversed creation order
    b.gauge("g", k="v").set(3.0)
    b.histogram("h", edges=(1.0,)).observe(0.5)
    b.counter("x").inc(2)
    sa, sb = a.snapshot(), b.snapshot()
    assert sa == sb
    assert json.dumps(sa) == json.dumps(sb)     # incl. key order
    assert json.dumps(a.snapshot()) == json.dumps(sa)   # stable


def test_render_text_prometheus_shape():
    reg = Registry()
    reg.counter("req_total", rung="policy").inc(3)
    reg.histogram("lat", edges=(0.5,)).observe(0.2)
    text = reg.render_text()
    assert "# TYPE req_total counter" in text
    assert 'req_total{rung="policy"} 3' in text
    assert "# TYPE lat histogram" in text
    assert 'lat_bucket{le="0.5"} 1' in text
    assert 'lat_bucket{le="+Inf"} 1' in text
    assert "lat_count 1" in text


def test_quantile_from_buckets():
    snap = {"count": 10, "sum": 1.0,
            "buckets": {"0.1": 5, "1": 9, "+Inf": 10}}
    assert quantile_from_buckets(snap, 0.5) == 0.1
    assert quantile_from_buckets(snap, 0.9) == 1.0
    assert quantile_from_buckets(snap, 1.0) == float("inf")
    assert quantile_from_buckets({"count": 0, "buckets": {}},
                                 0.5) is None


def test_timed_iterator_records_waits():
    reg = Registry()
    h = reg.histogram("wait", edges=(10.0,))
    assert list(obs_registry.timed(iter([1, 2, 3]), h)) == [1, 2, 3]
    assert h.snapshot()["count"] == 3


# --------------------------------------- MetricsLogger satellites

def test_metrics_logger_context_manager_closes(tmp_path):
    path = tmp_path / "m.jsonl"
    with MetricsLogger(str(path), echo=False) as log:
        log.log("e", x=1)
    assert log._f is None                       # closed by __exit__
    assert [r["x"] for r in _records(path)] == [1]


def test_metrics_logger_sanitizes_non_finite_floats(tmp_path):
    import numpy as np

    path = tmp_path / "m.jsonl"
    with MetricsLogger(str(path), echo=False) as log:
        log.log("e", loss=float("nan"), lr=0.1,
                nested={"v": float("inf"),
                        "l": [1.0, float("-inf")]},
                npnan=float(np.float64("nan")))
    raw = path.read_text()
    for token in ("NaN", "Infinity"):
        assert token not in raw
    # a STRICT parser (constants rejected) accepts every line

    def reject(c):
        raise ValueError(f"bare {c}")

    (rec,) = [json.loads(ln, parse_constant=reject)
              for ln in raw.splitlines()]
    assert rec["loss"] is None and rec["npnan"] is None
    assert rec["lr"] == 0.1
    assert rec["nested"] == {"v": None, "l": [1.0, None]}


def test_metrics_logger_write_is_file_only(tmp_path, capsys):
    path = tmp_path / "m.jsonl"
    with MetricsLogger(str(path), echo=True) as log:
        log.write("span", name="quiet")
        log.log("loud", x=1)
    out = capsys.readouterr().out
    assert "quiet" not in out and "loud" in out
    assert [r["event"] for r in _records(path)] == ["span", "loud"]


@pytest.mark.parametrize("with_lockcheck", [False, True],
                         ids=["plain", "lockcheck"])
def test_concurrent_emit_from_many_sessions(tmp_path, monkeypatch,
                                            with_lockcheck):
    """The serving pool's emit pattern — N session threads
    interleaving logger events with registry counter/histogram
    updates through ONE MetricsLogger — must lose nothing and tear
    nothing: every line strict-parses, counts are exact, and the
    histogram saw every observation (the thread-safety satellite of
    the serve PR; registry audit in obs/registry.py's docstring).
    The lockcheck variant rebuilds the logger with the instrumented
    lock (ROCALPHAGO_LOCKCHECK=1), turning the same hammering into a
    race/deadlock detector: any lock-order cycle or blocking-while-
    held raises out of a worker and fails the count asserts."""
    import threading

    from rocalphago_tpu.analysis import lockcheck
    from rocalphago_tpu.obs import registry

    if with_lockcheck:
        monkeypatch.setenv(lockcheck.LOCKCHECK_ENV, "1")
        lockcheck.reset()

    n_threads, n_events = 8, 150
    path = tmp_path / "m.jsonl"
    reg = registry.Registry()
    c = reg.counter("emit_total")
    h = reg.histogram("emit_seconds")
    with MetricsLogger(str(path), echo=False) as log:
        ready = threading.Barrier(n_threads)

        def emit(tid):
            ready.wait()
            for i in range(n_events):
                log.write("span", tid=tid, i=i)
                log.log("degradation", tid=tid, i=i, rung="policy")
                c.inc()
                h.observe(0.001 * (i % 7))

        threads = [threading.Thread(target=emit, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    lines = path.read_text().splitlines()
    assert len(lines) == n_threads * n_events * 2

    def reject(const):
        raise ValueError(f"bare {const}")

    recs = [json.loads(ln, parse_constant=reject) for ln in lines]
    per_thread = {}
    for r in recs:
        per_thread.setdefault(r["tid"], []).append(r)
    assert all(len(v) == n_events * 2 for v in per_thread.values())
    assert c.value == n_threads * n_events
    assert h.snapshot()["count"] == n_threads * n_events


# --------------------------------------------- jaxobs compile track

def test_compile_tracking_first_vs_second_call(tmp_path):
    import jax
    import jax.numpy as jnp

    reg = Registry()
    path = tmp_path / "m.jsonl"
    with MetricsLogger(str(path), echo=False) as log:
        trace.configure(log)
        f = jaxobs.track("toy_entry", jax.jit(lambda x: x * 2),
                         registry=reg)
        f(jnp.ones(3))                  # compile
        f(jnp.ones(3))                  # steady state
        f(jnp.ones(4))                  # new shape → recompile
    assert f.calls == 3 and f.compiles == 2
    assert f.first_call_s > 0
    assert f.steady_ema_s is not None   # the second call fed the EMA
    snap = reg.snapshot()
    assert snap["counters"]['jax_compiles_total{entry="toy_entry"}'] \
        == 2
    hist = snap["histograms"]['jax_compile_seconds{entry="toy_entry"}']
    assert hist["count"] == 2
    events = [r for r in _records(path) if r["event"] == "compile"]
    assert [e["recompile"] for e in events] == [False, True]
    assert all(e["entry"] == "toy_entry" for e in events)
    # attribute delegation: the wrapper still looks like the jit fn
    assert f._cache_size() == 2
    assert f.lower(jnp.ones(3)) is not None


# ------------------------------------------- watchdog span context

def test_watchdog_stall_names_the_open_span():
    events = []

    class Log:
        def log(self, event, **kw):
            events.append((event, kw))

    with Watchdog(0.05, metrics=Log(), poll_s=0.01, name="t",
                  exit=False):
        with trace.span("phase.outer"):
            with trace.span("inner"):
                time.sleep(0.2)          # no beats → stall
    stalls = [kw for ev, kw in events if ev == "stall"]
    assert stalls
    assert stalls[0]["span"] == "phase.outer/inner"


# -------------------------------------------------- obs_report path

def _load_obs_report():
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "obs_report.py")
    spec = importlib.util.spec_from_file_location("obs_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_obs_report_selftest_subprocess():
    """The CI guard the satellite asks for: the fixture render must
    succeed from a clean interpreter (stdlib-only import path)."""
    script = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "obs_report.py")
    proc = subprocess.run(
        [sys.executable, script, "--selftest"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "zero.selfplay" in proc.stdout


def test_obs_report_renders_a_run_dir(tmp_path, capsys):
    run = tmp_path / "run"
    run.mkdir()
    mod = _load_obs_report()
    (run / "metrics.jsonl").write_text(
        "\n".join(json.dumps(r) for r in mod.FIXTURE) + "\n"
        + "{torn line\n")                    # tolerant reader path
    assert mod.main([str(run)]) == 0
    out = capsys.readouterr().out
    assert "zero.selfplay" in out and "76.2%" in out
    assert "serve_rung_total" in out
    assert mod.main([str(run), "--json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["spans"]["zero.iteration"]["count"] == 1
    assert data["registry"]["gauges"]["device_mcts_deadline_margin_s"] \
        == 0.42


# ------------------------------------------- live registry over GTP

def test_gtp_stats_probe_returns_live_registry():
    """Acceptance: `rocalphago-stats` serves the live registry —
    ladder-rung counters + the genmove latency histogram — over the
    engine's pipe."""
    from rocalphago_tpu.interface.gtp import GTPEngine

    class FirstMovePlayer:
        def get_move(self, state):
            moves = state.get_legal_moves(include_eyes=False)
            return moves[0] if moves else None

    engine = GTPEngine(FirstMovePlayer())
    before = obs_registry.histogram(
        "gtp_genmove_seconds").snapshot()["count"]
    reply, _ = engine.handle("genmove b")
    assert reply.startswith("=")
    reply, _ = engine.handle("rocalphago-stats")
    assert reply.startswith("=")
    stats = json.loads(reply[1:].strip())
    reg = stats["registry"]
    assert reg["histograms"]["gtp_genmove_seconds"]["count"] \
        >= before + 1
    assert reg["counters"]['serve_rung_total{rung="search"}'] >= 1


# ------------------------------------------------ zero-trainer smoke

@pytest.mark.slow
def test_zero_smoke_emits_phase_spans_with_low_overhead(tmp_path):
    """Acceptance: a tier-1 zero run writes nested span records for
    every iteration phase (data/step/eval/checkpoint), logs its
    registry snapshot, and the instrumentation costs <2% of the
    iteration wall time."""
    from rocalphago_tpu.models import CNNPolicy, CNNValue
    from rocalphago_tpu.training.zero import run_training

    feats = ("board", "ones")
    pol = CNNPolicy(feats, board=5, layers=1, filters_per_layer=2)
    val = CNNValue(feats + ("color",), board=5, layers=1,
                   filters_per_layer=2)
    pj, vj = str(tmp_path / "p.json"), str(tmp_path / "v.json")
    pol.save_model(pj)
    val.save_model(vj)
    out = tmp_path / "out"
    run_training([pj, vj, str(out), "--game-batch", "2",
                  "--iterations", "1", "--move-limit", "8",
                  "--sims", "2", "--sim-chunk", "2",
                  "--save-every", "1", "--gate-games", "2"])

    recs = _records(out / "metrics.jsonl")
    spans = {r["path"]: r for r in recs if r.get("event") == "span"}
    for phase in ("zero.iteration",
                  "zero.iteration/zero.selfplay",    # data
                  "zero.iteration/zero.replay",      # step
                  "zero.iteration/zero.update",      # step
                  "zero.iteration/zero.gate",        # eval
                  "zero.iteration/zero.export",      # artifacts
                  "zero.iteration/zero.save"):       # checkpoint
        assert phase in spans, sorted(spans)
    assert spans["zero.iteration/zero.selfplay"]["parent"] \
        == "zero.iteration"
    assert all(r["ok"] for r in spans.values())

    # the end-of-run registry snapshot made it into the stream, and
    # the device search's counters saw the self-play simulations
    reg = [r for r in recs if r.get("event") == "registry"]
    assert reg, "no registry event in metrics.jsonl"
    snap = reg[-1]["snapshot"]
    assert snap["counters"].get("device_mcts_sims_total", 0) > 0
    # compile tracking named the replay/search programs
    compiled = {r["entry"] for r in recs
                if r.get("event") == "compile"}
    assert "zero.replay_segment" in compiled

    # overhead: per-span emission cost × spans per iteration must be
    # under 2% of the measured iteration wall time
    n_spans = sum(1 for r in recs if r.get("event") == "span")
    probe = MetricsLogger(str(tmp_path / "probe.jsonl"), echo=False)
    trace.configure(probe)
    reps = 500
    t0 = time.monotonic()
    for _ in range(reps):
        with trace.span("probe"):
            pass
    per_span = (time.monotonic() - t0) / reps
    trace.configure(None)
    probe.close()
    it_dur = spans["zero.iteration"]["dur_s"]
    assert n_spans * per_span < 0.02 * it_dur, (
        f"instrumentation overhead {n_spans} spans x {per_span:.2e}s "
        f"vs iteration {it_dur:.3f}s")
