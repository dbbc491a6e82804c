"""The headline benchmark's adaptive TPU sizing path, exercised on CPU.

bench.py failures are otherwise invisible until a run on real
hardware, so the risky code path — the mid-game probe that picks
batch/chunk — is covered off-chip: the ``_GRAFT_BENCH_FORCE_ADAPTIVE``
hook runs ``_measure`` in-process on the CPU backend with shrunken
workloads. The CLI itself (``python bench.py``) refuses a non-TPU
backend outright.
"""

import io
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_cli_refuses_without_a_chip():
    """``python bench.py`` off-TPU: nonzero exit, nothing on stdout —
    no fallback may print a CPU number under the device metric's
    name (how the driver's old captures came to be CPU figures)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "platform 'cpu'" in proc.stderr

import pytest


def test_harness_peak_table_refuses_unknown_device(monkeypatch):
    """benchmarks/_harness.py: the bf16 peak is keyed by device_kind;
    a TPU that is not in the table raises instead of borrowing the
    v5e's peak (an MFU against a guessed peak is worse than none),
    and off-TPU there is no peak at all."""
    monkeypatch.syspath_prepend(REPO)
    from benchmarks import _harness

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(_harness.jax, "devices", lambda: [Dev])
    assert _harness.bf16_peak_flops() == 197e12
    assert _harness.mfu(19.7e12) == pytest.approx(0.1)
    Dev.device_kind = "TPU v9 mystery"
    with pytest.raises(KeyError, match="TPU v9 mystery"):
        _harness.bf16_peak_flops()
    Dev.platform, Dev.device_kind = "cpu", "cpu"
    assert _harness.bf16_peak_flops() is None
    assert _harness.mfu(1e12) is None


def test_honest_metric_suffixes(monkeypatch):
    """The headline honesty rules in one table: a truncated or
    contended run reports under a suffixed metric name, and NO
    compromised measurement (truncated, compile-included, contended)
    emits a vs_baseline ratio."""
    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    m = bench.METRIC
    ok = bench._honest_metric(m, 10.0, 12.5, truncated=False,
                              includes_compile=False, contended=False)
    assert ok == (m, 0.8)
    name, vs = bench._honest_metric(m, 10.0, 12.5, truncated=True,
                                    includes_compile=False,
                                    contended=False)
    assert name == m + "_truncated" and vs is None
    name, vs = bench._honest_metric(m, 10.0, 12.5, truncated=False,
                                    includes_compile=False,
                                    contended=True)
    assert name == m + "_contended" and vs is None
    name, vs = bench._honest_metric(m, 10.0, 12.5, truncated=False,
                                    includes_compile=True,
                                    contended=False)
    # compile-polluted runs suffix too
    assert name == m + "_compiled" and vs is None
    name, vs = bench._honest_metric(m, 10.0, 12.5, truncated=True,
                                    includes_compile=True,
                                    contended=True)
    assert name == m + "_truncated_compiled_contended" and vs is None


def test_host_contention_reading(monkeypatch):
    """_host_contention returns a usable (load, flag, pids) triple on
    this platform and never raises — a missing /proc reading must not
    fail the bench."""
    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    load1, contended, heavy = bench._host_contention(sample_s=0.05)
    assert load1 is None or load1 >= 0.0
    assert isinstance(contended, bool)
    assert isinstance(heavy, list)
    assert os.getpid() not in heavy     # never flags itself


def test_warmup_compiles_exactly_the_timed_programs():
    """run.warmup must leave a subsequent full rep with ZERO segment
    compiles — the exact-program warmup discipline that keeps the
    headline row at includes_compile: false."""
    import jax

    from rocalphago_tpu.engine.jaxgo import GoConfig
    from rocalphago_tpu.models import CNNPolicy
    from rocalphago_tpu.search.selfplay import make_selfplay_chunked

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


@pytest.mark.slow
def test_adaptive_bench_measure_runs_and_reports(monkeypatch):
    monkeypatch.setenv("_GRAFT_BENCH_FORCE_ADAPTIVE", "1")
    monkeypatch.setenv("_GRAFT_BENCH_MAX_MOVES", "12")
    monkeypatch.setenv("_GRAFT_BENCH_SEED_PLIES", "12")
    monkeypatch.setenv("_GRAFT_BENCH_BATCHES", "16,8")
    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    # pin the contention sample: another process busy on the shared
    # CI box must not rename this run's metric under the test
    monkeypatch.setattr(bench, "_host_contention",
                        lambda sample_s=0.25: (0.1, False, []))
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench._measure()
    lines = [ln for ln in out.getvalue().splitlines() if ln.strip()]
    rec = json.loads(lines[-1])
    # 12-ply games are truncated: the record must carry its own
    # metric name — never the full-game headline's — and no ratio
    # against the full-game north star
    assert rec["metric"] == bench.METRIC + "_truncated"
    assert rec["load_1m"] == 0.1 and "contended" not in rec
    assert rec["unit"] == "games/min"
    assert rec["value"] > 0
    assert rec["batch"] in (16, 8)        # a probed candidate won
    assert 5 <= rec["chunk"] <= 100       # sized within the clamp
    assert rec["max_moves"] == 12
    assert rec["truncated"] is True
    assert rec["vs_baseline"] is None


@pytest.mark.slow
def test_fixed_override_ignored_off_tpu(monkeypatch):
    """_GRAFT_BENCH_FIXED must not leak into a CPU run: a TPU-sized
    batch on host would take hours."""
    monkeypatch.setenv("_GRAFT_BENCH_FIXED", "1024,10")
    monkeypatch.setenv("_GRAFT_BENCH_MAX_MOVES", "4")
    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    monkeypatch.setattr(bench, "_host_contention",
                        lambda sample_s=0.25: (0.1, False, []))
    out = io.StringIO()
    monkeypatch.setattr(sys, "stdout", out)
    bench._measure()
    rec = json.loads([ln for ln in out.getvalue().splitlines()
                      if ln.strip()][-1])
    assert rec["batch"] == 8          # CPU default, not the override
    assert rec["chunk"] == 40


def test_analyze_trace_reads_scopes_and_spans(tmp_path, monkeypatch,
                                              capsys):
    """scripts/analyze_trace.py over a real (CPU) profiler capture:
    the newest ``.xplane.pb`` is found, device time lands under the
    ``named_scope`` the program wrote, and the idle time under the
    ``obs.trace`` span the host was in — the chip benchmark's
    reducers, fronted for an operator's capture."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from rocalphago_tpu.obs import trace

    @jax.jit
    def work(x):
        with jax.named_scope("analyze.scope"):
            return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256))
    jax.block_until_ready(work(x))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with trace.span("analyze.phase"):
            jax.block_until_ready(work(x))
            _time.sleep(0.05)            # idle under the span
            jax.block_until_ready(work(x))
    finally:
        jax.profiler.stop_trace()

    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import analyze_trace

    path = analyze_trace.newest_trace(str(tmp_path))
    assert path.endswith(".xplane.pb")
    s = analyze_trace.summarize(path)
    assert 0 < s["busy_s"] <= s["window_s"]
    scoped = sum(t for k, t in s["by_scope"].items()
                 if "analyze.scope" in k)
    assert scoped > 0.5 * s["busy_s"], s["by_scope"]
    idle = dict(s["idle_by_span"])
    assert idle.get("rocalphago.analyze.phase", 0) >= 0.04, idle
    assert analyze_trace.main([str(tmp_path), "--top", "5"]) == 0
    out = capsys.readouterr().out
    assert "analyze.scope" in out and "rocalphago.analyze.phase" in out


def test_self_size_from_results(tmp_path, monkeypatch):
    """bench.py self-sizes from today's on-chip self-play records
    (and ignores other metrics, other platforms, other days)."""
    import time as _time

    monkeypatch.syspath_prepend(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    today = _time.strftime("%Y-%m-%d")
    log = tmp_path / "results.jsonl"
    log.write_text("\n".join([
        json.dumps({"metric": "selfplay_ply_program", "value": 80.0,
                    "batch": 64, "platform": "tpu",
                    "date": f"{today}T01:00:00"}),
        json.dumps({"metric": "selfplay_ply_program", "value": 120.0,
                    "batch": 256, "platform": "tpu",
                    "date": f"{today}T02:00:00"}),
        json.dumps({"metric": "selfplay_ply_program", "value": 999.0,
                    "batch": 16, "platform": "cpu",
                    "date": f"{today}T03:00:00"}),
        json.dumps({"metric": "selfplay_ply_program", "value": 999.0,
                    "batch": 16, "platform": "tpu",
                    "date": "2020-01-01T00:00:00"}),
        json.dumps({"metric": "engine_steps", "value": 9999.0,
                    "batch": 1024, "platform": "tpu",
                    "date": f"{today}T04:00:00"}),
        "{broken",
    ]) + "\n")
    monkeypatch.setenv("ROCALPHAGO_BENCH_LOG", str(log))
    got = bench._self_size_from_results()
    # best same-day TPU record: 120 plies/s at batch 256 ->
    # 2.13 s/ply -> chunk = int(20 / 2.13) = 9
    assert got == (256, 9)

    monkeypatch.setenv("ROCALPHAGO_BENCH_LOG", str(tmp_path / "no"))
    assert bench._self_size_from_results() is None


def test_bench_report_tables(tmp_path, monkeypatch):
    """scripts/bench_report.py: latest-record-per-config selection,
    date/platform filters."""
    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import bench_report

    log = tmp_path / "r.jsonl"
    log.write_text("\n".join([
        json.dumps({"metric": "m", "value": 1.0, "unit": "u",
                    "batch": 64, "platform": "tpu",
                    "date": "2026-07-31T01:00:00"}),
        json.dumps({"metric": "m", "value": 2.0, "unit": "u",
                    "batch": 64, "platform": "tpu",
                    "date": "2026-07-31T02:00:00"}),   # newer wins
        json.dumps({"metric": "m", "value": 9.0, "unit": "u",
                    "batch": 256, "platform": "tpu", "mfu": 0.1234,
                    "date": "2026-07-31T01:30:00"}),   # distinct cfg
        json.dumps({"metric": "m", "value": 3.0, "unit": "u",
                    "batch": 64, "platform": "tpu",
                    "pipeline_depth": 1, "host_gap_frac": 0.0421,
                    "date": "2026-07-31T02:00:00"}),   # dispatch A/B side
        json.dumps({"metric": "m", "value": 5.0, "unit": "u",
                    "batch": 64, "platform": "cpu",
                    "date": "2026-07-31T03:00:00"}),   # other platform
        json.dumps({"metric": "m", "value": 7.0, "unit": "u",
                    "batch": 64, "platform": "tpu",
                    "date": "2026-07-30T01:00:00"}),   # other day
        json.dumps({"metric": "encode_ab", "value": 100.0, "unit": "u",
                    "batch": 16, "platform": "tpu", "gating": "shared",
                    "phase1": 4, "chase_impl": "xla",
                    "us_per_pos": 123.4,
                    "date": "2026-07-31T01:00:00"}),   # encode A/B side
        json.dumps({"metric": "encode_ab", "value": 50.0, "unit": "u",
                    "batch": 16, "platform": "tpu", "gating": "split",
                    "phase1": 4, "chase_impl": "xla",
                    "us_per_pos": 246.8,
                    "date": "2026-07-31T01:05:00"}),   # distinct gating
        json.dumps({"metric": "serve_moves_per_s", "value": 88.0,
                    "unit": "moves/s", "platform": "tpu",
                    "sessions": 8, "mode": "batched",
                    "date": "2026-07-31T01:00:00"}),   # serving sweep
        json.dumps({"metric": "serve_moves_per_s", "value": 120.0,
                    "unit": "moves/s", "platform": "tpu",
                    "sessions": 64, "mode": "batched",
                    "date": "2026-07-31T01:00:00"}),   # distinct count
        json.dumps({"metric": "serve_moves_per_s", "value": 44.0,
                    "unit": "moves/s", "platform": "tpu",
                    "sessions": 16, "mode": "batched", "cache": "off",
                    "hit_rate": None,
                    "date": "2026-07-31T01:00:00"}),   # cache A/B off
        json.dumps({"metric": "serve_moves_per_s", "value": 175.0,
                    "unit": "moves/s", "platform": "tpu",
                    "sessions": 16, "mode": "batched", "cache": "on",
                    "hit_rate": 0.6491,
                    "date": "2026-07-31T01:00:00"}),   # cache A/B on
        json.dumps({"metric": "gateway_moves_per_s", "value": 95.0,
                    "unit": "moves/s", "platform": "tpu",
                    "conns": 4, "mode": "gateway", "p50_s": 0.01,
                    "date": "2026-07-31T01:00:00"}),   # gateway sweep
        json.dumps({"metric": "gateway_moves_per_s", "value": 90.0,
                    "unit": "moves/s", "platform": "tpu",
                    "conns": 16, "mode": "gateway", "p50_s": 0.02,
                    "date": "2026-07-31T01:00:00"}),   # distinct conns
        json.dumps({"metric": "zero_ingest_games_per_min", "value": 340.0,
                    "unit": "games/min", "platform": "tpu",
                    "actors": 2, "mesh_shape": "8x1",
                    "learner_idle_frac": 0.0714,
                    "date": "2026-07-31T01:00:00"}),   # actor sweep
        json.dumps({"metric": "zero_ingest_games_per_min", "value": 345.0,
                    "unit": "games/min", "platform": "tpu",
                    "actors": 4, "mesh_shape": "8x1",
                    "learner_idle_frac": 0.0574,
                    "date": "2026-07-31T01:00:00"}),   # distinct actors
        json.dumps({"metric": "multisize_moves_per_s", "value": 52.3,
                    "unit": "moves/s", "platform": "tpu",
                    "board": 13, "mode": "one_pool", "sessions": 4,
                    "date": "2026-07-31T01:00:00"}),   # size ladder row
        json.dumps({"metric": "selfplay_cap_games_per_min",
                    "value": 229.3, "unit": "games/min",
                    "platform": "tpu", "batch": 8, "board": 9,
                    "cap_p": 1.0, "fullsearch_frac": 1.0,
                    "date": "2026-07-31T01:00:00"}),   # cap A/B base
        json.dumps({"metric": "selfplay_cap_games_per_min",
                    "value": 582.5, "unit": "games/min",
                    "platform": "tpu", "batch": 8, "board": 9,
                    "cap_p": 0.25, "fullsearch_frac": 0.167,
                    "date": "2026-07-31T01:00:00"}),   # distinct cap_p
        json.dumps({"metric": "zero_ingest_games_per_min",
                    "value": 310.0, "unit": "games/min",
                    "platform": "tpu", "actors": 2,
                    "mesh_shape": "8x1", "learner_idle_frac": 0.09,
                    "kill_at": 2, "mttr_s": 2.442, "restarts": 1,
                    "date": "2026-07-31T01:00:00"}),   # recovery A/B
    ]) + "\n")
    recs = bench_report.load_records(str(log), "2026-07-31", "tpu")
    # pipeline_depth (and the encode gating/phase1/impl axes, the
    # serving sessions×mode axes, the actor/learner actors×mesh axes,
    # the cap-randomization cap_p axis and the recovery kill_at axis)
    # are part of the config key: each A/B side is a distinct row,
    # not a newer duplicate of its sibling
    assert sorted((r["value"], r.get("batch")) for r in recs) \
        == [(2.0, 64), (3.0, 64), (9.0, 256), (44.0, None),
            (50.0, 16), (52.3, None), (88.0, None), (90.0, None),
            (95.0, None), (100.0, 16), (120.0, None), (175.0, None),
            (229.3, 8), (310.0, None), (340.0, None), (345.0, None),
            (582.5, 8)]
    table = bench_report.render_table(recs)
    # board / MFU / host-gap / µs-per-pos / sessions / actors /
    # learner-idle columns: '—' when a record has none, the value
    # when it does
    assert ("| m | 2.0 | u | — | — | — | — | — | — | — | — | — | — | "
            "— | — | batch=64 |" in table)
    assert ("| m | 9.0 | u | — | 12.3% | — | — | — | — | — | — | — "
            "| — | — | — | batch=256 |" in table)
    assert ("| m | 3.0 | u | — | — | 4.21% | — | — | — | — | — | — "
            "| — | — | — | batch=64, pipeline_depth=1 |" in table)
    assert ("| encode_ab | 100.0 | u | — | — | — | 123.4 | — | — | — "
            "| — | — | — | — | — "
            "| batch=16, chase_impl=xla, gating=shared, phase1=4 |"
            in table)
    # the serving sweep keys by session count: both rows survive and
    # the sessions column carries the count (moves/sec-vs-sessions)
    assert ("| serve_moves_per_s | 88.0 | moves/s | — | — | — | — | 8 "
            "| — | — | — | — | — | — | — | mode=batched |" in table)
    assert ("| serve_moves_per_s | 120.0 | moves/s | — | — | — | — | "
            "64 | — | — | — | — | — | — | — | mode=batched |" in table)
    # the cache A/B (bench_serve.py --cache-ab) keys by the cache
    # on/off axis: both arms survive at ONE session count and the hit
    # rate column renders the on-arm's measured rate
    assert ("| serve_moves_per_s | 44.0 | moves/s | — | — | — | — | "
            "16 | — | — | — | — | — | — | — | cache=off, mode=batched |"
            in table)
    assert ("| serve_moves_per_s | 175.0 | moves/s | — | — | — | — | "
            "16 | — | — | — | — | — | — | 64.9% | cache=on, "
            "mode=batched |" in table)
    # the gateway sweep keys by connection count: both rows survive
    # and the conns column carries the count (bench_gateway.py's
    # wire-tax table; p50 stays in config)
    assert ("| gateway_moves_per_s | 95.0 | moves/s | — | — | — | — "
            "| — | 4 | — | — | — | — | — | — | mode=gateway, p50_s=0.01 |"
            in table)
    assert ("| gateway_moves_per_s | 90.0 | moves/s | — | — | — | — "
            "| — | 16 | — | — | — | — | — | — | mode=gateway, p50_s=0.02 |"
            in table)
    # the actor/learner sweep keys by actor count: both rows survive,
    # the actors column carries the count and learner idle renders as
    # a percentage (bench_zero_scale.py's scaling table)
    assert ("| zero_ingest_games_per_min | 340.0 | games/min | — | — "
            "| — | — | — | — | 2 | 7.1% | — | — | — | — | mesh_shape=8x1 |"
            in table)
    assert ("| zero_ingest_games_per_min | 345.0 | games/min | — | — "
            "| — | — | — | — | 4 | 5.7% | — | — | — | — | mesh_shape=8x1 |"
            in table)
    # the recovery A/B keys by kill_at: the killed-actor row survives
    # next to its fault-free sibling and the MTTR column carries the
    # kill-to-first-post-restart-game time (--kill-actor-at)
    assert ("| zero_ingest_games_per_min | 310.0 | games/min | — | — "
            "| — | — | — | — | 2 | 9.0% | — | — | 2.442s | — | "
            "kill_at=2, mesh_shape=8x1, restarts=1 |" in table)
    # the multi-size sweep keys by board: the board column carries it
    # (bench_multisize.py's size-scaling table)
    assert ("| multisize_moves_per_s | 52.3 | moves/s | 13 | — | — | "
            "— | 4 | — | — | — | — | — | — | — | mode=one_pool |" in table)
    # the cap-randomization A/B keys by cap_p: both rows survive, the
    # cap p / full frac columns carry them (bench_selfplay --cap-ab)
    assert ("| selfplay_cap_games_per_min | 229.3 | games/min | 9 | — "
            "| — | — | — | — | — | — | 1 | 100.0% | — | — | batch=8 |"
            in table)
    assert ("| selfplay_cap_games_per_min | 582.5 | games/min | 9 | — "
            "| — | — | — | — | — | — | 0.25 | 16.7% | — | — | batch=8 |"
            in table)


def test_zero_curve_summary(tmp_path, monkeypatch):
    """scripts/zero_curve.py: curve extraction, config echo, and the
    flat-vs-learning verdict thresholds."""
    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    import zero_curve

    run = tmp_path / "run"
    run.mkdir()
    (run / "metadata.json").write_text(json.dumps(
        {"config": {"game_batch": 4, "sims": 8}}))
    rows = [{"event": "iteration", "iteration": i,
             "value_acc": 0.5 + 0.04 * i, "value_mse": 1.0 - 0.05 * i,
             "policy_loss": 100.0 - i} for i in range(10)]
    (run / "metrics.jsonl").write_text(
        "\n".join(json.dumps(r) for r in rows) + "\n")

    out = tmp_path / "s.json"
    zero_curve.main([str(run), "--window", "3", "--out", str(out)])
    s = json.loads(out.read_text())
    assert s["iterations"] == 10 and s["games"] == 40
    acc = s["curves"]["value_acc"]
    assert acc["first"] == 0.5 and acc["last"] == pytest.approx(0.86)
    assert s["value_head_verdict"] == "learning"

    # flat curve -> flat verdict
    flat = [dict(r, value_acc=0.5) for r in rows]
    (run / "metrics.jsonl").write_text(
        "\n".join(json.dumps(r) for r in flat) + "\n")
    zero_curve.main([str(run), "--out", str(out)])
    assert json.loads(out.read_text())["value_head_verdict"] == "flat"

    # rising but still ~chance (tail below the 0.55 floor) is NOT
    # "learning" — the verdict needs level, not just slope
    low = [dict(r, value_acc=0.30 + 0.02 * r["iteration"])
           for r in rows]
    (run / "metrics.jsonl").write_text(
        "\n".join(json.dumps(r) for r in low) + "\n")
    zero_curve.main([str(run), "--out", str(out)])
    assert json.loads(out.read_text())["value_head_verdict"] == "flat"
