"""The operator scripts under ``scripts/`` that read a run's output:
the profiler-capture reader and the zero-run curve summary."""

import json
import os

import pytest

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")


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

    monkeypatch.syspath_prepend(SCRIPTS)
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


def test_zero_curve_summary(tmp_path, monkeypatch):
    """scripts/zero_curve.py: curve extraction, config echo, and the
    flat-vs-learning verdict thresholds."""
    monkeypatch.syspath_prepend(SCRIPTS)
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
