"""chip_smoke.py off the chip: it refuses, and its rules bite.

The smoke itself only means something on the TPU (the driver runs it
there). What CAN be pinned here: with no accelerator it exits nonzero
before anything compiles and prints no result line; and each rule that
guards against a hidden fallback — a ``retry`` event, a recompile in
iteration 2, a move served below the search rung, a non-``tpu``
platform in either leg — fails on synthetic artifacts shaped like the
legs' real ones. The full CPU rehearsal is ``slow``.
"""

import copy
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (stdlib-only at import: never jax)

DEVICE = {"event": "device", "platform": "tpu",
          "device_kind": "TPU v5 lite", "count": 1, "jax": "0.9.0",
          "jaxlib": "0.9.0", "libtpu": "0.0.34", "engine": "dense"}


def _run_smoke(cwd, *args, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)     # the script finds its own checkout
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True,
        timeout=timeout)


def test_refuses_without_a_chip_before_any_compile(tmp_path):
    out = tmp_path / "out"
    proc = _run_smoke(REPO, "--out", str(out))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout          # no result line
    assert "wanted 'tpu'" in proc.stderr
    # refused in the device probe: no spec was ever initialized (the
    # first thing that would compile), no leg ever started
    assert sorted(os.listdir(out)) == ["specs.log"]


def test_refuses_alone_in_a_directory(tmp_path):
    """The driver also runs the script with nothing else of the repo
    beside it: it must fail there, not find the package elsewhere."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = _run_smoke(str(tmp_path), "--rehearse-cpu")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "rocalphago_tpu" in proc.stderr      # the missing package


def test_parent_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "import rocalphago_tpu.gateway.client; "
            "import rocalphago_tpu.runtime.compilecache; "
            "sys.exit('jax' in sys.modules)" % REPO)
    assert subprocess.run([sys.executable, "-c", code],
                          timeout=60).returncode == 0


# ------------------------------------------------- leg A's rules

def train_events():
    it = {"event": "iteration", "policy_loss": 5.9, "value_loss": 0.0}
    return [
        dict(DEVICE),
        {"event": "compile", "entry": "zero.play", "dur_s": 40.0},
        {"event": "compile", "entry": "zero.replay_segment",
         "dur_s": 20.0},
        dict(it, iteration=0),
        {"event": "compile", "entry": "gate.match", "dur_s": 9.0},
        {"event": "gate", "iteration": 0, "promoted": False},
        dict(it, iteration=1),
        {"event": "gate", "iteration": 1, "promoted": False},
        {"event": "registry", "snapshot": {"histograms": {
            'jax_compile_seconds{entry="zero.play"}': {"sum": 40.0},
            'jax_compile_seconds{entry="gate.match"}': {"sum": 9.0},
            'zero_other_seconds': {"sum": 1e6}}}},
    ]


def test_train_rules_pass_on_a_clean_run():
    events = train_events()
    assert chip_smoke.check_train(events, "tpu")["engine"] == "dense"
    assert chip_smoke.compile_seconds(events) == 49.0


@pytest.mark.parametrize("mutate, why", [
    (lambda ev: ev.insert(3, {"event": "retry", "attempt": 1,
                              "error": "RESOURCE_EXHAUSTED"}), "retry"),
    (lambda ev: ev[0].update(platform="cpu", device_kind="cpu"),
     "platform 'cpu'"),
    (lambda ev: ev.insert(7, {"event": "compile", "entry": "zero.play",
                              "recompile": True}), "compiled again"),
    (lambda ev: ev[3].update(policy_loss=None), "policy_loss"),
    (lambda ev: ev.pop(6), "1 iteration events"),
    (lambda ev: ev.pop(0), "0 device events"),
])
def test_train_rules_fail(mutate, why):
    events = train_events()
    mutate(events)
    with pytest.raises(chip_smoke.SmokeFailure, match=why):
        chip_smoke.check_train(events, "tpu")


# ------------------------------------------------- leg B's rules

def serve_artifacts(n=3):
    replies = [{"type": "move", "move": "D4", "rung": "search",
                "elapsed_ms": 12.0} for _ in range(n)]
    health = {"status": "ok",
              "serve": {"warmed": True, "evaluator": {"failures": 0}},
              "gateway": {"requests": {"total": n + 1, "errors": 0,
                                       "genmoves": n, "unhandled": 0}}}
    prom = ("# TYPE serve_rung_total counter\n"
            f'serve_rung_total{{rung="search"}} {n}\n'
            "# TYPE serve_genmove_seconds histogram\n")
    return replies, health, prom, [dict(DEVICE)]


def test_serve_rules_pass_on_a_clean_run():
    assert chip_smoke.check_serve(*serve_artifacts(), "tpu")


def _degraded(art):
    # what a dead search looks like from outside: the ladder still
    # answers, from the raw policy net, and the process exits 0
    art[0][1]["rung"] = "policy"
    art[2] = (art[2].replace('rung="search"} 3', 'rung="search"} 2')
              + 'serve_rung_total{rung="policy"} 1\n'
              + 'serve_degradation_total{reason="error",'
                'rung="search"} 1\n')


@pytest.mark.parametrize("mutate, why", [
    (_degraded, "rung 'policy'"),
    (lambda a: a.__setitem__(2, a[2] + 'serve_degradation_total'
                             '{reason="illegal_from_player",'
                             'rung="search"} 1\n'), "ladder counters"),
    (lambda a: a[3].append({"event": "degradation", "rung": "search",
                            "reason": "error"}), "degradation events"),
    (lambda a: a[1]["serve"].update(warmed=False), "not warmed"),
    (lambda a: a[1]["serve"]["evaluator"].update(failures=2),
     "evaluator failures"),
    (lambda a: a[1]["gateway"]["requests"].update(unhandled=1),
     "gateway requests"),
    (lambda a: a[3][0].update(platform="cpu"), "platform 'cpu'"),
])
def test_serve_rules_fail(mutate, why):
    art = list(copy.deepcopy(serve_artifacts()))
    mutate(art)
    with pytest.raises(chip_smoke.SmokeFailure, match=why):
        chip_smoke.check_serve(*art, "tpu")


# ------------------------------------------------- the real thing

@pytest.mark.slow
def test_cpu_rehearsal_runs_both_legs(tmp_path):
    """Toy width, CPU backend, behind the explicit argument: proves
    the smoke's own control flow (both legs, the verify child, the
    drain), and that its result says ``cpu``."""
    proc = _run_smoke(REPO, "--rehearse-cpu", "--out",
                      str(tmp_path / "out"), timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    # count: the children inherit conftest's 8 virtual CPU devices
    count = result["device"].pop("count")
    assert count >= 1
    assert result == {"ok": True, "rehearsal": True,
                      "device": {"platform": "cpu", "kind": "cpu"}}
