"""The benchmark's own tests: run with

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

They rehearse every driver on the CPU at toy width through the same
code the chip runs (a FIXTURE manifest: its cells are a toy
configuration, three toy traffic files and one extra layer reader —
files and entries only, which is what adding a cell takes), and
check the arithmetic that needs no chip: the FLOP count, the trace
reduction, the manifest. Nothing here is a device number.
"""

from __future__ import annotations

import json
import os
import random
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

FIXTURE = os.path.join(HERE, "fixtures", "BENCHMARK.fixture.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


def run_cell(manifest, workload, trace, platform="cpu", seconds="1.5"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               ROCALPHAGO_ENGINE_DENSE="1")   # the chip's formulation
    argv = [sys.executable, os.path.join(BENCH, "run.py"),
            "--workload", workload, "--seed", "3000000019",
            "--seconds", seconds, "--trace", str(trace)]
    if manifest:
        argv += ["--manifest", manifest, "--platform", platform]
    return subprocess.run(argv, env=env, cwd=ROOT, text=True,
                          capture_output=True, timeout=900)


# ------------------------------------------------------- the manifest

@pytest.mark.parametrize("path", [os.path.join(ROOT, "BENCHMARK.json"),
                                  FIXTURE])
def test_manifest_names_units_and_files(path):
    m = load(path)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["chipbench"]
    assert 1 <= m["run_seconds"] <= 51
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    configs = {c["name"]: c for c in m["configs"]}
    cells = {w["name"]: w for w in m["workloads"]}
    for group in (m["configs"], m["workloads"], m["end_to_end"],
                  m["per_layer"]):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/")
        cfg = load(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert any(w["config"] == c["name"] for w in m["workloads"])
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1 and len(w["why"]) <= 200
        assert w["config"] in configs and NAME.match(w["traffic"])
        mix = load(os.path.join(BENCH, "traffic",
                                w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(
            BENCH, "drivers", mix["driver"] + ".py"))
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    for e in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(e["unit"]), e
        assert e["better"] in ("lower", "higher")
        assert e["source"] in SOURCES
        assert set(e.get("workloads", cells)) <= set(cells)
    for e in m["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0 < e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
    for e in m["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.isfile(os.path.join(
            BENCH, "layers", e["name"] + ".py")), e["name"]
        moved = e2e[e["moves"]]
        # the moved metric is reported in every cell this one is
        assert set(e.get("workloads", cells)) <= set(
            moved.get("workloads", cells)), e["name"]
    for name in cells:          # each cell: set-up, one more, a layer
        mine = [e for e in m["end_to_end"]
                if name in e.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(name in e.get("workloads", cells)
                   for e in m["per_layer"])


def test_run_py_names_no_cell_config_or_metric():
    m = load(os.path.join(ROOT, "BENCHMARK.json"))
    with open(os.path.join(BENCH, "run.py")) as f:
        source = f.read()
    names = [g["name"] for key in ("configs", "workloads", "per_layer")
             for g in m[key]]
    names += [w["traffic"] for w in m["workloads"]]
    names += [e["name"] for e in m["end_to_end"]
              if e["name"] != "setup_s"]   # the contract's own name
    assert not [n for n in names if n in source]


# --------------------------------------------------------- arithmetic

def test_forward_flops_against_a_hand_count_for_13x192():
    from chipbench.flops import forward_flops, train_step_flops

    policy = load(os.path.join(BENCH, "configs",
                               "sl13x192.json"))["policy"]
    # per point: 5x5 from 48 planes, eleven 3x3 at 192, a 1x1 head;
    # 361 points, two operations per multiply-add
    per_point = 25 * 48 * 192 + 11 * 9 * 192 * 192 + 192
    assert per_point == 3_880_128
    assert forward_flops(policy, 19) == 2 * 361 * per_point \
        == 2_801_452_416
    assert train_step_flops(policy, 19, 1024) == \
        3 * 1024 * 2_801_452_416
    wide = load(os.path.join(BENCH, "configs",
                             "sl13x256.json"))["policy"]
    assert forward_flops(wide, 19) == 2 * 361 * (
        25 * 48 * 256 + 11 * 9 * 256 * 256 + 256)


def test_peaks_table_refuses_an_unknown_tpu():
    from chipbench.peaks import peak

    assert peak({"platform": "tpu", "kind": "TPU v5 lite"}) == 197e12
    assert peak({"platform": "cpu", "kind": "cpu"}) is None
    with pytest.raises(KeyError):
        peak({"platform": "tpu", "kind": "TPU v99"})


def test_memory_peak_adds_the_reserved_program_temporaries(monkeypatch):
    """The two accounts as the v5e's runtime printed them after six
    steps at batch 2,048 (PR 23's probe): live buffers, and the loaded
    program's reservation; the fullest chip is the one reported."""
    import jax

    from chipbench import run

    class Chip:
        def __init__(self, stats):
            self.stats = stats

        def memory_stats(self):
            return self.stats

    chips = [Chip({"peak_bytes_in_use": 300_000_000,
                   "peak_bytes_reserved": 0}),
             Chip({"peak_bytes_in_use": 191_229_440,
                   "peak_bytes_reserved": 5_432_590_336}),
             Chip(None)]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)
    assert run.memory_peak() == {
        "held": 5_623_819_776, "peak_bytes_in_use": 191_229_440,
        "peak_bytes_reserved": 5_432_590_336}


def test_trace_reduction_on_a_hand_made_trace():
    from chipbench import trace_reduce as tr

    ms = 1e6
    events = {
        "device": {"/device:TPU:0": {"XLA Ops": [
            # a while op 10..60 ms containing two bodies, then a
            # lone fusion 80..90 ms; the window is 0..100 ms
            ["while.1", 10 * ms, 50 * ms],
            ["fusion.2", 10 * ms, 20 * ms],
            ["fusion.2", 35 * ms, 20 * ms],
            ["fusion.3", 80 * ms, 10 * ms],
        ]}},
        "spans": [
            ["chipbench.window", 0.0, 100 * ms],
            ["chipbench.dispatch", 0.0, 12 * ms],
            ["chipbench.block", 58 * ms, 30 * ms],
        ],
    }
    out = tr.reduce(events)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.060)
    assert tr.idle_pct(out) == pytest.approx(40.0)
    ops = dict(out["device_ops"])
    assert ops["fusion.2"] == pytest.approx(0.040)
    assert ops["while.1"] == pytest.approx(0.010)    # self time only
    assert ops["fusion.3"] == pytest.approx(0.010)
    assert out["idle_gaps"][0] == ["chipbench.block",
                                   pytest.approx(0.020)]
    assert sorted(g[1] for g in out["idle_gaps"]) == pytest.approx(
        [0.010, 0.010, 0.020])
    assert dict(out["idle_by_span"])["chipbench.dispatch"] == \
        pytest.approx(0.010)
    # no window span: the other spans' extent bounds the window
    events["spans"] = events["spans"][1:]
    assert tr.reduce(events)["window_s"] == pytest.approx(0.088)
    # no device operation at all is an error, not a zero
    with pytest.raises(ValueError):
        tr.reduce({"device": {}, "spans": []})


def test_trace_reduction_on_the_recorded_sample():
    """``data/trace_sample.json``: the head of a trace this benchmark
    recorded on the v5e (``trace_reduce.sample``); the reduction has
    to read the layout the chip really writes."""
    from chipbench import trace_reduce as tr

    events = load(os.path.join(HERE, "data", "trace_sample.json"))
    assert any(p.startswith("/device:TPU") for p in events["device"])
    out = tr.reduce(events)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["device_ops"] and out["device_ops"][0][1] > 0
    assert all(isinstance(n, str) for n, _ in out["idle_gaps"])


def test_profile_file_round_trip(tmp_path):
    """``load_events`` on a trace taken here: the benchmark's spans
    come back, and on the CPU XLA's threads stand in for the device."""
    import jax
    import jax.numpy as jnp

    from chipbench import trace_reduce as tr

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("chipbench.block"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = tr.load_events(tr.find_xplane(str(tmp_path)))
    assert {s[0] for s in events["spans"]} == {"chipbench.window",
                                              "chipbench.block"}
    out = tr.reduce(events)
    assert 0 < out["busy_s"] <= out["window_s"]


# ------------------------------------------------------ load generator

def test_vertices_and_dealt_prefixes_are_legal():
    from chipbench import loadgen
    from rocalphago_tpu.engine import pygo

    for move in [(0, 0), (8, 3), (18, 18), None]:
        assert loadgen.from_vertex(loadgen.to_vertex(move)) == move
    assert loadgen.to_vertex((8, 0)) == "J1"        # no column I

    def new_state():
        return pygo.GameState(size=9, komi=7.5)

    moves = loadgen.deal_prefix(new_state, 30, random.Random(5))
    assert moves == loadgen.deal_prefix(new_state, 30, random.Random(5))
    state = new_state()
    for move in moves:
        assert state.is_legal(move)
        state.do_move(move)
    assert len(moves) == 30


def test_inputs_follow_the_seed():
    import numpy as np

    from chipbench.nets import random_planes

    big = 3_000_000_019             # more than 32 signed bits hold
    a = np.asarray(random_planes(big, 2, 9, 4))
    assert (a == np.asarray(random_planes(big, 2, 9, 4))).all()
    assert (a != np.asarray(random_planes(big + 1, 2, 9, 4))).any()
    assert a.dtype == np.uint8 and set(np.unique(a)) <= {0, 1}


# ---------------------------------------------------------- rehearsals

@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", ["toy9x16.selfplay", "toy9x16.train",
                                  "toy9x16.serve"])
def test_driver_rehearsal_on_cpu(cell, trace):
    """Every driver through run.py, as the chip runs it, on fixture
    cells that are files and manifest entries only."""
    done = run_cell(FIXTURE, cell, trace)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, done.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"      # and says so
    m = load(FIXTURE)
    key = "per_layer" if trace else "end_to_end"
    want = {e["name"]: e["unit"] for e in m[key]
            if cell in e.get("workloads", [cell])}
    if trace:
        # a utilization against a peak is not reported off the TPU
        want.pop("train_mfu_pct", None)
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] >= line["device"]["busy_s"]
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert len(line["breakdown"]["idle_gaps"]) <= 10
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(isinstance(v["value"], (int, float))
               for v in line["metrics"].values())


def test_a_cell_of_the_manifest_refuses_anything_but_a_tpu():
    m = load(os.path.join(ROOT, "BENCHMARK.json"))
    done = run_cell(None, m["workloads"][0]["name"], 0)
    assert done.returncode != 0
    assert not [ln for ln in done.stdout.splitlines()
                if ln.startswith("{") and '"correct"' in ln]
    # and there is no switch that lets BENCHMARK.json run elsewhere
    argv = [sys.executable, os.path.join(BENCH, "run.py"),
            "--workload", m["workloads"][0]["name"], "--seed", "1",
            "--seconds", "1", "--trace", "0", "--platform", "cpu"]
    assert subprocess.run(argv, cwd=ROOT, capture_output=True,
                          timeout=120).returncode != 0
