"""``kda_inverse_ms_per_step.train``'s reader, checked without a chip:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_kda_inverse.py -q

the device time under ``seq.attn.kda.scan.inverse`` (the delta rule's
``(I + A)^−1``, forward and backward), which the whole scan's reader
holds too, on a made account of each cell that lists it; None on a
program without the scope. Nothing here is a device number. (The
kernel itself at toy size: ``tests/test_inverse_kernel.py``.)
"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

NAME = "kda_inverse_ms_per_step.train"
CELLS = {"ling-3.0-flash-ep64": "ling-3.0-flash-ep64.train-seq8k-r1",
         "olmo-hybrid-7b-pp8": "olmo-hybrid-7b-pp8.train-seq8k-r1"}
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
FWD, BWD = "jit(step)/jvp(N)/layer1/", "jit(step)/transpose(jvp(N))/"
SCAN = "seq.attn.kda/attn/seq.attn.kda.scan/"
INVERSE = SCAN + "seq.attn.kda.scan.inverse/"


def load(path):
    with open(path) as f:
        return json.load(f)


def made_context(config_name: str, by_scope: dict, steps: int):
    return types.SimpleNamespace(
        config=load(os.path.join(BENCH, "configs", config_name + ".json")),
        device=V5E, cell={"name": CELLS[config_name]},
        traffic={"rows": 1, "seq_len": 8192},
        counters_before={"counters": {}}, counters_after={"counters": {}},
        scope_account={
            "busy_s": sum(by_scope.values()), "window": {"steps": steps},
            "by_scope": by_scope,
            "scope_names": sorted({part for path in by_scope
                                   for part in path.split("/")})})


def read(name, ctx):
    from chipbench import run

    return run.load_by_name("layers", name).read(ctx, {})


@pytest.mark.parametrize("config_name", sorted(CELLS))
def test_the_inverses_reader_reads_inside_the_scan(config_name):
    """Forward and backward under the inverse's scope, per step, and
    the same seconds inside the whole scan's reading."""
    by_scope = {FWD + INVERSE + "unit_lower_inverse": 0.03,
                BWD + "layer1/" + INVERSE + "dot": 0.01,
                FWD + SCAN + "while/body/dot": 0.1}
    ctx = made_context(config_name, by_scope, steps=4)
    assert read(NAME, ctx) == pytest.approx(10.0)
    assert read("kda_scan_ms_per_step.train", ctx) == pytest.approx(35.0)


@pytest.mark.parametrize("config_name", sorted(CELLS))
def test_the_inverses_reader_is_silent_without_the_scope(config_name):
    """A program from before the inverse's scope has the scan alone:
    nothing to read, nothing raised."""
    bare = made_context(config_name, {FWD + SCAN + "dot": 0.1}, 4)
    assert read(NAME, bare) is None


def test_the_metric_lists_the_two_delta_cells():
    metric = {m["name"]: m for m in load(os.path.join(
        ROOT, "BENCHMARK.json"))["per_layer"]}[NAME]
    assert metric["workloads"] == list(CELLS.values())
    assert (metric["unit"], metric["better"], metric["source"],
            metric["layer"], metric["moves"]) \
        == ("ms", "lower", "device_trace", "kernels",
            "train_positions_per_s")
