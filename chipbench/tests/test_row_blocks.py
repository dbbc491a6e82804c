"""``moe_row_blocks_run_pct.train``: the reader on made counters and on
the program's own (CPU, toy size: nothing here is a device number),
and its entry in the manifest."""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
NAME = "moe_row_blocks_run_pct.train"
CELL = "laguna-s-2.1-ep16.train-seq8k-r2"


def reader():
    from chipbench import run

    return run.load_by_name("layers", NAME)


def ctx(before: dict, after: dict):
    return types.SimpleNamespace(
        counters_before={"counters": before},
        counters_after={"counters": after})


def test_a_program_without_the_counters_gives_nothing_to_read():
    """The parent of the PR that brought them: the metric is left
    off the line, and nothing raises."""
    old = {"moe_tokens_held_total": 10, "moe_tokens_routed_total": 99}
    assert reader().read(ctx(old, old), {"steps": 4}) is None
    assert reader().read(ctx({}, {}), {}) is None
    # one of the pair alone, or a window in which nothing was counted
    assert reader().read(
        ctx({}, {"moe_row_blocks_run_total": 3}), {}) is None
    same = {"moe_row_blocks_run_total": 3, "moe_row_blocks_total": 30}
    assert reader().read(ctx(same, same), {}) is None


@pytest.mark.parametrize("run,total,want", [
    (2 * 16 * 36, 20 * 16 * 36, 10.0),      # two blocks of twenty
    (3 * 16 * 36, 40 * 16 * 36, 7.5),
    (320, 320, 100.0)])                     # every pair lands here
def test_the_ratio_is_of_the_windows_growth(run, total, want):
    before = {"moe_row_blocks_run_total": 7, "moe_row_blocks_total": 70}
    after = {"moe_row_blocks_run_total": 7 + run,
             "moe_row_blocks_total": 70 + total}
    assert reader().read(ctx(before, after), {}) == pytest.approx(want)


def test_the_programs_own_counters_are_the_ones_read():
    """The names the reader asks for are the registry's, and what
    ``record_routing`` adds from a step's counts is what it reads."""
    from rocalphago_tpu.obs import registry
    from rocalphago_tpu.training import sl

    assert (registry.MOE_ROW_BLOCKS_RUN, registry.MOE_ROW_BLOCKS) == (
        "moe_row_blocks_run_total", "moe_row_blocks_total")
    before = registry.snapshot()
    step = {"moe_routed": 40, "moe_held": 8, "moe_dropped": 0,
            "moe_load_max": 3, "moe_row_blocks_run": 3,
            "moe_row_blocks": 24}
    sl.record_routing([step, step])
    assert reader().read(
        types.SimpleNamespace(counters_before=before,
                              counters_after=registry.snapshot()),
        {}) == pytest.approx(12.5)


def test_the_entry_in_the_manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    assert m["per_layer"][-1]["name"] == NAME       # added at the end
    assert m["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "networks",
        "moves": "train_positions_per_s", "workloads": [CELL]}
    for e in m["per_layer"]:
        assert os.path.isfile(os.path.join(
            ROOT, "chipbench", "layers", e["name"] + ".py")), e["name"]
        assert e["workloads"], e["name"]
