"""The split of the attention layers' time (PR 34): run with

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_attn_split.py -q

The eight readers ``attn_proj_`` / ``attn_rope_`` / ``attn_gate_`` /
``attn_out_`` / ``attn_unsplit_`` / ``kda_conv_`` / ``kda_qknorm_`` /
``kda_decay_ms_per_step.train`` on by-scope accounts recorded on the
chip (``data/attn_split_scopes.json``: the ``seq.attn`` paths of the
three sequence cells' ``scopes.json``, traced runs of PR 34's tree on
one v5e — their times are data to add up, as ``scope_events.json``'s),
on made accounts, in the manifest, and through ``run.py`` on a fixture
manifest of their own at toy width on the CPU. Nothing here is a
device number.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from chipbench import run  # noqa: E402
from chipbench.seq_readers import scope_ms_per_step  # noqa: E402

FIXTURE = os.path.join(HERE, "fixtures",
                       "BENCHMARK.attn_split.fixture.json")
LAGUNA = "laguna-s-2.1-ep16.train-seq8k-r2"
XING = "xing4.0-29b-a4b-ep8.train-seq8k-r1"
LING = "ling-3.0-flash-ep64.train-seq8k-r1"
#: metric → the scope it reads and the cells that list it
READS = {
    "attn_proj_ms_per_step.train": ("seq.attn.proj",
                                    [LAGUNA, XING, LING]),
    "attn_rope_ms_per_step.train": ("seq.attn.rope",
                                    [LAGUNA, XING, LING]),
    "attn_gate_ms_per_step.train": ("seq.attn.gate", [LAGUNA, LING]),
    "attn_out_ms_per_step.train": ("seq.attn.out", [LAGUNA, XING, LING]),
    "attn_unsplit_ms_per_step.train": (None, [LAGUNA, XING, LING]),
    "kda_conv_ms_per_step.train": ("seq.attn.kda.proj.conv", [LING]),
    "kda_qknorm_ms_per_step.train": ("seq.attn.kda.proj.norm", [LING]),
    "kda_decay_ms_per_step.train": ("seq.attn.kda.proj.decay", [LING]),
}
SOFTMAX = [n for n in READS if n.startswith("attn_")]
KDA = [n for n in READS if n.startswith("kda_")]
LAYERS = ("attn_full_ms_per_step.train", "attn_window_ms_per_step.train",
          "attn_mla_ms_per_step.train")


def load(path: str):
    with open(path) as f:
        return json.load(f)


def ctx_of(by_scope: dict, names, steps=1):
    """What a reader sees of a run whose by-scope account is this."""
    return types.SimpleNamespace(scope_account={
        "by_scope": by_scope, "scope_names": sorted(names),
        "busy_s": sum(by_scope.values()), "window": {"steps": steps}})


def read(metric: str, ctx):
    return run.load_by_name("layers", metric).read(ctx, {})


@pytest.fixture(scope="module")
def recorded():
    return load(os.path.join(HERE, "data", "attn_split_scopes.json"))


def recorded_ctx(recorded, cell):
    r = recorded[cell]
    return ctx_of(r["by_scope"], r["by_scope"], r["steps"])


# ------------------------------------------------- recorded accounts

@pytest.mark.parametrize("cell", [LAGUNA, XING, LING])
def test_the_parts_add_up_to_the_layers(recorded, cell):
    """Four parts (three where no layer is gated), what lies directly
    under a layer's scope and the kernel: the time under the three
    layer scopes, to rounding."""
    ctx = recorded_ctx(recorded, cell)
    parts = {m: read(m, ctx) for m in SOFTMAX if cell in READS[m][1]}
    assert all(v is not None and v > 0 for v in parts.values()), parts
    kernel = scope_ms_per_step(ctx, "seq.attn.kernel")
    layers = [read(m, ctx) for m in LAYERS]
    assert kernel and any(layers)
    assert sum(parts.values()) + kernel == pytest.approx(
        sum(v for v in layers if v), rel=1e-9)
    if cell == XING:        # latent attention without a gate
        assert read("attn_gate_ms_per_step.train", ctx) is None


def test_the_delta_layers_parts_add_up_to_their_scope(recorded):
    ctx = recorded_ctx(recorded, LING)
    parts = {m: read(m, ctx) for m in KDA}
    assert all(v is not None and v > 0 for v in parts.values()), parts
    by_scope = recorded[LING]["by_scope"]
    names = [READS[m][0] for m in KDA]
    directly = 1e3 / recorded[LING]["steps"] * sum(
        t for s, t in by_scope.items() if "seq.attn.kda.proj" in s
        and not any(n in s for n in names))
    assert directly > 0         # the three products q, k, v
    assert sum(parts.values()) + directly == pytest.approx(
        scope_ms_per_step(ctx, "seq.attn.kda.proj"), rel=1e-9)
    # and the accepted readers read what they read: the parts nest
    assert scope_ms_per_step(ctx, "seq.attn.kda") > scope_ms_per_step(
        ctx, "seq.attn.kda.proj") > sum(parts.values())


@pytest.mark.parametrize("cell", [LAGUNA, XING, LING])
def test_a_program_without_the_scopes_gives_none(recorded, cell):
    """The parent's program: the same account less the new names."""
    new = [s for s, _ in READS.values() if s]
    r = recorded[cell]
    old = {s: t for s, t in r["by_scope"].items()
           if not any(n in s for n in new)}
    ctx = ctx_of(old, old, r["steps"])
    for metric in READS:
        assert read(metric, ctx) is None, metric
    assert any(read(m, ctx) for m in LAYERS)    # the old ones still read


@pytest.mark.parametrize("metric", sorted(READS))
def test_a_window_without_a_step_gives_none(recorded, metric):
    ctx = recorded_ctx(recorded, LING)
    ctx.scope_account["window"] = {}
    assert read(metric, ctx) is None


# ----------------------------------------------------- made accounts

def test_a_softmax_part_does_not_read_the_delta_layers():
    """``seq.attn.out`` is no substring of ``seq.attn.kda.out``, nor
    ``seq.attn.proj`` of ``seq.attn.kda.proj``."""
    kda = {"t/layer0/seq.attn.kda/attn/seq.attn.kda.out": 1.0,
           "t/layer0/seq.attn.kda/attn/seq.attn.kda.proj": 2.0,
           "t/layer0/seq.attn.kda/attn/seq.attn.kda.proj/"
           "seq.attn.kda.proj.conv": 4.0}
    ctx = ctx_of(kda, kda)
    for metric in SOFTMAX:
        assert read(metric, ctx) is None, metric
    assert read("kda_conv_ms_per_step.train", ctx) == 4000.0
    both = dict(kda, **{
        "t/layer5/seq.attn.mla/attn/seq.attn.out": 0.25,
        "t/layer5/seq.attn.mla/attn/seq.attn.proj/kv_a_norm": 0.5,
        "t/layer5/seq.attn.mla/input_norm": 0.125})
    ctx = ctx_of(both, both, steps=2)
    assert read("attn_out_ms_per_step.train", ctx) == 125.0
    assert read("attn_proj_ms_per_step.train", ctx) == 250.0
    assert read("attn_unsplit_ms_per_step.train", ctx) == 62.5


def test_the_readers_names_are_the_programs_constants():
    """A reader names its scope by a literal (the parent's checkout,
    which the driver lays these files over, has no such constant to
    import): each literal is a constant of ``obs/scopes.py``."""
    from rocalphago_tpu.obs import scopes

    for metric, (scope, _) in READS.items():
        with open(os.path.join(BENCH, "layers", metric + ".py")) as f:
            code = f.read().split('"""')[2]
        found = set(re.findall(r'"(seq\.[a-z.]+)"', code))
        assert found <= set(scopes.ALL), metric
        if scope:
            assert found == {scope}, metric
        # no traced window of a reader's own: the by-scope account's
        assert "start_trace" not in code and ".window(" not in code


# ------------------------------------------------------ the manifest

def test_the_eight_entries_are_in_the_manifest():
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"] for w in manifest["workloads"]}
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as f:
        networks = [line for line in f if line.startswith("| networks |")]
    for metric, (_, wanted) in READS.items():
        entry = by_name[metric]
        assert os.path.isfile(os.path.join(BENCH, "layers",
                                           metric + ".py"))
        assert entry == {
            "name": metric, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "networks",
            "moves": "train_positions_per_s", "workloads": wanted}
        assert set(wanted) <= cells
        # the layer as PERF.md §3 spells it, in a row that names it
        assert any(f"`{metric}`" in line for line in networks), metric


# ------------------------------------------------- the CPU rehearsal

@pytest.mark.parametrize("cell", ["toy-laguna.train", "toy-xing.train",
                                  "toy-ling.train"])
def test_the_readers_rehearse_on_cpu(cell):
    """Through ``run.py`` at toy width: every listed reader reads, and
    the split is whole (off the TPU the XLA form of attention runs
    directly under the layer's scope, so it is the remainder's)."""
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         FIXTURE, "--platform", "cpu", "--workload", cell, "--seed",
         "3400000019", "--seconds", "1", "--trace", "1"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT, text=True,
        capture_output=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stdout[-3000:]
    wanted = {m["name"] for m in load(FIXTURE)["per_layer"]
              if cell in m["workloads"]}
    assert set(line["metrics"]) == wanted
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(v > 0 for v in value.values()), value
    assert sum(value[m] for m in SOFTMAX if m in value) \
        == pytest.approx(sum(value[m] for m in LAYERS if m in value),
                         rel=1e-9)
    if cell == "toy-ling.train":
        assert sum(value[m] for m in KDA) < value[
            "attn_kda_ms_per_step.train"] - value[
            "kda_scan_ms_per_step.train"]
