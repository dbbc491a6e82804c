"""The ``lfm2-8b-a1b-ep4`` cell's benchmark files, checked without a
chip:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_lfm2.py -q

the configuration against the catalog's keys, its byte count against
a hand count and against its parameter tree, ``flops_lfm2.py``
against a hand count, the seven new readers on made by-scope accounts,
and a fixture cell (toy widths, the
``train_seq_lfm2`` driver, every reader the cell lists) rehearsed on
the CPU through ``run.py`` and through the lowered control. Nothing
here is a device number. (The program against the reference at toy
size: ``tests/test_seqpolicy_lfm2.py``.)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

FIXTURE = os.path.join(HERE, "fixtures", "BENCHMARK.lfm2.fixture.json")
#: the published model's row, as the architecture catalog gives it
CATALOG = os.path.join(HERE, "fixtures", "lfm2-8b-a1b.catalog.json")
CONFIG = "lfm2-8b-a1b-ep4"
CELL = CONFIG + ".train-seq8k-r2"
REDUCED = {"num_hidden_layers": (24, 9), "num_experts": (32, 8),
           "vocab_size": (65536, 16384)}
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
#: the cell's own readers, and the accepted ones it is appended to
OWN = {"train_mfu_pct.lfm2", "shortconv_ms_per_step.train",
       "shortconv_mix_roofline_pct.train",
       "splash_attn_roofline_pct.lfm2",
       "moe_held_tokens_per_expert.lfm2", "ragged_dot_roofline_pct.lfm2",
       "moe_load_max_over_mean.lfm2"}
SHARED = {"device_idle_pct.train", "fwd_ms_per_step.train",
          "bwd_ms_per_step.train", "update_ms_per_step.train",
          "augment_ms_per_step.train", "unscoped_device_pct.train",
          "attn_full_ms_per_step.train", "splash_fwd_ms_per_step.train",
          "attn_proj_ms_per_step.train", "attn_rope_ms_per_step.train",
          "attn_out_ms_per_step.train", "attn_unsplit_ms_per_step.train",
          "experts_ms_per_step.train", "router_ms_per_step.train",
          "ragged_dot_ms_per_step.train", "moe_row_blocks_run_pct.train"}
#: config-bound readers of the other cells this one must not list
NOT_MINE = {"splash_attn_roofline_pct.train", "train_mfu_pct.seq",
            "train_mfu_pct.xing", "train_mfu_pct.ling",
            "train_mfu_pct.olmo", "attn_gate_ms_per_step.train",
            "moe_held_tokens_per_expert.train",
            "ragged_dot_roofline_pct.train", "attn_kda_ms_per_step.train",
            "moe_load_max_over_mean.train"}


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(os.path.join(BENCH, "configs", CONFIG + ".json"))


def test_the_configuration_keeps_every_published_width(config):
    assert config["reduced"] == list(REDUCED)
    assert config["published"] == {k: v[0] for k, v in REDUCED.items()}
    assert {k: config[k] for k in REDUCED} \
        == {k: v[1] for k, v in REDUCED.items()}
    widths = {"hidden_size": 2048, "intermediate_size": 7168,
              "moe_intermediate_size": 1792, "num_attention_heads": 32,
              "num_key_value_heads": 8, "num_experts_per_tok": 4,
              "conv_L_cache": 3}
    assert {k: config[k] for k in widths} == widths
    assert config["model_type"] == "lfm2_moe"
    assert config["expert_offset"] == 0
    # the cut's floors: the held stage is published layers 0-8 — the
    # two leading dense layers, then seven expert layers with attention
    # at 2 and 6 — 8 experts, a quarter of the vocabulary
    from chipbench.flops_lfm2 import _kinds

    assert _kinds(config) == ["conv"] * 2 + (["full_attention"]
                                             + ["conv"] * 3) + [
        "full_attention", "conv", "conv"]
    assert config["num_dense_layers"] == 2
    assert config["vocab_size"] * 4 == config["published"]["vocab_size"]
    for key in ("stands_for", "assumed", "bytes", "float32_parts"):
        assert config[key]
    assert "one chip of four" in config["stands_for"]
    from chipbench import reference_lfm2

    assert config["assumed"] == reference_lfm2.ASSUMED


def test_the_configuration_is_the_catalogs_row_but_for_the_cut(config):
    row = load(CATALOG)
    assert row["name"] == "LFM2-8B-A1B"
    assert config["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if config.get(k) != v]
    assert sorted(differs) == sorted(REDUCED)


def test_the_byte_sum_is_the_parameter_count(config):
    d = 2048
    mixer = d * 3 * d + 3 * d + d * d
    dense = mixer + 2 * d + 3 * d * 7168
    assert dense == 60_827_648
    experts = 8 * 3 * d * 1792
    router = d * 32 + 32
    conv = mixer + 2 * d + router + experts
    assert conv == 104_933_408
    attn = 2 * d * d + 2 * d * 512 + 2 * 64 + 2 * d + router + experts
    assert attn == 98_635_936
    parts = {"tied embedding": 16384 * d, "layers 0 and 1": 2 * dense,
             "conv": 5 * conv, "attention": 2 * attn, "final norm": d}
    assert sorted(config["bytes"]["parameters"].values()) \
        == sorted(parts.values())
    total = sum(parts.values())
    assert config["bytes"]["total_parameters"] == total == 877_150_688
    assert config["bytes"]["total_bytes"] == 8 * total == 7_017_205_504


def test_step_flops_against_a_hand_count(config):
    from chipbench import flops_lfm2

    seq, rows, d = 8192, 2, 2048
    tokens = rows * seq
    pairs = tokens * 4 * 8 / 32 * 7              # balanced: 2,048 an expert
    assert pairs / 7 / 8 == 2048
    assert (flops_lfm2.conv_layers(config),
            flops_lfm2.attention_layers(config),
            flops_lfm2.expert_layers(config)) == (7, 2, 7)
    conv = 2 * (4 * d * d + 3 * d)
    assert 7 * conv == 234_967_040               # 235 MFLOP a token
    dense = 2 * 3 * d * 7168
    head = 2 * d * 16384
    assert (round(dense / 1e6), round(head / 1e6)) == (88, 67)
    projections = 2 * 2 * d * 64 * (32 + 8)      # q, k, v, o a layer
    per_token = (7 * conv + 2 * projections + 2 * dense + 7 * 2 * d * 32
                 + head)
    assert flops_lfm2.dense_flops_per_token(config) == per_token
    scores = 2 * 32 * 2 * 64 * (seq * (seq + 1) // 2)
    assert flops_lfm2.attention_flops(config, seq) == scores
    expert = 2 * 3 * d * 1792
    forward = tokens * per_token + rows * 2 * scores + pairs * expert
    assert flops_lfm2.forward_flops(config, rows, seq, pairs) == forward
    assert flops_lfm2.train_step_flops(config, rows, seq, pairs) \
        == 3 * forward
    # the held experts ≈ 154 MFLOP a token, the two attention layers
    # ≈ 109 at 8k
    assert round(pairs * expert / tokens / 1e6) == 154
    attention = 2 * (projections + scores / seq)
    assert round(attention / 1e6) == 109
    # the kernel: six products on the unmasked pairs at 64 = 3.30
    # TFLOP a step, 16.75 ms at the peak; bytes far below
    kernel = flops_lfm2.attention_kernel_flops(config, rows, seq)
    assert kernel == 3 * rows * 2 * scores
    assert round(kernel / 1e12, 2) == 3.30
    assert round(kernel / 197e12 * 1e3, 2) == 16.75
    q, kv = 32 * 64, 2 * 8 * 64
    assert flops_lfm2.attention_kernel_bytes(config, rows, seq) \
        == 2 * tokens * 2 * (6 * q + 3 * kv)
    # the gated convolution: 45,056 B a token a layer, 5.17 GB a step,
    # 6.3 ms at 819 GB/s
    mix = flops_lfm2.shortconv_mix_bytes(config, rows, seq)
    assert mix / tokens / 7 == 45_056 == 2 * (4 + 7) * d
    assert round(mix / 1e9, 2) == 5.17
    assert round(mix / 819e9 * 1e3, 1) == 6.3
    # the grouped products: 3 × the held pairs' forward, and bytes as
    # flops_seq counts them over seven expert layers of 8 held
    assert flops_lfm2.expert_product_flops(config, pairs) \
        == 3 * pairs * expert
    weights = 3 * 7 * 8 * 3 * d * 1792
    assert flops_lfm2.expert_product_bytes(config, pairs) \
        == 2 * (weights + 3 * pairs * (2 * d + 3 * 1792))


def made_context(config, by_scope: dict, steps: int):
    return types.SimpleNamespace(
        config=config, device=V5E, cell={"name": CELL},
        traffic={"rows": 2, "seq_len": 8192},
        counters_before={}, counters_after={},
        scope_account={
            "busy_s": sum(by_scope.values()), "window": {"steps": steps},
            "by_scope": by_scope,
            "scope_names": sorted({part for path in by_scope
                                   for part in path.split("/")})})


def read(name, ctx, raw=None):
    from chipbench import run

    return run.load_by_name("layers", name).read(
        ctx, raw or {"steps": 40, "elapsed_s": 32.0})


def test_the_new_readers_on_a_made_account(config, monkeypatch):
    """Each reader on a by-scope account written out here: the scopes
    it sums, the operations and bytes it sets them against, None
    where the program has no such scope or counter and off the TPU."""
    from chipbench import flops_lfm2, seq_readers

    fwd, bwd = "jit(step)/jvp(N)/layer2/", "jit(step)/transpose(jvp(N))/"
    conv = "seq.shortconv/attn/"
    by_scope = {
        fwd + conv + "seq.shortconv.mix/mul": 0.1,
        bwd + "layer3/" + conv + "seq.shortconv.mix/add": 0.3,
        fwd + conv + "seq.shortconv.proj/dot": 0.2,
        fwd + "seq.shortconv/input_norm": 0.02,
        fwd + "seq.attn.full/attn/seq.attn.kernel/splash": 0.08,
        bwd + "layer1/seq.attn.full/attn/seq.attn.kernel/dq": 0.12,
        fwd + "seq.dense_ffn/ffn/dot": 0.5,
    }
    ctx = made_context(config, by_scope, steps=4)
    assert read("shortconv_ms_per_step.train", ctx) \
        == pytest.approx(1e3 * 0.62 / 4)
    least = flops_lfm2.shortconv_mix_bytes(config, 2, 8192) / 819e9
    assert read("shortconv_mix_roofline_pct.train", ctx) \
        == pytest.approx(100 * least / 0.1)
    kernel = max(flops_lfm2.attention_kernel_flops(config, 2, 8192)
                 / 197e12,
                 flops_lfm2.attention_kernel_bytes(config, 2, 8192)
                 / 819e9)
    assert read("splash_attn_roofline_pct.lfm2", ctx) \
        == pytest.approx(100 * kernel / 0.05)
    # the held pairs from the program's counter: 40 steps of 7 expert
    # layers with 2,048 pairs on each of 8 held experts
    held = 40 * 7 * 8 * 2048
    monkeypatch.setattr(seq_readers, "counter_delta",
                        lambda before, after, name: held)
    assert read("moe_held_tokens_per_expert.lfm2", ctx) == 2048
    ctx.counters_after = {"gauges": {"moe_expert_load_max": 2560}}
    assert read("moe_load_max_over_mean.lfm2", ctx) == 1.25
    ctx.op_account = {"by_op": {"ragged-dot.3": 0.3, "ragged-dot.7": 0.1,
                                "fusion.2": 0.5}, "steps": 4}
    least = max(flops_lfm2.expert_product_flops(config, held / 40)
                / 197e12,
                flops_lfm2.expert_product_bytes(config, held / 40)
                / 819e9)
    assert read("ragged_dot_roofline_pct.lfm2", ctx) \
        == pytest.approx(100 * least / 0.1)
    step = flops_lfm2.train_step_flops(config, 2, 8192, held / 40)
    assert read("train_mfu_pct.lfm2", ctx) \
        == pytest.approx(100 * step * 40 / 32.0 / 197e12)
    # a program without the scopes or the counter: nothing to read,
    # nothing raised
    monkeypatch.setattr(seq_readers, "counter_delta",
                        lambda before, after, name: None)
    bare = made_context(config, {fwd + "seq.dense_ffn/dot": 0.02}, 4)
    bare.op_account = {"by_op": {"fusion.2": 0.5}, "steps": 4}
    for name in OWN:
        assert read(name, bare) is None, name
    ctx.device = {"platform": "cpu", "kind": "cpu", "count": 1}
    for name in ("shortconv_mix_roofline_pct.train",
                 "splash_attn_roofline_pct.lfm2"):
        assert read(name, ctx) is None, name


def test_the_cell_and_its_metrics_are_in_the_manifest():
    m = load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, CONFIG, "train-seq8k-r2-lfm2")
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["source"] == load(os.path.join(
        BENCH, "configs", CONFIG + ".json"))["source"]
    traffic = load(os.path.join(BENCH, "traffic",
                                "train-seq8k-r2-lfm2.json"))
    want = {"driver": "train_seq_lfm2", "rows": 2, "seq_len": 8192,
            "symmetries": True, "resident_batches": 8,
            "steps_per_block": 4, "game_length": [180, 420],
            "sample_positions": 64}
    assert {k: traffic[k] for k in want} == want
    rate = {e["name"]: e for e in m["end_to_end"]}[
        "train_positions_per_s"]
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    only = {e["name"] for e in m["per_layer"]
            if e.get("workloads") == [CELL]}
    assert OWN == only
    mine = {e["name"] for e in m["per_layer"]
            if CELL in e.get("workloads", [])}
    assert OWN | SHARED == mine
    assert not NOT_MINE & mine
    assert all(e["moves"] == "train_positions_per_s"
               for e in m["per_layer"] if e["name"] in mine)
    for name in mine:
        assert os.path.isfile(os.path.join(BENCH, "layers",
                                           name + ".py")), name
    fixture = load(FIXTURE)
    assert OWN | SHARED == {e["name"] for e in fixture["per_layer"]}


def test_a_spec_from_the_configuration_builds_the_published_block(
        config):
    import jax
    import jax.numpy as jnp

    from chipbench.drivers.train_seq_lfm2 import (
        sampled_leaves,
        spec_kwargs,
    )
    from rocalphago_tpu.models.seqpolicy import SeqPolicy

    kw = spec_kwargs(config)
    assert (kw["num_hidden_layers"], kw["layers_held"],
            kw["num_dense_layers"], kw["num_experts"], kw["experts_held"],
            kw["vocab_size"], kw["vocab_held"]) \
        == (24, 9, 2, 32, 8, 65536, 16384)
    net = SeqPolicy(board=19, init_weights=False, **kw)
    layers = net.module.layers
    assert [s.short_conv for s in layers] == [3, 3, 0, 3, 3, 3, 0, 3, 3]
    assert [s.sparse for s in layers] == [False] * 2 + [True] * 7
    assert net.module.tied and dict(net.module.ffn)["shared_width"] == 0
    ids = jnp.zeros((1, 1), jnp.int32)
    shapes = jax.eval_shape(net.module.init, jax.random.key(0), ids, ids)
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == config["bytes"]["total_parameters"]
    p = shapes["params"]
    assert p["embed"].shape == (16384, 2048) and "head" not in p
    assert p["layer0"]["attn"]["in_proj"].shape == (2048, 6144)
    assert p["layer0"]["attn"]["conv"].shape == (3, 2048)
    assert p["layer0"]["ffn"]["gate_proj"].shape == (2048, 7168)
    assert p["layer1"]["ffn"]["gate_proj"].shape == (2048, 7168)
    assert p["layer2"]["attn"]["q_proj"].shape == (2048, 2048)
    assert p["layer2"]["attn"]["k_proj"].shape == (2048, 512)
    assert p["layer2"]["attn"]["q_norm"]["scale"].shape == (64,)
    assert p["layer2"]["ffn"]["experts_gate"].shape == (8, 2048, 1792)
    assert p["layer2"]["ffn"]["router"].shape == (2048, 32)
    paths = sampled_leaves(kw)
    assert paths[0] == ("layer3", "attn", "in_proj")
    assert paths[3] == ("layer2", "attn", "q_norm", "scale")
    for path in paths:
        node = p
        for key in path:
            node = node[key]


def run_fixture(script: str, *args: str):
    """A fixture run at a seed whose toy router flips no choice of the
    sampled expert's: it sees a few dozen pairs, so one flipped token
    is a quarter of its gradient and over the cell's limit."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), "--manifest",
         FIXTURE, "--workload", "toy-lfm2.train", "--seed", "3000000021",
         *args], env=env, cwd=ROOT, text=True, capture_output=True,
        timeout=900)
    return out, json.loads(out.stdout.strip().splitlines()[-1])


def test_the_lowered_controls_run_through_the_drivers_own_verify():
    """The bf16 control through ``Driver.verify`` at toy size on the
    CPU: refused by the gated output's check, which the program's own
    float32 gating passes by far."""
    from chipbench.reference_lfm2 import MIX_TOLERANCE

    out, said = run_fixture("lowered_reading_lfm2.py")
    assert out.returncode == 0, out.stderr[-2000:]
    control = said["control"]
    assert not control["correct"]
    assert said["program_mix_rel_err"] < MIX_TOLERANCE / 10
    assert control["checks"]["mix_rel_err"] > 100 * MIX_TOLERANCE
    assert set(control["checks"]["update_errs"]) == {
        "embed", "layer2/attn/k_norm/scale", "layer2/attn/q_norm/scale",
        "layer2/attn/q_proj", "layer2/ffn/experts_down",
        "layer2/ffn/router", "layer3/attn/conv", "layer3/attn/in_proj",
        "layer3/attn/out_proj"}


@pytest.mark.parametrize("trace", [0, 1])
def test_the_fixture_cell_rehearses_on_cpu(trace):
    out, line = run_fixture("run.py", "--platform", "cpu", "--seconds",
                            "1", "--trace", str(trace))
    assert out.returncode == 0, out.stderr[-2000:]
    assert line["correct"] is True, out.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    fixture = load(FIXTURE)
    if trace:
        wanted = {e["name"] for e in fixture["per_layer"]}
        # a share of a peak means nothing off the TPU, where the
        # attention kernel and the grouped products' named calls do
        # not run either
        assert set(line["metrics"]) == wanted - {
            "train_mfu_pct.lfm2", "shortconv_mix_roofline_pct.train",
            "splash_attn_roofline_pct.lfm2",
            "splash_fwd_ms_per_step.train",
            "ragged_dot_ms_per_step.train", "ragged_dot_roofline_pct.lfm2"}
        for name in ("shortconv_ms_per_step.train",
                     "attn_full_ms_per_step.train",
                     "experts_ms_per_step.train"):
            assert line["metrics"][name]["value"] > 0, name
        assert line["metrics"]["moe_held_tokens_per_expert.lfm2"][
            "value"] > 0
    else:
        assert set(line["metrics"]) == {"train_positions_per_s",
                                        "setup_s"}
