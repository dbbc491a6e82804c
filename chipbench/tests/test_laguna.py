"""The move-sequence cell's benchmark files, checked without a chip:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_laguna.py -q

the configuration against the catalog's keys, ``flops_seq.py``
against a hand count, the update check's rounding account, and a
fixture cell (toy widths, the ``train_seq`` driver, every reader PR
26 added) rehearsed on the CPU through ``run.py``. Nothing here is a
device number. (The program against the reference at toy size:
``tests/test_seqpolicy.py``.)
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

FIXTURE = os.path.join(HERE, "fixtures", "BENCHMARK.laguna.fixture.json")
CELL = "laguna-s-2.1-ep16.train-seq8k-r2"


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(os.path.join(BENCH, "configs", "laguna-s-2.1-ep16.json"))


def test_the_configuration_keeps_every_published_width(config):
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 256,
                                   "vocab_size": 100352}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 16, 12544)
    widths = {"hidden_size": 3072, "intermediate_size": 12288,
              "head_dim": 128, "num_key_value_heads": 8,
              "moe_intermediate_size": 1024,
              "shared_expert_intermediate_size": 1024,
              "num_experts_per_tok": 10, "sliding_window": 512}
    assert {k: config[k] for k in widths} == widths
    assert config["num_attention_heads_per_layer"][:5] == [
        48, 72, 72, 72, 48]
    assert config["layer_types"][:5] == [
        "full_attention"] + ["sliding_attention"] * 3 + [
        "full_attention"]
    assert len(config["layer_types"]) == 48     # the groups are whole
    # floors: a whole period and four layers after the dense one, at
    # least 8 experts, at least an eighth of the vocabulary
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]


def test_the_byte_sum_is_the_parameter_count(config):
    d, hd, g = 3072, 128, 8

    def attn(h):
        return 2 * d * h * hd + 2 * d * g * hd + d * h

    layer0 = attn(48) + 3 * d * 12288
    outside = 3 * attn(72) + attn(48) + 4 * (3 * d * 1024 + d * 256)
    experts = 4 * 16 * 3 * d * 1024
    vocab = 2 * 12544 * d
    norms = 11 * d
    parts = config["bytes"]["parameters"]
    assert sorted(parts.values()) == sorted(
        [layer0, outside, experts, vocab, norms])
    total = layer0 + outside + experts + vocab + norms
    assert config["bytes"]["total_parameters"] == total == 1_113_007_104
    assert config["bytes"]["total_bytes"] == 8 * total


def test_step_flops_against_a_hand_count(config):
    from chipbench import flops_seq

    seq, rows, d = 8192, 2, 3072
    assert flops_seq.causal_pairs(seq) == 33_558_528
    assert flops_seq.causal_pairs(seq, 512) == 131_328 + 7680 * 512
    assert flops_seq.causal_pairs(8, 8) == flops_seq.causal_pairs(8, 0)
    # multiply-adds per token outside attention's scores
    full = 2 * d * 48 * 128 + 2 * d * 8 * 128 + d * 48
    slide = 2 * d * 72 * 128 + 2 * d * 8 * 128 + d * 72
    sparse = d * 256 + 3 * d * 1024
    per_token = (full + 3 * d * 12288) + 3 * (slide + sparse) \
        + (full + sparse) + d * 12544
    assert per_token == 470_458_368
    scores = 2 * 128 * (2 * 48 * 33_558_528 + 3 * 72 * 4_063_488)
    pairs = 40_960.0
    forward = 2 * (rows * seq * per_token + rows * scores
                   + pairs * 3 * d * 1024)
    assert flops_seq.forward_flops(config, rows, seq, pairs) == forward
    assert flops_seq.train_step_flops(config, rows, seq, pairs) \
        == 3 * forward
    # ISSUE 26's reckoning: ~2.96 GFLOP a position + ~10 TFLOP of
    # full-attention scores, ~60 TFLOP a step
    assert 55e12 < 3 * forward < 62e12
    assert flops_seq.expert_product_flops(config, pairs) \
        == 3 * pairs * 2 * 3 * d * 1024
    assert flops_seq.expert_product_bytes(config, pairs) == 2 * (
        3 * 4 * 16 * 3 * d * 1024 + 3 * pairs * (2 * d + 3 * 1024))
    kernel = flops_seq.attention_kernel_flops(config, rows, seq)
    assert kernel == 3 * rows * scores * 2
    assert flops_seq.attention_kernel_bytes(config, rows, seq) == \
        2 * rows * seq * 128 * (6 * (2 * 48 + 3 * 72) + 3 * 16 * 5)


def test_the_update_check_takes_off_float32_storage_rounding():
    from chipbench import reference_laguna as reference

    rng = np.random.default_rng(0)
    old = rng.normal(0, 0.02, 200_000).astype(np.float32)
    grad = rng.normal(0, 2e-6, old.shape)           # a few ulp a step
    noisy = grad * (1 + rng.normal(0, 0.01, old.shape))
    new = (old.astype(np.float64) - 0.003 * noisy).astype(np.float32)
    u = reference.update_error(old, new, grad, 0.003)
    assert u["raw"] > 0.05 and u["rounding"] > 0.05
    assert 0.005 < u["excess"] < 0.02               # the 1 % put in
    wrong = (old.astype(np.float64) - 0.003 * 1.3 * grad).astype(
        np.float32)
    assert reference.update_error(old, wrong, grad, 0.003)["excess"] \
        > 0.25


def test_the_grouped_products_are_read_by_their_own_name():
    """XLA's ``ragged-dot-*`` custom calls carry no scope; the reader
    finds them by name in its own account of a traced window (here a
    made one: no chip)."""
    import types

    from chipbench import run, seq_readers

    config = load(os.path.join(BENCH, "configs",
                               "laguna-s-2.1-ep16.json"))
    held = 41_600.0 * 4             # four steps' pairs
    ctx = types.SimpleNamespace(
        config=config,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        counters_before={"counters": {"moe_tokens_held_total": 0}},
        counters_after={"counters": {"moe_tokens_held_total": held}},
        op_account={"steps": 4, "by_op": {
            "ragged-dot-none.3": 0.4, "ragged-dot-none": 0.1,
            "ragged-dot-metadata.1": 0.02, "fusion.7": 2.0}})
    raw = {"steps": 4}
    assert seq_readers.ops_ms_per_step(ctx, seq_readers.RAGGED_DOT) \
        == pytest.approx(130.0)
    assert seq_readers.ops_ms_per_step(ctx, "no-such-op") is None
    got = run.load_by_name(
        "layers", "ragged_dot_roofline_pct.train").read(ctx, raw)
    least = 3 * 41_600 * 2 * 3 * 3072 * 1024 / 197e12   # compute-bound
    assert got == pytest.approx(100 * least / 0.130)
    assert 0 < got < 100
    ctx.device = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert run.load_by_name(
        "layers", "ragged_dot_roofline_pct.train").read(ctx, raw) is None


def test_the_lowered_control_is_refused_by_the_drivers_own_verify():
    """The reference with its float32 parts in bf16, in the program's
    place, through ``Driver.verify`` (toy size, CPU): not correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "lowered_reading.py"),
         "--manifest", FIXTURE, "--workload", "toy-laguna.train",
         "--seed", "3000000019"], env=env, cwd=ROOT, text=True,
        capture_output=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    said = json.loads(out.stdout.strip().splitlines()[-1])
    assert said["correct"] is False and said["refused_by"]
    checks = said["checks"]
    assert len(checks["router_choice_flips"]) == 4      # sparse layers
    assert all(0 <= f <= 1 for f in checks["router_choice_flips"])
    assert set(checks["update_errs"]) >= {"embed", "layer1/ffn/router"}


def test_the_cell_and_its_metrics_are_in_the_manifest():
    m = load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert cell["chips"] == 1 and cell["config"] == "laguna-s-2.1-ep16"
    mine = {e["name"] for e in m["per_layer"]
            if CELL in e.get("workloads", [])}
    only = {e["name"] for e in m["per_layer"]
            if e.get("workloads") == [CELL]}
    assert only == {
        "train_mfu_pct.seq", "attn_full_ms_per_step.train",
        "attn_window_ms_per_step.train", "experts_ms_per_step.train",
        "router_ms_per_step.train", "moe_held_tokens_per_expert.train",
        "moe_load_max_over_mean.train",
        "splash_attn_roofline_pct.train",
        "ragged_dot_ms_per_step.train",
        "ragged_dot_roofline_pct.train"}
    assert mine - only == {
        "device_idle_pct.train", "augment_ms_per_step.train",
        "update_ms_per_step.train", "unscoped_device_pct.train",
        "fwd_ms_per_step.train", "bwd_ms_per_step.train"}
    fixture = load(FIXTURE)
    assert {e["name"] for e in fixture["per_layer"]} == mine


def test_a_spec_from_the_configuration_builds_the_published_block(
        config):
    import jax
    import jax.numpy as jnp

    from chipbench.drivers.train_seq import sampled_leaves, spec_kwargs
    from rocalphago_tpu.models.seqpolicy import SeqPolicy

    kw = spec_kwargs(config)
    assert (kw["num_experts"], kw["experts_held"], kw["vocab_held"],
            kw["layers_held"]) == (256, 16, 12544, 5)
    net = SeqPolicy(board=19, init_weights=False, **kw)
    shapes = jax.eval_shape(net.module.init, jax.random.key(0),
                            jnp.zeros((1, 1), jnp.int32))
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == config["bytes"]["total_parameters"]
    p = shapes["params"]
    assert p["layer1"]["ffn"]["router"].shape == (3072, 256)
    assert p["layer1"]["ffn"]["experts_gate"].shape == (16, 3072, 1024)
    assert p["layer1"]["attn"]["q_proj"].shape == (3072, 72 * 128)
    assert p["layer4"]["attn"]["gate_proj"].shape == (3072, 48)
    assert ("layer4", "attn", "gate_proj") in sampled_leaves(kw)


@pytest.mark.parametrize("trace", [0, 1])
def test_the_fixture_cell_rehearses_on_cpu(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--manifest",
         FIXTURE, "--platform", "cpu", "--workload", "toy-laguna.train",
         "--seed", "3000000019", "--seconds", "1", "--trace",
         str(trace)], env=env, cwd=ROOT, text=True, capture_output=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, out.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    fixture = load(FIXTURE)
    if trace:
        wanted = {e["name"] for e in fixture["per_layer"]}
        # a share of a peak means nothing off the TPU, where neither
        # the attention kernel nor XLA's grouped products run
        assert set(line["metrics"]) == wanted - {
            "train_mfu_pct.seq", "splash_attn_roofline_pct.train",
            "ragged_dot_ms_per_step.train",
            "ragged_dot_roofline_pct.train"}
        assert line["metrics"]["fwd_ms_per_step.train"]["value"] > 0
        assert line["metrics"]["bwd_ms_per_step.train"]["value"] > 0
        assert line["metrics"]["moe_held_tokens_per_expert.train"][
            "value"] > 0
    else:
        assert set(line["metrics"]) == {"train_positions_per_s",
                                        "setup_s"}
