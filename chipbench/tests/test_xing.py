"""The ``xing4.0-29b-a4b-ep8`` cell's benchmark files, checked without
a chip:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_xing.py -q

the configuration against the catalog's keys, its byte count against
a hand count, ``flops_xing.py`` against a hand count, the new readers
on a made account, and a fixture cell (toy widths, the
``train_seq_xing`` driver, every reader the cell lists) rehearsed on
the CPU through ``run.py`` and through the lowered control. Nothing
here is a device number. (The program against the reference at toy
size: ``tests/test_seqpolicy_xing.py``.)
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

FIXTURE = os.path.join(HERE, "fixtures", "BENCHMARK.xing.fixture.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = "xing4.0-29b-a4b-ep8"
CELL = CONFIG + ".train-seq8k-r1"
REDUCED = {"num_hidden_layers": (40, 5), "first_k_dense_replace": (2, 1),
           "n_routed_experts": (64, 8), "vocab_size": (131072, 16384)}
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


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
    widths = {"hidden_size": 3584, "intermediate_size": 9216,
              "moe_intermediate_size": 1024, "num_attention_heads": 32,
              "q_lora_rank": 768, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "num_experts_per_tok": 4,
              "n_shared_experts": 1, "hc_mult": 4,
              "hc_sinkhorn_iters": 20, "num_nextn_predict_layers": 1}
    assert {k: config[k] for k in widths} == widths
    assert config["scoring_func"] == "sigmoid"
    assert config["topk_method"] == "noaux_tc"
    # the guide's floors: four layers after the dense one, 8 experts,
    # an eighth of the vocabulary
    assert config["num_hidden_layers"] \
        - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
    for key in ("stands_for", "assumed", "bytes", "float32_parts"):
        assert config[key]
    assert "one chip of eight" in config["stands_for"]
    from chipbench import reference_xing

    assert set(reference_xing.ASSUMED) <= set(config["assumed"])


def test_the_configuration_is_the_catalogs_row_but_for_the_cut(config):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert config["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if config.get(k) != v]
    assert sorted(differs) == sorted(REDUCED)


def test_the_byte_sum_is_the_parameter_count(config):
    d, h, n = 3584, 32, 4
    attention = (d * 768 + 768 * h * 192 + d * 576 + 512 * h * 256
                 + h * 128 * d)
    assert attention == 28_409_856              # ISSUE 30's count
    expert = 3 * d * 1024
    hyper = n * d * (2 * n + n * n) + n * d + 3 + 2 * n + n * n
    blocks = 6                                  # five layers + the MTP's
    parts = {
        "embedding + head": 2 * 16384 * d,
        "attention": blocks * (attention + 768 + 512),
        "hyper": 2 * blocks * hyper,
        "dense": 3 * d * 9216,
        "norms": (2 * blocks + 1) * d,          # two a block, the final
        "experts": 5 * 8 * expert,
        "router + shared": 5 * (d * 64 + 64 + expert),
        "mtp": 2 * d * d + 3 * d,
    }
    assert sorted(config["bytes"]["parameters"].values()) \
        == sorted(parts.values())
    total = sum(parts.values())
    assert config["bytes"]["total_parameters"] == total == 913_645_700
    assert config["bytes"]["total_bytes"] == 8 * total


def test_step_flops_against_a_hand_count(config):
    from chipbench import flops_xing

    seq, rows, d = 8192, 1, 3584
    pairs_of_a_head = 33_558_528                # j <= i over 8,192
    attention = 28_409_856
    per_token = (6 * attention                  # five layers + MTP
                 + 12 * 4 * d * 24              # coefficients
                 + 3 * d * 9216                 # layer 0
                 + 5 * (d * 64 + 3 * d * 1024)  # routers, shared experts
                 + 2 * d * 16384                # two heads
                 + 2 * d * d)                   # eh_proj
    scores = 6 * 2 * 32 * (192 + 128) * pairs_of_a_head
    pairs = 20_480.0                            # 8192 * 4 * 8/64 * 5
    forward = 2 * seq * per_token + scores + pairs * 6 * d * 1024
    assert flops_xing.forward_flops(config, rows, seq, pairs) == forward
    assert flops_xing.train_step_flops(config, rows, seq, pairs) \
        == 3 * forward
    # ISSUE 30's reckoning: 13.2 TFLOP forward with the held experts
    # at 1.35; 20,480 pairs x 22.0 MFLOP are 0.45, so 12.3
    assert 12.2e12 < forward < 12.4e12
    assert flops_xing.attention_kernel_flops(config, rows, seq) \
        == 3 * scores
    assert 4.1e12 < scores < 4.15e12
    assert flops_xing.attention_kernel_bytes(config, rows, seq) \
        == 2 * seq * 6 * 32 * (6 * 192 + 6 * 128)
    per_sublayer = (8 * 4 + 5) * d
    assert flops_xing.stream_mix_bytes(config, rows, seq) \
        == 2 * seq * (12 * per_sublayer + 2 * 2 * 5 * d)
    assert (flops_xing.blocks(config), flops_xing.expert_blocks(config),
            flops_xing.sublayers(config)) == (6, 5, 12)


def made_context(config, by_scope: dict, steps: int, held: float):
    return types.SimpleNamespace(
        config=config, device=V5E, cell={"name": CELL},
        traffic={"rows": 1, "seq_len": 8192},
        counters_before={"counters": {"moe_tokens_held_total": 0}},
        counters_after={"counters": {"moe_tokens_held_total": held}},
        scope_account={
            "busy_s": sum(by_scope.values()), "window": {"steps": steps},
            "by_scope": by_scope,
            "scope_names": sorted({part for path in by_scope
                                   for part in path.split("/")})})


def test_the_new_readers_on_a_made_account(config):
    """Each reader on a by-scope account written out here (no chip):
    the scopes it sums, the operations and bytes it sets them
    against, None where the program has no such scope."""
    from chipbench import flops_xing, run

    fwd, bwd = "jit(step)/jvp(N)/layer1/", "jit(step)/transpose(jvp(N))/"
    by_scope = {
        fwd + "seq.attn.mla/attn/dot": 0.4,
        fwd + "seq.attn.mla/seq.attn.kernel/pallas": 0.3,
        bwd + "layer1/seq.attn.mla/seq.attn.kernel/pallas": 0.7,
        fwd + "seq.mhc.coeff/dot": 0.05,
        bwd + "layer1/seq.mhc.sinkhorn/div": 0.01,
        fwd + "seq.mhc.mix/mul": 0.06,
        bwd + "mtp_layer/seq.mhc.mix/mul": 0.1,
        fwd.replace("layer1/", "") + "seq.mtp/dot": 0.08,
        fwd + "seq.router/dot": 0.02,
    }
    ctx = made_context(config, by_scope, steps=4, held=4 * 20_480.0)
    raw = {"steps": 40, "elapsed_s": 32.0}

    def read(name, ctx=ctx):
        return run.load_by_name("layers", name).read(ctx, raw)

    assert read("attn_mla_ms_per_step.train") == pytest.approx(350.0)
    assert read("mhc_ms_per_step.train") == pytest.approx(55.0)
    assert read("mtp_ms_per_step.train") == pytest.approx(20.0)
    assert read("moe_held_tokens_per_expert.xing") \
        == pytest.approx(20_480.0 / 40 / 5 / 8 * 4)
    kernel = flops_xing.attention_kernel_flops(config, 1, 8192) / 197e12
    assert read("mla_attn_roofline_pct.train") \
        == pytest.approx(100 * kernel / 0.25)
    mix = flops_xing.stream_mix_bytes(config, 1, 8192) / 819e9
    assert read("mhc_roofline_pct.train") \
        == pytest.approx(100 * mix / 0.04)
    assert 0 < read("mla_attn_roofline_pct.train") < 100
    assert 0 < read("mhc_roofline_pct.train") < 100
    step = flops_xing.train_step_flops(config, 1, 8192, 2048.0)
    assert read("train_mfu_pct.xing") \
        == pytest.approx(100 * step * 40 / 32.0 / 197e12)
    # a program without the scopes or the counter (the parent of the
    # PR that brought them): nothing to read, nothing raised
    bare = made_context(config, {fwd + "seq.router/dot": 0.02}, 4, 0)
    bare.counters_before = bare.counters_after = {"counters": {}}
    for name in ("attn_mla_ms_per_step.train", "mhc_ms_per_step.train",
                 "mtp_ms_per_step.train", "mla_attn_roofline_pct.train",
                 "mhc_roofline_pct.train", "train_mfu_pct.xing",
                 "moe_held_tokens_per_expert.xing"):
        assert read(name, bare) is None, name
    # and no share of a peak off the TPU
    ctx.device = {"platform": "cpu", "kind": "cpu", "count": 1}
    for name in ("mla_attn_roofline_pct.train", "mhc_roofline_pct.train",
                 "train_mfu_pct.xing"):
        assert read(name) is None, name


def test_the_cell_and_its_metrics_are_in_the_manifest():
    m = load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, CONFIG, "train-seq8k-r1")
    entry = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    traffic = load(os.path.join(BENCH, "traffic", "train-seq8k-r1.json"))
    want = {"driver": "train_seq_xing", "rows": 1, "seq_len": 8192,
            "symmetries": True, "resident_batches": 8,
            "steps_per_block": 4, "game_length": [180, 420],
            "sample_positions": 64}
    assert {k: traffic[k] for k in want} == want
    rate = {e["name"]: e for e in m["end_to_end"]}[
        "train_positions_per_s"]
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    only = {e["name"] for e in m["per_layer"]
            if e.get("workloads") == [CELL]}
    assert only == {
        "train_mfu_pct.xing", "attn_mla_ms_per_step.train",
        "mhc_ms_per_step.train", "mtp_ms_per_step.train",
        "mla_attn_roofline_pct.train", "mhc_roofline_pct.train",
        "moe_held_tokens_per_expert.xing"}
    mine = {e["name"] for e in m["per_layer"]
            if CELL in e.get("workloads", [])}
    assert mine - only == {
        "device_idle_pct.train", "fwd_ms_per_step.train",
        "bwd_ms_per_step.train", "update_ms_per_step.train",
        "augment_ms_per_step.train", "unscoped_device_pct.train",
        "experts_ms_per_step.train", "router_ms_per_step.train",
        "ragged_dot_ms_per_step.train", "moe_row_blocks_run_pct.train"}
    assert all(e["moves"] == "train_positions_per_s"
               for e in m["per_layer"] if e["name"] in mine)
    for name in mine:
        assert os.path.isfile(os.path.join(BENCH, "layers",
                                           name + ".py")), name
    fixture = load(FIXTURE)
    assert {e["name"] for e in fixture["per_layer"]} == mine


def test_a_spec_from_the_configuration_builds_the_published_block(
        config):
    import jax
    import jax.numpy as jnp

    from chipbench.drivers.train_seq_xing import (
        sampled_leaves,
        spec_kwargs,
    )
    from rocalphago_tpu.models.seqpolicy import SeqPolicy

    kw = spec_kwargs(config)
    assert (kw["n_routed_experts"], kw["experts_held"],
            kw["vocab_held"], kw["layers_held"],
            kw["first_k_dense_replace"]) == (64, 8, 16384, 5, 1)
    net = SeqPolicy(board=19, init_weights=False, **kw)
    ids = jnp.zeros((1, 1), jnp.int32)
    shapes = jax.eval_shape(net.module.init, jax.random.key(0), ids, ids)
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == config["bytes"]["total_parameters"]
    p = shapes["params"]
    assert p["layer1"]["ffn"]["router"].shape == (3584, 64)
    assert p["layer1"]["ffn"]["router_bias"].shape == (64,)
    assert p["layer1"]["ffn"]["experts_gate"].shape == (8, 3584, 1024)
    assert p["layer0"]["ffn"]["gate_proj"].shape == (3584, 9216)
    assert p["layer4"]["attn"]["q_b_proj"].shape == (768, 32 * 192)
    assert p["layer4"]["attn"]["kv_b_proj"].shape == (512, 32 * 256)
    assert p["layer4"]["attn"]["kv_a_proj"].shape == (3584, 576)
    assert p["layer1"]["attn_hc"]["phi_res"].shape == (14336, 16)
    assert p["mtp_eh_proj"].shape == (7168, 3584)
    assert "ffn" in p["mtp_layer"] and "router" in p["mtp_layer"]["ffn"]
    latent = net.module.layers[0].latent
    assert (latent.nope + latent.rope, latent.value) == (192, 128)
    for path in sampled_leaves(kw):
        node = p
        for key in path:
            node = node[key]


def run_fixture(script: str, *args: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), "--manifest",
         FIXTURE, "--workload", "toy-xing.train", "--seed", "3000000019",
         *args], env=env, cwd=ROOT, text=True, capture_output=True,
        timeout=900)
    return out, json.loads(out.stdout.strip().splitlines()[-1])


def test_the_lowered_control_is_refused_by_the_drivers_own_verify():
    """The reference with its float32 parts in bf16, in the program's
    place, through ``Driver.verify`` (toy size, CPU): not correct."""
    out, said = run_fixture("lowered_reading_xing.py")
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert said["correct"] is False and said["refused_by"]
    checks = said["checks"]
    assert len(checks["router_choice_flips"]) == 3  # 2 layers + the MTP's
    assert all(0 <= f <= 1 for f in checks["router_choice_flips"])
    assert set(checks["logit_rows"]) == {"main", "mtp"}
    assert set(checks["update_errs"]) >= {
        "embed", "mtp_eh_proj", "layer1/ffn/router",
        "layer1/attn_hc/phi_res", "layer2/attn/q_b_proj"}


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
        # a share of a peak means nothing off the TPU, where neither
        # the attention kernel nor XLA's grouped products run
        assert set(line["metrics"]) == wanted - {
            "train_mfu_pct.xing", "mla_attn_roofline_pct.train",
            "mhc_roofline_pct.train", "ragged_dot_ms_per_step.train"}
        for name in ("attn_mla_ms_per_step.train",
                     "mhc_ms_per_step.train", "mtp_ms_per_step.train",
                     "moe_held_tokens_per_expert.xing",
                     "experts_ms_per_step.train"):
            assert line["metrics"][name]["value"] > 0, name
    else:
        assert set(line["metrics"]) == {"train_positions_per_s",
                                        "setup_s"}
