"""The ``ling-3.0-flash-ep64`` cell's benchmark files, checked without
a chip:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_ling.py -q

the configuration against the catalog's keys, its byte count against
a hand count, ``flops_ling.py`` against a hand count, the new readers
on a made account, and a fixture cell (toy widths, the
``train_seq_ling`` driver, every reader the cell lists) rehearsed on
the CPU through ``run.py`` and through the lowered control. Nothing
here is a device number. (The program against the reference at toy
size: ``tests/test_seqpolicy_ling.py``.)
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

FIXTURE = os.path.join(HERE, "fixtures", "BENCHMARK.ling.fixture.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = "ling-3.0-flash-ep64"
CELL = CONFIG + ".train-seq8k-r1"
REDUCED = {"num_hidden_layers": (42, 7), "first_k_dense_replace": (2, 1),
           "num_experts": (512, 8), "vocab_size": (157184, 19648),
           "num_nextn_predict_layers": (1, 0)}
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
#: the cell's own readers, and the accepted ones it is appended to
OWN = {"train_mfu_pct.ling", "attn_kda_ms_per_step.train",
       "kda_scan_ms_per_step.train", "kda_scan_roofline_pct.train",
       "moe_held_tokens_per_expert.ling"}
SHARED = {"device_idle_pct.train", "fwd_ms_per_step.train",
          "bwd_ms_per_step.train", "update_ms_per_step.train",
          "augment_ms_per_step.train", "unscoped_device_pct.train",
          "experts_ms_per_step.train", "router_ms_per_step.train",
          "ragged_dot_ms_per_step.train", "moe_row_blocks_run_pct.train",
          "attn_mla_ms_per_step.train", "splash_fwd_ms_per_step.train"}


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
    widths = {"hidden_size": 2560, "intermediate_size": 6144,
              "moe_intermediate_size": 768,
              "moe_shared_expert_intermediate_size": 768,
              "num_attention_heads": 32, "head_dim": 128,
              "kv_lora_rank": 512, "qk_nope_head_dim": 128,
              "qk_rope_head_dim": 64, "v_head_dim": 128,
              "num_experts_per_tok": 8, "num_shared_experts": 1,
              "short_conv_kernel_size": 4, "kda_lower_bound": -5,
              "layer_group_size": 6, "n_group": 8, "topk_group": 4}
    assert {k: config[k] for k in widths} == widths
    assert config["q_lora_rank"] is None
    assert config["model_type"] == "bailing_hybrid"
    # the guide's floors: a whole period after the dense layer, 8
    # experts, an eighth of the vocabulary
    assert config["num_hidden_layers"] \
        - config["first_k_dense_replace"] >= config["layer_group_size"]
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    for key in ("stands_for", "assumed", "bytes", "float32_parts"):
        assert config[key]
    assert "one chip of sixty-four" in config["stands_for"]
    assert "eight groups of eight" in config["stands_for"]
    assert any("carried state" in part
               for part in config["float32_parts"])
    from chipbench import reference_ling

    assert config["assumed"] == reference_ling.ASSUMED


def test_the_configuration_is_the_catalogs_row_but_for_the_cut(config):
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash")
    assert config["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if config.get(k) != v]
    assert sorted(differs) == sorted(REDUCED)


def test_the_byte_sum_is_the_parameter_count(config):
    d, wide = 2560, 4096
    delta = (6 * d * wide + 3 * 4 * wide + wide + 32 + d * 32 + 128)
    assert delta == 63_049_888                  # ISSUE 32's count
    latent = (d * 6144 + d * 576 + 512 + 512 * 8192 + d * 32 + wide * d)
    assert latent == 31_965_696
    expert = 3 * d * 768
    parts = {
        "embedding + head": 2 * 19648 * d,
        "delta": 6 * delta,
        "latent": latent,
        "dense": 3 * d * 6144,
        "norms": (2 * 7 + 1) * d,
        "experts": 6 * 8 * expert,
        "router + shared": 6 * (d * 512 + 512 + expert),
    }
    assert sorted(config["bytes"]["parameters"].values()) \
        == sorted(parts.values())
    total = sum(parts.values())
    assert config["bytes"]["total_parameters"] == total == 884_459_456
    assert config["bytes"]["total_bytes"] == 8 * total == 7_075_675_648


def test_step_flops_against_a_hand_count(config):
    from chipbench import flops_ling

    seq, rows, d, wide = 8192, 1, 2560, 4096
    pairs_of_a_head = 33_558_528                # j <= i over 8,192
    delta = 6 * d * wide + d * 32 + 3 * 4 * wide
    latent = d * 6144 + d * 576 + 512 * 8192 + d * 32 + wide * d
    per_token = (6 * delta + latent
                 + 3 * d * 6144                 # layer 0
                 + 6 * (d * 512 + 3 * d * 768)  # routers, shared experts
                 + d * 19648)                   # one head
    recurrence = 6 * 7 * 32 * 128 * 128
    scores = 2 * 32 * (192 + 128) * pairs_of_a_head
    pairs = 6_144.0                             # 8192 * 8 * 8/512 * 6
    forward = (seq * (2 * per_token + recurrence) + scores
               + pairs * 6 * d * 768)
    assert flops_ling.forward_flops(config, rows, seq, pairs) == forward
    assert flops_ling.train_step_flops(config, rows, seq, pairs) \
        == 3 * forward
    # ISSUE 32's reckoning: 1,229 MFLOP a token with the recurrence
    # at the chunked form's 5.8 a layer; at the algorithm's 3.67 it
    # is 1,217
    assert 1.21e9 < forward / seq < 1.22e9
    assert (flops_ling.delta_layers(config),
            flops_ling.latent_layers(config),
            flops_ling.expert_blocks(config)) == (6, 1, 6)
    assert flops_ling.scan_flops(config, rows, seq) \
        == 3 * seq * recurrence
    per_token_bytes = 4 * wide * 2 + wide * 4 + 32 * 4
    assert flops_ling.scan_bytes(config, rows, seq) \
        == 3 * 6 * seq * per_token_bytes
    # bandwidth-bound: 8.9 ms of bytes against 2.7 ms of operations
    assert flops_ling.scan_bytes(config, rows, seq) / 819e9 \
        > 3 * flops_ling.scan_flops(config, rows, seq) / 197e12


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
    from chipbench import flops_ling, run

    fwd, bwd = "jit(step)/jvp(N)/layer1/", "jit(step)/transpose(jvp(N))/"
    kda = "seq.attn.kda/attn/"
    by_scope = {
        fwd + kda + "seq.attn.kda.proj/dot": 0.2,
        fwd + kda + "seq.attn.kda.scan/while/body/dot": 0.1,
        bwd + "layer1/" + kda + "seq.attn.kda.scan/mul": 0.3,
        bwd + "layer1/" + kda + "seq.attn.kda.out/dot": 0.12,
        fwd + "seq.attn.kda/add": 0.04,
        fwd.replace("layer1", "layer5") + "seq.attn.mla/attn/dot": 0.06,
        fwd + "seq.router/dot": 0.02,
    }
    ctx = made_context(config, by_scope, steps=4, held=4 * 6_144.0)
    raw = {"steps": 40, "elapsed_s": 32.0}

    def read(name, ctx=ctx):
        return run.load_by_name("layers", name).read(ctx, raw)

    assert read("attn_kda_ms_per_step.train") == pytest.approx(190.0)
    assert read("kda_scan_ms_per_step.train") == pytest.approx(100.0)
    assert read("attn_mla_ms_per_step.train") == pytest.approx(15.0)
    assert read("moe_held_tokens_per_expert.ling") \
        == pytest.approx(6_144.0 / 40 / 6 / 8 * 4)
    least = flops_ling.scan_bytes(config, 1, 8192) / 819e9
    assert read("kda_scan_roofline_pct.train") \
        == pytest.approx(100 * least / 0.1)
    assert 0 < read("kda_scan_roofline_pct.train") < 100
    step = flops_ling.train_step_flops(config, 1, 8192, 614.4)
    assert read("train_mfu_pct.ling") \
        == pytest.approx(100 * step * 40 / 32.0 / 197e12)
    assert 0 < read("train_mfu_pct.ling") < 100
    # a program without the scopes or the counter (the parent of the
    # PR that brought them): nothing to read, nothing raised
    bare = made_context(config, {fwd + "seq.router/dot": 0.02}, 4, 0)
    bare.counters_before = bare.counters_after = {"counters": {}}
    for name in OWN:
        assert read(name, bare) is None, name
    # and no share of a peak off the TPU
    ctx.device = {"platform": "cpu", "kind": "cpu", "count": 1}
    for name in ("kda_scan_roofline_pct.train", "train_mfu_pct.ling"):
        assert read(name) is None, name


def test_the_cell_and_its_metrics_are_in_the_manifest():
    m = load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {w["name"]: w for w in m["workloads"]}[CELL]
    assert (cell["chips"], cell["config"], cell["traffic"]) \
        == (1, CONFIG, "train-seq8k-r1-ling")
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in m["configs"]}[CONFIG]
    assert entry["reduced"] == list(REDUCED)
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    assert entry["source"] == load(os.path.join(
        BENCH, "configs", CONFIG + ".json"))["source"]
    traffic = load(os.path.join(BENCH, "traffic",
                                "train-seq8k-r1-ling.json"))
    want = {"driver": "train_seq_ling", "rows": 1, "seq_len": 8192,
            "symmetries": True, "resident_batches": 8,
            "steps_per_block": 4, "game_length": [180, 420],
            "sample_positions": 64}
    assert {k: traffic[k] for k in want} == want
    rate = {e["name"]: e for e in m["end_to_end"]}[
        "train_positions_per_s"]
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    # by inclusion: a later PR may append this cell to more readers
    only = {e["name"] for e in m["per_layer"]
            if e.get("workloads") == [CELL]}
    assert OWN <= only
    mine = {e["name"] for e in m["per_layer"]
            if CELL in e.get("workloads", [])}
    assert OWN | SHARED <= mine
    assert "mla_attn_roofline_pct.train" not in mine    # Xing's keys
    assert all(e["moves"] == "train_positions_per_s"
               for e in m["per_layer"] if e["name"] in mine)
    for name in mine:
        assert os.path.isfile(os.path.join(BENCH, "layers",
                                           name + ".py")), name
    fixture = load(FIXTURE)
    assert OWN | SHARED <= {e["name"] for e in fixture["per_layer"]}


def test_a_spec_from_the_configuration_builds_the_published_block(
        config):
    import jax
    import jax.numpy as jnp

    from chipbench.drivers.train_seq_ling import (
        sampled_leaves,
        spec_kwargs,
    )
    from rocalphago_tpu.models.seqpolicy import SeqPolicy

    kw = spec_kwargs(config)
    assert (kw["num_experts"], kw["experts_held"], kw["vocab_held"],
            kw["layers_held"], kw["first_k_dense_replace"],
            kw["num_nextn_predict_layers"]) == (512, 8, 19648, 7, 1, 0)
    net = SeqPolicy(board=19, init_weights=False, **kw)
    assert [bool(s.latent) for s in net.module.layers] \
        == [False] * 5 + [True, False]
    assert [s.sparse for s in net.module.layers] == [False] + [True] * 6
    assert net.module.mtp == 0
    ids = jnp.zeros((1, 1), jnp.int32)
    shapes = jax.eval_shape(net.module.init, jax.random.key(0), ids, ids)
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == config["bytes"]["total_parameters"]
    p = shapes["params"]
    assert p["layer1"]["ffn"]["router"].shape == (2560, 512)
    assert p["layer1"]["ffn"]["router_bias"].shape == (512,)
    assert p["layer1"]["ffn"]["experts_gate"].shape == (8, 2560, 768)
    assert p["layer0"]["ffn"]["gate_proj"].shape == (2560, 6144)
    assert p["layer2"]["attn"]["f_proj"].shape == (2560, 4096)
    assert p["layer2"]["attn"]["k_conv"].shape == (4, 4096)
    assert p["layer2"]["attn"]["A_log"].shape == (32,)
    assert p["layer2"]["attn"]["o_norm"]["scale"].shape == (128,)
    assert p["layer5"]["attn"]["q_proj"].shape == (2560, 32 * 192)
    assert p["layer5"]["attn"]["gate_proj"].shape == (2560, 32)
    assert "mtp_layer" not in p
    ffn = dict(net.module.ffn)
    assert (ffn["n_group"], ffn["topk_group"], ffn["top_k"]) == (8, 4, 8)
    paths = sampled_leaves(kw)
    assert ("layer2", "attn", "A_log") in paths
    assert ("layer5", "attn", "gate_proj") in paths
    for path in paths:
        node = p
        for key in path:
            node = node[key]


def run_fixture(script: str, *args: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, script), "--manifest",
         FIXTURE, "--workload", "toy-ling.train", "--seed", "3000000019",
         *args], env=env, cwd=ROOT, text=True, capture_output=True,
        timeout=900)
    return out, json.loads(out.stdout.strip().splitlines()[-1])


def test_the_lowered_control_is_refused_by_the_drivers_own_verify():
    """The reference with its float32 parts in bf16, in the program's
    place, through ``Driver.verify`` (toy size, CPU): not correct."""
    out, said = run_fixture("lowered_reading_ling.py")
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert said["correct"] is False and said["refused_by"]
    checks = said["checks"]
    assert len(checks["router_choice_flips"]) == 3  # three expert layers
    assert all(0 <= f <= 1 for f in checks["router_choice_flips"])
    assert set(checks["update_errs"]) >= {
        "embed", "layer1/ffn/router", "layer3/attn/f_proj",
        "layer3/attn/A_log", "layer3/attn/k_conv",
        "layer2/attn/gate_proj"}


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
            "train_mfu_pct.ling", "kda_scan_roofline_pct.train",
            "ragged_dot_ms_per_step.train",
            "splash_fwd_ms_per_step.train"}
        for name in ("attn_kda_ms_per_step.train",
                     "kda_scan_ms_per_step.train",
                     "attn_mla_ms_per_step.train",
                     "moe_held_tokens_per_expert.ling",
                     "experts_ms_per_step.train"):
            assert line["metrics"][name]["value"] > 0, name
        assert line["metrics"]["kda_scan_ms_per_step.train"]["value"] \
            < line["metrics"]["attn_kda_ms_per_step.train"]["value"]
    else:
        assert set(line["metrics"]) == {"train_positions_per_s",
                                        "setup_s"}
