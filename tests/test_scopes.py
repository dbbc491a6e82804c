"""The names a profiler trace is read by (``obs/scopes.py``,
``obs/trace.py``): every scope constant is lowered through the
program that owns it at a toy size and looked for in ``op_name`` of
the HLO, and a span is looked for in a real profiler capture.

The train step is COMPILED (its fusions decide the by-scope account
of ``chipbench/scopes.py``, and the backward pass must keep the
names); the self-play ply, the leaf evaluation and the tree phases
are read from the module as lowered, name stacks and all, before
XLA's optimiser — a compile of the ladder chase per case would cost
the suite minutes and add nothing about names.
"""

from __future__ import annotations

import functools
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import optax
import pytest

from rocalphago_tpu.engine.jaxgo import GoConfig, new_states
from rocalphago_tpu.obs import scopes, trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIZE = 5
CFG = GoConfig(size=SIZE)
#: both ladder planes (the shared chase) and a candidate-analysis
#: plane, so that every ``encode.*`` stage has something to do
FEATS = ("board", "ones", "capture_size", "ladder_capture",
         "ladder_escape", "sensibleness")
VFEATS = FEATS + ("color",)


def op_names(hlo_text: str) -> list:
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def lowered_hlo(fn, *args) -> list:
    """The name-stack of every operation of ``fn`` as lowered: the
    locations that become ``op_name`` in the compiled HLO, before
    the optimiser."""
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    return re.findall(r'loc\("([^"]*)"', text)


@pytest.fixture(scope="module")
def policy():
    from rocalphago_tpu.models import CNNPolicy

    return CNNPolicy(("board", "ones"), board=SIZE, layers=3,
                     filters_per_layer=4)


@pytest.fixture(scope="module")
def train_hlo(policy):
    """The COMPILED supervised train step, as HLO text."""
    from rocalphago_tpu.io.checkpoint import pack_rng
    from rocalphago_tpu.training import sl

    tx = sl.make_optimizer(sl.SLConfig())
    step = sl.make_train_step(policy.module.apply, tx, SIZE, True)
    state = sl.SLState(policy.params, tx.init(policy.params),
                       jnp.int32(0), pack_rng(jax.random.key(0)))
    planes = jnp.zeros((4, SIZE, SIZE, policy.preprocess.output_dim),
                       jnp.uint8)
    actions = jnp.zeros((4,), jnp.int32)
    return jax.jit(step).lower(
        state, planes, actions).compile().as_text()


@pytest.fixture(scope="module")
def train_ops(train_hlo):
    return op_names(train_hlo)


@pytest.fixture(scope="module")
def value_ops():
    from rocalphago_tpu.io.checkpoint import pack_rng
    from rocalphago_tpu.models import CNNValue
    from rocalphago_tpu.training import value as value_lib

    net = CNNValue(("board", "ones", "color"), board=SIZE, layers=2,
                   filters_per_layer=4)
    tx = optax.sgd(0.01)
    step = value_lib.make_train_step(net.module.apply, tx, True)
    state = value_lib.ValueState(net.params, tx.init(net.params),
                                 jnp.int32(0),
                                 pack_rng(jax.random.key(0)))
    planes = jnp.zeros((4, SIZE, SIZE, net.preprocess.output_dim),
                       jnp.uint8)
    return lowered_hlo(step, state, planes, jnp.zeros((4,), jnp.int8))


@pytest.fixture(scope="module")
def seq_ops():
    """The COMPILED supervised train step of the move-sequence policy
    at a toy size (one full and one sliding layer, a dense and a
    sparse MLP)."""
    from rocalphago_tpu.io.checkpoint import pack_rng
    from rocalphago_tpu.models.seqpolicy import SeqPolicy
    from rocalphago_tpu.training import sl

    rope = {"rope_theta": 10000, "partial_rotary_factor": 1}
    net = SeqPolicy(
        board=SIZE, vocab_size=32, vocab_held=32, hidden_size=8,
        intermediate_size=8, num_hidden_layers=2, layers_held=2,
        num_attention_heads_per_layer=[2, 4], num_key_value_heads=2,
        head_dim=4, sliding_window=4,
        layer_types=["full_attention", "sliding_attention"],
        mlp_layer_types=["dense", "sparse"],
        rope_parameters={"full_attention": rope,
                         "sliding_attention": rope},
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=4,
        shared_expert_intermediate_size=4, norm_topk_prob=True,
        moe_routed_scaling_factor=2.5, experts_held=2, expert_offset=0,
        rms_norm_eps=1e-6)
    tx = sl.make_optimizer(sl.SLConfig())
    step = sl.make_train_step(net.module.apply, tx, SIZE, True)
    state = sl.SLState(net.params, tx.init(net.params), jnp.int32(0),
                       pack_rng(jax.random.key(0)))
    ids = jnp.zeros((2, 8), jnp.int32)
    return op_names(jax.jit(step).lower(
        state, ids, ids).compile().as_text())


@pytest.fixture(scope="module")
def xing_ops():
    """The COMPILED supervised train step of the move-sequence policy
    built from a toy ``xing4_0`` spec: latent attention, four
    hyper-connected streams, a dense and an expert layer, the
    multi-token-prediction module."""
    from rocalphago_tpu.io.checkpoint import pack_rng
    from rocalphago_tpu.models.seqpolicy import SeqPolicy
    from rocalphago_tpu.training import sl

    net = SeqPolicy(
        board=SIZE, model_type="xing4_0", vocab_size=32, vocab_held=32,
        hidden_size=8, intermediate_size=8, num_hidden_layers=2,
        layers_held=2, first_k_dense_replace=1, num_attention_heads=2,
        q_lora_rank=4, kv_lora_rank=4, qk_nope_head_dim=4,
        qk_rope_head_dim=2, v_head_dim=4, rope_theta=10000,
        n_routed_experts=4, n_shared_experts=1, num_experts_per_tok=2,
        moe_intermediate_size=4, norm_topk_prob=True,
        routed_scaling_factor=2, scoring_func="sigmoid", hc_mult=4,
        hc_sinkhorn_iters=3, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
        mhc_h_res_clamp_max=30, num_nextn_predict_layers=1,
        experts_held=2, expert_offset=0, rms_norm_eps=1e-6)
    tx = sl.make_optimizer(sl.SLConfig())
    step = sl.make_train_step(net.module.apply, tx, SIZE, True)
    state = sl.SLState(net.params, tx.init(net.params), jnp.int32(0),
                       pack_rng(jax.random.key(0)))
    ids = jnp.zeros((2, 8), jnp.int32)
    return op_names(jax.jit(step).lower(
        state, ids, ids).compile().as_text())


@pytest.fixture(scope="module")
def ling_ops():
    """The COMPILED supervised train step of the move-sequence policy
    built from a toy ``bailing_hybrid`` spec: a delta layer with the
    dense MLP, a latent layer with experts under a group limit."""
    from rocalphago_tpu.io.checkpoint import pack_rng
    from rocalphago_tpu.models.seqpolicy import SeqPolicy
    from rocalphago_tpu.training import sl

    net = SeqPolicy(
        board=SIZE, model_type="bailing_hybrid", vocab_size=32,
        vocab_held=32, hidden_size=8, intermediate_size=8,
        num_hidden_layers=2, layers_held=2, layer_group_size=2,
        first_k_dense_replace=1, num_attention_heads=2, head_dim=4,
        q_lora_rank=None, kv_lora_rank=4, qk_nope_head_dim=4,
        qk_rope_head_dim=2, v_head_dim=4, rope_theta=10000,
        short_conv_kernel_size=4, kda_lower_bound=-5, num_experts=4,
        num_shared_experts=1, num_experts_per_tok=2,
        moe_intermediate_size=4, moe_shared_expert_intermediate_size=4,
        n_group=2, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.5, scoring_func="sigmoid",
        num_nextn_predict_layers=0, mtp_loss_scaling_factor=0,
        experts_held=2, expert_offset=0, rms_norm_eps=1e-6)
    tx = sl.make_optimizer(sl.SLConfig())
    step = sl.make_train_step(net.module.apply, tx, SIZE, True)
    state = sl.SLState(net.params, tx.init(net.params), jnp.int32(0),
                       pack_rng(jax.random.key(0)))
    ids = jnp.zeros((2, 8), jnp.int32)
    return op_names(jax.jit(step).lower(
        state, ids, ids).compile().as_text())


@pytest.fixture(scope="module")
def ply_ops():
    from rocalphago_tpu.search.selfplay import _make_ply

    def apply(params, planes):     # reads the planes: no dead encode
        return jnp.zeros((planes.shape[0], SIZE * SIZE)) \
            + planes.sum(axis=(1, 2, 3))[:, None]

    ply = _make_ply(CFG, FEATS, apply, apply, 2, 1.0)
    return lowered_hlo(
        functools.partial(ply, None, None), new_states(CFG, 2), None,
        jax.random.key(0), jnp.int32(0))


@pytest.fixture(scope="module")
def search():
    from rocalphago_tpu.search.device_mcts import make_device_mcts

    def fake_policy(params, planes):
        return jnp.zeros((planes.shape[0], SIZE * SIZE))

    def fake_value(params, planes):
        return planes[..., 0].sum(axis=(1, 2)) / (SIZE * SIZE)

    return make_device_mcts(CFG, FEATS, VFEATS, fake_policy,
                            fake_value, n_sim=4, max_nodes=8)


@pytest.fixture(scope="module")
def sim_ops(search):
    """One fused simulation — select, expand, the leaf evaluation
    (``eval_batch``: groups, encode, both nets), backup."""
    roots = new_states(CFG, 2)
    tree = search.assemble_tree(
        roots, jnp.full((2, SIZE * SIZE + 1), 1.0 / (SIZE * SIZE + 1)))
    return lowered_hlo(lambda t: search.simulate(None, None, t), tree)


@pytest.fixture(scope="module")
def kernel_paths():
    """The name stack of every equation of a gated and a latent layer
    traced for a TPU (nothing runs: the kernel's scope exists only in
    such a trace) — ``{"pallas": [...], "all": [...]}``."""
    from rocalphago_tpu.models import seqpolicy

    rope = seqpolicy.Rope("default", 1e4, 64)
    gated = seqpolicy.LayerSpec(heads=2, window=0, rope=rope,
                                sparse=False)
    latent = gated._replace(latent=seqpolicy.Latent(
        q_rank=8, kv_rank=8, nope=128, rope=64, value=128, scale=0.07,
        gate=True))
    module = seqpolicy.SeqPolicyNet(
        layers=(gated, latent), hidden=32, vocab_held=64, kv_heads=1,
        head_dim=128, dense_width=32, ffn=())
    ids = jax.ShapeDtypeStruct((1, seqpolicy.KERNEL_BLOCK), jnp.int32)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(seqpolicy, "kernel_platform", lambda: "tpu")
        params = jax.eval_shape(module.init, jax.random.key(0), ids)
        jaxpr = jax.make_jaxpr(module.apply)(params, ids)
    found = {"pallas": [], "all": []}

    def walk(jaxpr, outer=""):
        # an inner jaxpr's name stacks are relative to its caller's
        for eqn in jaxpr.eqns:
            name = f"{outer}/{eqn.source_info.name_stack}"
            found["all"].append(name)
            if eqn.primitive.name == "pallas_call":
                found["pallas"].append(name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, name)

    walk(jaxpr.jaxpr)
    return found


def has(ops: list, name: str) -> bool:
    return any(name in op.split("/") or f"({name})" in op
               for op in ops)


# --------------------------------------------------------- every name

TRAIN = [scopes.TRAIN_AUGMENT, scopes.TRAIN_LOSS, scopes.TRAIN_UPDATE]
PLY = [scopes.PLY_GROUPS, scopes.PLY_ENCODE, scopes.PLY_FORWARD,
       scopes.PLY_SAMPLE, scopes.PLY_STEP]
ENCODE = [scopes.ENCODE_CANDIDATES, scopes.ENCODE_LADDER,
          scopes.ENCODE_PLANES]
EVAL = [scopes.EVAL_GROUPS, scopes.EVAL_ENCODE, scopes.EVAL_POLICY,
        scopes.EVAL_VALUE]
MCTS = [scopes.MCTS_SELECT, scopes.MCTS_EXPAND, scopes.MCTS_BACKUP]
SEQ = [scopes.SEQ_EMBED, scopes.SEQ_ATTN_FULL, scopes.SEQ_ATTN_WINDOW,
       scopes.SEQ_ROUTER, scopes.SEQ_EXPERTS, scopes.SEQ_SHARED,
       scopes.SEQ_DENSE_FFN, scopes.SEQ_HEAD,
       scopes.SEQ_EXPERTS_SORT, scopes.SEQ_EXPERTS_DISPATCH,
       scopes.SEQ_EXPERTS_ACT, scopes.SEQ_EXPERTS_COMBINE]
#: the parts of a softmax layer beside the kernel's, each inside one
#: of the three layer scopes (Xing's latent layers have no gate)
ATTN_LAYERS = [scopes.SEQ_ATTN_FULL, scopes.SEQ_ATTN_WINDOW,
               scopes.SEQ_ATTN_MLA]
ATTN_PARTS = [scopes.SEQ_ATTN_PROJ, scopes.SEQ_ATTN_ROPE,
              scopes.SEQ_ATTN_GATE, scopes.SEQ_ATTN_OUT]
XING_PARTS = [n for n in ATTN_PARTS if n != scopes.SEQ_ATTN_GATE]
#: the parts inside ``seq.attn.kda.proj``
KDA_PARTS = [scopes.SEQ_ATTN_KDA_CONV, scopes.SEQ_ATTN_KDA_QKNORM,
             scopes.SEQ_ATTN_KDA_DECAY]
#: every scope that lies inside another's and is no top-level one
PARTS = ATTN_PARTS + KDA_PARTS
#: what an ``xing4_0`` spec adds to the sequence step
XING = [scopes.SEQ_ATTN_MLA, scopes.SEQ_MHC_COEFF,
        scopes.SEQ_MHC_SINKHORN, scopes.SEQ_MHC_MIX, scopes.SEQ_MTP]


#: what a ``bailing_hybrid`` spec adds: the delta layers' scope and
#: the three parts inside it
LING = [scopes.SEQ_ATTN_KDA, scopes.SEQ_ATTN_KDA_PROJ,
        scopes.SEQ_ATTN_KDA_SCAN, scopes.SEQ_ATTN_KDA_OUT]


def test_every_constant_has_a_case():
    # the attention kernel's scope exists only in a program traced
    # for a TPU: tests/test_seqpolicy.py lowers it there
    assert sorted(TRAIN + PLY + ENCODE + EVAL + MCTS + SEQ + XING + LING
                  + PARTS + [scopes.SEQ_ATTN_KERNEL]) == sorted(scopes.ALL)
    assert len(set(scopes.ALL)) == len(scopes.ALL)


@pytest.mark.parametrize("name", TRAIN)
def test_train_step_scope_survives_the_compile(train_ops, name):
    assert has(train_ops, name), sorted(set(train_ops))[:40]


@pytest.mark.parametrize("name", TRAIN + SEQ + ATTN_PARTS)
def test_sequence_step_scope_survives_the_compile(seq_ops, name):
    assert has(seq_ops, name), sorted(set(seq_ops))[:40]


@pytest.mark.parametrize("name", SEQ + ATTN_PARTS)
def test_sequence_scopes_name_the_backward_pass_too(seq_ops, name):
    """Forward under ``jvp(SeqPolicyNet)``, backward — and each
    layer's recomputed forward — under ``transpose(jvp(…))``."""
    mine = [op for op in seq_ops if name in op.split("/")]
    assert any("transpose(jvp(SeqPolicyNet))" in op for op in mine)
    assert any("transpose(" not in op for op in mine)


@pytest.mark.parametrize("name", XING + [
    scopes.SEQ_ROUTER, scopes.SEQ_EXPERTS, scopes.SEQ_DENSE_FFN,
    scopes.SEQ_HEAD, scopes.TRAIN_LOSS] + XING_PARTS)
def test_xing_step_scope_survives_the_compile(xing_ops, name):
    assert has(xing_ops, name), sorted(set(xing_ops))[:40]


@pytest.mark.parametrize("name", XING + XING_PARTS)
def test_xing_scopes_name_the_backward_pass_too(xing_ops, name):
    mine = [op for op in xing_ops if name in op.split("/")]
    assert any("transpose(jvp(SeqPolicyNet))" in op for op in mine)
    assert any("transpose(" not in op for op in mine)


def test_xing_scopes_lie_side_by_side(xing_ops):
    """No new scope inside another top-level ``seq.*`` one (the
    attention kernel's aside), so the by-scope account still
    partitions; the MTP module's block runs under the layers'."""
    tops = [n for n in scopes.ALL if n.startswith("seq.")
            and n != scopes.SEQ_ATTN_KERNEL
            and n not in PARTS
            and not n.startswith(scopes.SEQ_EXPERTS + ".")]
    for op in xing_ops:
        inside = [n for n in tops if n in op.split("/")]
        assert len(inside) <= 1, op
    block = [op for op in xing_ops if "/mtp_layer/" in op]
    assert block and not any(scopes.SEQ_MTP in op.split("/")
                             for op in block)
    assert any(scopes.SEQ_ATTN_MLA in op.split("/") for op in block)


@pytest.mark.parametrize("name", LING + [
    scopes.SEQ_ATTN_MLA, scopes.SEQ_ROUTER, scopes.SEQ_EXPERTS,
    scopes.SEQ_DENSE_FFN, scopes.SEQ_HEAD, scopes.TRAIN_LOSS] + PARTS)
def test_ling_step_scope_survives_the_compile(ling_ops, name):
    assert has(ling_ops, name), sorted(set(ling_ops))[:40]


@pytest.mark.parametrize("name", LING + PARTS)
def test_ling_scopes_name_the_backward_pass_too(ling_ops, name):
    mine = [op for op in ling_ops if name in op.split("/")]
    assert any("transpose(jvp(SeqPolicyNet))" in op for op in mine)
    assert any("transpose(" not in op for op in mine)


def test_ling_scopes_lie_side_by_side(ling_ops):
    """The delta layer's three parts lie inside ``seq.attn.kda`` and
    nowhere else, beside each other; no other top-level ``seq.*``
    scope holds or is held by them, so the by-scope account still
    partitions; the group limit runs under the router's scope."""
    parts = LING[1:]
    tops = [n for n in scopes.ALL if n.startswith("seq.")
            and n != scopes.SEQ_ATTN_KERNEL
            and n not in parts + PARTS
            and not n.startswith(scopes.SEQ_EXPERTS + ".")]
    for op in ling_ops:
        path = op.split("/")
        assert len([n for n in tops if n in path]) <= 1, op
        inside = [n for n in parts if n in path]
        assert len(inside) <= 1, op
        if inside:
            assert scopes.SEQ_ATTN_KDA in path, op
    scan = [op for op in ling_ops if scopes.SEQ_ATTN_KDA_SCAN
            in op.split("/")]
    assert any("while" in op for op in scan)     # the loop over chunks
    assert any(scopes.SEQ_ROUTER in op.split("/") and "top_k" in op
               for op in ling_ops)


@pytest.mark.parametrize("ops, name", [
    (ops, name) for ops, names in (
        ("seq_ops", ATTN_PARTS), ("xing_ops", XING_PARTS),
        ("ling_ops", PARTS)) for name in names])
def test_a_part_lies_inside_its_parent_and_beside_the_others(
        request, ops, name):
    """The four parts of a softmax layer each inside exactly one of
    the three layer scopes, the delta layer's three inside
    ``seq.attn.kda.proj`` and no softmax layer; no part inside another,
    so a reader's substring match reads one part's time."""
    mine = [op.split("/") for op in request.getfixturevalue(ops)
            if name in op.split("/")]
    assert mine
    for path in mine:
        assert [n for n in PARTS if n in path] == [name], path
        layers = [n for n in ATTN_LAYERS if n in path]
        if name in KDA_PARTS:
            assert scopes.SEQ_ATTN_KDA_PROJ in path and not layers, path
        else:
            assert len(layers) == 1, path
            assert scopes.SEQ_ATTN_KDA not in path, path


def test_no_part_is_a_substring_of_another_scope():
    """``chipbench``'s readers match a scope by substring: a new name
    may be contained in, or contain, its parents' alone."""
    for name in PARTS:
        for other in scopes.ALL:
            if other == name:
                continue
            assert name not in other, (name, other)
            if other in name:
                assert name in KDA_PARTS and other in (
                    scopes.SEQ_ATTN_KDA, scopes.SEQ_ATTN_KDA_PROJ)


@pytest.mark.parametrize("name", ATTN_PARTS)
def test_the_kernels_scope_holds_no_part(kernel_paths, name):
    """Traced for a TPU, a gated and a gated latent layer: the kernel
    runs under ``seq.attn.kernel`` beside the four parts, never inside
    one, and each part is there."""
    assert len(kernel_paths["pallas"]) == 2
    for path in kernel_paths["pallas"]:
        assert scopes.SEQ_ATTN_KERNEL in path and name not in path
    mine = [p for p in kernel_paths["all"] if name in p.split("/")]
    assert mine
    assert not [p for p in mine if scopes.SEQ_ATTN_KERNEL in p]


def test_train_loss_is_scoped_forward_and_backward(train_ops):
    loss = [op for op in train_ops if scopes.TRAIN_LOSS in op]
    assert any(f"jvp({scopes.TRAIN_LOSS})" in op
               and "transpose(" not in op for op in loss), loss
    assert any(f"transpose(jvp({scopes.TRAIN_LOSS}))" in op
               for op in loss), loss


def test_augmentation_holds_no_scatter(train_hlo):
    """The expert moves go through the dihedral transform by
    arithmetic: a scatter under ``train.augment`` was the longest
    non-convolution op of the step on the chip (PERF.md, PR 25)."""
    augment = [line for line in train_hlo.splitlines()
               if scopes.TRAIN_AUGMENT in line]
    assert augment
    assert not [line for line in augment if "scatter" in line]


def test_flax_module_scopes_are_pinned(train_ops):
    """The by-scope account leans on Flax naming the network's ops by
    module (``flax_profile``): forward under ``jvp(<Net>)``, backward
    under ``transpose(jvp(<Net>))``."""
    assert any("/jvp(PolicyNet)/trunk/conv1/" in op
               for op in train_ops)
    assert any("/transpose(jvp(PolicyNet))/trunk/conv1/" in op
               for op in train_ops)
    assert any("/jvp(PolicyNet)/head/conv/" in op for op in train_ops)


@pytest.mark.parametrize("name", TRAIN)
def test_value_step_carries_the_same_scopes(value_ops, name):
    assert has(value_ops, name)


@pytest.mark.parametrize("name", PLY + ENCODE)
def test_selfplay_ply_scope(ply_ops, name):
    assert has(ply_ops, name), name
    if name in ENCODE:      # the encode's stages sit inside the ply's
        assert any(scopes.PLY_ENCODE in op and name in op
                   for op in ply_ops)


@pytest.mark.parametrize("name", MCTS + EVAL + ENCODE)
def test_simulation_scope(sim_ops, name):
    assert has(sim_ops, name), name
    if name in ENCODE:      # … and inside the leaf evaluation's
        assert any(scopes.EVAL_ENCODE in op and name in op
                   for op in sim_ops)


def test_scopes_are_written_once():
    """Every ``named_scope`` in the package takes a constant of
    ``obs/scopes.py``, and every constant has a call site."""
    used, pkg = set(), os.path.join(ROOT, "rocalphago_tpu")
    for folder, _, files in os.walk(pkg):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(folder, f)
            with open(path) as fh:
                text = fh.read()
            for arg in re.findall(r"named_scope\(\s*([^)]*)\)", text):
                if os.path.relpath(path, pkg) == os.path.join(
                        "obs", "scopes.py"):
                    continue        # its docstring names the call
                m = re.fullmatch(r"scopes\.([A-Z_]+)", arg.strip())
                assert m, f"{path}: named_scope({arg}) is not a " \
                          "constant of obs/scopes.py"
                used.add(getattr(scopes, m.group(1)))
    assert used == set(scopes.ALL), set(scopes.ALL) - used


# ------------------------------------------- spans on the profiler

def test_span_lands_in_a_profiler_capture(tmp_path):
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with trace.span("test.phase"):
            with trace.annotation("test.item"):
                jax.block_until_ready(jnp.arange(8) + 1)
    finally:
        jax.profiler.stop_trace()
    found = sorted(
        os.path.join(folder, f)
        for folder, _, files in os.walk(tmp_path) for f in files
        if f.endswith(".xplane.pb"))
    assert found
    events = {e.name: (e.start_ns, e.duration_ns)
              for plane in ProfileData.from_file(found[-1]).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith(trace.ANNOTATION_PREFIX)}
    assert set(events) == {"rocalphago.test.phase",
                           "rocalphago.test.item"}
    (p0, pd), (i0, idur) = (events["rocalphago.test.phase"],
                            events["rocalphago.test.item"])
    assert p0 <= i0 and i0 + idur <= p0 + pd     # nested, one clock


def test_obs_imports_without_jax_and_spans_still_work():
    code = (
        "import sys\n"
        "import rocalphago_tpu.obs as obs\n"
        "assert 'jax' not in sys.modules\n"
        "with obs.span('a'):\n"
        "    with obs.annotation('b'):\n"
        "        assert obs.current_path() == 'a'\n"
        "assert 'jax' not in sys.modules\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]
