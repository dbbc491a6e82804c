"""The move-sequence policy built from an ``lfm2_moe`` spec
(``models/seqpolicy.py``: gated short convolutions in three layers of
four beside softmax attention with per-head QK-norm, a dense leading
layer, then a sigmoid router steered by a selection bias over routed
experts with no shared expert, and a head tied to the embedding)
against its plain reference (``chipbench/reference_lfm2.py``) at a toy
size that keeps the structure: the published two leading dense
layers, then one whole period (full, conv, conv, conv), 4 query heads
over 2 key/value heads of 8, 4 of 16 experts held. A row is 32
tokens. Seeded random weights; nothing here is a device number.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import reference_lfm2 as reference  # noqa: E402
from rocalphago_tpu.io.checkpoint import pack_rng  # noqa: E402
from rocalphago_tpu.models import seqpolicy  # noqa: E402
from rocalphago_tpu.models.seqpolicy import MOE_STATS, SeqPolicy  # noqa: E402
from rocalphago_tpu.training import sl  # noqa: E402

SEQ, VOCAB, SIZE = 32, 512, 19
TOY = dict(
    model_type="lfm2_moe", vocab_size=2 * VOCAB, vocab_held=VOCAB,
    hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
    num_hidden_layers=12, layers_held=6, num_dense_layers=2,
    num_attention_heads=4, num_key_value_heads=2,
    num_experts=16, num_experts_per_tok=3, experts_held=4,
    expert_offset=4,
    layer_types=["conv", "conv", "full_attention", "conv", "conv",
                 "conv"] * 2,
    conv_L_cache=3, conv_bias=False, norm_eps=1e-5,
    norm_topk_prob=True, routed_scaling_factor=1.5,
    use_expert_bias=True, rope_theta=1000000,
    max_position_embeddings=128)
#: relative L2 error allowed at each compute type (float32: the same
#: arithmetic in another order; bf16: products and the residual stream
#: in bf16 through five layers, with routers that may flip a choice)
TOLERANCE = {"float32": 2e-5, "bfloat16": 0.2}
LOSS_TOLERANCE = {"float32": 2e-6, "bfloat16": 2e-3}


@pytest.fixture(scope="module", autouse=True)
def toy_tiles():
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(seqpolicy, "EXPERT_CHUNK", 16)
        patch.setattr(seqpolicy, "ATTENTION_BLOCK", 8)
        yield


@pytest.fixture(scope="module")
def net(toy_tiles):
    return SeqPolicy(board=SIZE, seed=3, **TOY)


@pytest.fixture(scope="module")
def batch():
    ids = jax.random.randint(jax.random.key(1), (2, SEQ), 0, VOCAB)
    labels = jax.random.randint(jax.random.key(2), (2, SEQ), 0, VOCAB)
    return ids, labels


@pytest.fixture(scope="module")
def wanted(net, batch):
    """The reference's logits, loss and gradient tree."""
    logits = jax.jit(lambda p: reference.forward(p, batch[0], TOY))(
        net.params)
    loss, grads = jax.jit(lambda p: reference.loss_and_grads(
        p, *batch, TOY))(net.params)
    return logits, loss, grads


def module_at(net, dtype: str):
    return net.module.clone(dtype=jnp.dtype(dtype))


def leaf(tree, path):
    node = tree["params"]
    for key in path:
        node = node[key]
    return node


# ------------------------------------------- system vs the reference

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_logits_match_the_reference(net, batch, wanted, dtype):
    logits, extras = jax.jit(module_at(net, dtype).apply)(
        net.params, batch[0])
    assert logits.shape == (2, SEQ, VOCAB)
    assert logits.dtype == jnp.float32
    assert reference.relative_error(logits, wanted[0]) \
        < TOLERANCE[dtype] / 4
    # four expert layers
    assert int(extras["moe_routed"]) == 4 * 2 * SEQ * 3
    assert 0 < int(extras["moe_held"]) < int(extras["moe_routed"])
    assert int(extras["moe_dropped"]) == 0


@pytest.fixture(scope="module")
def program_grads(net, batch):
    """``dtype`` → the trainer's own loss on the program at that
    compute type, and its gradients."""
    made = {}

    def at(dtype: str):
        if dtype not in made:
            module = module_at(net, dtype)
            made[dtype] = jax.jit(jax.value_and_grad(
                lambda p: sl._policy_loss(module.apply, p, *batch)[0])
            )(net.params)
        return made[dtype]

    return at


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_loss_matches_the_reference(program_grads, wanted, dtype):
    loss, _ = program_grads(dtype)
    assert abs(float(loss) - float(wanted[1])) / float(wanted[1]) \
        < LOSS_TOLERANCE[dtype]


#: a leaf of every kind: conv + dense layers 0-1, full layer 2, conv
#: expert layers 3-5
LEAVES = [
    ("embed",), ("norm", "scale"),
    ("layer0", "attn", "in_proj"), ("layer0", "attn", "conv"),
    ("layer1", "attn", "out_proj"), ("layer0", "ffn", "gate_proj"),
    ("layer1", "ffn", "down_proj"), ("layer0", "input_norm", "scale"),
    ("layer2", "attn", "q_proj"), ("layer2", "attn", "k_proj"),
    ("layer2", "attn", "v_proj"), ("layer2", "attn", "o_proj"),
    ("layer2", "attn", "q_norm", "scale"),
    ("layer2", "attn", "k_norm", "scale"),
    ("layer2", "ffn", "router"), ("layer2", "ffn", "experts_gate"),
    ("layer2", "ffn", "experts_down"),
    ("layer3", "attn", "in_proj"), ("layer3", "attn", "conv"),
    ("layer4", "attn", "out_proj"), ("layer4", "ffn", "experts_up"),
    ("layer5", "post_attn_norm", "scale"),
]


@pytest.mark.parametrize("path", LEAVES, ids="/".join)
def test_a_leafs_gradient_matches_the_reference(program_grads, wanted,
                                                path):
    _, grads = program_grads("float32")
    want = leaf(wanted[2], path)
    assert float(jnp.abs(want).max()) > 0
    assert reference.relative_error(leaf(grads, path), want) \
        < TOLERANCE["float32"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_no_other_leaf_is_far_from_the_reference(program_grads, wanted,
                                                 dtype):
    """Every leaf but the selection biases, which get no gradient."""
    _, grads = program_grads(dtype)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    ref = dict(jax.tree_util.tree_flatten_with_path(wanted[2])[0])
    assert len(flat) == len(ref)
    for path, got in flat:
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert float(jnp.abs(got).max()) == 0.0, name
            continue
        assert reference.relative_error(got, ref[path]) \
            < TOLERANCE[dtype], name


def test_the_reference_in_blocks_is_the_reference(net, batch, wanted):
    logits = jax.jit(lambda p: reference.forward(
        p, batch[0], TOY, blocks=True))(net.params)
    assert reference.relative_error(logits, wanted[0]) < 1e-6
    paths = [("layer3", "attn", "conv"),
             ("layer2", "attn", "q_norm", "scale"), ("embed",)]
    loss, grads = jax.jit(lambda p: reference.loss_and_grads(
        p, *batch, TOY, paths=paths, blocks=True))(net.params)
    assert abs(float(loss) - float(wanted[1])) < 1e-5
    for path in paths:
        assert reference.relative_error(
            grads["/".join(path)], leaf(wanted[2], path)) < 1e-5


def test_lowering_the_float32_parts_is_caught(net, batch, wanted):
    """The reference with its float32 parts in bf16 is further from
    the reference than the program in bf16 is."""
    low = jax.jit(lambda p: reference.forward(
        p, batch[0], TOY, dtype=jnp.bfloat16))(net.params)
    program, _ = jax.jit(module_at(net, "bfloat16").apply)(
        net.params, batch[0])
    assert reference.relative_error(low, wanted[0]) \
        > reference.relative_error(program, wanted[0])


# ------------------------------------------- the gated short convolution

def conv_case(key, s_len=SEQ, d=32, taps=3):
    module = seqpolicy.ShortConv(taps, jnp.float32)
    x = jax.random.normal(key, (2, s_len, d))
    params = module.init(jax.random.key(30), x)
    return module, params, x


def test_the_short_convolution_is_the_token_by_token_one():
    module, params, x = conv_case(jax.random.key(31))
    got = module.apply(params, x)
    p = params["params"]
    for row in range(2):
        assert reference.relative_error(
            got[row], reference.short_conv(p, x[row])) < 1e-5
    # by hand: the gate, the three taps, the gate again
    xs = np.asarray(x[0], np.float64)
    bcx = xs @ np.asarray(p["in_proj"], np.float64)
    b, c, xt = np.split(bcx, 3, axis=-1)
    u = np.concatenate([np.zeros((2, 32)), b * xt])
    w = np.asarray(p["conv"], np.float64)
    v = sum(w[j] * u[j:j + SEQ] for j in range(3))
    want = (c * v) @ np.asarray(p["out_proj"], np.float64)
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-6)


def test_the_kept_gated_output_is_the_references_and_bf16_is_not():
    """What the layer keeps for the benchmark's check: its product
    ``[B | C | x̃]`` as the gating reads it and the gated output, both
    in float32, the reference's from that product to float32 rounding;
    the reference's in bf16 is a thousand times further and over
    ``MIX_TOLERANCE``."""
    module, params, x = conv_case(jax.random.key(37))
    module = module.clone(dtype=jnp.bfloat16)
    _, kept = module.apply(params, x.astype(jnp.bfloat16),
                           mutable=["intermediates"])
    bcx, mixed = kept["intermediates"]["mix"][0]
    assert bcx.dtype == mixed.dtype == jnp.float32
    taps = params["params"]["conv"]
    product = x.astype(jnp.bfloat16) @ params["params"]["in_proj"].astype(
        jnp.bfloat16)
    assert reference.relative_error(bcx, product) == 0.0
    want = jnp.stack([reference.gated_mix(r, taps) for r in bcx])
    program = reference.relative_error(mixed, want)
    low = jnp.stack([reference.gated_mix(r, taps, jnp.bfloat16)
                     for r in bcx])
    control = reference.relative_error(low, want)
    assert program < reference.MIX_TOLERANCE / 10
    assert control > 100 * reference.MIX_TOLERANCE


@pytest.mark.parametrize("t", [0, 7, 31])
def test_the_short_convolution_reaches_two_tokens_back_and_no_further(t):
    """A change at token ``t`` moves the mixer's output at ``t``,
    ``t + 1`` and ``t + 2`` and nowhere else: causal, and three taps
    long."""
    module, params, x = conv_case(jax.random.key(32))
    moved = x.at[:, t].add(1.0)
    diff = jnp.abs(module.apply(params, moved) - module.apply(params, x))
    changed = np.asarray(diff.max(axis=(0, 2)) > 0)
    want = np.zeros(SEQ, bool)
    want[t:t + 3] = True
    assert (changed == want).all(), np.nonzero(changed)


@pytest.mark.parametrize("t", [5, 20])
def test_a_change_at_token_t_moves_no_logit_before_t(net, t):
    """Through the convolutions and attention alike: the logits before
    ``t`` are what they were, those from ``t`` on are not."""
    ids = jax.random.randint(jax.random.key(33), (1, SEQ), 0, VOCAB)
    other = ids.at[0, t].set((ids[0, t] + 1) % VOCAB)
    apply = jax.jit(module_at(net, "float32").apply)
    a, b = apply(net.params, ids)[0], apply(net.params, other)[0]
    np.testing.assert_array_equal(a[0, :t], b[0, :t])
    assert bool((jnp.abs(a[0, t:] - b[0, t:]).max(axis=-1) > 0).all())


# ------------------------------------------------ per-head QK-norm

def attention_case(qk_norm: str):
    spec = seqpolicy.LayerSpec(
        heads=4, window=0, rope=seqpolicy.Rope("default", 1e6, 8),
        sparse=False, gate=False, qk_norm=qk_norm)
    return seqpolicy.GatedAttention(spec, 2, 8, jnp.float32, 1e-5)


def test_the_qk_norm_per_head_is_the_references_and_not_the_whole():
    """Per head: one scale of 8 (the head's width), each head of q and
    k normed over its own lanes; over the whole projection (Olmo's)
    the same weights give another layer."""
    x = jax.random.normal(jax.random.key(34), (1, SEQ, 32))
    per_head = attention_case("head")
    params = per_head.init(jax.random.key(35), x)
    p = params["params"]
    assert p["q_norm"]["scale"].shape == p["k_norm"]["scale"].shape \
        == (8,)
    # scales off one, so that a scale in the wrong place shows
    p = dict(p, q_norm={"scale": 1 + 0.3 * jax.random.normal(
        jax.random.key(36), (8,))}, k_norm={"scale": 0.7 * jnp.ones(8)})
    got = per_head.apply({"params": p}, x)
    kw = dict(TOY, hidden_size=32, num_attention_heads=4,
              num_key_value_heads=2)
    want = reference.attention(p, x[0], kw)
    assert reference.relative_error(got[0], want) < 1e-5
    whole = attention_case("whole")
    wide = dict(p, q_norm={"scale": jnp.tile(p["q_norm"]["scale"], 4)},
                k_norm={"scale": jnp.tile(p["k_norm"]["scale"], 2)})
    other = whole.apply({"params": wide}, x)
    assert reference.relative_error(other[0], want) > 0.05


# ------------------------------------------------ the kernel at 64 lanes

def test_the_kernel_takes_64_wide_heads_on_a_tpu():
    import unittest.mock

    with unittest.mock.patch.object(seqpolicy, "kernel_platform",
                                    lambda: "tpu"):
        assert seqpolicy.use_kernel(8192, 64, 64)
        assert not seqpolicy.use_kernel(8192, 32, 32)
    assert not seqpolicy.use_kernel(8192, 64, 64)       # this is a CPU


def test_the_kernel_at_64_is_the_dense_masked_one():
    """The splash kernel at half a lane tile, four query heads over two
    key/value heads, interpreted on the CPU at the smallest shape its
    tiles take: no padding, the scores' scale on the queries."""
    import unittest.mock

    from chipbench.reference_xing import _head

    keys = jax.random.split(jax.random.key(40), 3)
    q = jax.random.normal(keys[0], (1, 256, 4, 64))
    k = jax.random.normal(keys[1], (1, 256, 2, 64))
    v = jax.random.normal(keys[2], (1, 256, 2, 64))
    with unittest.mock.patch.object(seqpolicy, "KERNEL_BLOCK", 128):
        got = seqpolicy.kernel_attention(
            (q / 8.0).transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), 0, interpret=True)
    assert got.shape == (1, 256, 4, 64)
    want = jnp.stack([_head(q[0, :, h], k[0, :, h // 2], v[0, :, h // 2],
                            1 / 8.0) for h in range(4)], axis=1)
    assert reference.relative_error(got[0], want) < 1e-4
    xla = seqpolicy.grouped_attention(q / 8.0, k, v, 0)
    assert reference.relative_error(xla[0], want) < 1e-5


#: the toy at the published head width, 64: two heads over one
#: key/value head in its one attention layer
KERNEL_TOY = dict(TOY, hidden_size=128, num_attention_heads=2,
                  num_key_value_heads=1)


@pytest.mark.parametrize("kept", [True, False])
def test_the_backward_pass_recomputes_a_layer_but_not_its_kernel(
        kernel_gradient, kept):
    """The attention layer's forward, ``dq`` and ``dkv`` — and a second
    forward call where the layer is recomputed whole, as it was; the
    short convolutions keep nothing by name."""
    calls, names = kernel_gradient(KERNEL_TOY, kept)
    assert calls == (3 if kept else 4)
    assert names == {seqpolicy.KERNEL_RESIDUALS}


# ------------------------------------------------------- the tied head

def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses(
        net, batch, program_grads):
    """With the lookup and the head read from two tables equal to
    ``embed``, their gradients add up to the program's gradient of
    the one tied leaf; each part moves the rows it should."""
    p = net.params["params"]
    dtype = jnp.float32

    def two_tables(lookup, head):
        def row(ids):
            x = lookup[ids]
            for i in range(TOY["layers_held"]):
                x, _ = reference.layer(p[f"layer{i}"], x, i, TOY, False)
            n = reference._rms(x, p["norm"]["scale"], TOY["norm_eps"],
                               dtype)
            return n @ head.T

        with jax.default_matmul_precision("highest"):
            logits = jnp.stack([row(r) for r in batch[0]])
        return reference.loss_of(logits, batch[1])

    by_lookup, by_head = jax.jit(jax.grad(two_tables, argnums=(0, 1)))(
        p["embed"], p["embed"])
    _, grads = program_grads("float32")
    tied = grads["params"]["embed"]
    assert reference.relative_error(tied, by_lookup + by_head) < 2e-5
    # a row never looked up gets the head's part alone; every row the
    # head's, since every logit reads its row
    seen = np.zeros(VOCAB, bool)
    seen[np.asarray(batch[0]).ravel()] = True
    assert not bool(jnp.abs(by_lookup[~seen]).max())
    assert bool((jnp.abs(by_head).max(axis=1) > 0).all())
    assert reference.relative_error(tied[~seen], by_head[~seen]) < 2e-5
    assert "head" not in net.params["params"]


# --------------------------------------------- the expert layer's share

def ffn_module(held: int, offset: int, experts: int = 16):
    return seqpolicy.SparseFFN(
        num_experts=experts, top_k=4, width=16, shared_width=0,
        experts_held=held, expert_offset=offset, norm_topk=True,
        routed_scale=1.0, scoring="sigmoid", dtype=jnp.float32)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The cell's split at toy widths: 32 routed experts over four
    chips with 8 each (``expert_offset`` 0, 8, 16, 24) and no shared
    expert, so nothing is counted once: the shares' outputs sum to
    the uncut reference layer, and every pair lands on one chip."""
    whole = ffn_module(32, 0, experts=32)
    x = jax.random.normal(jax.random.key(15), (2, SEQ, 32))
    params = whole.init(jax.random.key(16), x)["params"]
    assert "shared" not in params
    kw = dict(TOY, num_experts=32, num_experts_per_tok=4,
              experts_held=32, expert_offset=0, routed_scaling_factor=1.0)
    flat = x.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        want = reference.sparse_ffn(
            params, flat, kw, shared=False,
            weights=reference.routed_weights(params, flat, kw))
    total, pairs = jnp.zeros_like(flat), 0
    for offset in range(0, 32, 8):
        mine = dict(params, **{
            name: params[name][offset:offset + 8]
            for name in ("experts_gate", "experts_up", "experts_down")})
        out, stats = ffn_module(8, offset, experts=32).apply(
            {"params": mine}, x)
        total = total + out.reshape(-1, 32)
        pairs += int(stats["moe_held"])
        assert int(stats["moe_dropped"]) == 0
    assert pairs == 2 * SEQ * 4
    assert reference.relative_error(total, want) < 1e-5


def test_the_weights_are_the_chosen_scores_over_their_sum_and_eps():
    """The chosen scores over their sum: the family's ε under the sum
    is left out, by program and reference alike (``ASSUMED``)."""
    module = ffn_module(16, 0)
    x = jax.random.normal(jax.random.key(17), (1, 8, 32))
    params = module.init(jax.random.key(18), x)
    (_, _), kept = module.apply(params, x, mutable=["intermediates"])
    chosen = np.asarray(kept["intermediates"]["chosen"][0])
    p = params["params"]
    s = np.asarray(jax.nn.sigmoid(x[0] @ p["router"]), np.float64)
    want = np.argsort(-(s + np.asarray(p["router_bias"])), -1)[:, :4]
    assert (np.sort(chosen, -1) == np.sort(want, -1)).all()
    kw = dict(TOY, num_experts=16, num_experts_per_tok=4,
              routed_scaling_factor=1.0)
    weights = np.asarray(reference.routed_weights(p, x[0], kw))
    picked = np.take_along_axis(s, chosen, -1)
    np.testing.assert_allclose(
        np.take_along_axis(weights, chosen, -1),
        picked / picked.sum(-1, keepdims=True), rtol=1e-5)


# ---------------------------------------------------- the step and spec

def test_the_step_runs_through_the_trainer_and_moves_the_bias(net,
                                                              batch):
    """``make_train_step`` with ``make_optimizer(SLConfig())``: a loss,
    the routing counts, every matrix moved by the update and every
    selection bias by the published rule."""
    tx = sl.make_optimizer(sl.SLConfig())
    step = jax.jit(sl.make_train_step(net.module.apply, tx, SIZE, True))
    state = sl.SLState(net.params, tx.init(net.params), jnp.int32(0),
                       pack_rng(jax.random.key(0)))
    new, metrics = step(state, *batch)
    assert set(metrics) == {"loss", "accuracy", *MOE_STATS}
    assert np.isfinite(float(metrics["loss"]))
    assert int(metrics["moe_routed"]) == 4 * 2 * SEQ * 3
    for name in ("layer2", "layer3", "layer4", "layer5"):
        moved = (new.params["params"][name]["ffn"]["router_bias"]
                 - net.params["params"][name]["ffn"]["router_bias"])
        assert float(jnp.abs(moved).max()) == pytest.approx(0.001)
    moved = jax.tree.map(lambda a, b: a.ndim < 2 or bool((a != b).any()),
                         net.params, new.params)
    assert all(jax.tree.leaves(moved))


def test_the_spec_builds_the_published_block(net):
    layers = net.module.layers
    assert [s.short_conv for s in layers] == [3, 3, 0, 3, 3, 3]
    assert [s.sparse for s in layers] == [False] * 2 + [True] * 4
    assert all(s.qk_norm == "head" and not s.gate and not s.post_norm
               for s in layers)
    assert layers[2].rope == seqpolicy.Rope("default", 1e6, 8)
    assert net.module.tied and net.module.eps == 1e-5
    fields = dict(net.module.ffn)
    assert (fields["shared_width"], fields["scoring"],
            fields["num_experts"]) == (0, "sigmoid", 16)
    p = net.params["params"]
    assert set(p) == {"embed", "norm", *(f"layer{i}" for i in range(6))}
    assert set(p["layer0"]["attn"]) == {"in_proj", "conv", "out_proj"}
    assert p["layer0"]["attn"]["in_proj"].shape == (32, 96)
    assert p["layer0"]["attn"]["conv"].shape == (3, 32)
    assert set(p["layer2"]["attn"]) == {"q_proj", "k_proj", "v_proj",
                                        "o_proj", "q_norm", "k_norm"}
    assert set(p["layer2"]["ffn"]) == {"router", "router_bias",
                                       "experts_gate", "experts_up",
                                       "experts_down"}


def test_every_dense_layer_held_keeps_the_published_order():
    """A stage is the published layers 0 to ``layers_held − 1``: of
    three, the two dense convolutions and the first attention layer."""
    net = SeqPolicy(board=SIZE, init_weights=False,
                    **dict(TOY, layers_held=3))
    assert [s.short_conv for s in net.module.layers] == [3, 3, 0]
    assert [s.sparse for s in net.module.layers] == [False] * 2 + [True]


@pytest.mark.parametrize("key,value", [
    ("layer_types", ["conv", "conv", "sliding_attention", "conv",
                     "conv", "conv"] * 2),
    ("conv_bias", True), ("use_expert_bias", False),
    ("tie_word_embeddings", False), ("model_type", "lfm2"),
    ("hidden_act", "gelu"),
])
def test_a_spec_that_asks_for_what_is_not_computed_is_refused(key,
                                                              value):
    with pytest.raises(ValueError, match=key):
        SeqPolicy(board=SIZE, init_weights=False,
                  **dict(TOY, **{key: value}))


def test_a_layer_kind_that_is_not_held_is_no_refusal():
    kinds = list(TOY["layer_types"])
    kinds[-1] = "sliding_attention"
    SeqPolicy(board=SIZE, init_weights=False,
              **dict(TOY, layer_types=kinds))


@pytest.mark.parametrize("other", ["test_seqpolicy_olmo",
                                   "test_seqpolicy_xing"])
def test_another_dialect_keeps_its_untied_head(other):
    """A tie is computed where a reference reads it (this dialect's);
    the others' configs publish an untied head, and a tie there is
    refused."""
    import importlib

    toy = importlib.import_module(other).TOY
    net = SeqPolicy(board=SIZE, init_weights=False, **toy)
    assert not net.module.tied
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        SeqPolicy(board=SIZE, init_weights=False,
                  **dict(toy, tie_word_embeddings=True))
