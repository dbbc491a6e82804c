"""The move-sequence policy built from an ``xing4_0`` spec
(``models/seqpolicy.py``: latent attention, hyper-connections, a
sigmoid router with a selection bias, a multi-token-prediction
module) against its plain reference (``chipbench/reference_xing.py``)
at a toy size that keeps the structure: one dense layer and two
expert layers, 4 heads of 12 for queries and keys and 8 for values,
4 streams, 16 experts top-3 of which 4 are held. Seeded random
weights, the hyper-connections' parameters moved off their
near-identity start so that every coefficient matters; nothing here
is a device number.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import reference_xing as reference  # noqa: E402
from rocalphago_tpu.models import NeuralNetBase, seqpolicy  # noqa: E402
from rocalphago_tpu.models.seqpolicy import SeqPolicy  # noqa: E402
from rocalphago_tpu.training import sl  # noqa: E402

SEQ, VOCAB, SIZE = 32, 512, 19
TOY = dict(
    model_type="xing4_0", vocab_size=VOCAB, vocab_held=VOCAB,
    hidden_size=32, intermediate_size=64, num_hidden_layers=3,
    layers_held=3, first_k_dense_replace=1, num_attention_heads=4,
    num_key_value_heads=4, q_lora_rank=16, kv_lora_rank=12,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    rope_theta=10000,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64,
                  "mscale": 1, "mscale_all_dim": 1,
                  "original_max_position_embeddings": 16,
                  "type": "yarn"},
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=3,
    moe_intermediate_size=16, moe_layer_freq=1, n_group=1,
    topk_group=1, topk_method="noaux_tc", scoring_func="sigmoid",
    norm_topk_prob=True, routed_scaling_factor=2, hidden_act="silu",
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
    mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
    num_nextn_predict_layers=1, experts_held=4, expert_offset=4,
    rms_norm_eps=1e-6)
#: relative L2 error allowed at each compute type (as for the laguna
#: block: float32 is the same arithmetic in another order)
TOLERANCE = {"float32": 2e-5, "bfloat16": 0.2}
LOSS_TOLERANCE = {"float32": 2e-6, "bfloat16": 2e-3}


@pytest.fixture(scope="module", autouse=True)
def toy_tiles():
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(seqpolicy, "EXPERT_CHUNK", 16)
        patch.setattr(seqpolicy, "ATTENTION_BLOCK", 8)
        yield


def off_identity(params, key):
    """The hyper-connections' ``alpha`` up by a half and their biases
    spread by a half: streams that differ, coefficients that move
    with the token."""
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    moved = []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        if "alpha_" in name:
            leaf = leaf + 0.5
        elif any(b in name for b in ("b_pre", "b_post", "b_res")):
            leaf = leaf + 0.5 * jax.random.normal(
                jax.random.fold_in(key, i), leaf.shape)
        moved.append(leaf)
    return jax.tree_util.tree_unflatten(tree, moved)


@pytest.fixture(scope="module")
def net(toy_tiles):
    net = SeqPolicy(board=SIZE, seed=3, **TOY)
    net.params = off_identity(net.params, jax.random.key(9))
    return net


@pytest.fixture(scope="module")
def batch():
    ids = jax.random.randint(jax.random.key(1), (2, SEQ), 0, VOCAB)
    labels = jax.random.randint(jax.random.key(2), (2, SEQ), 0, VOCAB)
    return ids, labels


@pytest.fixture(scope="module")
def wanted(net, batch):
    """The reference's two heads' logits, loss and gradient tree."""
    heads = reference.forward(net.params, *batch, TOY)
    loss, grads = reference.loss_and_grads(net.params, *batch, TOY)
    return heads, loss, grads


def module_at(net, dtype: str):
    return net.module.clone(dtype=jnp.dtype(dtype))


# ------------------------------------------- system vs the reference

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_both_heads_logits_match_the_reference(net, batch, wanted,
                                               dtype):
    logits, extras = jax.jit(module_at(net, dtype).apply)(
        net.params, *batch)
    assert logits.shape == extras["mtp_logits"].shape \
        == (2, SEQ, VOCAB)
    assert logits.dtype == extras["mtp_logits"].dtype == jnp.float32
    assert reference.relative_error(logits, wanted[0][0]) \
        < TOLERANCE[dtype] / 4
    assert reference.relative_error(extras["mtp_logits"],
                                    wanted[0][1]) < TOLERANCE[dtype] / 4
    # two expert layers and the MTP block's
    assert int(extras["moe_routed"]) == 3 * 2 * SEQ * 3
    assert 0 < int(extras["moe_held"]) < int(extras["moe_routed"])
    assert int(extras["moe_dropped"]) == 0


def test_without_next_ids_there_is_no_second_head(net, batch):
    logits, extras = jax.jit(module_at(net, "float32").apply)(
        net.params, batch[0])
    assert "mtp_logits" not in extras
    assert int(extras["moe_routed"]) == 2 * 2 * SEQ * 3
    both, _ = jax.jit(module_at(net, "float32").apply)(
        net.params, *batch)
    np.testing.assert_array_equal(logits, both)
    np.testing.assert_array_equal(net.forward(batch[0]).shape,
                                  (2, SEQ, VOCAB))


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def program_grads(request, net, batch):
    """(dtype, loss, MTP loss, gradient tree) through the trainer's
    own loss."""
    apply = module_at(net, request.param).apply

    def loss(p):
        total, _, extras = sl._policy_loss(apply, p, *batch)
        return total, extras["mtp_loss"]

    (total, mtp), grads = jax.jit(jax.value_and_grad(
        loss, has_aux=True))(net.params)
    return request.param, total, mtp, grads


def test_loss_matches_the_reference(program_grads, wanted, batch):
    dtype, total, mtp, _ = program_grads
    assert abs(float(total) - float(wanted[1])) / float(wanted[1]) \
        < LOSS_TOLERANCE[dtype]
    _, want_mtp = reference.loss_of(*wanted[0], batch[1])
    assert abs(float(mtp) - float(want_mtp)) / float(want_mtp) \
        < 4 * LOSS_TOLERANCE[dtype]


#: a leaf of every kind the spec adds, and of those it shares
LEAVES = [
    ("embed",), ("head",), ("norm", "scale"),
    ("layer0", "ffn", "up_proj"),
    ("layer1", "attn", "q_a_proj"), ("layer1", "attn", "q_b_proj"),
    ("layer1", "attn", "kv_a_proj"), ("layer1", "attn", "kv_b_proj"),
    ("layer1", "attn", "o_proj"),
    ("layer1", "attn", "q_a_norm", "scale"),
    ("layer1", "attn", "kv_a_norm", "scale"),
    ("layer1", "attn_hc", "phi_res"), ("layer1", "attn_hc", "phi_pre"),
    ("layer1", "attn_hc", "phi_post"), ("layer1", "attn_hc", "norm"),
    ("layer1", "attn_hc", "b_res"),
    ("layer1", "ffn_hc", "b_pre"), ("layer1", "ffn_hc", "b_post"),
    ("layer1", "ffn", "router"), ("layer1", "ffn", "experts_gate"),
    ("layer1", "ffn", "experts_down"),
    ("layer2", "ffn", "shared", "down_proj"),
    ("mtp_eh_proj",), ("mtp_hnorm", "scale"), ("mtp_enorm", "scale"),
    ("mtp_norm", "scale"), ("mtp_layer", "attn", "kv_b_proj"),
    ("mtp_layer", "attn_hc", "phi_post"),
    ("mtp_layer", "ffn", "experts_up"),
]


@pytest.mark.parametrize("path", LEAVES, ids="/".join)
def test_a_leafs_gradient_matches_the_reference(program_grads, wanted,
                                                path):
    dtype, _, _, grads = program_grads
    got = reference.pick(grads, [path])["/".join(path)]
    want = reference.pick(wanted[2], [path])["/".join(path)]
    assert float(jnp.linalg.norm(want)) > 1e-6, "a gradient of nothing"
    limit = TOLERANCE[dtype]
    if any("_hc" in key for key in path):
        # twenty Sinkhorn iterations and their transposes: the same
        # arithmetic in another order, forty divisions deep; in bf16 a
        # few numbers a sublayer, each a sum over every token of
        # small differences between streams
        limit = {"float32": 1e-4, "bfloat16": 0.3}[dtype]
    assert reference.relative_error(got, want) < limit


def test_no_other_leaf_is_far_from_the_reference(program_grads, wanted):
    """Every leaf, the error against the whole tree's size: a leaf
    whose gradient is nothing by construction (the selection bias;
    ``H_res`` of the last sublayer before the streams are summed,
    whose columns sum to one whatever it is) has nothing to be
    relative to."""
    dtype, _, _, grads = program_grads
    if dtype != "float32":
        pytest.skip("the float32 program is the one held to 1e-5")
    whole = np.sqrt(sum(float(jnp.sum(g * g))
                        for g in jax.tree.leaves(wanted[2])))
    for got, want in zip(jax.tree.leaves(grads),
                         jax.tree.leaves(wanted[2])):
        assert float(jnp.linalg.norm(got - want)) < 1e-5 * whole


def test_the_reference_in_blocks_is_the_reference(net, batch, wanted):
    heads = reference.forward(net.params, *batch, TOY, blocks=True)
    for got, want in zip(heads, wanted[0]):
        assert reference.relative_error(got, want) < 1e-5
    loss, grads = reference.loss_and_grads(
        net.params, *batch, TOY, blocks=True,
        paths=[("layer1", "attn_hc", "phi_res"), ("mtp_eh_proj",)])
    assert abs(float(loss) - float(wanted[1])) < 1e-5
    for name, got in grads.items():
        want = reference.pick(wanted[2], [tuple(name.split("/"))])[name]
        assert reference.relative_error(got, want) < 1e-4


def test_lowering_the_float32_parts_is_caught(net, batch, wanted):
    """The reference with router, softmax, norms, loss and Sinkhorn
    in bf16 strays further from the reference than the program
    does."""
    low = reference.forward(net.params, *batch, TOY,
                            dtype=jnp.bfloat16)
    got, extras = jax.jit(module_at(net, "bfloat16").apply)(
        net.params, *batch)
    for lowered, mine, want in zip(
            low, (got, extras["mtp_logits"]), wanted[0]):
        assert reference.relative_error(lowered, want) \
            > 1.5 * reference.relative_error(mine, want)


# ----------------------------------------------------- latent attention

def test_latent_attention_is_a_plain_softmax_per_head_at_192_and_128():
    """The module at the PUBLISHED head sizes (128 + 64 for queries
    and keys, 128 for values; 2 heads, hidden 64) against one dense
    masked softmax per head written out here."""
    kw = dict(TOY, num_attention_heads=2, hidden_size=64,
              q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=128,
              qk_rope_head_dim=64, v_head_dim=128, layers_held=1)
    spec = seqpolicy.latent_layer_specs(kw)[0]
    assert (spec.latent.nope + spec.latent.rope, spec.latent.value) \
        == (192, 128)
    m = 0.1 * np.log(64) + 1
    assert spec.latent.scale == pytest.approx(m * m / np.sqrt(192))
    assert spec.rope.attention_factor == 1.0
    module = seqpolicy.LatentAttention(spec, 1e-6, jnp.float32)
    x = jax.random.normal(jax.random.key(0), (1, 16, 64))
    params = module.init(jax.random.key(1), x)
    # weights large enough that the softmax is far from uniform
    params = jax.tree.map(lambda w: w * 20 if w.ndim == 2 else w,
                          params)
    got = module.apply(params, x)
    want = reference.latent_attention(params["params"], x[0], kw)
    assert got.shape == (1, 16, 64)
    assert reference.relative_error(got[0], want) < 1e-5
    # and the reference is what its docstring says, head by head
    p = params["params"]

    def rms(v, scale):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-6) * scale

    xs = np.asarray(x[0], np.float64)
    q = (rms(xs @ p["q_a_proj"], p["q_a_norm"]["scale"])
         @ p["q_b_proj"]).reshape(16, 2, 192)
    kv_a = xs @ p["kv_a_proj"]
    kv = (rms(kv_a[:, :32], p["kv_a_norm"]["scale"])
          @ p["kv_b_proj"]).reshape(16, 2, 256)
    inv = seqpolicy.rope_inv_freq(spec.rope).astype(np.float64)
    angle = np.arange(16)[:, None] * inv[None]

    def rotate(v):                          # [16, 64], half-split
        a, b = v[:, :32], v[:, 32:]
        return np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                               b * np.cos(angle) + a * np.sin(angle)], 1)

    k_pe = rotate(kv_a[:, 32:])
    heads = []
    for h in range(2):
        qh = np.concatenate([q[:, h, :128], rotate(q[:, h, 128:])], 1)
        kh = np.concatenate([kv[:, h, :128], k_pe], 1)
        s = qh @ kh.T * m * m / np.sqrt(192)
        s = np.where(np.tril(np.ones((16, 16), bool)), s, -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        heads.append(e / e.sum(-1, keepdims=True) @ kv[:, h, 128:])
    by_hand = np.concatenate(heads, 1) @ p["o_proj"]
    assert reference.relative_error(want, by_hand) < 1e-5


def test_the_kernel_takes_the_published_heads_on_a_tpu():
    import unittest.mock

    with unittest.mock.patch.object(seqpolicy, "kernel_platform",
                                    lambda: "tpu"):
        assert seqpolicy.use_kernel(8192, 192, 128)
        assert seqpolicy.use_kernel(8192, 128, 128)
        assert not seqpolicy.use_kernel(8192, 192, 96)
        assert not seqpolicy.use_kernel(8192, 12, 8)
    assert not seqpolicy.use_kernel(8192, 192, 128)     # this is a CPU


def test_the_kernel_at_192_and_128_is_the_dense_masked_one():
    """The splash kernel with a value head narrower than the key
    head, interpreted on the CPU at the smallest shape its tiles
    take."""
    import unittest.mock

    keys = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(keys[0], (1, 256, 2, 192))
    k = jax.random.normal(keys[1], (1, 256, 2, 192))
    v = jax.random.normal(keys[2], (1, 256, 2, 128))
    with unittest.mock.patch.object(seqpolicy, "KERNEL_BLOCK", 128):
        got = seqpolicy.kernel_attention(q / np.sqrt(192), k, v, 0,
                                         interpret=True)
    assert got.shape == (1, 256, 2, 128)
    want = jnp.stack([reference._head(q[0, :, h], k[0, :, h],
                                      v[0, :, h], 1 / np.sqrt(192))
                      for h in range(2)], axis=1)
    assert reference.relative_error(got[0], want) < 1e-4
    xla = seqpolicy.grouped_attention(q / np.sqrt(192), k, v, 0)
    assert reference.relative_error(xla[0], want) < 1e-5


# what a block's recomputation keeps: the kernel's output and row
# statistics by name (tests/test_seqpolicy.py has the gated layers')

#: the toy at the published heads, 192 for queries and keys and 128
#: for values: three layers and the MTP's block, each with a kernel
KERNEL_TOY = dict(TOY, qk_nope_head_dim=128, qk_rope_head_dim=64,
                  v_head_dim=128)
KERNEL_BLOCKS = 4


@pytest.mark.parametrize("kept", [True, False])
def test_the_backward_pass_recomputes_a_block_but_not_its_kernel(
        kernel_gradient, kept):
    """Forward, ``dq`` and ``dkv`` a block, the MTP's among them,
    through the streams' mixing — and a second forward call where
    the block is recomputed whole, as it was."""
    calls, names = kernel_gradient(KERNEL_TOY, kept)
    assert calls == (3 if kept else 4) * KERNEL_BLOCKS
    assert names == {seqpolicy.KERNEL_RESIDUALS}


def test_the_policy_keeps_nothing_of_the_xla_form(
        net, batch, whole_layer_remat):
    """No value of the XLA form carries the name, so both losses and
    every gradient are the whole-block recomputation's to the bit:
    the lowered program is that one's, letter for letter."""
    def lowered():
        return jax.jit(jax.value_and_grad(
            lambda p: sl._policy_loss(
                net.module.apply, p, *batch)[::2],
            has_aux=True)).lower(net.params).as_text()

    # two traces of the whole step: jax's own checks of every
    # equation are not what is compared
    with jax.enable_checks(False):
        kept = lowered()
        with whole_layer_remat():
            assert lowered() == kept


# ---------------------------------------------------- hyper-connections

def raw_matrices(n: int = 4, tokens: int = 6):
    return jax.random.normal(jax.random.key(5), (n, n, tokens))


def test_sinkhorn_makes_doubly_stochastic_matrices():
    m = seqpolicy.sinkhorn(raw_matrices(), 20, 1e-6)
    assert m.shape == (4, 4, 6) and bool((m > 0).all())
    np.testing.assert_allclose(m.sum(axis=0), 1.0, atol=1e-4)
    np.testing.assert_allclose(m.sum(axis=1), 1.0, atol=1e-4)
    # tokens on the last axis here, on the first in the reference
    want = reference.sinkhorn(raw_matrices().transpose(2, 0, 1), 20,
                              1e-6)
    np.testing.assert_allclose(m.transpose(2, 0, 1), want, rtol=1e-5)


def test_sinkhorns_gradient_is_the_finite_difference():
    jax.config.update("jax_enable_x64", True)
    try:
        raw = raw_matrices().astype(jnp.float64)
        weight = jax.random.normal(jax.random.key(6), raw.shape,
                                   jnp.float64)

        def f(r):
            return (seqpolicy.sinkhorn(r, 20, 1e-6) * weight).sum()

        grad = np.asarray(jax.grad(f)(raw))
        assert grad.dtype == np.float64
        rng = np.random.default_rng(0)
        for _ in range(8):
            i = tuple(rng.integers(0, s) for s in raw.shape)
            step = np.zeros(raw.shape)
            step[i] = 1e-5
            numeric = (f(raw + step) - f(raw - step)) / 2e-5
            assert abs(grad[i] - numeric) < 1e-6 * max(1, abs(numeric))
    finally:
        jax.config.update("jax_enable_x64", False)


def test_one_stream_with_unit_coefficients_is_the_plain_residual():
    x = jax.random.normal(jax.random.key(7), (2, 5, 8))
    y = jax.random.normal(jax.random.key(8), (2, 5, 8))
    np.testing.assert_allclose(
        seqpolicy.mix_in(x, jnp.ones((1, 10))), x, rtol=1e-6)
    np.testing.assert_allclose(
        seqpolicy.mix_out(x, y, jnp.ones((1, 1, 10)), jnp.ones((1, 10))),
        x + y, rtol=1e-6)


def test_the_mixes_are_the_matrix_products_per_token():
    n, d, tokens = 4, 8, 10
    x = jax.random.normal(jax.random.key(7), (2, 5, n * d))
    y = jax.random.normal(jax.random.key(8), (2, 5, d))
    pre = jax.random.normal(jax.random.key(9), (n, tokens))
    post = jax.random.normal(jax.random.key(10), (n, tokens))
    res = jax.random.normal(jax.random.key(11), (n, n, tokens))
    xs = x.reshape(tokens, n, d)
    np.testing.assert_allclose(
        seqpolicy.mix_in(x, pre).reshape(tokens, d),
        jnp.einsum("nt,tnd->td", pre, xs), rtol=1e-5, atol=1e-6)
    want = (jnp.einsum("ijt,tjd->tid", res, xs)
            + post.T[:, :, None] * y.reshape(tokens, 1, d))
    np.testing.assert_allclose(
        seqpolicy.mix_out(x, y, res, post).reshape(tokens, n, d), want,
        rtol=1e-5, atol=1e-6)


def test_the_coefficients_are_the_references(net):
    """``HyperConnection`` (the norm folded into one product, tokens
    on the last axis) against the reference's three products."""
    x = jax.random.normal(jax.random.key(12), (2, SEQ, 4 * 32))
    hc = net.params["params"]["layer1"]["attn_hc"]
    pre, post, res = seqpolicy.HyperConnection(
        seqpolicy.Hyper(4, 20, 1e-6, (-30.0, 30.0)), 1e-6).apply(
            {"params": hc}, x)
    want = reference.hyper_coefficients(
        hc, x.reshape(2 * SEQ, 4, 32), TOY)
    np.testing.assert_allclose(pre.T, want[0], rtol=2e-5)
    np.testing.assert_allclose(post.T, want[1], rtol=2e-5)
    np.testing.assert_allclose(res.transpose(2, 0, 1), want[2],
                               rtol=2e-5, atol=1e-7)
    # the columns were normalised last; the rows are as near as
    # twenty iterations bring these spread matrices
    np.testing.assert_allclose(res.sum(axis=0), 1.0, atol=1e-5)
    np.testing.assert_allclose(res.sum(axis=1), 1.0, atol=1e-2)


def test_the_streams_start_near_the_plain_residual():
    """At the start ``H_pre`` is a mean, ``H_post`` one and ``H_res``
    nearly the identity."""
    fresh = SeqPolicy(board=SIZE, seed=0, **TOY)
    hc = fresh.params["params"]["layer1"]["ffn_hc"]
    x = jax.random.normal(jax.random.key(12), (1, 8, 4 * 32))
    pre, post, res = seqpolicy.HyperConnection(
        seqpolicy.Hyper(4, 20, 1e-6, (-30.0, 30.0)), 1e-6).apply(
            {"params": hc}, x)
    np.testing.assert_allclose(pre, 0.25, atol=0.01)
    np.testing.assert_allclose(post, 1.0, atol=0.02)
    eye = np.eye(4)[:, :, None]
    assert float(jnp.abs(res - eye).max()) < 0.06
    assert float(jnp.abs(res - eye).max()) > 0.01    # and is not it


# ------------------------------------------------------------ the router

def ffn_module(held: int, offset: int, experts: int = 16):
    return seqpolicy.SparseFFN(
        num_experts=experts, top_k=3, width=16, shared_width=16,
        experts_held=held, expert_offset=offset, norm_topk=True,
        routed_scale=2.0, scoring="sigmoid", dtype=jnp.float32)


@pytest.fixture(scope="module")
def router_case():
    module = ffn_module(16, 0)
    x = jax.random.normal(jax.random.key(13), (1, 32, 32))
    params = module.init(jax.random.key(14), x)
    # a router that spreads its scores
    params = {"params": dict(params["params"],
                             router=params["params"]["router"] * 10)}
    return module, params, x


def chosen_of(module, params, x):
    (_, _), kept = module.apply(params, x, mutable=["intermediates"])
    return np.asarray(kept["intermediates"]["chosen"][0])


def test_selection_follows_score_plus_bias_and_weights_the_score(
        router_case):
    module, params, x = router_case
    p = params["params"]
    s = jax.nn.sigmoid(x[0] @ p["router"])
    assert float(jnp.abs(p["router_bias"]).max()) > 0   # seeded
    want = np.argsort(-(s + p["router_bias"]), axis=-1)[:, :3]
    got = chosen_of(module, params, x)
    assert (np.sort(got, -1) == np.sort(want, -1)).all()
    # a bias that flips a choice...
    loser = int(np.argmin(s[0]))
    assert loser not in got[0]
    flipped = dict(p, router_bias=p["router_bias"].at[loser].set(5.0))
    now = chosen_of(module, {"params": flipped}, x)
    assert (now == loser).any(axis=-1).all()
    # ...leaves the weights' formula as it was: the chosen scores,
    # without the bias, renormalised and scaled
    out, _ = module.apply({"params": flipped}, x)
    weights = np.zeros((32, 16), np.float32)
    picked = np.take_along_axis(np.asarray(s), now, axis=-1)
    np.put_along_axis(weights, now,
                      2.0 * picked / picked.sum(-1, keepdims=True), -1)
    kw = dict(TOY, experts_held=16, expert_offset=0)
    want_out = reference.sparse_ffn(flipped, x[0], kw,
                                    weights=jnp.asarray(weights))
    assert reference.relative_error(out[0], want_out) < 1e-5
    assert reference.relative_error(
        out[0], reference.sparse_ffn(flipped, x[0], kw)) < 1e-5


def test_the_selection_bias_gets_no_gradient(router_case):
    module, params, x = router_case
    grads = jax.grad(
        lambda p: (module.apply(p, x)[0] ** 2).sum())(params)["params"]
    assert float(jnp.abs(grads["router_bias"]).max()) == 0.0
    assert float(jnp.abs(grads["router"]).max()) > 0.0


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The published split at toy widths: 64 routed experts, eight
    chips with 8 each (``expert_offset`` 0, 8, …, 56). The shares'
    routed parts, and the shared expert counted once, are the uncut
    reference layer."""
    whole = ffn_module(64, 0, experts=64)
    x = jax.random.normal(jax.random.key(15), (2, SEQ, 32))
    params = whole.init(jax.random.key(16), x)["params"]
    kw = dict(TOY, n_routed_experts=64, experts_held=64, expert_offset=0)
    flat = x.reshape(-1, 32)
    want = reference.sparse_ffn(params, flat, kw)
    shared = reference.sparse_ffn(params, flat, kw) \
        - reference.sparse_ffn(params, flat, kw, shared=False)
    total, pairs = jnp.zeros_like(flat), 0
    for offset in range(0, 64, 8):
        mine = dict(params, **{
            name: params[name][offset:offset + 8]
            for name in ("experts_gate", "experts_up", "experts_down")})
        out, stats = ffn_module(8, offset, experts=64).apply(
            {"params": mine}, x)
        total = total + out.reshape(-1, 32) - shared
        pairs += int(stats["moe_held"])
        assert int(stats["moe_dropped"]) == 0
    assert pairs == 2 * SEQ * 3           # every pair on one chip
    assert reference.relative_error(total + shared, want) < 1e-5


# ------------------------------------------------- the second loss head

def fake_apply(main, ahead):
    return lambda params, ids, next_ids: (main, {"mtp_logits": ahead})


def xent_rows(logits, targets):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]


def test_mtp_targets_are_the_labels_one_to_the_left_last_masked():
    main = jax.random.normal(jax.random.key(17), (2, 6, 10))
    ahead = jax.random.normal(jax.random.key(18), (2, 6, 10))
    labels = jax.random.randint(jax.random.key(19), (2, 6), 0, 10)
    ids = jnp.zeros((2, 6), jnp.int32)
    loss, _, extras = sl._policy_loss(fake_apply(main, ahead), None,
                                      ids, labels)
    want = xent_rows(ahead[:, :-1], labels[:, 1:]).mean()
    assert float(extras["mtp_loss"]) == pytest.approx(float(want),
                                                      rel=1e-6)
    assert float(loss) == pytest.approx(
        float(xent_rows(main, labels).mean()
              + sl.MTP_LOSS_WEIGHT * want), rel=1e-6)
    # what the module says at a row's last position reaches nothing
    other = ahead.at[:, -1].set(100.0)
    again = sl._policy_loss(fake_apply(main, other), None, ids,
                            labels)[2]["mtp_loss"]
    assert float(again) == float(extras["mtp_loss"])
    assert sl.MTP_LOSS_WEIGHT == reference.MTP_WEIGHT == 0.3


def test_mtp_targets_cross_a_game_separator_like_any_other():
    """No document mask: the separator (362 on a 19x19 board) is a
    target where it is the id after next, and the id after it is one
    too."""
    sep = SIZE * SIZE + 1
    labels = jnp.array([[5, sep, 7, 8, sep, 2]])
    ahead = jax.random.normal(jax.random.key(20), (1, 6, 400))
    main = jnp.zeros((1, 6, 400))
    got = sl._policy_loss(fake_apply(main, ahead), None,
                          jnp.zeros((1, 6), jnp.int32), labels)[2]
    want = xent_rows(ahead[:, :5], jnp.array([[sep, 7, 8, sep, 2]]))
    assert float(got["mtp_loss"]) == pytest.approx(float(want.mean()),
                                                   rel=1e-6)
    # a target outside the logits' range is masked, as in the main loss
    wide = labels.at[0, 2].set(400)
    got = sl._policy_loss(fake_apply(main, ahead), None,
                          jnp.zeros((1, 6), jnp.int32), wide)[2]
    assert float(got["mtp_loss"]) == pytest.approx(
        float(want[0, [0, 2, 3, 4]].mean()), rel=1e-6)


def test_the_train_step_returns_the_mtp_loss_and_counts_its_block(net):
    import optax

    from rocalphago_tpu.io.checkpoint import pack_rng
    from rocalphago_tpu.obs import registry

    tx = optax.sgd(0.05)
    step = jax.jit(sl.make_train_step(net.module.apply, tx, SIZE, True))
    state = sl.SLState(net.params, tx.init(net.params), jnp.int32(0),
                       pack_rng(jax.random.key(0)))
    ids = jax.random.randint(jax.random.key(21), (2, SEQ), 0, 361)
    labels = jnp.roll(ids, -1, axis=1)
    losses, metrics = [], []
    for _ in range(8):
        state, m = step(state, ids, labels)
        losses.append(float(m["loss"]))
        metrics.append(jax.device_get(m))
    assert losses[-1] < losses[0]
    assert set(metrics[0]) == {"loss", "accuracy", "mtp_loss",
                               *seqpolicy.MOE_STATS}
    assert metrics[-1]["mtp_loss"] < metrics[0]["mtp_loss"]
    assert int(metrics[0]["moe_routed"]) == 3 * 2 * SEQ * 3
    sl.record_routing(metrics)
    assert registry.gauge(registry.SEQ_MTP_LOSS).value \
        == pytest.approx(float(metrics[-1]["mtp_loss"]))


def test_the_step_moves_the_selection_bias_by_the_published_rule(net):
    """After the optimizer's update (which leaves a leaf of no
    gradient as it is) every expert layer's bias goes up 0.001 for an
    expert that took less than the mean of the step's choices and
    down for one that took more; nothing else of the tree is touched
    by it, and a network without the bias returns no such moves."""
    import optax

    from rocalphago_tpu.io.checkpoint import pack_rng

    tx = optax.sgd(0.0)                     # the optimizer moves nothing
    step = jax.jit(sl.make_train_step(net.module.apply, tx, SIZE, False))
    state = sl.SLState(net.params, tx.init(net.params), jnp.int32(0),
                       pack_rng(jax.random.key(0)))
    ids = jax.random.randint(jax.random.key(22), (2, SEQ), 0, VOCAB)
    labels = jnp.roll(ids, -1, axis=1)
    (_, extras), kept = net.module.apply(net.params, ids, labels,
                                         mutable=["intermediates"])
    chosen = seqpolicy.chosen_experts(kept)
    assert set(chosen) == {"layer1", "layer2", "mtp_layer"}
    assert set(extras["no_grad_updates"]["params"]) == set(chosen)
    new, metrics = step(state, ids, labels)
    assert "no_grad_updates" not in metrics
    for name, picks in chosen.items():
        load = np.bincount(np.asarray(picks).ravel(), minlength=16)
        assert load.sum() == 2 * SEQ * 3
        old = net.params["params"][name]["ffn"]["router_bias"]
        got = new.params["params"][name]["ffn"]["router_bias"] - old
        np.testing.assert_allclose(
            got, seqpolicy.ROUTER_BIAS_RATE * np.sign(load.mean() - load),
            atol=1e-9)
        assert float(jnp.abs(got).max()) == pytest.approx(0.001)
    moved = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(new.params)[0],
        jax.tree.leaves(net.params)) if not np.array_equal(a, b)]
    assert len(moved) == 3 and all("router_bias" in m for m in moved)
    # the rule evens a lopsided load: the busiest expert's share falls
    lopsided = jnp.array([30, 2, 2, 2] + [3] * 12)
    assert float(seqpolicy.bias_step(lopsided)[0]) == pytest.approx(-0.001)
    assert float(seqpolicy.bias_step(lopsided)[1]) == pytest.approx(0.001)


# ------------------------------------------------------------- the specs

def test_spec_round_trip_through_the_cli_and_a_saved_model(tmp_path,
                                                           batch):
    from rocalphago_tpu.models import specs

    cfg = tmp_path / "cfg.json"
    cfg.write_text(json.dumps(TOY))
    path = str(tmp_path / "seq.json")
    made = specs.main(["seq", "--config", str(cfg), "--out", path,
                       "--seed", "3"])
    loaded = NeuralNetBase.load_model(path)
    assert isinstance(loaded, SeqPolicy)
    assert loaded.spec_kwargs == made.spec_kwargs == TOY
    assert loaded.module == made.module
    assert loaded.module.hyper == seqpolicy.Hyper(4, 20, 1e-6,
                                                  (-30.0, 30.0))
    assert loaded.module.mtp == 1
    assert jax.tree.structure(loaded.params) \
        == jax.tree.structure(made.params)
    for a, b in zip(jax.tree.leaves(loaded.params),
                    jax.tree.leaves(made.params)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(loaded.forward(batch[0]),
                                  made.forward(batch[0]))


def test_a_laguna_spec_still_builds_the_network_it_built():
    import test_seqpolicy

    net = SeqPolicy(board=SIZE, seed=3, **test_seqpolicy.TOY)
    assert net.module.hyper is None and net.module.mtp == 0
    assert all(spec.latent is None for spec in net.module.layers)
    assert dict(net.module.ffn)["scoring"] == "softmax"
    p = net.params["params"]
    assert set(p) == {"embed", "head", "norm",
                      *(f"layer{i}" for i in range(5))}
    assert set(p["layer1"]) == {"attn", "ffn", "input_norm",
                                "post_attn_norm"}
    assert set(p["layer1"]["attn"]) == {"q_proj", "k_proj", "v_proj",
                                        "gate_proj", "o_proj"}
    assert set(p["layer1"]["ffn"]) == {"router", "experts_gate",
                                       "experts_up", "experts_down",
                                       "shared"}
    ids = jnp.zeros((1, 8), jnp.int32)
    # next ids change nothing of a network without the module
    a, b = net.module.apply(net.params, ids), \
        net.module.apply(net.params, ids, ids)
    np.testing.assert_array_equal(a[0], b[0])
    assert set(b[1]) == set(seqpolicy.MOE_STATS)


@pytest.mark.parametrize("key,value", [
    ("topk_method", "greedy"),
    ("scoring_func", "tanh"), ("num_nextn_predict_layers", 2),
    ("hidden_act", "gelu"), ("model_type", "deepseek_v3"),
    ("moe_layer_freq", 2)])
def test_a_spec_that_asks_for_what_is_not_computed_is_refused(key,
                                                              value):
    with pytest.raises(ValueError, match=key):
        SeqPolicy(board=SIZE, init_weights=False,
                  **dict(TOY, **{key: value}))


@pytest.mark.parametrize("key,value", [("n_group", 4),
                                       ("topk_group", 2)])
def test_a_group_limit_is_taken_since_pr_32(key, value):
    """``FIXED`` refused ``n_group != 1`` until the decoder computed
    the group-limited choice (``tests/test_seqpolicy_ling.py`` holds
    it to the reference): an xing4_0 spec with groups now builds a
    router with them."""
    spec = dict(TOY, n_group=4, topk_group=2)
    net = SeqPolicy(board=SIZE, init_weights=False, **spec)
    assert dict(net.module.ffn)[key] == value


def test_another_rotary_scaling_is_refused():
    scaling = dict(TOY["rope_scaling"], type="linear")
    with pytest.raises(ValueError, match="linear"):
        SeqPolicy(board=SIZE, init_weights=False,
                  **dict(TOY, rope_scaling=scaling))
