"""The move-sequence policy built from a ``bailing_hybrid`` spec
(``models/seqpolicy.py``: Kimi delta attention beside gated latent
attention by ``layer_group_size``, a group-limited sigmoid router)
against its plain reference (``chipbench/reference_ling.py``) at a toy
size that keeps the structure: a period of three (delta, delta,
latent), one dense layer and three expert layers, 4 heads of 8 (12 for
latent queries and keys), 16 experts in 4 groups of which 2 stay,
top-3, 4 held, and a multi-token-prediction module at weight 0.3. A
row is 32 tokens and a chunk of the scan 8, in blocks of 4, so a row
is four chunks. Seeded random weights; nothing here is a device
number.
"""

from __future__ import annotations

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import reference_ling as reference  # noqa: E402
from rocalphago_tpu.models import NeuralNetBase, seqpolicy  # noqa: E402
from rocalphago_tpu.models.seqpolicy import SeqPolicy  # noqa: E402
from rocalphago_tpu.training import sl  # noqa: E402

SEQ, VOCAB, SIZE = 32, 512, 19
TOY = dict(
    model_type="bailing_hybrid", vocab_size=VOCAB, vocab_held=VOCAB,
    hidden_size=32, intermediate_size=64, num_hidden_layers=12,
    layers_held=4, layer_group_size=3, first_k_dense_replace=1,
    num_attention_heads=4, num_key_value_heads=4, head_dim=8,
    q_lora_rank=None, kv_lora_rank=12, qk_nope_head_dim=8,
    qk_rope_head_dim=4, qk_head_dim=12, v_head_dim=8,
    rope_theta=6000000, rope_scaling=None, short_conv_kernel_size=4,
    kda_lower_bound=-5, kda_safe_gate=True, no_kda_lora=True,
    linear_silu=True, use_qk_norm=True, num_experts=16,
    num_shared_experts=1, num_experts_per_tok=3,
    moe_intermediate_size=16, moe_shared_expert_intermediate_size=16,
    n_group=4, topk_group=2, topk_method="noaux_tc",
    scoring_func="sigmoid", score_function="sigmoid",
    moe_router_enable_expert_bias=True, norm_topk_prob=True,
    routed_scaling_factor=2.5, hidden_act="silu",
    num_nextn_predict_layers=1, mtp_loss_scaling_factor=0.3,
    mtp_use_kda=False, expert_swiglu_limit_list=[0] * 12,
    share_expert_swiglu_limit_list=[0] * 12, experts_held=4,
    expert_offset=4, rms_norm_eps=1e-6)
#: relative L2 error allowed at each compute type (as for the other
#: two blocks: float32 is the same arithmetic in another order)
#: (bf16: one flipped top-3 choice among 64 tokens moves a held
#: expert's gradient by a fifth)
TOLERANCE = {"float32": 2e-5, "bfloat16": 0.3}
LOSS_TOLERANCE = {"float32": 2e-6, "bfloat16": 2e-3}


@pytest.fixture(scope="module", autouse=True)
def toy_tiles():
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(seqpolicy, "EXPERT_CHUNK", 16)
        patch.setattr(seqpolicy, "ATTENTION_BLOCK", 8)
        patch.setattr(seqpolicy, "KDA_CHUNK", 8)
        patch.setattr(seqpolicy, "KDA_SUB", 4)
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
    """The reference's two heads' logits, loss and gradient tree."""
    heads = jax.jit(lambda p: reference.forward(p, *batch, TOY))(
        net.params)
    loss, grads = jax.jit(lambda p: reference.loss_and_grads(
        p, *batch, TOY))(net.params)
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
    assert logits.dtype == jnp.float32
    assert reference.relative_error(logits, wanted[0][0]) \
        < TOLERANCE[dtype] / 4
    assert reference.relative_error(extras["mtp_logits"],
                                    wanted[0][1]) < TOLERANCE[dtype] / 4
    # the module's loss weight is the config's own key
    assert float(extras["mtp_loss_weight"]) == pytest.approx(0.3)
    # three expert layers and the MTP block's
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


def leaf(tree, path):
    node = tree["params"]
    for key in path:
        node = node[key]
    return node


#: a leaf of every kind: delta layers 0, 1, 3 (0 dense), latent 2
LEAVES = [
    ("embed",), ("head",), ("norm", "scale"),
    ("layer0", "ffn", "gate_proj"), ("layer0", "input_norm", "scale"),
    ("layer1", "attn", "q_proj"), ("layer1", "attn", "k_proj"),
    ("layer1", "attn", "v_proj"), ("layer1", "attn", "q_conv"),
    ("layer1", "attn", "k_conv"), ("layer1", "attn", "v_conv"),
    ("layer1", "attn", "f_proj"), ("layer1", "attn", "dt_bias"),
    ("layer1", "attn", "A_log"), ("layer1", "attn", "b_proj"),
    ("layer1", "attn", "g_proj"), ("layer1", "attn", "o_norm", "scale"),
    ("layer1", "attn", "o_proj"), ("layer3", "attn", "f_proj"),
    ("layer2", "attn", "q_proj"), ("layer2", "attn", "kv_a_proj"),
    ("layer2", "attn", "kv_a_norm", "scale"),
    ("layer2", "attn", "kv_b_proj"), ("layer2", "attn", "gate_proj"),
    ("layer2", "attn", "o_proj"), ("layer1", "ffn", "router"),
    ("layer1", "ffn", "experts_gate"), ("layer1", "ffn", "experts_up"),
    ("layer1", "ffn", "experts_down"),
    ("layer1", "ffn", "shared", "down_proj"),
    ("mtp_eh_proj",), ("mtp_layer", "attn", "q_proj"),
    ("mtp_layer", "ffn", "router"),
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
    _, grads = program_grads(dtype)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    ref = dict(jax.tree_util.tree_flatten_with_path(wanted[2])[0])
    assert len(flat) == len(ref)
    for path, got in flat:
        if "router_bias" in jax.tree_util.keystr(path):
            assert not np.asarray(got).any()     # it moves by its rule
            continue
        assert reference.relative_error(got, ref[path]) \
            < TOLERANCE[dtype], jax.tree_util.keystr(path)


def test_the_reference_in_blocks_is_the_reference(net, batch, wanted,
                                                  monkeypatch):
    monkeypatch.setattr(reference, "RECURRENCE_SEGMENT", 8)
    heads = jax.jit(lambda p: reference.forward(
        p, *batch, TOY, blocks=True))(net.params)
    for got, want in zip(heads, wanted[0]):
        assert reference.relative_error(got, want) < 1e-6
    paths = [("layer1", "attn", "f_proj"), ("layer2", "attn", "q_proj")]
    loss, grads = jax.jit(lambda p: reference.loss_and_grads(
        p, *batch, TOY, paths=paths, blocks=True))(net.params)
    assert abs(float(loss) - float(wanted[1])) < 1e-5
    for path in paths:
        assert reference.relative_error(
            grads["/".join(path)], leaf(wanted[2], path)) < 1e-5


def test_lowering_the_float32_parts_is_caught(net, batch, wanted):
    """The reference with its float32 parts in bf16 — the decay, its
    exponentials and the carried state among them — is further from
    the reference than the program in bf16 is."""
    low, _ = jax.jit(lambda p: reference.forward(
        p, *batch, TOY, dtype=jnp.bfloat16))(net.params)
    program, _ = jax.jit(module_at(net, "bfloat16").apply)(
        net.params, *batch)
    assert reference.relative_error(low, wanted[0][0]) \
        > reference.relative_error(program, wanted[0][0])


# ----------------------------------------- the delta rule's chunked scan

def scan_inputs(key, s_len=64, heads=2, dk=8, dv=8, g_scale=1.0):
    ks = jax.random.split(key, 5)
    q = seqpolicy.l2_normed(jax.random.normal(ks[0], (1, s_len, heads, dk)))
    k = seqpolicy.l2_normed(jax.random.normal(ks[1], (1, s_len, heads, dk)))
    v = jax.random.normal(ks[2], (1, s_len, heads, dv))
    g = -5.0 * jax.nn.sigmoid(
        g_scale * jax.random.normal(ks[3], (1, s_len, heads, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, s_len, heads)))
    return q, k, v, g, beta


def token_by_token(q, k, v, g, beta):
    return reference.delta_recurrence(q[0], k[0], v[0], g[0], beta[0])[None]


@pytest.mark.parametrize("chunk,sub", [(8, 4), (16, 16), (64, 16)])
def test_the_chunked_scan_is_the_recurrence(monkeypatch, chunk, sub):
    """A row of 64 tokens as eight, four and one chunk."""
    monkeypatch.setattr(seqpolicy, "KDA_CHUNK", chunk)
    monkeypatch.setattr(seqpolicy, "KDA_SUB", sub)
    args = scan_inputs(jax.random.key(5))
    got = seqpolicy.kda_chunked(*args, dtype=jnp.float32)
    assert got.shape == (1, 64, 2, 8)
    assert reference.relative_error(got, token_by_token(*args)) < 2e-5


def test_the_chunked_scans_gradient_is_the_recurrences(monkeypatch):
    monkeypatch.setattr(seqpolicy, "KDA_SUB", 4)
    args = scan_inputs(jax.random.key(6), s_len=32)
    weigh = jax.random.normal(jax.random.key(7), (1, 32, 2, 8))

    def chunked(*a):
        return (seqpolicy.kda_chunked(*a, dtype=jnp.float32)
                * weigh).sum()

    def plain(*a):
        return (token_by_token(*a) * weigh).sum()

    got = jax.grad(chunked, argnums=range(5))(*args)
    want = jax.grad(plain, argnums=range(5))(*args)
    for a, b in zip(got, want):
        assert reference.relative_error(a, b) < 5e-5


@pytest.mark.parametrize("chunk", [16, 64])
def test_gates_at_their_bound_stay_finite_and_exact(monkeypatch, chunk):
    """Every log-decay within 1e-3 of −5 over the whole row: ``e^−G``
    would reach ``e^320`` over a chunk of 64, far past float32;
    relative to sixteen rows it stays under ``e^75``."""
    monkeypatch.setattr(seqpolicy, "KDA_CHUNK", chunk)
    monkeypatch.setattr(seqpolicy, "KDA_SUB", 16)
    q, k, v, _, beta = scan_inputs(jax.random.key(8))
    g = jnp.full(q.shape, -5.0 + 1e-3)
    def scan(g):
        return seqpolicy.kda_chunked(q, k, v, g, beta,
                                     dtype=jnp.float32)

    got, grads = jax.jit(jax.value_and_grad(
        lambda g: (lambda o: (o.sum(), o))(scan(g)), has_aux=True))(g)
    got = got[1]
    assert np.isfinite(np.asarray(got)).all()
    assert np.isfinite(np.asarray(grads)).all()
    assert reference.relative_error(
        got, token_by_token(q, k, v, g, beta)) < 2e-5


def test_without_decay_or_correction_it_is_causal_linear_attention(
        monkeypatch):
    """``g → 0`` and ``beta → 0``: ``S_t = Σ beta k vᵀ`` to first
    order, so ``o_t / beta`` is ``Σ_{i≤t} (q_t·k_i) v_i``."""
    monkeypatch.setattr(seqpolicy, "KDA_CHUNK", 16)
    q, k, v, _, _ = scan_inputs(jax.random.key(9))
    small = 1e-4
    got = seqpolicy.kda_chunked(
        q, k, v, jnp.zeros(q.shape), jnp.full(q.shape[:3], small),
        dtype=jnp.float32) / small
    scores = jnp.einsum("bshd,bthd->bhst", q, k)
    scores = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), scores, 0.0)
    want = jnp.einsum("bhst,bthd->bshd", scores, v)
    assert reference.relative_error(got, want) < 1e-3


def pairwise_by_definition(q, k, gc, d_q, d_k):
    """``A_x[r, c] = Σ_d x_{r,d} k_{c,d} e^{G_{r,d} − G_{c,d}}`` where
    ``r ≥ c`` for ``x`` = ``q`` and ``x`` = ``k``, and the gradients
    of ``Σ d_q A_q + Σ d_k A_k`` over those entries, in float64 on
    the host: ``q, k, gc [C, d]``, ``d_q, d_k [C, C]``."""
    q, k, gc, d_q, d_k = (np.asarray(x, np.float64)
                          for x in (q, k, gc, d_q, d_k))
    lower = np.tril(np.ones(d_q.shape, bool))
    decay = np.exp(np.where(lower[..., None],
                            gc[:, None, :] - gc[None, :, :], -np.inf))
    a_q = np.einsum("rd,cd,rcd->rc", q, k, decay)
    a_k = np.einsum("rd,cd,rcd->rc", k, k, decay)
    dq = np.einsum("rc,cd,rcd->rd", d_q, k, decay)
    dk_rows = np.einsum("rc,cd,rcd->rd", d_k, k, decay)
    dk_columns = (np.einsum("rc,rd,rcd->cd", d_q, q, decay)
                  + np.einsum("rc,rd,rcd->cd", d_k, k, decay))
    return (a_q, a_k), (dq, dk_rows + dk_columns,
                        q * dq + k * (dk_rows - dk_columns))


@pytest.mark.parametrize("gates", ["drawn", "at_their_bound"])
@pytest.mark.parametrize("chunk,sub", [(8, 4), (16, 16), (64, 16)])
def test_the_pairwise_decays_are_their_definition(chunk, sub, gates):
    """The blocks of rows as a batch axis of one product, and its
    hand-written backward, against the definition in float64. With
    every gate at −5 + 1e-3 the columns AFTER a block of rows would
    be scaled by up to ``e^{5·48}``: the exponent is zeroed there, so
    value and gradient stay finite."""
    ks = jax.random.split(jax.random.key(12), 5)
    q = seqpolicy.l2_normed(jax.random.normal(ks[0], (chunk, 8)))
    k = seqpolicy.l2_normed(jax.random.normal(ks[1], (chunk, 8)))
    if gates == "drawn":
        g = -5.0 * jax.random.uniform(ks[2], (chunk, 8))
    else:
        g = jnp.full((chunk, 8), -5.0 + 1e-3)
    gc = jnp.cumsum(g, axis=0)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    weigh = [jax.random.normal(key, (chunk, chunk)) for key in ks[3:]]

    def weighed(q, k, gc):
        both = seqpolicy._pairwise_decayed(q, k, gc, sub)
        # the caller's mask: what lies above the diagonal is dropped
        return sum((jnp.where(lower, a, 0.0) * w).sum()
                   for a, w in zip(both, weigh)), both

    (_, got), grads = jax.jit(jax.value_and_grad(
        weighed, argnums=(0, 1, 2), has_aux=True))(q, k, gc)
    want, want_grads = pairwise_by_definition(q, k, gc, *weigh)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        assert reference.relative_error(
            jnp.where(lower, a, 0.0), np.tril(b)) < 2e-6
    # the diagonal's two shares of dG cancel, and at the bound what
    # is left is e^−5 of them: float32 keeps five digits of it
    for a, b, limit in zip(grads, want_grads, (2e-6, 2e-6, 5e-5)):
        assert np.isfinite(np.asarray(a)).all()
        assert reference.relative_error(a, b) < limit


def test_the_scans_forward_builds_nothing_by_pads_or_concatenations():
    """Every intra-chunk array is written once in the form its
    consumer reads: the lowered forward holds no ``pad`` and no
    ``concatenate`` (their cotangents are slices and pads again)."""
    shaped = jax.ShapeDtypeStruct
    rows = shaped((1, 256, 2, 16), jnp.bfloat16)
    text = jax.jit(seqpolicy.kda_chunked).lower(
        rows, rows, rows, shaped((1, 256, 2, 16), jnp.float32),
        shaped((1, 256, 2), jnp.float32)).as_text()
    assert "stablehlo.dot_general" in text
    assert "stablehlo.pad" not in text
    assert "stablehlo.concatenate" not in text


def test_the_inverse_is_the_inverse_and_so_is_its_gradient():
    a = jnp.tril(jax.random.normal(jax.random.key(10), (3, 16, 16)), -1)
    eye = jnp.eye(16)
    got = seqpolicy.unit_lower_inverse(a)
    assert reference.relative_error(
        got, jnp.linalg.inv(eye + a)) < 1e-5
    weigh = jax.random.normal(jax.random.key(11), (3, 16, 16))
    grad = jax.grad(lambda a: (seqpolicy.unit_lower_inverse(a)
                               * weigh).sum())(a)
    want = jax.grad(lambda a: (jnp.linalg.inv(eye + a) * weigh).sum())(a)
    assert reference.relative_error(jnp.tril(grad, -1),
                                    jnp.tril(want, -1)) < 1e-4


def test_the_convolutions_first_positions_see_zeros():
    x = jax.random.normal(jax.random.key(12), (1, 6, 5))
    taps = jax.random.normal(jax.random.key(13), (4, 5))
    y = seqpolicy.causal_conv(x, taps)
    np.testing.assert_allclose(y[0, 0], taps[3] * x[0, 0], rtol=1e-6)
    np.testing.assert_allclose(
        y[0, 1], taps[2] * x[0, 0] + taps[3] * x[0, 1], rtol=1e-6)
    np.testing.assert_allclose(
        y[0, 2], taps[1] * x[0, 0] + taps[2] * x[0, 1]
        + taps[3] * x[0, 2], rtol=1e-6)
    np.testing.assert_allclose(
        y[0, 5], sum(taps[j] * x[0, 2 + j] for j in range(4)),
        rtol=1e-5)
    np.testing.assert_allclose(y[0], reference.causal_conv(x[0], taps),
                               rtol=1e-6)


# ------------------- from a projection's product to the scan's operand

#: a row of three blocks of rows written in chunks of sixteen, two
#: heads to a block of lanes: block, chunk and head boundaries, the
#: first three positions and the last three all lie inside what is
#: compared, and the second batch row starts where the first ends
MIX = dict(rows=32, lanes=256, chunk=16)
MIX_SHAPE = (2, 96, 4, 128)                         # B, S, H, w
NORMS = {"values": None, "keys": 1.0, "queries": math.sqrt(128)}


def mixing_case(dtype):
    b, s_len, h, w = MIX_SHAPE
    y = jax.random.normal(jax.random.key(30), (b, s_len, h * w))
    taps = 0.5 * jax.random.normal(jax.random.key(31), (4, h * w))
    d = jax.random.normal(jax.random.key(32), MIX_SHAPE)
    return y.astype(dtype), taps, d.astype(dtype)


def mixed_in_float64(y, taps, d, norm):
    """The definition on the host: the convolution as ``causal_conv``
    has it, SiLU, the L2 norm over a head (ε 1e-6) divided by
    ``norm`` — value, ``dy`` and ``dtaps`` by JAX in float64."""
    b, s_len, h, w = MIX_SHAPE
    with jax.enable_x64(True):
        def definition(y, taps):
            k = taps.shape[0]
            yp = jnp.pad(y, ((0, 0), (k - 1, 0), (0, 0)))
            c = sum(taps[j] * yp[:, j:j + s_len] for j in range(k))
            a = (c / (1.0 + jnp.exp(-c))).reshape(b, s_len, h, w)
            if norm is not None:
                a = a / jnp.sqrt((a * a).sum(-1, keepdims=True)
                                 + 1e-6) / norm
            return a

        y, taps, d = (jnp.asarray(np.asarray(x, np.float64))
                      for x in (y, taps, d))
        value, back = jax.vjp(definition, y, taps)
        return tuple(np.asarray(x) for x in (value, *back(d)))


def mixed_by(form: str, y, taps, d, norm):
    """Value, ``dy`` and ``dtaps`` of the XLA form (through
    ``kda_mixed`` itself, which takes it on the CPU) or of the two
    Pallas kernels, interpreted."""
    h = MIX_SHAPE[2]
    if form == "xla":
        value, back = jax.vjp(
            lambda y, taps: seqpolicy.kda_mixed(y, taps, h, norm),
            y, taps)
        return (value, *back(d))
    return (seqpolicy.mixing_kernel_forward(
        y, taps, h, norm, interpret=True, **MIX),
        *seqpolicy.mixing_kernel_backward(
            y, taps, d, h, norm, interpret=True, **MIX))


def far(got, want) -> float:
    """The largest difference, relative to the largest entry."""
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("norm", list(NORMS))
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_mixing_pass_is_its_definition(form, norm, dtype):
    """Value AND backward of both lowerings against the definition in
    float64, over two batch rows and every position (the definition
    starts each row from zeros: a leak across rows or blocks would
    show). float32 holds the formulae to rounding; bf16 is what the
    chip runs, results and ``dy`` rounded once to it."""
    y, taps, d = mixing_case(dtype)
    got = mixed_by(form, y, taps, d, NORMS[norm])
    want = mixed_in_float64(y, taps, d, NORMS[norm])
    assert [x.dtype for x in got] == [y.dtype, y.dtype, jnp.float32]
    assert got[0].shape == MIX_SHAPE and got[1].shape == y.shape
    rounding = {"float32": 2e-6, "bfloat16": 2.0 ** -8}[dtype]
    for name, a, b, limit in zip(("value", "dy", "dtaps"), got, want,
                                 (rounding, rounding, 1e-5)):
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        assert far(a, b) < limit, name
    if norm == "values":
        # the first positions see zeros, not the row before them
        np.testing.assert_allclose(
            np.asarray(got[0][:, 0].reshape(2, -1), np.float32),
            jax.nn.silu(taps[3] * y[:, 0].astype(jnp.float32)),
            rtol=3 * rounding, atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("norm", list(NORMS))
def test_the_mixing_kernel_is_the_xla_form(norm, dtype):
    """The same inputs through both lowerings, forward and backward:
    in float32 to a few roundings, in bf16 to one of the result's."""
    y, taps, d = mixing_case(dtype)
    xla = mixed_by("xla", y, taps, d, NORMS[norm])
    kernel = mixed_by("kernel", y, taps, d, NORMS[norm])
    limit = {"float32": 2e-6, "bfloat16": 2.0 ** -8}[dtype]
    for name, a, b in zip(("value", "dy", "dtaps"), kernel, xla):
        assert far(a, b) < (2e-6 if name == "dtaps" else limit), name


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
@pytest.mark.parametrize("norm", ["values", "queries"])
def test_the_mixing_backward_keeps_the_product_and_the_taps_alone(
        monkeypatch, norm, platform):
    """What a ``jax.vjp`` of the pass saves, traced for the CPU (the
    XLA form) and for a TPU at whole tiles (the kernels; shapes only,
    nothing runs): the bf16 product and the float32 taps, no float32
    array of the product's size."""
    from jax._src.ad_checkpoint import saved_residuals

    monkeypatch.setattr(seqpolicy, "kernel_platform", lambda: platform)
    monkeypatch.setattr(seqpolicy, "KDA_CHUNK", 64)     # not the toy's
    y = jax.ShapeDtypeStruct((1, 1024, 4 * 128), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((4, 4 * 128), jnp.float32)
    assert seqpolicy.use_mixing_kernel(y, taps, 4) == (platform == "tpu")
    assert not seqpolicy.use_mixing_kernel(       # a head of 64 lanes
        y, taps, 8)
    kept = [aval for aval, _ in saved_residuals(
        lambda y, taps: seqpolicy.kda_mixed(y, taps, 4, NORMS[norm]),
        y, taps)]
    assert sorted((str(a.dtype), a.shape) for a in kept) == [
        ("bfloat16", y.shape), ("float32", taps.shape)]


def test_the_layer_saves_no_float32_array_between_product_and_scan(net):
    """The layer's ``jax.vjp``: it keeps the three bf16 products, and
    nothing made between a product and the scan's operand (the parent
    kept the convolution's four shifted slices and sum, two of SiLU's
    and the norm's — float32 ``[B, S, H·w]`` each — for q, k and
    v)."""
    from jax._src.ad_checkpoint import saved_residuals

    layer = seqpolicy.KimiDeltaAttention(net.module.layers[1], 1e-6,
                                         jnp.bfloat16)
    attn = net.params["params"]["layer1"]["attn"]
    x = jax.random.normal(jax.random.key(33), (2, SEQ, 32), jnp.bfloat16)
    kept = [(aval, str(why)) for aval, why in saved_residuals(
        lambda p, x: layer.apply({"params": p}, x), attn, x)]
    products = [aval for aval, why in kept
                if "dot_general" in why and "mixed" in why]
    assert [(a.shape, str(a.dtype)) for a in products] == [
        ((2, SEQ, 4 * 8), "bfloat16")] * 3
    for made_in in ("causal_conv", "l2_normed", "silu",
                    "_mixed_by_definition"):
        assert not [why for _, why in kept if made_in in why], made_in


def test_the_decay_stays_above_its_bound_whatever_the_weights(net):
    """The log-decay the layer hands its scan lies in (−5, 0) even
    with the decay's projection blown up."""
    params = jax.tree_util.tree_map(lambda a: a, net.params)
    attn = dict(params["params"]["layer1"]["attn"])
    attn["f_proj"] = attn["f_proj"] * 1e4
    seen = {}

    def spy(q, k, v, g, beta, dtype):
        seen["g"] = g
        return jnp.zeros(v.shape, jnp.float32)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(seqpolicy, "kda_chunked", spy)
        layer = seqpolicy.KimiDeltaAttention(
            net.module.layers[1], 1e-6, jnp.float32)
        layer.apply({"params": attn},
                    jax.random.normal(jax.random.key(14), (1, 16, 32)))
    g = np.asarray(seen["g"])
    assert g.dtype == np.float32 and g.min() >= -5.0 and g.max() <= 0.0
    assert g.min() < -4.9            # the bound is reached, not clamped


# -------------------------------------------------- latent attention

def test_latent_attention_without_a_query_path_gated_per_head():
    """``q_lora_rank`` null: one ``q_proj``; ``head_wise``: a sigmoid
    gate per head — against a plain softmax per head."""
    spec = seqpolicy.ling_layer_specs(TOY)[0][2]
    assert spec.latent and spec.latent.q_rank == 0 and spec.latent.gate
    module = seqpolicy.LatentAttention(spec, 1e-6, jnp.float32)
    x = jax.random.normal(jax.random.key(15), (1, 16, 32))
    params = module.init(jax.random.key(16), x)
    # a gate that differs by head and token (at its start it is a half)
    params = {"params": dict(params["params"], gate_proj=jax.random.normal(
        jax.random.key(23), (32, 4)))}
    assert set(params["params"]) == {"q_proj", "kv_a_proj", "kv_a_norm",
                                     "kv_b_proj", "gate_proj", "o_proj"}
    assert params["params"]["q_proj"].shape == (32, 4 * 12)
    assert params["params"]["gate_proj"].shape == (32, 4)
    got = module.apply(params, x)[0]
    want = reference.latent_attention(params["params"], x[0], TOY)
    assert reference.relative_error(got, want) < 2e-5
    # the gate matters, per head
    shut = dict(params["params"], gate_proj=jnp.zeros((32, 4)))
    half = module.apply({"params": shut}, x)[0]
    assert reference.relative_error(half, want) > 0.1


# -------------------------------------------------------- the router

def ffn_module(held: int, offset: int, experts: int = 16, groups: int = 4,
               kept: int = 2, top_k: int = 3):
    return seqpolicy.SparseFFN(
        num_experts=experts, top_k=top_k, width=16, shared_width=16,
        experts_held=held, expert_offset=offset, norm_topk=True,
        routed_scale=2.5, scoring="sigmoid", n_group=groups,
        topk_group=kept, dtype=jnp.float32)


@pytest.fixture(scope="module")
def router_case():
    module = ffn_module(16, 0)
    x = jax.random.normal(jax.random.key(17), (1, 32, 32))
    params = module.init(jax.random.key(18), x)
    bias = 0.3 * jax.random.normal(jax.random.key(19), (16,))
    params = {"params": dict(params["params"], router_bias=bias)}
    return module, params, x


def chosen_of(module, params, x):
    _, kept = module.apply(params, x, mutable=["intermediates"])
    return np.asarray(kept["intermediates"]["chosen"][0])


def test_a_choice_never_leaves_the_best_groups(router_case):
    module, params, x = router_case
    p = params["params"]
    biased = np.asarray(jax.nn.sigmoid(x[0] @ p["router"])
                        + p["router_bias"])
    chosen = chosen_of(module, params, x)
    two_best = np.sort(biased.reshape(32, 4, 4), axis=-1)[..., -2:].sum(-1)
    best_groups = np.argsort(-two_best, axis=-1)[:, :2]
    for t in range(32):
        assert set(chosen[t] // 4) <= set(best_groups[t]), t
        # and inside them it is the plain top-k
        allowed = np.isin(np.arange(16) // 4, best_groups[t])
        want = np.argsort(-np.where(allowed, biased[t], -np.inf))[:3]
        assert set(chosen[t]) == set(want), t
    # the limit binds: without it some token chooses otherwise
    free = chosen_of(ffn_module(16, 0, groups=1, kept=1), params, x)
    assert (np.sort(free, -1) != np.sort(chosen, -1)).any()


def test_one_group_is_the_unlimited_choice_bit_for_bit(router_case):
    _, params, x = router_case
    scores = jax.random.normal(jax.random.key(20), (7, 16))
    assert seqpolicy.group_limited(scores, 1, 1) is scores
    plain = seqpolicy.SparseFFN(
        num_experts=16, top_k=3, width=16, shared_width=16,
        experts_held=16, expert_offset=0, norm_topk=True,
        routed_scale=2.5, scoring="sigmoid", dtype=jnp.float32)
    one = ffn_module(16, 0, groups=1, kept=1)
    a, sa = plain.apply(params, x)
    b, sb = one.apply(params, x)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(chosen_of(plain, params, x),
                                  chosen_of(one, params, x))
    # and every group kept is no limit either
    np.testing.assert_array_equal(
        chosen_of(ffn_module(16, 0, groups=4, kept=4), params, x),
        chosen_of(one, params, x))


def test_the_bias_steers_the_choice_and_not_the_weight(router_case):
    module, params, x = router_case
    p = params["params"]
    flat = dict(p, router_bias=jnp.zeros((16,)))
    with_bias = chosen_of(module, params, x)
    without = chosen_of(module, {"params": flat}, x)
    assert (np.sort(with_bias, -1) != np.sort(without, -1)).any()
    want = reference.sparse_ffn(
        p, x[0], dict(TOY, experts_held=16, expert_offset=0))
    got, _ = module.apply(params, x)
    assert reference.relative_error(got[0], want) < 2e-5
    grads = jax.grad(lambda q: module.apply({"params": q}, x)[0].sum())(p)
    assert not np.asarray(grads["router_bias"]).any()
    assert np.asarray(grads["router"]).any()


def test_the_sixty_four_shares_add_up_to_the_uncut_layer():
    """128 experts in 8 groups of 16 (4 stay), top-8, 2 held by each
    of 64 chips: the shares' routed parts and the shared expert once
    are the uncut reference layer."""
    kw = dict(TOY, num_experts=128, n_group=8, topk_group=4,
              num_experts_per_tok=8)
    x = jax.random.normal(jax.random.key(21), (1, 16, 32))
    whole = ffn_module(128, 0, experts=128, groups=8, kept=4, top_k=8)
    params = whole.init(jax.random.key(22), x)["params"]
    want = reference.sparse_ffn(
        params, x[0], dict(kw, experts_held=128, expert_offset=0))
    shared = reference._mlp(x[0], *(params["shared"][n] for n in
                                    ("gate_proj", "up_proj", "down_proj")))
    @jax.jit
    def one_share(offset):
        """The layer as the chip that holds experts ``offset`` and
        ``offset + 1`` computes it (one program for all 64: the
        offset is an argument)."""
        share = {k: jax.lax.dynamic_slice_in_dim(v, offset, 2)
                 if k.startswith("experts_") else v
                 for k, v in params.items()}
        return ffn_module(2, offset, experts=128, groups=8, kept=4,
                          top_k=8).apply({"params": share}, x)

    total, held = jnp.zeros_like(want), 0
    for offset in range(0, 128, 2):
        out, stats = one_share(offset)
        total = total + out[0] - shared
        held += int(stats["moe_held"])
        assert int(stats["moe_dropped"]) == 0
    assert held == 16 * 8
    assert reference.relative_error(total + shared, want) < 2e-5


# ------------------------------------------------ the layers' pattern

def test_the_pattern_of_mixers_over_the_published_layers():
    kw = dict(TOY, num_hidden_layers=42, layers_held=42,
              layer_group_size=6, first_k_dense_replace=2,
              expert_swiglu_limit_list=[0] * 42,
              share_expert_swiglu_limit_list=[0] * 42)
    layers, mtp = seqpolicy.ling_layer_specs(kw)
    latent = [i for i, s in enumerate(layers) if s.latent]
    assert latent == [5, 11, 17, 23, 29, 35, 41]
    assert all((s.kda is None) != (s.latent is None) for s in layers)
    assert sum(1 for s in layers if s.kda) == 35
    assert [s.sparse for s in layers] == [False] * 2 + [True] * 40
    assert mtp.latent and mtp.sparse and mtp.kda is None
    kda = layers[0].kda
    assert (kda.key, kda.value, kda.conv, kda.lower) == (8, 8, 4, -5.0)
    # the toy's held stage: delta, delta, latent, delta
    assert [bool(s.latent) for s in
            seqpolicy.ling_layer_specs(TOY)[0]] == [False, False, True,
                                                    False]


# ------------------------------------------ multi-token prediction

def test_the_step_weighs_the_mtp_loss_by_the_configs_key(net, batch):
    """At 0.3 the loss is main + 0.3 · MTP (the reference's); at the
    published 0 the trunk's gradients are those of a model without
    the module."""
    module = module_at(net, "float32")
    loss, _, extras = jax.jit(
        lambda p: sl._policy_loss(module.apply, p, *batch))(net.params)
    main = jax.jit(lambda p: sl._policy_loss(
        lambda p, i, _: module.apply(p, i), p, *batch)[0])(net.params)
    assert "mtp_loss_weight" not in extras
    assert float(loss) == pytest.approx(
        float(main) + 0.3 * float(extras["mtp_loss"]), rel=1e-6)

    zero = SeqPolicy(board=SIZE, init_weights=False,
                     **dict(TOY, mtp_loss_scaling_factor=0))
    without = SeqPolicy(board=SIZE, init_weights=False,
                        **dict(TOY, num_nextn_predict_layers=0))
    at_zero = zero.module.clone(dtype=jnp.float32)
    bare = without.module.clone(dtype=jnp.float32)
    trunk = {"params": {k: v for k, v in net.params["params"].items()
                        if not k.startswith("mtp_")}}
    g0 = jax.jit(jax.grad(lambda p: sl._policy_loss(
        at_zero.apply, p, *batch)[0]))(net.params)
    g1 = jax.jit(jax.grad(lambda p: sl._policy_loss(
        bare.apply, p, *batch)[0]))(trunk)
    for name, want in g1["params"].items():
        got = g0["params"][name]
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            if np.asarray(b).any():
                assert reference.relative_error(a, b) < 1e-5, name
            else:
                assert not np.asarray(a).any(), name
    assert not any(np.asarray(x).any() for x in
                   jax.tree_util.tree_leaves(g0["params"]["mtp_layer"]))


def test_the_train_step_runs_and_moves_the_selection_bias(net, batch):
    tx = sl.make_optimizer(sl.SLConfig())
    step = jax.jit(sl.make_train_step(net.module.apply, tx, SIZE, True))
    from rocalphago_tpu.io.checkpoint import pack_rng

    state = sl.SLState(net.params, tx.init(net.params), jnp.int32(0),
                       pack_rng(jax.random.key(0)))
    new, metrics = step(state, *batch)
    assert np.isfinite(float(metrics["loss"]))
    assert "mtp_loss" in metrics and "mtp_loss_weight" not in metrics
    assert int(metrics["moe_dropped"]) == 0
    before = net.params["params"]["layer1"]["ffn"]["router_bias"]
    after = new.params["params"]["layer1"]["ffn"]["router_bias"]
    moved = np.abs(np.asarray(after - before))
    assert (np.isclose(moved, 0.0, atol=1e-7)
            | np.isclose(moved, 0.001, atol=1e-7)).all()
    assert moved.any()


# ------------------------------------------------- specs and refusals

def test_spec_round_trip_through_the_cli_and_a_saved_model(tmp_path,
                                                           batch):
    from rocalphago_tpu.models import specs

    cfg = tmp_path / "cfg.json"
    cfg.write_text(json.dumps(TOY))
    made = specs.main(["seq", "--config", str(cfg), "--out",
                       str(tmp_path / "seq.json"), "--seed", "3"])
    loaded = NeuralNetBase.load_model(str(tmp_path / "seq.json"))
    assert type(loaded) is SeqPolicy
    assert loaded.spec_kwargs == made.spec_kwargs
    assert loaded.spec_kwargs["q_lora_rank"] is None
    assert loaded.module == made.module
    np.testing.assert_array_equal(loaded.forward(batch[0]),
                                  made.forward(batch[0]))


@pytest.mark.parametrize("other", ["test_seqpolicy",
                                   "test_seqpolicy_xing"])
def test_the_other_two_blocks_build_what_they_built(other):
    """A laguna and an xing4_0 spec: no delta layer, no gate on the
    latent layers, the low-rank query path where the spec has one —
    and with one group the router's program is the one it was: the
    expert layer lowers to the same text with the group limit taken
    out. (The whole toy steps of both lower to the text the parent
    commit's do, byte for byte: PERF.md §6, PR 32.)"""
    import importlib

    toy = importlib.import_module(other).TOY
    net = SeqPolicy(board=SIZE, init_weights=False, **toy)
    assert not any(s.kda for s in net.module.layers)
    assert net.module.mtp_layer is None and net.module.mtp_weight is None
    for s in net.module.layers:
        if s.latent:
            assert s.latent.q_rank == toy["q_lora_rank"]
            assert not s.latent.gate
    fields = dict(net.module.ffn)
    assert fields.get("n_group", 1) == 1
    ffn = seqpolicy.SparseFFN(dtype=jnp.float32, **fields)
    x = jnp.zeros((1, 16, toy["hidden_size"]))
    params = jax.eval_shape(ffn.init, jax.random.key(0), x)

    def lowered():
        return jax.jit(ffn.apply).lower(params, x).as_text()

    with pytest.MonkeyPatch.context() as patch:
        text = lowered()
        patch.setattr(seqpolicy, "group_limited", lambda s, *_: s)
        assert lowered() == text


#: sha256 of the toy Laguna and Xing train steps' lowered text, as
#: the last commit that meant to change those steps left it
LOWERINGS = os.path.join(ROOT, "tests", "data",
                         "seq_step_lowerings.json")


def toy_step_digest(other: str) -> str:
    """sha256 of the StableHLO the toy train step of ``tests/<other>``
    lowers to (this file's toy tiles; shapes only, nothing runs)."""
    import hashlib
    import importlib

    toy = importlib.import_module(other).TOY
    net = SeqPolicy(board=SIZE, init_weights=False, **toy)
    ids = jax.ShapeDtypeStruct((2, SEQ), jnp.int32)
    params = jax.eval_shape(net.module.init, jax.random.key(0), ids,
                            ids)
    tx = sl.make_optimizer(sl.SLConfig())
    state = sl.SLState(params, jax.eval_shape(tx.init, params),
                       jax.ShapeDtypeStruct((), jnp.int32),
                       jax.eval_shape(jax.random.key_data,
                                      jax.random.key(0)))
    step = jax.jit(sl.make_train_step(net.module.apply, tx, SIZE, True))
    return hashlib.sha256(
        step.lower(state, ids, ids).as_text().encode()).hexdigest()


@pytest.mark.parametrize("other", ["test_seqpolicy",
                                   "test_seqpolicy_xing"])
def test_the_other_two_steps_lower_to_the_text_on_record(other):
    """A laguna and an xing4_0 spec build no delta layer, so a change
    to the delta layers leaves their train steps' StableHLO what it
    was, byte for byte: the digests on record are those of the commit
    before the mixing pass (PR 34's), taken from its checkout by this
    function. A commit that MEANS to change those steps records anew:
    ``python tests/test_seqpolicy_ling.py`` rewrites the file."""
    with open(LOWERINGS) as f:
        assert toy_step_digest(other) == json.load(f)[other]


@pytest.mark.parametrize("key,value", [
    ("expert_swiglu_limit_list", [0, 0, 4, 0]),
    ("share_expert_swiglu_limit_list", [0, 5, 0, 0]),
    ("kda_safe_gate", False), ("no_kda_lora", False),
    ("mtp_use_kda", True), ("use_qkv_bias", True),
    ("gated_attention_proj_granularity_type", "element_wise"),
    ("model_type", "bailing_moe"), ("use_mla_nope", True),
    ("rope_scaling", {"type": "yarn", "factor": 4}),
])
def test_a_spec_that_asks_for_what_is_not_computed_is_refused(key,
                                                              value):
    with pytest.raises(ValueError, match=key):
        SeqPolicy(board=SIZE, init_weights=False,
                  **dict(TOY, **{key: value}))


def test_a_limit_on_a_layer_that_is_not_held_is_no_refusal():
    limits = [0] * 4 + [4] * 8
    SeqPolicy(board=SIZE, init_weights=False,
              **dict(TOY, expert_swiglu_limit_list=limits,
                     share_expert_swiglu_limit_list=limits))


if __name__ == "__main__":
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(seqpolicy, "EXPERT_CHUNK", 16)
        patch.setattr(seqpolicy, "ATTENTION_BLOCK", 8)
        digests = {other: toy_step_digest(other) for other in
                   ("test_seqpolicy", "test_seqpolicy_xing")}
    os.makedirs(os.path.dirname(LOWERINGS), exist_ok=True)
    with open(LOWERINGS, "w") as f:
        json.dump(digests, f, indent=1)
        f.write("\n")
    print(digests)
