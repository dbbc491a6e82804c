"""The move-sequence policy built from an ``olmo_hybrid`` spec
(``models/seqpolicy.py``: Gated DeltaNet in three layers of four beside
QK-normed full attention without a gate, post-norm blocks, a dense
SwiGLU everywhere and no expert layer) against its plain reference
(``chipbench/reference_olmo.py``) at a toy size that keeps the
structure: one whole period (delta, delta, delta, full), 4 delta heads
of d_k 6 and d_v 12 — a key head unlike a value head, as published
(96 / 192) — and 4 attention heads of 8. A row is 32 tokens and a
chunk of the scan 8, so a row is four chunks. Seeded random weights;
nothing here is a device number.
"""

from __future__ import annotations

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import reference_olmo as reference  # noqa: E402
from rocalphago_tpu.io.checkpoint import pack_rng  # noqa: E402
from rocalphago_tpu.models import seqpolicy  # noqa: E402
from rocalphago_tpu.models.seqpolicy import MOE_STATS, SeqPolicy  # noqa: E402
from rocalphago_tpu.training import sl  # noqa: E402

SEQ, VOCAB, SIZE = 32, 512, 19
TOY = dict(
    model_type="olmo_hybrid", vocab_size=VOCAB, vocab_held=VOCAB,
    hidden_size=32, intermediate_size=48, num_hidden_layers=8,
    layers_held=4, num_attention_heads=4, num_key_value_heads=4,
    hidden_act="silu", attention_bias=False, rms_norm_eps=1e-6,
    tie_word_embeddings=False,
    layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 2,
    linear_num_key_heads=4, linear_num_value_heads=4,
    linear_key_head_dim=6, linear_value_head_dim=12,
    linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
    rope_parameters={"rope_theta": 500000})
#: relative L2 error allowed at each compute type (float32: the same
#: arithmetic in another order; bf16: products and the residual
#: stream in bf16 through four layers, no router to flip)
TOLERANCE = {"float32": 2e-5, "bfloat16": 0.05}
LOSS_TOLERANCE = {"float32": 2e-6, "bfloat16": 2e-3}


@pytest.fixture(scope="module", autouse=True)
def toy_tiles():
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(seqpolicy, "ATTENTION_BLOCK", 8)
        patch.setattr(seqpolicy, "KDA_CHUNK", 8)
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
    assert extras == {}                 # no expert layer, no counts
    assert reference.relative_error(logits, wanted[0]) \
        < TOLERANCE[dtype] / 4


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


#: a leaf of every kind: delta layers 0-2, full layer 3
LEAVES = [
    ("embed",), ("head",), ("norm", "scale"),
    ("layer0", "ffn", "gate_proj"), ("layer3", "ffn", "down_proj"),
    ("layer1", "attn", "q_proj"), ("layer1", "attn", "k_proj"),
    ("layer1", "attn", "v_proj"), ("layer1", "attn", "q_conv"),
    ("layer1", "attn", "k_conv"), ("layer1", "attn", "v_conv"),
    ("layer1", "attn", "a_proj"), ("layer1", "attn", "dt_bias"),
    ("layer1", "attn", "A_log"), ("layer1", "attn", "b_proj"),
    ("layer1", "attn", "g_proj"), ("layer1", "attn", "o_norm", "scale"),
    ("layer1", "attn", "o_proj"), ("layer0", "attn", "a_proj"),
    ("layer3", "attn", "q_proj"), ("layer3", "attn", "k_proj"),
    ("layer3", "attn", "v_proj"), ("layer3", "attn", "o_proj"),
    ("layer3", "attn", "q_norm", "scale"),
    ("layer3", "attn", "k_norm", "scale"),
    ("layer2", "post_attention_norm", "scale"),
    ("layer2", "post_feedforward_norm", "scale"),
    ("layer3", "post_attention_norm", "scale"),
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
        assert reference.relative_error(got, ref[path]) \
            < TOLERANCE[dtype], jax.tree_util.keystr(path)


def test_the_reference_in_blocks_is_the_reference(net, batch, wanted,
                                                  monkeypatch):
    from chipbench import reference_ling

    monkeypatch.setattr(reference_ling, "RECURRENCE_SEGMENT", 8)
    logits = jax.jit(lambda p: reference.forward(
        p, batch[0], TOY, blocks=True))(net.params)
    assert reference.relative_error(logits, wanted[0]) < 1e-6
    paths = [("layer1", "attn", "a_proj"), ("layer3", "attn", "q_norm",
                                            "scale")]
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
    low = jax.jit(lambda p: reference.forward(
        p, batch[0], TOY, dtype=jnp.bfloat16))(net.params)
    program, _ = jax.jit(module_at(net, "bfloat16").apply)(
        net.params, batch[0])
    assert reference.relative_error(low, wanted[0]) \
        > reference.relative_error(program, wanted[0])


# ------------------------------------ the scan with one decay a head

def scan_inputs(key, s_len=64, heads=2, dk=6, dv=12, beta="drawn",
                gate=1.0):
    """Normed ``q, k``, ``v``, a log-decay ``[1, S, H]`` of about
    ``−gate`` a token and ``beta`` in (0, 2): drawn, or within 1e-3 of
    2 or of 0."""
    ks = jax.random.split(key, 5)
    q = seqpolicy.l2_normed(jax.random.normal(ks[0], (1, s_len, heads, dk)))
    k = seqpolicy.l2_normed(jax.random.normal(ks[1], (1, s_len, heads, dk)))
    v = jax.random.normal(ks[2], (1, s_len, heads, dv))
    g = -gate * jax.random.uniform(ks[3], (1, s_len, heads), minval=0.5,
                                   maxval=1.5)
    u = jax.random.uniform(ks[4], (1, s_len, heads))
    beta = {"drawn": 2 * u, "near_two": 2 - 1e-3 * u,
            "near_zero": 1e-3 * u}[beta]
    return q, k, v, g, beta


def token_by_token(q, k, v, g, beta):
    return reference.delta_recurrence(
        q[0], k[0], v[0], g[0][..., None], beta[0])[None]


@pytest.mark.parametrize("beta", ["drawn", "near_two", "near_zero"])
@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_scalar_decay_scan_is_the_recurrence(monkeypatch, chunk,
                                                 beta):
    """A row of 64 tokens as eight, four and one chunk, ``d_k`` 6
    beside ``d_v`` 12; ``β`` up to 2, where ``I − βkkᵀ`` reflects."""
    monkeypatch.setattr(seqpolicy, "KDA_CHUNK", chunk)
    args = scan_inputs(jax.random.key(5), beta=beta)
    got = seqpolicy.kda_chunked(*args, dtype=jnp.float32)
    assert got.shape == (1, 64, 2, 12)
    assert reference.relative_error(got, token_by_token(*args)) < 2e-5


@pytest.mark.parametrize("beta", ["drawn", "near_two"])
def test_the_scalar_decay_scans_gradient_is_the_recurrences(monkeypatch,
                                                            beta):
    monkeypatch.setattr(seqpolicy, "KDA_CHUNK", 8)
    args = scan_inputs(jax.random.key(6), s_len=32, beta=beta)
    weigh = jax.random.normal(jax.random.key(7), (1, 32, 2, 12))

    def chunked(*a):
        return (seqpolicy.kda_chunked(*a, dtype=jnp.float32)
                * weigh).sum()

    def plain(*a):
        return (token_by_token(*a) * weigh).sum()

    got = jax.grad(chunked, argnums=range(5))(*args)
    want = jax.grad(plain, argnums=range(5))(*args)
    for a, b in zip(got, want):
        assert reference.relative_error(a, b) < 5e-5


@pytest.mark.parametrize("gate", [12.0, 40.0, 200.0])
@pytest.mark.parametrize("chunk", [16, 64])
def test_gates_far_below_float32s_range_stay_finite_and_exact(
        monkeypatch, chunk, gate):
    """A log-decay of about −12, −40 and −200 a token: summed over a
    chunk it passes −88 (float32's ``e^−88``) within a few tokens, so
    the per-channel form's ``e^−G`` would overflow; ``D ≤ 1``
    never does, forward or backward."""
    monkeypatch.setattr(seqpolicy, "KDA_CHUNK", chunk)
    q, k, v, g, beta = scan_inputs(jax.random.key(8), gate=gate)
    assert float(jnp.cumsum(g[0, :chunk], axis=0).min()) < -88

    def scan(g, beta):
        return seqpolicy.kda_chunked(q, k, v, g, beta,
                                     dtype=jnp.float32)

    (_, got), grads = jax.jit(jax.value_and_grad(
        lambda g, b: (lambda o: (o.sum(), o))(scan(g, b)),
        argnums=(0, 1), has_aux=True))(g, beta)
    assert np.isfinite(np.asarray(got)).all()
    for x in grads:
        assert np.isfinite(np.asarray(x)).all()
    assert reference.relative_error(
        got, token_by_token(q, k, v, g, beta)) < 2e-5


def test_the_per_channel_scan_takes_a_broadcast_head_decay(monkeypatch):
    """The two forms agree where both hold: one log-decay a head,
    above KDA's bound of −5, given per key channel."""
    monkeypatch.setattr(seqpolicy, "KDA_CHUNK", 16)
    monkeypatch.setattr(seqpolicy, "KDA_SUB", 16)
    q, k, v, g, beta = scan_inputs(jax.random.key(9), gate=0.3)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    assert reference.relative_error(
        seqpolicy.kda_chunked(q, k, v, g, beta, dtype=jnp.float32),
        seqpolicy.kda_chunked(q, k, v, wide, beta, dtype=jnp.float32)
    ) < 2e-6


# ------------------------------------------------ the dense step

@pytest.mark.parametrize("width,norm", [(96, math.sqrt(96)), (96, 1.0),
                                        (192, None)],
                         ids=["queries", "keys", "values"])
def test_the_mixing_kernels_keep_the_product_and_the_taps_alone(
        monkeypatch, width, norm):
    """The mixing pass at the cell's heads — 30 of 96 lanes (q, k), 30
    of 192 (v) — traced for a TPU (shapes only, nothing runs): the
    Pallas kernels take it, and a ``jax.vjp`` of it saves the bf16
    product and the float32 taps, no float32 array of the product's
    size."""
    from jax._src.ad_checkpoint import saved_residuals

    monkeypatch.setattr(seqpolicy, "kernel_platform", lambda: "tpu")
    monkeypatch.setattr(seqpolicy, "KDA_CHUNK", 64)     # not the toy's
    y = jax.ShapeDtypeStruct((1, 8192, 30 * width), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((4, 30 * width), jnp.float32)

    def mixed(y, taps):
        return seqpolicy.kda_mixed(y, taps, 30, norm)

    assert seqpolicy.use_mixing_kernel(y, taps, 30)
    assert "kda_mixed_fwd" in str(jax.make_jaxpr(mixed)(y, taps))
    kept = [aval for aval, _ in saved_residuals(mixed, y, taps)]
    assert sorted((str(a.dtype), a.shape) for a in kept) == [
        ("bfloat16", y.shape), ("float32", taps.shape)]


def test_the_dense_step_runs_through_the_trainer_without_counts(net,
                                                                 batch):
    """``make_train_step`` with ``make_optimizer(SLConfig())``: a loss,
    an update, and no routing count for the counters to add."""
    tx = sl.make_optimizer(sl.SLConfig())
    step = jax.jit(sl.make_train_step(net.module.apply, tx, SIZE, True))
    state = sl.SLState(net.params, tx.init(net.params), jnp.int32(0),
                       pack_rng(jax.random.key(0)))
    new, metrics = step(state, *batch)
    assert set(metrics) == {"loss", "accuracy"}
    assert not set(metrics) & set(MOE_STATS)
    assert np.isfinite(float(metrics["loss"]))
    sl.record_routing(jax.device_get([metrics]))     # adds nothing
    # every matrix moves (a step of a per-head scalar such as A_log
    # may round away in float32)
    moved = jax.tree.map(lambda a, b: a.ndim < 2 or bool((a != b).any()),
                         net.params, new.params)
    assert all(jax.tree.leaves(moved))


def test_the_block_is_post_norm_and_dense(net):
    layers = net.module.layers
    assert [bool(s.kda) for s in layers] == [True, True, True, False]
    assert all(s.post_norm and not s.sparse for s in layers)
    full = layers[3]
    assert full.qk_norm and not full.gate and full.rope.dims == 8
    assert full.rope.theta == 500000
    kda = layers[0].kda
    assert (kda.key, kda.value, kda.lower) == (6, 12, None)
    assert net.module.ffn == ()
    p = net.params["params"]
    assert "input_norm" not in p["layer0"]
    assert p["layer3"]["attn"]["q_norm"]["scale"].shape == (32,)
    assert "gate_proj" not in p["layer3"]["attn"]
    assert p["layer0"]["attn"]["a_proj"].shape == (32, 4)
    assert p["layer0"]["attn"]["dt_bias"].shape == (4,)
    assert p["layer0"]["attn"]["v_conv"].shape == (4, 48)


# ------------------------------------------------- specs and refusals

@pytest.mark.parametrize("key,value", [
    ("layer_types", ["linear_attention", "sliding_attention",
                     "linear_attention", "full_attention"]),
    ("linear_num_key_heads", 2),
    ("rope_parameters", {"rope_theta": None}),
    ("attention_bias", True),
    ("hidden_act", "gelu"),
    ("linear_allow_neg_eigval", False),
    ("linear_allow_neg_eigval", "sometimes"),
    ("model_type", "olmo3"),
    ("tie_word_embeddings", True),
])
def test_a_spec_that_asks_for_what_is_not_computed_is_refused(key,
                                                              value):
    match = "rope_theta" if key == "rope_parameters" else key
    with pytest.raises(ValueError, match=match):
        SeqPolicy(board=SIZE, init_weights=False,
                  **dict(TOY, **{key: value}))


def test_a_layer_kind_that_is_not_held_is_no_refusal():
    SeqPolicy(board=SIZE, init_weights=False,
              **dict(TOY, layer_types=TOY["layer_types"][:4]
                     + ["sliding_attention"] * 4))
