"""The move-sequence policy (``models/seqpolicy.py``) against its
plain reference (``chipbench/reference_laguna.py``) at a toy size
that keeps the structure: 5 layers ``full, sliding ×3, full``,
per-layer head counts 6/9 over 3 key/value heads (the published
48/72 over 8: six and nine to a group), window 8 on S = 32, 16
experts top-3 of which 4 are held, a dense layer 0, partial rotary on
the full layers only. Seeded random weights; nothing here is a device
number.
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

from chipbench import reference_laguna as reference  # noqa: E402
from rocalphago_tpu.models import NeuralNetBase, seqpolicy  # noqa: E402
from rocalphago_tpu.models.seqpolicy import SeqPolicy  # noqa: E402
from rocalphago_tpu.training import sl  # noqa: E402
from rocalphago_tpu.training.symmetries import (  # noqa: E402
    random_transform_batch,
    transform_action,
    transform_planes,
)

SEQ, VOCAB, SIZE = 32, 512, 19
TOY = dict(
    vocab_size=VOCAB, vocab_held=VOCAB, hidden_size=32,
    intermediate_size=64, num_hidden_layers=5, layers_held=5,
    num_attention_heads_per_layer=[6, 9, 9, 9, 6],
    num_key_value_heads=3, head_dim=8,
    layer_types=["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4, sliding_window=8,
    rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    num_experts=16, num_experts_per_tok=3, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, norm_topk_prob=True,
    moe_routed_scaling_factor=2.5, experts_held=4, expert_offset=4,
    rms_norm_eps=1e-6)
#: relative L2 error allowed at each compute type: float32 is the
#: same arithmetic in another order; bf16 products put ~1e-2 on a
#: leaf, and a top-3 choice flipped near a tie a little more
TOLERANCE = {"float32": 1e-5, "bfloat16": 0.2}
#: and on the loss, a mean over 64 positions
LOSS_TOLERANCE = {"float32": 2e-6, "bfloat16": 2e-3}


@pytest.fixture(scope="module", autouse=True)
def toy_tiles():
    """The toy rows in more than one piece: four chunks of tokens in
    the expert layer, four query blocks in a full layer."""
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
    logits = reference.forward(net.params, batch[0], TOY)
    loss, grads = reference.loss_and_grads(net.params, *batch, TOY)
    return logits, loss, grads


def module_at(net, dtype: str):
    return net.module.clone(dtype=jnp.dtype(dtype))


# ------------------------------------------- system vs the reference

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_logits_match_the_reference(net, batch, wanted, dtype):
    logits, stats = jax.jit(module_at(net, dtype).apply)(
        net.params, batch[0])
    assert logits.shape == (2, SEQ, VOCAB)
    assert logits.dtype == jnp.float32
    assert reference.relative_error(logits, wanted[0]) \
        < TOLERANCE[dtype] / 4
    assert int(stats["moe_routed"]) == 4 * 2 * SEQ * 3
    assert 0 < int(stats["moe_held"]) < int(stats["moe_routed"])
    assert int(stats["moe_dropped"]) == 0


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def program_grads(request, net, batch):
    """(dtype, loss, gradient tree) through the trainer's own loss."""
    apply = module_at(net, request.param).apply
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: sl.policy_loss_fn(apply, p, *batch)[0]))(net.params)
    return request.param, loss, grads


def test_loss_matches_the_reference(program_grads, wanted):
    dtype, loss, _ = program_grads
    assert abs(float(loss) - float(wanted[1])) / float(wanted[1]) \
        < LOSS_TOLERANCE[dtype]


def leaf_paths():
    net = SeqPolicy(board=SIZE, init_weights=False, **TOY)
    shapes = jax.eval_shape(net.module.init, jax.random.key(0),
                            jnp.zeros((1, 1), jnp.int32))
    return ["/".join(k.key for k in path) for path, _ in
            jax.tree_util.tree_flatten_with_path(shapes)[0]]


@pytest.mark.parametrize("path", leaf_paths())
def test_every_leafs_gradient_matches_the_reference(
        program_grads, wanted, path):
    dtype, _, grads = program_grads

    def leaf(tree):
        for key in path.split("/"):
            tree = tree[key]
        return tree

    assert reference.relative_error(leaf(grads), leaf(wanted[2])) \
        < TOLERANCE[dtype]


def test_the_reference_in_blocks_is_the_reference(net, batch, wanted):
    logits = reference.forward(net.params, batch[0], TOY, blocks=True)
    assert reference.relative_error(logits, wanted[0]) < 1e-5
    paths = [("layer1", "ffn", "router"), ("embed",),
             ("layer4", "attn", "gate_proj")]
    loss, grads = reference.loss_and_grads(
        net.params, *batch, TOY, paths=paths, blocks=True)
    assert abs(float(loss) - float(wanted[1])) < 1e-5
    for name, g in grads.items():
        want = reference.pick(wanted[2], [tuple(name.split("/"))])
        assert reference.relative_error(g, want[name]) < 1e-5


def test_lowering_the_float32_parts_is_caught(net, batch, wanted):
    """bf16 router, softmax, norms and loss move a gradient past what
    bf16 products alone do — the reading the chip's tolerances are
    set against."""
    _, grads = reference.loss_and_grads(net.params, *batch, TOY,
                                        dtype=jnp.bfloat16)
    errs = jax.tree.leaves(jax.tree.map(
        reference.relative_error, grads, wanted[2]))
    assert max(errs) > TOLERANCE["bfloat16"]


# ---------------------------------------------------- the expert layer

def ffn_module(held: int, offset: int, dtype=jnp.float32):
    return seqpolicy.SparseFFN(
        num_experts=16, top_k=3, width=16, shared_width=16,
        experts_held=held, expert_offset=offset, norm_topk=True,
        routed_scale=2.5, dtype=dtype)


@pytest.fixture(scope="module")
def whole_layer():
    """An uncut sparse layer (all 16 experts) and its input."""
    x = jax.random.normal(jax.random.key(5), (2, SEQ, 32))
    module = ffn_module(16, 0)
    return module.init(jax.random.key(6), x), x


def middle_share(params) -> dict:
    """The layer's parameters with experts 4..7 of the 16 alone."""
    p = params["params"]
    return dict(p, **{n: p[n][4:8] for n in
                      ("experts_gate", "experts_up", "experts_down")})


MIDDLE = dict(TOY, experts_held=4, expert_offset=4)


def share_and_grads(mine, x):
    """(value, routing counts), gradients — of a probe's dot with
    what ``SparseFFN`` holding experts 4..7 returns — and the same of
    the reference."""
    probe = jax.random.normal(jax.random.key(11), x.shape)

    def program(mine, x):
        out, stats = ffn_module(4, 4).apply({"params": mine}, x)
        return (out * probe).sum(), stats

    def plain(mine, x):
        out = jnp.stack([reference.sparse_ffn(mine, row, MIDDLE)
                         for row in x])
        return (out * probe).sum()

    return (jax.value_and_grad(program, (0, 1), has_aux=True)(mine, x),
            jax.value_and_grad(plain, (0, 1))(mine, x))


def test_the_shares_add_up_to_the_uncut_layer(whole_layer):
    """Four chips' routed parts plus the shared expert, which every
    chip computes alike, counted once = the uncut reference."""
    params, x = whole_layer
    p = params["params"]
    kw = dict(TOY, experts_held=16, expert_offset=0)
    want = jnp.stack([reference.sparse_ffn(p, row, kw) for row in x])
    shared = jnp.stack([
        reference._mlp(row, *(p["shared"][n] for n in
                              ("gate_proj", "up_proj", "down_proj")))
        for row in x])
    total, held = shared, 0
    for share in range(4):
        mine = dict(p, **{n: p[n][4 * share:4 * share + 4] for n in
                          ("experts_gate", "experts_up",
                           "experts_down")})
        out, stats = ffn_module(4, 4 * share).apply({"params": mine}, x)
        total = total + (out - shared)
        held += int(stats["moe_held"])
        assert int(stats["moe_dropped"]) == 0
    assert held == 2 * SEQ * 3          # every pair landed somewhere
    assert reference.relative_error(total, want) < 1e-5


@pytest.fixture(params=[16, 64], ids=["four chunks", "one chunk"])
def buffers(request, monkeypatch):
    """The layer's 64 tokens in chunks of 16 (buffers of 48 rows) and
    in one chunk (a buffer of 192)."""
    monkeypatch.setattr(seqpolicy, "EXPERT_CHUNK", request.param)
    return request.param


#: heights of the buffers' row blocks, against buffers of 48 and 192
#: rows of which a quarter hold a pair (30 of 48 in ``one_chunk``):
#: the module's own (one block), 4 (most blocks hold nothing; the
#: last that runs is cut mid-way), 16 (48 rows: three blocks, of
#: which ``one_chunk`` runs two), 48 (one block of 48, four of 192)
#: and 5, which divides neither buffer (one block; six whole blocks
#: of ``one_chunk``'s 30-row buffer)
ROW_BLOCKS = [None, 4, 16, 48, 5]


@pytest.fixture(params=ROW_BLOCKS,
                ids=[f"blocks of {b or 'all'}" for b in ROW_BLOCKS])
def row_block(request, monkeypatch):
    if request.param:
        monkeypatch.setattr(seqpolicy, "EXPERT_ROW_BLOCK",
                            request.param)
    return request.param


def test_the_held_share_and_its_gradients_with_either_buffer(
        whole_layer, buffers, row_block):
    params, x = whole_layer
    ((got, stats), g), (want, w) = share_and_grads(
        middle_share(params), x)
    assert abs(float(got) - float(want)) < 1e-4
    assert int(stats["moe_dropped"]) == 0
    if row_block == 4:          # a quarter of the pairs are held
        assert int(stats["moe_row_blocks_run"]) \
            < int(stats["moe_row_blocks"]) // 2
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
        assert np.isfinite(np.asarray(a)).all()
        assert reference.relative_error(a, b) < 1e-5


def test_no_pair_is_dropped_when_every_token_picks_one_expert(
        whole_layer, buffers, row_block):
    """The worst imbalance: the router sends every token to the same
    three experts, all held here."""
    params, x = whole_layer
    p = dict(params["params"])
    p["router"] = jnp.zeros_like(p["router"]).at[:, 4:7].set(
        jnp.array([3.0, 2.0, 1.0]))
    x = jnp.abs(x)                      # so the logits keep their order
    mine = middle_share({"params": p})
    out, stats = ffn_module(4, 4).apply({"params": mine}, x)
    assert int(stats["moe_held"]) == int(stats["moe_routed"]) \
        == 2 * SEQ * 3
    assert int(stats["moe_dropped"]) == 0
    assert int(stats["moe_load_max"]) == 2 * SEQ
    assert int(stats["moe_row_blocks_run"]) \
        == int(stats["moe_row_blocks"]) \
        == 2 * SEQ * 3 // seqpolicy._row_block(buffers * 3)
    want = jnp.stack([reference.sparse_ffn(mine, row, MIDDLE)
                      for row in x])
    assert reference.relative_error(out, want) < 1e-5


def chunk_inputs():
    """16 tokens' 48 pairs, 30 of them for the 4 held experts (sizes
    12, 9, 6, 3): ``x``, ``local``, ``weight`` and the matrices."""
    keys = jax.random.split(jax.random.key(13), 5)
    x = jax.random.normal(keys[0], (16, 32))
    local = jnp.array([0] * 12 + [1] * 9 + [2] * 6 + [3] * 3
                      + [7] * 18).reshape(16, 3)
    weight = jax.random.uniform(keys[1], (16, 3))
    mats = [jax.random.normal(k, shape) for k, shape in zip(
        keys[2:], [(4, 32, 16), (4, 32, 16), (4, 16, 32)])]
    return x, local, weight, mats


def one_chunk(rows):
    """``chunk_inputs`` through a buffer of ``rows`` rows."""
    x, local, weight, mats = chunk_inputs()
    out, sizes, dropped, blocks = seqpolicy.held_experts(
        x, local, weight, *mats, rows=rows)
    # the row blocks follow the pairs the buffer holds
    block = seqpolicy._row_block(rows)
    assert blocks.tolist() == [-(-min(30, rows) // block),
                               rows // block]
    each = jnp.stack([reference._mlp(x, *(m[e] for m in mats))
                      for e in range(4)], axis=1)       # [T, E, D]
    return out, sizes, int(dropped), each, local, weight


@pytest.mark.parametrize("rows", [48, 32, 30])
def test_a_buffer_that_holds_what_arrives_drops_nothing(rows,
                                                        row_block):
    out, sizes, dropped, each, local, weight = one_chunk(rows)
    assert sizes.tolist() == [12, 9, 6, 3] and dropped == 0
    want = sum(jnp.where((local == e)[..., None], weight[..., None]
                         * each[:, e, None], 0) for e in range(4))
    assert reference.relative_error(out, want.sum(axis=1)) < 1e-5


@pytest.mark.parametrize("rows,lost", [(24, 6), (16, 14), (8, 22)])
def test_a_buffer_cut_below_what_arrives_counts_what_it_left_out(
        rows, lost, row_block):
    """The count is taken from the buffer, not assumed: pairs past
    its last row are counted and add nothing; the others are whole."""
    out, sizes, dropped, each, local, weight = one_chunk(rows)
    assert sizes.tolist() == [12, 9, 6, 3]      # what ARRIVED
    assert dropped == lost
    # pairs are kept in sorted order: expert 0's first, in token order
    flat = np.asarray(local).reshape(-1)
    kept = np.zeros(48, bool)
    kept[np.argsort(np.where(flat < 4, flat, 4), kind="stable")[:rows]] \
        = True
    kept = jnp.asarray(kept.reshape(16, 3)) & (local < 4)
    want = sum(jnp.where(((local == e) & kept)[..., None],
                         weight[..., None] * each[:, e, None], 0)
               for e in range(4))
    assert reference.relative_error(out, want.sum(axis=1)) < 1e-5


@pytest.mark.parametrize("block,run,of", [
    (4, 8, 12), (16, 2, 3), (48, 1, 1), (5, 1, 1), (2048, 1, 1)])
def test_the_row_blocks_that_run_are_those_that_hold_a_pair(
        monkeypatch, block, run, of):
    """30 held pairs in a buffer of 48 rows."""
    monkeypatch.setattr(seqpolicy, "EXPERT_ROW_BLOCK", block)
    x, local, weight, mats = chunk_inputs()
    blocks = seqpolicy.held_experts(x, local, weight, *mats)[3]
    assert blocks.tolist() == [run, of]


def spoiled_ragged_dot(real):
    """``ragged_dot`` at its least forgiving: the rows past its
    groups — of the product, and of the cotangent it hands back for
    its left side — hold NaN, and if anything but zero was FED to it
    in such a row, everything it returns is NaN."""
    def spoil(out, sizes, *fed):
        past = (jnp.arange(fed[0].shape[0]) >= sizes.sum())[:, None]
        dirty = jnp.any(jnp.stack(
            [((x != 0) & past).any() for x in fed]))
        if out.shape[0] == past.shape[0] and out.ndim == 2:
            dirty = dirty | past
        return jnp.where(dirty, jnp.nan, out)

    @jax.custom_vjp
    def dot(a, w, sizes):
        return spoil(real(a, w, sizes), sizes, a)

    def fwd(a, w, sizes):
        return dot(a, w, sizes), (a, w, sizes)

    def bwd(res, g):
        a, w, sizes = res
        da, dw = jax.vjp(lambda a, w: real(a, w, sizes), a, w)[1](g)
        return spoil(da, sizes, a, g), spoil(dw, sizes, a, g), None

    dot.defvjp(fwd, bwd)
    return dot


@pytest.mark.parametrize("block", [4, 16, 2048])
def test_what_a_product_leaves_past_its_groups_reaches_nothing(
        whole_layer, monkeypatch, block):
    """The guard against ``0 × garbage``: with NaN in every row a
    product does not own, the held share and all its gradients are
    finite and the reference's, most blocks skipped or none."""
    monkeypatch.setattr(seqpolicy, "EXPERT_ROW_BLOCK", block)
    monkeypatch.setattr(seqpolicy, "EXPERT_CHUNK", 64)
    params, x = whole_layer
    # the reference multiplies densely: no product of its own
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        spoiled_ragged_dot(jax.lax.ragged_dot))
    ((got, stats), g), (want, w) = share_and_grads(
        middle_share(params), x)
    assert int(stats["moe_held"]) < 2 * SEQ * 3 // 2    # rows are left
    assert abs(float(got) - float(want)) < 1e-4
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
        assert np.isfinite(np.asarray(a)).all()
        assert reference.relative_error(a, b) < 1e-5


@pytest.mark.parametrize("block", [4, 16, 48])
def test_skipping_blocks_changes_no_row_that_holds_a_pair(
        whole_layer, monkeypatch, block):
    """The layer with most row blocks skipped against the layer at
    one block: float32 round-off apart, and no further."""
    params, x = whole_layer
    mine = middle_share(params)
    monkeypatch.setattr(seqpolicy, "EXPERT_CHUNK", 64)
    whole, _ = ffn_module(4, 4).apply({"params": mine}, x)
    monkeypatch.setattr(seqpolicy, "EXPERT_ROW_BLOCK", block)
    out, stats = ffn_module(4, 4).apply({"params": mine}, x)
    assert int(stats["moe_row_blocks"]) == 192 // block
    assert int(stats["moe_row_blocks_run"]) \
        == -(-int(stats["moe_held"]) // block)
    assert reference.relative_error(out, whole) < 1e-6


# The token-major forms that the rules of ``held_experts`` had before
# they walked the buffer's row blocks (PR 29), kept here as the oracle:
# every pair's row gathered by the inverse of the sort, ``[T, K, D]``.

def from_rows(rows, inverse, held):
    t, k = held.shape
    r = rows.shape[0]
    inside = held & (inverse.reshape(t, k) < r)
    at = jnp.minimum(inverse, r - 1)
    return jnp.where(inside[..., None], rows[at].reshape(t, k, -1), 0)


def token_major_combine(y, weight, inverse, held):
    return (from_rows(y, inverse, held) * weight[..., None]).sum(axis=1)


def token_major_dispatch_bwd(g, inverse, held, n_held):
    live = (jnp.arange(g[0].shape[0]) < n_held)[:, None]
    return from_rows(jnp.where(live, g[0] + g[1], 0), inverse,
                     held).sum(axis=1)


def sorted_pairs(local, rows):
    """``held_experts``' sort of a chunk's pairs by held expert (4
    held): the buffer's pairs and tokens, the inverse of the sort,
    which pairs are held, and the rows that hold one."""
    t, k = local.shape
    held = (local >= 0) & (local < 4)
    order = jnp.argsort(jnp.where(held, local, 4).reshape(-1),
                        stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    mine = order[:rows]
    return mine, mine // k, inverse, held, jnp.minimum(held.sum(), rows)


#: 16 tokens' three choices among 4 held experts (others: 7)
ROUTINGS = {
    # chunk_inputs' own: 30 of 48 pairs held, sizes 12, 9, 6, 3
    "balanced": [0] * 12 + [1] * 9 + [2] * 6 + [3] * 3 + [7] * 18,
    # every pair lands here, every token on the same three experts
    "every token on one expert": [2, 0, 1] * 16,
    # a token's pairs lie rows apart in the sort and, with three
    # tokens all told, several times inside any block
    "a token holds several": ([0, 1, 2] * 3 + [1, 1, 1, 7, 3, 3]
                              + [7] * 33),
    "no pair held": [7] * 48,
}
#: (routing, rows of the buffer, height of its row blocks, NaN past
#: the rows that hold a pair)
PASSES = (
    [(name, 48, block, False) for name in ROUTINGS
     for block in (4, 16, 2048)]
    + [("balanced", rows, block, False) for rows in (24, 16, 8)
       for block in (4, 16)]
    + [("balanced", 48, block, True) for block in (4, 16, 2048)]
    + [("a token holds several", 48, 4, True), ("no pair held", 48, 16,
                                               True)])


@pytest.fixture(params=PASSES, ids=[
    f"{name}, {rows} rows in blocks of {block}"
    + (", NaN past the pairs" if spoiled else "")
    for name, rows, block, spoiled in PASSES])
def a_pass(request, monkeypatch):
    """A chunk's sort and a buffer ``[rows, 32]`` of it, twice."""
    name, rows, block, spoiled = request.param
    monkeypatch.setattr(seqpolicy, "EXPERT_ROW_BLOCK", block)
    local = jnp.array(ROUTINGS[name]).reshape(16, 3)
    mine, tok, inverse, held, n_held = sorted_pairs(local, rows)
    keys = jax.random.split(jax.random.key(17), 5)
    bufs = [jax.random.normal(k, (rows, 32)) for k in keys[:2]]
    if spoiled:
        past = (jnp.arange(rows) >= n_held)[:, None]
        bufs = [jnp.where(past, jnp.nan, b) for b in bufs]
    weight = jax.random.uniform(keys[2], (16, 3))
    probe = jax.random.normal(keys[3], (16, 32))
    x = jax.random.normal(keys[4], (16, 32))
    return (mine, tok, inverse, held, n_held), bufs, weight, probe, x


def test_combine_by_row_blocks_is_the_token_major_sum(a_pass):
    """The weighted sum and both its gradients — the rows' and the
    weights' — against a gather of every pair's row."""
    (mine, tok, inverse, held, n_held), (y, _), weight, probe, _ = a_pass

    def rows_major(y, weight):
        return seqpolicy._combine(y, weight, mine, tok, n_held)

    def token_major(y, weight):
        return token_major_combine(y, weight, inverse, held)

    got, want = rows_major(y, weight), token_major(y, weight)
    assert got.dtype == jnp.float32 and got.shape == (16, 32)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    g, w = (jax.grad(lambda *a: (f(*a) * probe).sum(), (0, 1))(y, weight)
            for f in (rows_major, token_major))
    for a, b in zip(g, w):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # a row that holds no pair is owed nothing
    assert not np.asarray(g[0])[int(n_held):].any()


def test_dispatch_backward_by_row_blocks_is_the_token_major_sum(a_pass):
    """The buffer's rows are their tokens', zero past the pairs, and
    the two products' cotangents come back summed by token — against
    the gather of every pair's row and its sum over ``top_k``."""
    (mine, tok, inverse, held, n_held), g, _, probe, x = a_pass
    (xs, again), back = jax.vjp(
        lambda x: seqpolicy._dispatch(x, tok, n_held), x)
    live = (jnp.arange(mine.shape[0]) < n_held)[:, None]
    np.testing.assert_array_equal(xs, jnp.where(live, x[tok], 0))
    np.testing.assert_array_equal(xs, again)
    got, = back(tuple(g))
    assert got.dtype == x.dtype and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(
        got, token_major_dispatch_bwd(g, inverse, held, n_held),
        rtol=1e-5, atol=1e-6)
    # and through ``jax.grad``, both outputs read
    dx = jax.grad(lambda x: sum(
        (o[:n_held] * probe[0]).sum()
        for o in seqpolicy._dispatch(x, tok, n_held)))(x)
    want = jnp.zeros_like(x).at[tok[:n_held]].add(2 * probe[0])
    np.testing.assert_allclose(dx, want, rtol=1e-5, atol=1e-6)


def test_no_pass_of_the_expert_layer_is_token_major(monkeypatch):
    """Forward and backward of a chunk hold no ``[T, K, D]`` array
    and gather no ``T·K`` rows of width ``D``: what goes through the
    buffer goes by row blocks (48 rows in blocks of 16 here)."""
    monkeypatch.setattr(seqpolicy, "EXPERT_ROW_BLOCK", 16)
    x, local, weight, mats = chunk_inputs()

    def share(x, weight, *mats):
        return seqpolicy.held_experts(x, local, weight, *mats)[0].sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(share, (0, 1, 2, 3, 4)))(
        x, weight, *mats)

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    found = list(eqns(jaxpr.jaxpr))
    shapes = {v.aval.shape for eqn in found for v in eqn.outvars}
    assert (48, 32) in shapes           # the buffer itself
    assert (16, 3, 32) not in shapes
    gathers = [eqn.outvars[0].aval.shape for eqn in found
               if eqn.primitive.name == "gather"]
    assert (16, 32) in gathers          # a block of the buffer's rows
    assert not [s for s in gathers if s[0] == 48 and s[-1] == 32]
    loops = [eqn for eqn in found if eqn.primitive.name == "while"]
    assert len(loops) >= 6              # every rule's, and no other


def test_the_routers_choices_are_kept_for_who_asks(net, batch):
    """``chipbench`` compares the program's top-k choices with the
    reference's: a caller that makes ``intermediates`` mutable gets
    each sparse layer's, the train step gets nothing more."""
    (logits, _), kept = jax.jit(
        lambda p, i: net.module.clone(dtype=jnp.float32).apply(
            p, i, mutable=["intermediates"]))(net.params, batch[0])
    chosen = seqpolicy.chosen_experts(kept)
    assert sorted(chosen) == ["layer1", "layer2", "layer3", "layer4"]
    _, masks = reference.forward(net.params, batch[0], TOY,
                                 choices=True)
    for i, name in enumerate(sorted(chosen)):
        got = np.asarray(chosen[name])              # [T, K]
        assert got.shape == (2 * SEQ, 3)
        mask = np.asarray(masks[i]).reshape(2 * SEQ, 16)
        assert mask.sum(axis=1).tolist() == [3] * (2 * SEQ)
        assert np.take_along_axis(mask, got, axis=1).all()
    out = net.module.apply(net.params, batch[0])
    assert len(out) == 2                            # no third part


# ---------------------------------------------------------- attention

def random_qkv(seq: int, heads: int = 6, groups: int = 3, d: int = 8):
    keys = jax.random.split(jax.random.key(7), 3)
    return (jax.random.normal(keys[0], (2, seq, heads, d)),
            jax.random.normal(keys[1], (2, seq, groups, d)),
            jax.random.normal(keys[2], (2, seq, groups, d)))


def program_attention(q, k, v, window: int, block: int):
    """The XLA form at a query block of ``block``; it takes queries
    already scaled by 1/√d."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(seqpolicy, "ATTENTION_BLOCK", block)
        return seqpolicy.grouped_attention(
            q / np.sqrt(q.shape[-1]), k, v, window)


def dense_attention(q, k, v, window: int):
    """Per head under the reference's dense mask."""
    per = q.shape[2] // k.shape[2]
    return jnp.stack([jnp.stack([
        reference._head(q[b, :, h], k[b, :, h // per],
                        v[b, :, h // per], window)
        for h in range(q.shape[2])], axis=1)
        for b in range(q.shape[0])])


def test_window_mask_is_the_full_mask_when_it_covers_the_row():
    q, k, v = random_qkv(8)
    full = program_attention(q, k, v, 0, 4)
    for window in (8, 16):
        got = program_attention(q, k, v, window, 4)
        assert reference.relative_error(got, full) < 1e-6
    assert reference.relative_error(
        full, dense_attention(q, k, v, 8)) < 1e-5


@pytest.mark.parametrize("window,block", [(0, 8), (0, 32), (8, 8),
                                          (16, 8)])
def test_blockwise_attention_is_the_dense_masked_one(window, block):
    q, k, v = random_qkv(SEQ)
    got = program_attention(q, k, v, window, block)
    assert reference.relative_error(
        got, dense_attention(q, k, v, window)) < 1e-5


@pytest.mark.parametrize("window", [0, 128])
def test_the_attention_kernel_is_the_dense_masked_one(window):
    """The splash kernel's masks and grouping, interpreted on the CPU
    at the smallest shape its tiles take: causal, and ``i − j <
    window`` to the position."""
    import unittest.mock

    q, k, v = random_qkv(256, heads=4, groups=2, d=128)
    with unittest.mock.patch.object(seqpolicy, "KERNEL_BLOCK", 128):
        got = seqpolicy.kernel_attention(
            q / np.sqrt(128), k, v, window, interpret=True)
    assert reference.relative_error(
        got, dense_attention(q, k, v, window)) < 1e-4


# what a layer's recomputation keeps: the kernel's output and row
# statistics by name, so its forward runs once a step

#: the toy at a head the kernel takes: 5 layers, each with a kernel
KERNEL_TOY = dict(TOY, head_dim=128)
KERNEL_LAYERS = 5


@pytest.mark.parametrize("kept", [True, False])
def test_the_backward_pass_recomputes_a_layer_but_not_its_kernel(
        kernel_gradient, kept):
    """Forward, ``dq`` and ``dkv`` a layer — and a second forward
    call where the layer is recomputed whole, as it was."""
    calls, names = kernel_gradient(KERNEL_TOY, kept)
    assert calls == (3 if kept else 4) * KERNEL_LAYERS
    assert names == {seqpolicy.KERNEL_RESIDUALS}


def test_the_kernels_name_and_the_policys_are_one_constant(
        kernel_gradient, monkeypatch):
    monkeypatch.setattr(seqpolicy, "KERNEL_RESIDUALS", "another")
    assert kernel_gradient(KERNEL_TOY) == (3 * KERNEL_LAYERS,
                                           {"another"})


def test_the_policy_keeps_nothing_of_the_xla_form(
        net, batch, whole_layer_remat):
    """No value of the XLA form carries the name, so loss and
    gradients are the whole-layer recomputation's to the bit: the
    lowered program is that one's, letter for letter."""
    def lowered():
        # a fresh function each time: jit's cache holds functions,
        # not what they close over
        return jax.jit(jax.value_and_grad(
            lambda p: sl.policy_loss_fn(
                net.module.apply, p, *batch)[0])).lower(
                net.params).as_text()

    # two traces of the whole step: jax's own checks of every
    # equation are not what is compared
    with jax.enable_checks(False):
        kept = lowered()
        with whole_layer_remat():
            assert lowered() == kept


def test_the_kept_residuals_give_the_xla_forms_gradients(
        whole_layer_remat):
    """One full-attention layer through the kernel, interpreted on
    the CPU in float32: the backward kernels read the output and row
    statistics the forward call left, and the gradients are the
    whole-layer recomputation's to the bit and the XLA form's to
    rounding."""
    import functools

    layer = seqpolicy.LayerSpec(
        heads=2, window=0, rope=seqpolicy.Rope("default", 1e4, 128),
        sparse=False)
    module = seqpolicy.SeqPolicyNet(
        layers=(layer,), hidden=32, vocab_held=64, kv_heads=1,
        head_dim=128, dense_width=32, ffn=(), dtype=jnp.float32)
    ids = jax.random.randint(jax.random.key(5), (1, 256), 0, 64)
    params = module.init(jax.random.key(6), ids)

    def grads():
        return jax.jit(jax.grad(lambda p: sl.policy_loss_fn(
            module.apply, p, ids, ids[:, ::-1])[0]))(params)

    xla = grads()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(seqpolicy, "kernel_platform", lambda: "tpu")
        patch.setattr(seqpolicy, "KERNEL_BLOCK", 128)
        patch.setattr(seqpolicy, "kernel_attention", functools.partial(
            seqpolicy.kernel_attention, interpret=True))
        kept = grads()
        with whole_layer_remat():
            whole = grads()
    for a, b, c in zip(*map(jax.tree.leaves, (kept, whole, xla))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert reference.relative_error(a, c) < 1e-4


def test_the_kernel_runs_under_its_scope_where_its_tiles_fit():
    from rocalphago_tpu.obs import scopes

    assert not seqpolicy.use_kernel(8192, 128)      # this is a CPU
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (
        (1, 512, 4, 128), (1, 512, 2, 128), (1, 512, 2, 128)))
    jaxpr = jax.make_jaxpr(
        lambda q, k, v: seqpolicy.kernel_attention(q, k, v, 0))(q, k, v)

    def calls(jaxpr, outer=""):
        # an inner jaxpr's name stacks are relative to its caller's
        for eqn in jaxpr.eqns:
            name = f"{outer}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                yield name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub, name)

    found = list(calls(jaxpr.jaxpr))
    assert found and all(scopes.SEQ_ATTN_KERNEL in n for n in found)
    import unittest.mock

    with unittest.mock.patch.object(seqpolicy, "kernel_platform",
                                    lambda: "tpu"):
        assert seqpolicy.use_kernel(8192, 128)
        assert not seqpolicy.use_kernel(8192, 64)
        assert not seqpolicy.use_kernel(32, 128)


def test_rotary_tables_agree_with_the_reference():
    for kind, r in TOY["rope_parameters"].items():
        rope = seqpolicy.layer_specs(TOY)[
            TOY["layer_types"].index(kind)].rope
        np.testing.assert_allclose(
            seqpolicy.rope_inv_freq(rope),
            reference._inv_freq(r, TOY["head_dim"]), rtol=1e-6)
    yarn = seqpolicy.layer_specs(TOY)[0].rope
    assert yarn.kind == "yarn" and yarn.dims == 4   # half the head


# ------------------------------------------------------- augmentation

@pytest.mark.parametrize("t", range(8))
def test_a_rows_transform_is_the_planes_transform(t):
    """A replayed game: the stone of move ``i`` lands where the
    transformed id says, and pass, separator and any later id stay."""
    rng = np.random.default_rng(t)
    moves = rng.permutation(SIZE * SIZE)[:60]
    row = np.concatenate([moves, [361, 362, 400, 12543]])
    out = np.asarray(transform_action(jnp.asarray(row), t, SIZE))
    assert (out[60:] == row[60:]).all()
    board = np.zeros((SIZE, SIZE), np.int32)
    board.reshape(-1)[moves] = np.arange(1, 61)     # move numbers
    turned = np.asarray(transform_planes(jnp.asarray(board), t))
    assert (turned.reshape(-1)[out[:60]] == np.arange(1, 61)).all()


def test_one_transform_per_row_of_a_batch():
    ids = jnp.tile(jnp.arange(SEQ, dtype=jnp.int32), (16, 1))
    a, b = random_transform_batch(jax.random.key(0), ids, ids + 1,
                                  SIZE)
    assert a.shape == b.shape == (16, SEQ) and a.dtype == jnp.int32
    kinds = {tuple(np.asarray(row)) for row in a}
    assert 1 < len(kinds) <= 8          # rows differ, by ≤ 8 elements
    for row_in, row_out in zip(np.asarray(ids), np.asarray(a)):
        ts = [t for t in range(8) if (np.asarray(transform_action(
            jnp.asarray(row_in), t, SIZE)) == row_out).all()]
        assert ts                       # a single t explains the row


# ------------------------------------------------ the trainer's path

def test_the_train_step_learns_and_returns_the_routing_counts(net):
    tx = sl.make_optimizer(sl.SLConfig(learning_rate=0.05))
    step = jax.jit(sl.make_train_step(net.module.apply, tx, SIZE, True))
    state = sl.SLState(net.params, tx.init(net.params), jnp.int32(0),
                       jax.random.key_data(jax.random.key(0)))
    ids = jax.random.randint(jax.random.key(9), (2, SEQ), 362, VOCAB)
    losses = []
    for _ in range(8):
        state, m = step(state, ids, jnp.roll(ids, -1, axis=1))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert set(seqpolicy.MOE_STATS) <= set(m)
    from rocalphago_tpu.obs import registry

    before = registry.snapshot()["counters"].get(
        registry.MOE_TOKENS_HELD, 0)
    sl.record_routing([jax.device_get(m)])
    after = registry.snapshot()
    assert after["counters"][registry.MOE_TOKENS_HELD] - before \
        == int(m["moe_held"])
    assert after["gauges"][registry.MOE_EXPERT_LOAD_MAX] \
        == int(m["moe_load_max"])
    # 2 rows of 32 tokens in chunks of 16 tokens, 4 sparse layers
    assert int(m["moe_row_blocks_run"]) == int(m["moe_row_blocks"]) \
        == after["counters"][registry.MOE_ROW_BLOCKS] == 4 * 4
    assert after["counters"][registry.MOE_ROW_BLOCKS_RUN] == 4 * 4
    sl.record_routing([{"loss": 1.0}])      # a conv step: nothing


def test_spec_round_trip_and_checkpoint(tmp_path, net):
    from rocalphago_tpu.io.checkpoint import TrainCheckpointer
    from rocalphago_tpu.models import specs

    cfg = tmp_path / "cfg.json"
    cfg.write_text(json.dumps(TOY))
    made = specs.main(["seq", "--config", str(cfg), "--seed", "3",
                       "--out", str(tmp_path / "seq.json")])
    back = NeuralNetBase.load_model(str(tmp_path / "seq.json"))
    assert type(back) is SeqPolicy and back.spec_kwargs == TOY
    for a, b, c in zip(*(jax.tree.leaves(n.params)
                         for n in (net, made, back))):
        assert (a == b).all() and (a == c).all()
    state = sl.SLState(jax.device_get(net.params), (), np.array(7, np.int32),
                       np.asarray(jax.random.key_data(
                           jax.random.key(1))))
    ckpt = TrainCheckpointer(str(tmp_path / "ckpt"))
    ckpt.save(7, state, wait=True)
    restored, step = ckpt.restore(state)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        assert (np.asarray(a) == np.asarray(b)).all()
    ckpt.close()


def test_a_spec_that_asks_for_another_model_is_refused():
    with pytest.raises(ValueError, match="attention_bias"):
        SeqPolicy(board=SIZE, init_weights=False,
                  **dict(TOY, attention_bias=True))
    with pytest.raises(ValueError, match="ids"):
        SeqPolicy(board=SIZE, init_weights=False,
                  **dict(TOY, vocab_held=300))


def write_sgfs(directory, games: int, moves: int) -> None:
    letters = "abcdefghijklmnopqrs"
    rng = np.random.default_rng(0)
    for g in range(games):
        points = rng.permutation(SIZE * SIZE)[:moves]
        body = "".join(
            f";{'BW'[i % 2]}[{letters[p // SIZE]}{letters[p % SIZE]}]"
            for i, p in enumerate(points))
        (directory / f"g{g}.sgf").write_text(
            f"(;GM[1]SZ[{SIZE}]KM[7.5]{body})")


def test_sl_trainer_trains_the_spec_on_a_sequence_corpus(tmp_path):
    from rocalphago_tpu.data import convert
    from rocalphago_tpu.data.pipeline import ShardedDataset

    sgfs = tmp_path / "sgf"
    sgfs.mkdir()
    write_sgfs(sgfs, games=24, moves=40)
    convert.run_game_converter([
        "--directory", str(sgfs), "--outfile", str(tmp_path / "seq"),
        "--sequence", str(SEQ), "--shard-size", "8"])
    data = ShardedDataset(str(tmp_path / "seq"))
    assert data.planes == 0 and len(data) == 24 * 41 // SEQ
    ids, nxt = data.gather(np.arange(len(data)))
    assert ids.shape == nxt.shape == (len(data), SEQ)
    assert (ids[:, 1:] == nxt[:, :-1]).all()        # the next token
    assert (ids[1:, 0] == nxt[:-1, -1]).all()       # rows chain
    assert (ids.reshape(-1)[40::41] == SIZE * SIZE + 1).all()

    spec = tmp_path / "seq.json"
    SeqPolicy(board=SIZE, seed=0, **TOY).save_model(str(spec))
    out = tmp_path / "out"
    final = sl.run_training([
        str(spec), str(tmp_path / "seq"), str(out), "--minibatch", "8",
        "--epochs", "2", "--learning-rate", "0.05"])
    assert np.isfinite(final["train_loss"])
    assert np.isfinite(final["val_loss"])
    trained = NeuralNetBase.load_model(str(out / "model.json"))
    assert type(trained) is SeqPolicy
    events = [json.loads(line) for line in
              (out / "metrics.jsonl").read_text().splitlines()]
    counters = [e for e in events if e.get("event") == "registry"][-1][
        "snapshot"]["counters"]
    assert counters["moe_tokens_dropped_total"] == 0
    assert counters["moe_tokens_routed_total"] >= 2 * 8 * SEQ * 3 * 4
