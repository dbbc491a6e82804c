"""The rotary pass from a q or k projection's product to the attention
kernel's head-major operand (``seqpolicy.rope_head_major``,
``seqpolicy.latent_head_major``) against its definition, ``apply_rope``
and the transposition (and, for latent attention, the non-rotary
scale, the key's broadcast, the concatenations and the value), at toy
sizes of every rotary layout the five sequence configurations hold:
Laguna's whole-head ``default`` at 128 and half-head YaRN (64 of 128),
latent attention's 64 of 192 after 128 non-rotary lanes and its shared
key, LFM2's 64-wide heads after the per-head QK-norm and Olmo's 128
after the whole-width one. The Pallas kernels run with
``interpret=True``; where the pass engages is read off traces for a
TPU (shapes only, nothing runs). Nothing here is a device number.
"""

from __future__ import annotations

import functools
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

from rocalphago_tpu.models import seqpolicy  # noqa: E402
from rocalphago_tpu.obs import scopes  # noqa: E402

SEQ, ROWS = 64, 2
LAGUNA_YARN = seqpolicy.Rope("yarn", 5e5, 64, factor=128.0, original=8192,
                             attention_factor=1.4852030263919618)
LATENT_YARN = seqpolicy.Rope("yarn", 1e4, 64, factor=64.0, original=4096,
                             attention_factor=0.8)
#: layout → (heads, head width, rotated lanes from, rope, scale, norm
#: of the product before the pass: "head", "whole" or "")
LAYOUTS = {
    "laguna_window": (4, 128, 0, seqpolicy.Rope("default", 1e4, 128),
                      1 / math.sqrt(128), ""),
    "laguna_full": (4, 128, 0, LAGUNA_YARN, 1 / math.sqrt(128), ""),
    "latent_queries": (4, 192, 128, LATENT_YARN, 0.0721, ""),
    "lfm2": (8, 64, 0, seqpolicy.Rope("default", 1e6, 64), 0.125,
             "head"),
    "olmo": (3, 128, 0, seqpolicy.Rope("default", 5e5, 128),
             1 / math.sqrt(128), "whole"),
}
#: latent attention's key and value: heads, non-rotary and value
#: widths, rope
LATENT_KEY = (4, 128, 128, LATENT_YARN)


def product(layout: str, powers: bool):
    """A bf16 product ``[B, S, H·d]`` as the layer hands it over. With
    ``powers`` every element is ± a power of two, so every float32
    product the rotary takes is exact and the order of its roundings
    cannot show (comment on the forward test)."""
    heads, head, _, _, _, norm = LAYOUTS[layout]
    x = jax.random.normal(jax.random.key(1), (ROWS, SEQ, heads * head))
    if powers:
        return (jnp.sign(x) * jnp.exp2(jnp.round(jnp.log2(
            jnp.abs(x) + 1e-3)))).astype(jnp.bfloat16)
    if norm == "head":
        x = x.reshape(ROWS, SEQ, heads, head)
    if norm:
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    return x.reshape(ROWS, SEQ, heads * head).astype(jnp.bfloat16)


def by_definition(x, layout: str):
    """``apply_rope`` on the head's rotated lanes (and the scale on the
    rest), then the transposition to head-major."""
    heads, head, at, rope, scale, _ = LAYOUTS[layout]
    x = x.reshape(ROWS, SEQ, heads, head)
    if at:
        x = jnp.concatenate(
            [(x[..., :at].astype(jnp.float32) * scale).astype(x.dtype),
             seqpolicy.apply_rope(x[..., at:], rope, scale)], axis=-1)
    else:
        x = seqpolicy.apply_rope(x, rope, scale)
    return x.transpose(0, 2, 1, 3)


def kernel_args(layout: str):
    heads, _, at, rope, scale, _ = LAYOUTS[layout]
    return rope, heads, at, scale


def latent_key_inputs(powers: bool = False):
    heads, nope, value, rope = LATENT_KEY
    kv = jax.random.normal(jax.random.key(3), (ROWS, SEQ, heads,
                                               nope + value))
    pe = jax.random.normal(jax.random.key(4), (ROWS, SEQ, rope.dims))
    if powers:
        kv, pe = (jnp.sign(a) * jnp.exp2(jnp.round(jnp.log2(
            jnp.abs(a) + 1e-3))) for a in (kv, pe))
    return kv.astype(jnp.bfloat16), pe.astype(jnp.bfloat16)


def latent_key_by_definition(kv, pe):
    """The layer's own lines: ``k_pe`` rotated once, broadcast to the
    heads beside each head's non-rotary key; the value the rest of
    ``kv``; both head-major."""
    heads, nope, _, rope = LATENT_KEY
    k_pe = seqpolicy.apply_rope(pe[:, :, None], rope)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_pe, (ROWS, SEQ, heads, rope.dims))], axis=-1)
    return k.transpose(0, 2, 1, 3), kv[..., nope:].transpose(0, 2, 1, 3)


def latent_cotangents():
    heads, nope, value, rope = LATENT_KEY
    keys = jax.random.split(jax.random.key(5))
    return (jax.random.normal(keys[0], (ROWS, heads, SEQ, nope + rope.dims)
                              ).astype(jnp.bfloat16),
            jax.random.normal(keys[1], (ROWS, heads, SEQ, value)
                              ).astype(jnp.bfloat16))


def bits(a) -> np.ndarray:
    return np.asarray(jnp.asarray(a, jnp.float32))


def ulps_apart(a, b) -> np.ndarray:
    """How many bf16 steps apart two bf16 arrays are, element by
    element (on their float32 images: bf16 is float32's top half)."""
    ia = np.asarray(jnp.asarray(a, jnp.float32)).view(np.int32) >> 16
    ib = np.asarray(jnp.asarray(b, jnp.float32)).view(np.int32) >> 16
    return np.abs(ia.astype(np.int64) - ib)


# ---------------------------------------------------------- the forward

@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_the_forward_is_apply_ropes_bits(layout):
    """The kernel does ``apply_rope``'s float32 products and sums
    (a negation is exact, a zero term adds nothing), so on a v5e, whose
    float32 arithmetic rounds each product and each sum, its output is
    the definition's bit for bit (PERF.md §6: the chip's reading at the
    cells' shapes). On the CPU, XLA contracts a product and the
    sum it feeds into one fused multiply-add where it chooses, and
    chooses differently in the two forms: on products of ± powers of
    two every product is exact and that choice moves no bit, which is
    what is held here; on drawn numbers an element may land one bf16
    step away, in a few elements of a thousand (the next test)."""
    x = product(layout, powers=True)
    got = seqpolicy.rope_kernel_forward(x, *kernel_args(layout),
                                        interpret=True)
    heads, head = LAYOUTS[layout][:2]
    assert got.shape == (ROWS, heads, SEQ, head)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(bits(got), bits(by_definition(x,
                                                                layout)))


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_drawn_products_differ_by_the_cpus_contractions_alone(layout):
    """On drawn numbers (normed as the layer norms them) the CPU's
    fused multiply-adds may move an element by one bf16 step: at most
    one step, in at most a few elements a thousand."""
    x = product(layout, powers=False)
    got = seqpolicy.rope_kernel_forward(x, *kernel_args(layout),
                                        interpret=True)
    apart = ulps_apart(got, by_definition(x, layout))
    assert apart.max() <= 1
    assert apart.mean() < 5e-3


def test_the_latent_key_and_value_are_the_layers_lines():
    kv, pe = latent_key_inputs(powers=True)
    heads, nope, value, rope = LATENT_KEY
    k, v = seqpolicy.latent_kernel_forward(kv, pe, rope, nope,
                                           interpret=True)
    assert k.shape == (ROWS, heads, SEQ, nope + rope.dims)
    assert v.shape == (ROWS, heads, SEQ, value)
    want_k, want_v = latent_key_by_definition(kv, pe)
    np.testing.assert_array_equal(bits(k), bits(want_k))
    np.testing.assert_array_equal(bits(v), bits(want_v))
    kv, pe = latent_key_inputs()
    k, _ = seqpolicy.latent_kernel_forward(kv, pe, rope, nope,
                                           interpret=True)
    apart = ulps_apart(k, latent_key_by_definition(kv, pe)[0])
    assert apart.max() <= 1 and apart.mean() < 5e-3


# --------------------------------------------------------- the backward

def close_to_bf16(got, want, steps: float = 4.0):
    """Within a few bf16 steps of the largest cotangent."""
    got, want = bits(got), bits(want)
    assert np.abs(got - want).max() <= steps * 2.0 ** -8 * np.abs(
        want).max()


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_the_backward_is_the_definitions_vjp(layout):
    """The map's transpose, one pass: ``jax.vjp`` of the definition to
    bf16 rounding, in the projection's ``[B, S, H·d]`` order."""
    x = product(layout, powers=False)
    heads, head = LAYOUTS[layout][:2]
    d = jax.random.normal(jax.random.key(2), (ROWS, heads, SEQ, head)
                          ).astype(jnp.bfloat16)
    want, = jax.vjp(lambda x: by_definition(x, layout), x)[1](d)
    got = seqpolicy.rope_kernel_backward(d, *kernel_args(layout),
                                         interpret=True)
    assert got.shape == x.shape and got.dtype == x.dtype
    close_to_bf16(got, want)


def test_the_latent_backward_sums_the_heads_inside_the_pass():
    """``kv``'s cotangent is each head's key and value lanes, written
    whole; the rotary key's is the heads' sum rotated back — float32
    inside the pass where JAX's transpose of the broadcast sums bf16 —
    to bf16 rounding of the sum."""
    kv, pe = latent_key_inputs()
    heads, nope, value, rope = LATENT_KEY
    d = latent_cotangents()
    want_kv, want_pe = jax.vjp(latent_key_by_definition, kv, pe)[1](d)
    d_kv, d_pe = seqpolicy.latent_kernel_backward(*d, rope,
                                                  interpret=True)
    np.testing.assert_array_equal(
        bits(d_kv.reshape(kv.shape)), bits(want_kv))
    assert d_pe.dtype == jnp.float32
    close_to_bf16(d_pe, want_pe, steps=heads)


# ------------------------------------------------ the custom_vjp's paths

@pytest.fixture
def on_a_tpu(monkeypatch):
    """Traced as for a TPU, the Pallas kernels interpreted."""
    monkeypatch.setattr(seqpolicy, "kernel_platform", lambda: "tpu")
    for name in ("rope_kernel_forward", "rope_kernel_backward",
                 "latent_kernel_forward", "latent_kernel_backward"):
        monkeypatch.setattr(seqpolicy, name, functools.partial(
            getattr(seqpolicy, name), interpret=True))


def through_the_pass(layout: str):
    heads, head = LAYOUTS[layout][:2]
    x = product(layout, powers=True)
    d = jax.random.normal(jax.random.key(2), (ROWS, heads, SEQ, head)
                          ).astype(jnp.bfloat16)
    y, back = jax.vjp(lambda x: seqpolicy.rope_head_major(
        x, *kernel_args(layout)), x)
    return x, d, y, back(d)[0]


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_the_pass_is_its_definition_on_the_cpu(layout):
    """No kernel on the CPU: the definition, and its ``jax.vjp``, to
    the bit."""
    x, d, y, dx = through_the_pass(layout)
    np.testing.assert_array_equal(bits(y), bits(by_definition(x, layout)))
    want, = jax.vjp(lambda x: by_definition(x, layout), x)[1](d)
    np.testing.assert_array_equal(bits(dx), bits(want))


@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_the_pass_takes_the_kernels_on_a_tpu(on_a_tpu, layout):
    x, d, y, dx = through_the_pass(layout)
    rope, heads, at, scale = kernel_args(layout)
    assert seqpolicy.use_rope_kernel(x, heads, rope, at)
    np.testing.assert_array_equal(bits(y), bits(by_definition(x, layout)))
    close_to_bf16(dx, jax.vjp(lambda x: by_definition(x, layout), x)[1](
        d)[0])
    with jax.enable_checks(False):
        text = str(jax.make_jaxpr(lambda x: jax.vjp(
            lambda x: seqpolicy.rope_head_major(x, rope, heads, at, scale),
            x)[1](d))(x))
    assert "rope_pass_bwd" in text


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_the_latent_pass_takes_the_kernels_on_a_tpu_alone(
        on_a_tpu, monkeypatch, platform):
    """The kernels on a TPU; the definition, and its ``jax.vjp`` to the
    bit, on the CPU."""
    monkeypatch.setattr(seqpolicy, "kernel_platform", lambda: platform)
    kv, pe = latent_key_inputs(powers=True)
    heads, nope, value, rope = LATENT_KEY
    assert seqpolicy.use_latent_kernel(kv, rope, nope) == (
        platform == "tpu")
    d = latent_cotangents()
    y, back = jax.vjp(lambda kv, pe: seqpolicy.latent_head_major(
        kv, pe, rope, nope), kv, pe)
    for got, want in zip(y, latent_key_by_definition(kv, pe)):
        np.testing.assert_array_equal(bits(got), bits(want))
    got_kv, got_pe = back(d)
    want_kv, want_pe = jax.vjp(latent_key_by_definition, kv, pe)[1](d)
    assert got_kv.shape == kv.shape and got_pe.dtype == pe.dtype
    np.testing.assert_array_equal(bits(got_kv), bits(want_kv))
    if platform == "cpu":
        np.testing.assert_array_equal(bits(got_pe), bits(want_pe))
    close_to_bf16(got_pe, want_pe, steps=heads)


def test_the_pass_keeps_nothing(on_a_tpu):
    """The tables are functions of the shapes: a ``jax.vjp`` of either
    pass saves no array at all."""
    from jax._src.ad_checkpoint import saved_residuals

    x = product("laguna_full", powers=True)
    kv, pe = latent_key_inputs()
    heads, nope, _, rope = LATENT_KEY
    assert not saved_residuals(lambda x: seqpolicy.rope_head_major(
        x, *kernel_args("laguna_full")), x)
    assert not saved_residuals(lambda kv, pe: seqpolicy.latent_head_major(
        kv, pe, rope, nope), kv, pe)


# ----------------------------------------------------- the blocks chosen

def test_the_blocks_hold_whole_heads_in_whole_tiles():
    """At the cells' widths: 512 lanes of heads of 128 or 64, 384 of
    Olmo's 30 heads of 128 (3,840 lanes) and of latent attention's
    heads of 192; rows whole bf16 tiles under ``ROPE_BLOCK``
    elements. None where a head's rotated lanes cross a tile (a
    rotary on 96 lanes from 64), where the heads are not whole tiles
    together, or where no row count divides the row."""
    blocks = seqpolicy.rope_blocks
    assert blocks(8192, 72 * 128, 128, 0, 128) == (1024, 512)
    assert blocks(8192, 48 * 128, 128, 0, 64) == (1024, 512)
    assert blocks(8192, 32 * 64, 64, 0, 64) == (1024, 512)
    assert blocks(8192, 30 * 128, 128, 0, 128) == (1024, 384)
    assert blocks(8192, 32 * 192, 192, 128, 64) == (1024, 384)
    assert blocks(8192, 4 * 128, 128, 64, 96) is None
    assert blocks(8192, 3 * 192, 192, 128, 64) is None
    assert blocks(8, 4 * 128, 128, 0, 128) is None
    assert seqpolicy.latent_blocks(8192, 128, 128, 64) == 2048
    assert seqpolicy.latent_blocks(8192, 96, 128, 64) is None


def test_the_tables_are_apply_ropes_cos_and_sin():
    """``C``, ``S₊``, ``S₋`` on a head's lanes from the same float32
    angles: ``cos`` on both halves of the rotated lanes and 1 on the
    rest, ``sin`` on the second half, ``−sin`` on the first."""
    rope = LATENT_YARN
    c, plus, minus = map(np.asarray, seqpolicy.rope_tables(rope, SEQ,
                                                           192, 128))
    angle = seqpolicy.rope_angles(rope, SEQ)
    cos = np.asarray(jnp.cos(angle) * rope.attention_factor)
    sin = np.asarray(jnp.sin(angle) * rope.attention_factor)
    np.testing.assert_array_equal(c[:, :128], 1.0)
    np.testing.assert_array_equal(c[:, 128:160], cos)
    np.testing.assert_array_equal(c[:, 160:], cos)
    np.testing.assert_array_equal(plus[:, 160:], sin)
    np.testing.assert_array_equal(minus[:, 128:160], -sin)
    assert not plus[:, :160].any() and not minus[:, :128].any() \
        and not minus[:, 160:].any()


# ------------------------------------------ where it engages: the cells

CELLS = {
    "laguna-s-2.1-ep16": "train_seq",
    "xing4.0-29b-a4b-ep8": "train_seq_xing",
    "ling-3.0-flash-ep64": "train_seq_ling",
    "olmo-hybrid-7b-pp8": "train_seq_olmo",
    "lfm2-8b-a1b-ep4": "train_seq_lfm2",
}


def attention_layers(cell: str):
    """The configuration's softmax attention modules at its real
    widths: one of each distinct spec its held layers (and MTP block)
    have, with the batch of a row of 8,192."""
    import importlib

    from rocalphago_tpu.models.seqpolicy import SeqPolicy

    with open(os.path.join(ROOT, "chipbench", "configs",
                           f"{cell}.json")) as f:
        config = json.load(f)
    spec_kwargs = importlib.import_module(
        f"chipbench.drivers.{CELLS[cell]}").spec_kwargs
    net = SeqPolicy(board=19, init_weights=False,
                    **spec_kwargs(config)).module
    specs = list(net.layers) + [net.mtp_layer] * bool(net.mtp_layer)
    out = []
    for spec in dict.fromkeys(s for s in specs
                              if not s.kda and not s.short_conv):
        if spec.latent:
            module = seqpolicy.LatentAttention(spec, net.eps, net.dtype)
        else:
            module = seqpolicy.GatedAttention(spec, net.kv_heads,
                                              net.head_dim, net.dtype,
                                              net.eps)
        out.append((module, net.hidden))
    return out


def kernel_calls(module, hidden: int) -> list:
    """``(name stack, kernel name)`` of every Pallas call of the
    module's gradient traced at a row of 8,192 — the attention kernel
    stood in for by a sum of its operands (its masks are not what is
    read)."""
    x = jax.ShapeDtypeStruct((1, 8192, hidden), jnp.bfloat16)

    def stand_in(q, k, v, window):
        b, _, s_len, dv = v.shape
        return jnp.zeros((b, s_len, q.shape[1], dv), v.dtype) + (
            q.sum() + k.sum() + v.sum()).astype(v.dtype)

    params = jax.eval_shape(module.init, jax.random.key(0), x)
    with pytest.MonkeyPatch.context() as patch, jax.enable_checks(False):
        patch.setattr(seqpolicy, "kernel_attention", stand_in)
        jaxpr = jax.make_jaxpr(jax.grad(lambda p, x: module.apply(
            p, x).astype(jnp.float32).sum()))(params, x)
    found = []

    def walk(jaxpr, outer=""):
        for eqn in jaxpr.eqns:
            name = f"{outer}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                found.append((name, eqn.params["name"]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, name)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("cell", list(CELLS))
def test_every_attention_layer_of_the_cells_takes_the_pass(cell,
                                                           monkeypatch):
    """Traced for a TPU, every softmax attention layer of the five
    sequence configurations (Laguna's window and full layers, Xing's
    and Ling's latent layers, Olmo's and LFM2's full layers) makes its
    q and k (and latent attention its v) by the rotary kernels, forward
    and backward, under ``seq.attn.rope``; traced for the CPU, none
    does."""
    layers = attention_layers(cell)
    assert layers
    for module, hidden in layers:
        assert not kernel_calls(module, hidden)
    monkeypatch.setattr(seqpolicy, "kernel_platform", lambda: "tpu")
    for module, hidden in layers:
        found = kernel_calls(module, hidden)
        key = ("rope_latent" if module.spec.latent else "rope_pass")
        assert sorted(n for _, n in found) == sorted(
            ["rope_pass_fwd", "rope_pass_bwd", f"{key}_fwd",
             f"{key}_bwd"])
        for path, _ in found:
            assert scopes.SEQ_ATTN_ROPE in path
            assert scopes.SEQ_ATTN_KERNEL not in path
