"""The mixing kernels (``seqpolicy.mixing_kernel_forward`` /
``_backward``) compiled for a described TPU v5e at the sequence cells'
real widths, with the blocks ``seqpolicy.mixing_blocks`` chooses:
Ling's heads of 128, Olmo's q and k heads of 96 (the whole 2,880 lanes
a block) and v heads of 192; the scan's inverse
(``seqpolicy.inverse_kernel``) at the cells' chunks, 32 and 30 heads
of 128 chunks of 64; and the rotary pass's kernels
(``seqpolicy.rope_kernel_forward`` / ``_backward``,
``latent_kernel_forward`` / ``_backward``) at every rotary layout
of the five sequence cells, with the blocks ``seqpolicy.rope_blocks``
chooses. The chip's compiler refuses here what
interpret mode passes — a slice it cannot lay out, more VMEM than a
call may take — at no chip time. Nothing runs; nothing here is a
device number.
"""

from __future__ import annotations

import math
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from rocalphago_tpu.models import seqpolicy  # noqa: E402

#: cell → (heads, head width, norm) of one tensor of a delta layer
CASES = {
    "ling_qk": (32, 128, math.sqrt(128)),
    "ling_v": (32, 128, None),
    "olmo_q": (30, 96, math.sqrt(96)),
    "olmo_v": (30, 192, None),
}


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e, with the persistent compile
    cache off (a compile for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("which", ["fwd", "bwd"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_mixing_kernel_compiles_for_a_v5e(one_chip, case, which):
    heads, w, norm = CASES[case]
    width = heads * w
    y = jax.ShapeDtypeStruct((1, 8192, width), jnp.bfloat16,
                             sharding=one_chip)
    taps = jax.ShapeDtypeStruct((4, width), jnp.float32,
                                sharding=one_chip)
    d = jax.ShapeDtypeStruct((1, 8192, heads, w), jnp.bfloat16,
                             sharding=one_chip)
    assert seqpolicy.mixing_blocks(8192, width, w, 4) is not None
    if which == "fwd":
        lowered = jax.jit(lambda y, taps: seqpolicy.mixing_kernel_forward(
            y, taps, heads, norm)).lower(y, taps)
    else:
        lowered = jax.jit(
            lambda y, taps, d: seqpolicy.mixing_kernel_backward(
                y, taps, d, heads, norm)).lower(y, taps, d)
    text = lowered.compile().as_text()
    assert f"kda_mixed_{which}" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("heads", [32, 30], ids=["ling", "olmo"])
def test_the_inverse_kernel_compiles_for_a_v5e(one_chip, heads):
    a = jax.ShapeDtypeStruct((128, 1, heads, 64, 64), jnp.float32,
                             sharding=one_chip)
    text = jax.jit(seqpolicy.inverse_kernel).lower(a).compile().as_text()
    assert "unit_lower_inverse" in text and "tpu_custom_call" in text


#: cell layout → (rows, heads, head width, rotated lanes from, rope,
#: scale) of a q or k projection's product
ROPE_CASES = {
    "laguna_window_q": (2, 72, 128, 0, seqpolicy.Rope("default", 1e4,
                                                      128), 0.0884),
    "laguna_full_k": (2, 8, 128, 0, seqpolicy.Rope(
        "yarn", 5e5, 64, factor=128.0, original=8192,
        attention_factor=1.4852), 1.0),
    "latent_q": (1, 32, 192, 128, seqpolicy.Rope(
        "yarn", 1e4, 64, factor=64.0, original=4096), 0.0721),
    "lfm2_q": (2, 32, 64, 0, seqpolicy.Rope("default", 1e6, 64), 0.125),
    "olmo_q": (1, 30, 128, 0, seqpolicy.Rope("default", 5e5, 128),
               0.0884),
}


@pytest.mark.parametrize("which", ["fwd", "bwd"])
@pytest.mark.parametrize("case", list(ROPE_CASES))
def test_the_rotary_kernel_compiles_for_a_v5e(one_chip, case, which):
    b, heads, head, at, rope, scale = ROPE_CASES[case]
    assert seqpolicy.rope_blocks(8192, heads * head, head, at,
                                 rope.dims) is not None
    if which == "fwd":
        x = jax.ShapeDtypeStruct((b, 8192, heads * head), jnp.bfloat16,
                                 sharding=one_chip)
        lowered = jax.jit(lambda x: seqpolicy.rope_kernel_forward(
            x, rope, heads, at, scale)).lower(x)
    else:
        d = jax.ShapeDtypeStruct((b, heads, 8192, head), jnp.bfloat16,
                                 sharding=one_chip)
        lowered = jax.jit(lambda d: seqpolicy.rope_kernel_backward(
            d, rope, heads, at, scale)).lower(d)
    text = lowered.compile().as_text()
    assert f"rope_pass_{which}" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("which", ["fwd", "bwd"])
def test_the_latent_kernel_compiles_for_a_v5e(one_chip, which):
    rope = ROPE_CASES["latent_q"][4]

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.bfloat16, sharding=one_chip)

    if which == "fwd":
        lowered = jax.jit(lambda kv, pe: seqpolicy.latent_kernel_forward(
            kv, pe, rope, 128)).lower(shape(1, 8192, 32, 256),
                                      shape(1, 8192, 64))
    else:
        lowered = jax.jit(lambda dk, dv: seqpolicy.latent_kernel_backward(
            dk, dv, rope)).lower(shape(1, 32, 8192, 192),
                                 shape(1, 32, 8192, 128))
    text = lowered.compile().as_text()
    assert f"rope_latent_{which}" in text and "tpu_custom_call" in text
