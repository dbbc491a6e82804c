"""The mixing kernels (``seqpolicy.mixing_kernel_forward`` /
``_backward``) compiled for a described TPU v5e at the sequence cells'
real widths, with the blocks ``seqpolicy.mixing_blocks`` chooses:
Ling's heads of 128, Olmo's q and k heads of 96 (the whole 2,880 lanes
a block) and v heads of 192; and the scan's inverse
(``seqpolicy.inverse_kernel``) at the cells' chunks, 32 and 30 heads
of 128 chunks of 64. The chip's compiler refuses here what
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
