"""Plain references: the published forward pass in float32 ``jax.numpy``.

Independent of ``rocalphago_tpu.models``: no flax module, no bfloat16,
no fusion. They read the SAME parameter tree the system serves (the
names of its leaves are the only thing taken from the program) and
follow Silver et al. 2016, Methods "Neural network architecture":
a 5×5 convolution from the input planes, then 3×3 convolutions, ReLU
after each, SAME zero padding, and a 1×1 head. Departures, both
listed under ``assumed`` in the configuration files: the policy head
has a channel bias and no per-position bias; the value head is the
repo's fully convolutional one (1×1 conv → ReLU → mean and max over
the board → dense → ReLU → dense → tanh).

Every product runs at ``highest`` matmul precision — on a TPU a
float32 convolution is otherwise computed in bfloat16 passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

#: Why 0.10 for logits. The measure is the root
#: mean square of (system - reference) over the root mean square of
#: the reference, on a seeded sample. The system computes trunk and
#: heads in bfloat16 (8 mantissa bits, relative rounding 2**-9 ≈
#: 0.2 % per operation, float32 accumulation); through 13 layers
#: that grows to 0.4-2.5 % of the outputs' size, depending on how
#: near zero the seed's head puts the mean output (PERF.md §6 has
#: the chip's readings). A trunk in an 8-bit float (3 mantissa bits,
#: 6 % per operation) or a dropped layer lands at tens of percent;
#: float32 lands under 0.01 %. So 10 % separates "as stated" from
#: "something cheaper" with a factor of four on the near side.
OUTPUT_TOLERANCE = 0.10
#: Why 0.25 for the value. It is ONE number per position, the end of
#: a 256-term dot product whose terms largely cancel (seed weights
#: put it near 0.001), so bfloat16 rounding of the terms is a larger
#: share of the result than of a logit, and how much larger swings
#: with the seed: 0.004-0.057 over 11 seeds on the v5e (PERF.md §6).
#: An 8-bit trunk or a dropped layer still lands far above 0.25.
VALUE_TOLERANCE = 0.25
#: first-step training loss, relative: the loss is a mean of 1024
#: cross-entropies near ln(361), so bfloat16 logit noise averages
#: out to well under 0.1 %; 0.5 % still fails a wrong label layout,
#: a wrong augmentation or a missing layer
LOSS_TOLERANCE = 0.005


def _conv(x, leaf):
    y = lax.conv_general_dilated(
        x, leaf["kernel"].astype(jnp.float32), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    return y + leaf["bias"].astype(jnp.float32)


def _dense(x, leaf):
    return jnp.dot(x, leaf["kernel"].astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) \
        + leaf["bias"].astype(jnp.float32)


def _trunk(tree, x):
    x = x.astype(jnp.float32)
    convs = sorted(tree, key=lambda k: int(k.removeprefix("conv")))
    for name in convs:
        x = jax.nn.relu(_conv(x, tree[name]))
    return x


def policy_logits(params, planes):
    """float32 logits ``[B, N]`` over board points."""
    p = params["params"]
    x = _conv(_trunk(p["trunk"], planes), p["head"]["conv"])
    return x.reshape((x.shape[0], -1))


def value(params, planes):
    """float32 value ``[B]`` in [-1, 1]."""
    p = params["params"]
    x = jax.nn.relu(_conv(_trunk(p["trunk"], planes), p["head_conv"]))
    x = jnp.concatenate([x.mean(axis=(1, 2)), x.max(axis=(1, 2))],
                        axis=-1)
    x = jax.nn.relu(_dense(x, p["dense1"]))
    return jnp.tanh(_dense(x, p["dense2"])[:, 0])


def policy_loss(params, planes, actions):
    """Mean categorical cross-entropy of the expert moves (the SL
    stage's loss). Actions are board points; none is a pass here."""
    logp = jax.nn.log_softmax(policy_logits(params, planes), axis=-1)
    return -jnp.take_along_axis(
        logp, actions[:, None].astype(jnp.int32), axis=-1).mean()


def relative_rms_error(system, reference) -> float:
    """RMS of the difference over RMS of the reference."""
    system = jnp.asarray(system, jnp.float32)
    reference = jnp.asarray(reference, jnp.float32)
    num = jnp.sqrt(jnp.mean((system - reference) ** 2))
    return float(num / jnp.maximum(
        jnp.sqrt(jnp.mean(reference ** 2)), 1e-30))


def check_nets(nets: dict, seed: int, sample: int = 16,
               density: float = 0.25) -> tuple[list, dict]:
    """Compare each system network in ``nets`` (``{"policy": net,
    "value": net}``, any subset) with its reference on ``sample``
    seeded positions at the configuration's own width. Returns
    ``(problems, readings)``."""
    from chipbench.nets import random_planes

    refs = {"policy": policy_logits, "value": value}
    problems, readings = [], {}
    for which, net in nets.items():
        planes = random_planes(seed + 17, sample, net.board,
                               net.preprocess.output_dim, density)
        system = jax.jit(net.module.apply)(net.params, planes)
        ref = jax.jit(refs[which])(net.params, planes)
        err = relative_rms_error(system, ref)
        tol = VALUE_TOLERANCE if which == "value" else OUTPUT_TOLERANCE
        readings[f"{which}_vs_reference"] = err
        if not err <= tol:                       # catches NaN too
            problems.append(
                f"{which} net differs from the float32 reference by "
                f"{err:.4f} of its RMS (tolerance {tol})")
    return problems, readings
