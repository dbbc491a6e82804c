"""The delta rule's inverse ``(I + A)^−1`` (``seqpolicy.unit_lower_inverse``)
by its two lowerings: the series in XLA, which is the definition and
what runs on the CPU, and the Pallas kernel a TPU takes
(``seqpolicy.inverse_kernel``), here in interpret mode. Inputs are
built the way the scan builds them: Kimi delta attention's (every
gate at the −5 floor, β in (0, 1)) and Gated DeltaNet's (one decay a
head, unbounded below, β up to 2 and correlated keys, so entries
pass 1). Nothing here is a device number.
"""

from __future__ import annotations

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from rocalphago_tpu.models import seqpolicy  # noqa: E402

C = 64


def relative_error(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def chunks(kind: str, lead: tuple, seed: int = 0, d: int = 32):
    """Strictly lower ``β_r (k_r · k_c) e^{G_r − G_c}`` of ``[*lead, C,
    C]`` chunks, masked as ``kda_chunked`` masks it."""
    keys, alike, rate, gate, b = jax.random.split(jax.random.key(seed), 5)
    k = jax.random.normal(keys, (*lead, C, d))
    if kind == "olmo":      # keys that point alike: A's entries pass 1
        k = k + 2.0 * jax.random.normal(alike, (*lead, 1, d))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    beta = jax.nn.sigmoid(jax.random.normal(b, (*lead, C, 1)))
    if kind == "ling":
        gc = jnp.cumsum(jnp.full((*lead, C, d), -5.0), axis=-2)
        _, a = seqpolicy._pairwise_decayed(k, k, gc, seqpolicy.KDA_SUB)
    else:
        g = -jnp.exp(jax.random.uniform(rate, (*lead, C, 1), maxval=2.0)) \
            * jax.nn.softplus(3.0 * jax.random.normal(gate, (*lead, C, 1)))
        _, a = seqpolicy._pairwise_scalar(k, k, jnp.cumsum(g, axis=-2))
        beta = 2.0 * beta ** 0.25       # up to 2, most of it above 1
    row, col = jnp.arange(C)[:, None], jnp.arange(C)[None, :]
    return jnp.where(row > col, beta * a, 0.0)


@pytest.mark.parametrize("kind", ["ling", "olmo"])
@pytest.mark.parametrize("lead, block", [
    ((4, 1, 8), None),          # one block
    ((3, 1, 6), 8),             # 18 matrices: the last block partial
    ((2, 2, 2), 2),             # a block of one pair
], ids=["one_block", "partial_block", "pair_blocks"])
def test_the_kernel_is_the_series(kind, lead, block):
    a = chunks(kind, lead)
    if kind == "olmo":
        assert float(jnp.abs(a).max()) > 1.0
    want = seqpolicy._inverse_by_series(a)
    got = seqpolicy.inverse_kernel(a, block=block, interpret=True)
    assert got.shape == a.shape and got.dtype == jnp.float32
    assert relative_error(got, want) <= 1e-5
    # and the series is the inverse
    exact = np.linalg.inv(np.eye(C) + np.asarray(a, np.float64))
    assert relative_error(want, exact) <= 1e-5


@pytest.mark.parametrize("kind", ["ling", "olmo"])
def test_the_gradient_through_the_kernel_is_the_definitions(
        monkeypatch, kind):
    """With the kernel taking the forward, the ``custom_vjp``'s
    gradient is JAX's gradient of the series."""
    a = chunks(kind, (2, 1, 4), seed=1)
    weigh = jax.random.normal(jax.random.key(2), a.shape)

    def loss(inverse):
        return lambda a: (inverse(a) * weigh).sum()

    want = jax.grad(loss(seqpolicy._inverse_by_series))(a)
    monkeypatch.setattr(seqpolicy, "use_inverse_kernel", lambda a: True)
    monkeypatch.setattr(seqpolicy, "inverse_kernel", functools.partial(
        seqpolicy.inverse_kernel, interpret=True))
    got = jax.grad(loss(seqpolicy.unit_lower_inverse))(a)
    lower = jnp.tril(jnp.ones((C, C)), -1).astype(bool)
    assert relative_error(jnp.where(lower, got, 0.0),
                          jnp.where(lower, want, 0.0)) <= 1e-5


def test_the_cpu_takes_the_definition():
    a = jnp.zeros((128, 1, 32, C, C), jnp.float32)
    assert jax.default_backend() == "cpu"
    assert not seqpolicy.use_inverse_kernel(a)


@pytest.mark.parametrize("shape, dtype, takes", [
    ((128, 1, 32, 64, 64), jnp.float32, True),      # Ling's
    ((128, 1, 30, 64, 64), jnp.float32, True),      # Olmo's
    ((128, 1, 32, 64, 64), jnp.bfloat16, False),
    ((4, 1, 4, 8, 8), jnp.float32, False),          # the tests' chunks
    ((3, 1, 1, 64, 64), jnp.float32, False),        # an odd count
], ids=["ling", "olmo", "bf16", "chunk_8", "odd_count"])
def test_the_kernel_is_chosen_from_backend_dtype_and_shape(
        monkeypatch, shape, dtype, takes):
    monkeypatch.setattr(seqpolicy, "kernel_platform", lambda: "tpu")
    a = jax.ShapeDtypeStruct(shape, dtype)
    assert seqpolicy.use_inverse_kernel(a) == takes


def pallas_names(jaxpr) -> list:
    """The ``name`` of every ``pallas_call`` in ``jaxpr``, inner
    jaxprs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(str(eqn.params["name"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += pallas_names(sub)
    return found


@pytest.mark.parametrize("cell, module", [
    ("ling-3.0-flash-ep64", "train_seq_ling"),
    ("olmo-hybrid-7b-pp8", "train_seq_olmo"),
])
def test_every_delta_layer_of_the_cells_takes_the_kernel(
        monkeypatch, cell, module):
    """The cells' networks traced for a TPU at a row of 8,192 (shapes
    only, nothing runs): one inverse kernel in the forward of each
    delta layer, so nothing needs to count engagement on the chip."""
    import importlib

    from rocalphago_tpu.models.seqpolicy import SeqPolicy

    with open(os.path.join(ROOT, "chipbench", "configs",
                           f"{cell}.json")) as f:
        config = json.load(f)
    kw = importlib.import_module(
        "chipbench.drivers." + module).spec_kwargs(config)
    monkeypatch.setattr(seqpolicy, "kernel_platform", lambda: "tpu")
    net = SeqPolicy(board=config["board"], init_weights=False, **kw)
    delta = sum(1 for spec in net.module.layers if spec.kda)
    assert delta == {"ling-3.0-flash-ep64": 6,
                     "olmo-hybrid-7b-pp8": 3}[cell]
    ids = jax.ShapeDtypeStruct((1, 8192), jnp.int32)
    params = jax.eval_shape(net.module.init, jax.random.key(0), ids)
    jaxpr = jax.make_jaxpr(net.module.apply)(params, ids)
    names = pallas_names(jaxpr.jaxpr)
    assert names.count("unit_lower_inverse") == delta, names
