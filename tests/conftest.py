"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU mesh (SURVEY.md §4 "multi-node
testing") so data-parallel training, collectives, and shardings are
exercised in CI without TPU hardware. The tier-1 command passes
``JAX_PLATFORMS=cpu``; the config update below makes a bare ``pytest``
on a machine with a chip stay off it too. Both settings must land
before the first backend touch (backends initialize lazily, at the
first ``jax.devices()`` call), so they are made at conftest-import
time.
"""

import functools
import os

import pytest

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_CHECKS", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# persistent compile cache, by the one rule every entry point follows
# (runtime/compilecache.py): JAX_COMPILATION_CACHE_DIR if the caller
# set it, else <checkout>/.jax_cache — so the suite, the CLIs its
# subprocess tests launch, and a developer's own runs share entries.
from rocalphago_tpu.runtime.compilecache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()


@pytest.fixture
def random_game_states():
    """``(cfg, batch, moves, key) -> GoState``: batched positions after
    ``moves`` uniform random legal plies, played under one jit."""
    import jax.numpy as jnp

    from rocalphago_tpu.engine import jaxgo

    def play(cfg, batch, moves, key):
        vstep = jax.vmap(functools.partial(jaxgo.step, cfg))
        vlegal = jax.vmap(functools.partial(jaxgo.legal_mask, cfg))

        def ply(carry, _):
            states, key = carry
            key, sub = jax.random.split(key)
            legal = vlegal(states)[:, :-1]
            action = jnp.where(
                legal.any(-1),
                jax.random.categorical(
                    sub, jnp.where(legal, 0.0, -1e30), axis=-1),
                cfg.num_points).astype(jnp.int32)
            return (vstep(states, action), key), None

        return jax.jit(lambda key: jax.lax.scan(
            ply, (jaxgo.new_states(cfg, batch), key),
            length=moves)[0][0])(key)

    return play


@pytest.fixture
def whole_layer_remat():
    """A context in which ``SeqPolicyNet`` recomputes every layer
    whole — ``nn.remat`` as it was before it had a policy: what the
    tests of the policy compare with."""
    import contextlib

    import flax.linen as nn

    @contextlib.contextmanager
    def context():
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(nn, "remat",
                          lambda cls, policy, plain=nn.remat: plain(cls))
            yield

    return context


@pytest.fixture
def kernel_gradient(whole_layer_remat):
    """``(cfg, kept=True) -> (pallas_calls, names)``: the kernel calls
    in the gradient's jaxpr of the trainer's loss of the sequence
    policy ``cfg`` builds, and the names its values carry
    (``checkpoint_name``), with the splash kernel traced as on a TPU
    at the smallest shape its tiles take (one row of 256, blocks of
    128); ``kept`` false recomputes every layer whole. A trace alone:
    nothing is compiled or run."""
    import contextlib

    import jax.numpy as jnp

    from rocalphago_tpu.models import seqpolicy
    from rocalphago_tpu.training import sl

    def eqns(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    def read(cfg, kept=True):
        net = seqpolicy.SeqPolicy(board=19, init_weights=False, **cfg)
        dummy = jnp.zeros((1, 1), jnp.int32)
        params = jax.eval_shape(net.module.init, jax.random.key(0),
                                dummy, dummy)
        ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
        # a trace that is only counted: without jax's own checks of
        # every equation (JAX_ENABLE_CHECKS, above), which take most
        # of its time
        with pytest.MonkeyPatch.context() as patch, \
                jax.enable_checks(False), \
                contextlib.nullcontext() if kept else whole_layer_remat():
            patch.setattr(seqpolicy, "kernel_platform", lambda: "tpu")
            patch.setattr(seqpolicy, "KERNEL_BLOCK", 128)
            found = list(eqns(jax.make_jaxpr(jax.grad(
                lambda p, i, n: sl.policy_loss_fn(
                    net.module.apply, p, i, n)[0]))(
                        params, ids, ids).jaxpr))
        return (sum(e.primitive.name == "pallas_call" for e in found),
                {e.params["name"] for e in found
                 if e.primitive.name == "name"})

    return read
