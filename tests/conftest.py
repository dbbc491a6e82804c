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
