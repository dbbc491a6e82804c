"""Build a configuration's networks with weights made on the device.

A configuration file (``configs/<name>.json``) holds one group of
sizes per network (``policy``, ``value``). The networks are the
program's own classes, built WITHOUT its eager leaf-by-leaf
initialiser: all leaves come from one jitted ``module.init`` call on
the run's seed, in the type they are served in (float32 parameters,
cast to the bfloat16 compute type inside the module).
"""

from __future__ import annotations

#: keys of a network group that are constructor arguments
_KWARGS = ("layers", "filters_per_layer", "filter_width_1",
           "filter_width_K", "head", "head_filters", "dense_units")


def build_net(config: dict, which: str, seed: int):
    """The program's network object for group ``which`` of ``config``
    with seed-made parameters on the device."""
    import jax
    import jax.numpy as jnp

    from rocalphago_tpu.models import nn_util

    group = config[which]
    cls = nn_util.NEURALNETS[group["class"]]
    kwargs = {k: group[k] for k in _KWARGS if k in group}
    net = cls(board=config["board"], init_weights=False, **kwargs)
    planes = net.preprocess.output_dim
    if planes != group["input_planes"]:
        raise ValueError(
            f"{config['name']}.{which}: the program encodes {planes} "
            f"planes, the configuration says {group['input_planes']}")
    dummy = jnp.zeros((1, net.board, net.board, planes), jnp.float32)
    # distinct streams per network from the one run seed
    key = jax.random.fold_in(jax.random.key(seed % (2 ** 31)),
                             0 if which == "policy" else 1)
    net.params = jax.jit(net.module.init)(key, dummy)
    return net


def random_planes(seed: int, batch: int, board: int, planes: int,
                  density: float = 0.25):
    """Seeded 0/1 planes ``uint8 [batch, board, board, planes]`` made
    on the device in one call — network inputs with the sparsity of
    real feature planes, for cells and checks that need no encoder."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        return jax.random.bernoulli(
            key, density, (batch, board, board, planes)).astype(
                jnp.uint8)

    return make(jax.random.key(seed % (2 ** 31)))
