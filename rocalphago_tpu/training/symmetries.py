"""On-device dihedral (D4) augmentation.

Parity: the reference SL trainer's ``BOARD_TRANSFORMATIONS`` — 8 board
symmetries applied randomly per sample on the *host* with
``np.rot90/fliplr`` (SURVEY.md §2 "SL trainer"). Here one random int
per sample picks the group element inside the compiled train step: the
NHWC plane stack goes through a ``lax.switch`` over the eight
flip/rot90 variants, and the flat action index through the same map
written as integer arithmetic on ``(row, col)`` — elementwise on
``[B]``, no permutation is built.

The convention, once: group element ``t`` in 0..7 is a flip of axis 1
(columns, ``c → m − c`` with ``m = size − 1``) when ``t >= 4``,
*followed by* ``t % 4`` quarter turns as ``jnp.rot90`` makes them
(counter-clockwise: the value at ``(r, c)`` moves to ``(m − c, r)``).
``inverse_transform_planes`` undoes it for symmetry-averaged
evaluation (``models/nn_util.py::forward_symmetric``, search).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def transform_planes(x: jax.Array, t: jax.Array) -> jax.Array:
    """Apply group element ``t`` (int scalar) to one ``[s, s, F]`` (or
    ``[s, s]``) array. Branchless: composed from flips/transposes picked
    by ``lax.switch``."""
    return jax.lax.switch(t, [
        lambda a: a,
        lambda a: jnp.rot90(a, 1),
        lambda a: jnp.rot90(a, 2),
        lambda a: jnp.rot90(a, 3),
        lambda a: jnp.flip(a, axis=1),
        lambda a: jnp.rot90(jnp.flip(a, axis=1), 1),
        lambda a: jnp.rot90(jnp.flip(a, axis=1), 2),
        lambda a: jnp.rot90(jnp.flip(a, axis=1), 3),
    ], x)


def transform_action(action: jax.Array, t: jax.Array, size: int
                     ) -> jax.Array:
    """Where group element ``t`` moves a flat board action: the index
    of the point at which ``transform_planes`` puts that point's value
    (pass = ``size²`` maps to itself). Elementwise, so scalars and
    ``[B]`` arrays both work."""
    n, m = size * size, size - 1
    t = jnp.asarray(t)
    p = jnp.minimum(action, n - 1)
    r, c = jnp.divmod(p, size)
    c = jnp.where(t >= 4, m - c, c)
    k = t % 4
    turns = [k == 0, k == 1, k == 2]
    r2 = jnp.select(turns, [r, m - c, m - r], c)
    c2 = jnp.select(turns, [c, r, m - c], m - r)
    return jnp.where(action >= n, action, r2 * size + c2)


def inverse_transform_planes(x: jax.Array, t: jax.Array) -> jax.Array:
    """Inverse group element (t<4 → rot90^(4-t); t>=4 is an involution
    composed as flip∘rot, whose inverse is rot^{-1}∘flip = itself for
    these generators)."""
    return jax.lax.switch(t, [
        lambda a: a,
        lambda a: jnp.rot90(a, 3),
        lambda a: jnp.rot90(a, 2),
        lambda a: jnp.rot90(a, 1),
        lambda a: jnp.flip(a, axis=1),
        lambda a: jnp.flip(jnp.rot90(a, 3), axis=1),
        lambda a: jnp.flip(jnp.rot90(a, 2), axis=1),
        lambda a: jnp.flip(jnp.rot90(a, 1), axis=1),
    ], x)


def random_transform_batch(rng: jax.Array, planes: jax.Array,
                           actions: jax.Array, size: int):
    """Random per-sample symmetry for a training batch
    (``planes [B,s,s,F]``, ``actions [B]``) — or, for a sequence
    policy, one transform per row of move ids (``planes [B,S]``,
    ``actions [B,S]``): every board point of a packed record moves
    with its board, and pass, separators and any id past them stay
    (``transform_action`` leaves ids ≥ ``size²`` alone)."""
    t = jax.random.randint(rng, (planes.shape[0],), 0, 8)
    if planes.ndim == 2:
        return (transform_action(planes, t[:, None], size),
                transform_action(actions, t[:, None], size))
    planes = jax.vmap(transform_planes)(planes, t)
    actions = transform_action(actions, t, size)
    return planes, actions
