"""Value network: position → expected outcome in [-1, 1].

Parity: ``AlphaGo/models/value.py::CNNValue`` (same conv trunk as the
policy + 1×1 conv + ``Dense(256, relu)`` + ``Dense(1, tanh)``;
``eval_state``; SURVEY.md §2 "Value net"). NHWC bfloat16 trunk, float32
head, scalar per position.

Head variants (``head=`` kwarg, recorded in saved specs):

* ``"fcn"`` (default) — fully convolutional: 1×1 conv → global
  mean+max spatial pooling → small dense head. No parameter shape
  depends on H×W, so ONE checkpoint applies at 9×9/13×13/19×19
  unchanged (the transfer result of "Transfer of Fully Convolutional
  Policy-Value Networks", PAPERS.md) — the contract
  ``rocalphago_tpu/multisize`` serves and ``training/curriculum.py``
  trains across.
* ``"dense"`` — the legacy size-locked head (flatten H×W into
  ``Dense(dense_units)``). ``ROCALPHAGO_VALUE_HEAD=dense`` restores it
  as the default for new nets; specs saved before the head kwarg
  existed load as this via :meth:`CNNValue.migrate_spec`.

Auxiliary heads (``aux_heads=("ownership", "score")``, KataGo's
"Accelerating Self-Play Learning in Go"): extra prediction heads
sharing the trunk — per-point terminal ownership (tanh ``[B, N]``)
and final score margin (scalar) — trained against the engine's
terminal labels (:func:`rocalphago_tpu.engine.jaxgo.terminal_labels`)
as regularizers that feed territory signal back into the shared
trunk. Default ``()``: the param tree, the value output, and every
compiled program are unchanged. With heads on, the main ``__call__``
still returns only the value (XLA dead-code-eliminates the aux
compute from search programs); training asks for ``with_aux=True``.
Both aux heads are size-generic (1×1 conv / pooled dense), so the
FCN multi-size contract survives.
"""

from __future__ import annotations

import functools
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization

from rocalphago_tpu.features import VALUE_FEATURES
from rocalphago_tpu.models.nn_util import ConvTrunk, NeuralNetBase, neuralnet

#: legacy escape hatch: set to ``dense`` to build new value nets with
#: the size-locked flattened head (pre-multisize behavior)
VALUE_HEAD_ENV = "ROCALPHAGO_VALUE_HEAD"


def default_value_head() -> str:
    """The head new value nets build with: ``fcn`` unless
    ``ROCALPHAGO_VALUE_HEAD`` overrides."""
    head = os.environ.get(VALUE_HEAD_ENV, "") or "fcn"
    if head not in ("fcn", "dense"):
        raise ValueError(
            f"{VALUE_HEAD_ENV}={head!r}: expected 'fcn' or 'dense'")
    return head


class ValueNet(nn.Module):
    """Conv trunk → value head → tanh scalar ``[B]``.

    ``head="fcn"``: 1×1 conv (``head_filters`` channels) → global
    mean+max pooling over the board axes → ``Dense(dense_units)`` →
    ``Dense(1)``; every parameter shape is board-size-free.
    ``head="dense"``: the legacy 1-channel 1×1 conv flattened over
    H×W into ``Dense(dense_units)`` (size-locked)."""

    board: int = 19
    input_planes: int = 49
    layers: int = 12
    filters_per_layer: int = 128
    filter_width_1: int = 5
    filter_width_K: int = 3
    dense_units: int = 256
    head: str = "fcn"
    head_filters: int = 32
    aux_heads: tuple = ()
    trunk_pool: int = 0
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array, with_aux: bool = False):
        t = ConvTrunk(layers=self.layers,
                      filters_per_layer=self.filters_per_layer,
                      filter_width_1=self.filter_width_1,
                      filter_width_K=self.filter_width_K,
                      global_pool=self.trunk_pool,
                      dtype=self.dtype, name="trunk")(x)
        aux = {}
        if "ownership" in self.aux_heads:
            # per-point ownership off the TRUNK (pre-pooling — the
            # head pooling destroys the spatial signal this head
            # exists to supervise); computed whether or not the
            # caller wants it so the params exist at init — XLA
            # removes it from programs that only use the value
            o = nn.Conv(1, (1, 1), padding="SAME", dtype=self.dtype,
                        name="own_conv")(t)
            aux["ownership"] = jnp.tanh(
                o.reshape((o.shape[0], -1)).astype(jnp.float32))
        if self.head == "dense":
            x = nn.Conv(1, (1, 1), padding="SAME", dtype=self.dtype,
                        name="head_conv")(t)
            x = x.reshape((x.shape[0], -1))
        else:
            x = nn.relu(nn.Conv(self.head_filters, (1, 1),
                                padding="SAME", dtype=self.dtype,
                                name="head_conv")(t))
            # mean+max over the board axes: mean carries territory
            # balance, max carries "is there a winning region
            # anywhere" — both invariant to H×W
            x = jnp.concatenate(
                [x.mean(axis=(1, 2)), x.max(axis=(1, 2))], axis=-1)
        x = nn.relu(nn.Dense(self.dense_units, dtype=self.dtype,
                             name="dense1")(x))
        if "score" in self.aux_heads:
            # score margin from the shared penultimate features,
            # unsquashed (a regression target in board points)
            s = nn.Dense(1, dtype=self.dtype, name="score_dense")(x)
            aux["score"] = s[:, 0].astype(jnp.float32)
        v = nn.Dense(1, dtype=self.dtype, name="dense2")(x)
        value = jnp.tanh(v[:, 0].astype(jnp.float32))
        return (value, aux) if with_aux else value


@neuralnet
class CNNValue(NeuralNetBase):
    """Scalar position evaluator.

    Defaults to the 49-plane ``VALUE_FEATURES`` input (the 48 policy
    planes + the player-color plane): komi breaks color symmetry, so
    the color plane is what lets the net value a position differently
    from its color-swapped mirror.
    """

    def __init__(self, feature_list=VALUE_FEATURES, **kwargs):
        # resolve the head NOW so every saved spec records it
        # explicitly (specs without it predate the kwarg and load as
        # the legacy dense head via migrate_spec)
        kwargs.setdefault("head", default_value_head())
        super().__init__(feature_list, **kwargs)

    @staticmethod
    def create_network(board: int = 19, input_planes: int = 49,
                       layers: int = 12, filters_per_layer: int = 128,
                       filter_width_1: int = 5, filter_width_K: int = 3,
                       dense_units: int = 256, head: str = "fcn",
                       head_filters: int = 32,
                       aux_heads=(), trunk_pool: int = 0) -> ValueNet:
        allowed = {"ownership", "score"}
        if not set(aux_heads) <= allowed:
            raise ValueError(
                f"unknown aux heads {sorted(set(aux_heads) - allowed)}"
                f"; supported: {sorted(allowed)}")
        return ValueNet(board=board, input_planes=input_planes,
                        layers=layers,
                        filters_per_layer=filters_per_layer,
                        filter_width_1=filter_width_1,
                        filter_width_K=filter_width_K,
                        dense_units=dense_units, head=head,
                        head_filters=head_filters,
                        aux_heads=tuple(aux_heads),
                        trunk_pool=trunk_pool)

    @classmethod
    def migrate_spec(cls, spec: dict) -> dict:
        """Checkpoint migration: value specs written before the
        ``head`` kwarg existed were trained with the size-locked
        flattened head — load them as such."""
        spec.setdefault("kwargs", {}).setdefault("head", "dense")
        return spec

    def size_generic(self) -> bool:
        return self.module.head == "fcn"

    def _symmetric_spec(self):
        """The scalar value needs no inverse mapping — plain mean."""
        return None, None

    def eval_state(self, state, symmetric: bool = False) -> float:
        """Expected outcome of one state from the player to move's
        perspective, in [-1, 1]."""
        return float(self.batch_eval_state([state], symmetric)[0])

    def batch_eval_state(self, states,
                         symmetric: bool = False) -> np.ndarray:
        planes = self._states_to_planes(self._as_state_list(states))
        return self.values_from_planes(planes, symmetric=symmetric)

    def values_from_planes(self, planes,
                           symmetric: bool = False) -> np.ndarray:
        """Forward from already-encoded planes (encode-sharing seam;
        see ``PointPolicyEval.dists_from_planes``)."""
        planes, b = self._pad_bucket(planes)  # stable compiled shapes
        fwd = self.forward_symmetric if symmetric else self.forward
        return np.asarray(fwd(planes))[:b]

    def forward_aux(self, planes):
        """Jitted apply returning ``(value [B], {head: pred})`` —
        the training-side entry for the auxiliary heads (the plain
        :meth:`forward` keeps the search-side value-only contract)."""
        if getattr(self, "_apply_aux", None) is None:
            self._apply_aux = jax.jit(functools.partial(
                self.module.apply, with_aux=True))
        return self._apply_aux(self.params, planes)


def with_aux_heads(net: CNNValue,
                   aux_heads=("ownership", "score"),
                   seed: int = 0) -> CNNValue:
    """A copy of ``net`` with auxiliary heads grafted on: trunk and
    value-head params are the TRAINED ones (by value, not reference),
    the new heads initialize fresh from ``seed``. The upgrade path for
    a checkpoint that predates the aux heads — the value output is
    bit-identical to ``net``'s, only the aux predictions start
    untrained."""
    kwargs = dict(net.spec_kwargs)
    kwargs["aux_heads"] = tuple(aux_heads)
    grown = CNNValue(net.feature_list, board=net.board, seed=seed,
                     **kwargs)

    def merge(new, old):
        if isinstance(new, dict):
            return {k: merge(v, old[k]) if k in old else v
                    for k, v in new.items()}
        return old

    grown.params = jax.tree.map(
        jnp.asarray,
        merge(serialization.to_state_dict(grown.params),
              serialization.to_state_dict(net.params)))
    return grown
