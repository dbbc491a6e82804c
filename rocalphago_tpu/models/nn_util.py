"""Model base: JSON spec ⇄ network contract, registry, save/load.

Parity: ``AlphaGo/models/nn_util.py`` (``NeuralNetBase`` with JSON model
spec + HDF5 weights, the ``@neuralnet`` subclass registry, and the
per-position ``Bias`` Keras layer; SURVEY.md §2 "NN base / registry").
TPU-native differences:

* networks are Flax modules; parameters live in a pytree, serialized
  with Flax msgpack (``*.flax.msgpack``) instead of Keras HDF5 — but
  the load-bearing idea is kept: a small JSON spec records the class
  name, the **feature list** (the feature⇄network contract the GTP
  server needs to rebuild the encoder), and the architecture kwargs;
* the per-position learned bias is a parameter of the Flax modules
  (see ``policy.PolicyNet``), not a custom layer class;
* ``forward`` is a jitted apply (the reference compiled a raw
  ``K.function`` to bypass Keras predict overhead — ``jax.jit`` is the
  equivalent and better);
* evaluation is batched and device-resident; host-facing ``eval_state``
  accepts either a host ``pygo.GameState`` or a device ``GoState``.
"""

from __future__ import annotations

import functools
import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import msgpack
import numpy as np
from flax import serialization

from rocalphago_tpu.engine import jaxgo, pygo
from rocalphago_tpu.features import DEFAULT_FEATURES, Preprocess
from rocalphago_tpu.runtime.atomic import (
    atomic_write_json,
    atomic_writer,
)

NEURALNETS: dict[str, type] = {}

# Model-spec format version, bumped whenever the flax param-tree layout
# changes (e.g. a trunk refactor renames conv1.. → trunk/*): loading a
# spec written under another format fails with a clear message instead
# of a deep deserialization error. Specs without the field predate the
# versioning and are assumed current.
SPEC_FORMAT = 2


class GlobalPoolBias(nn.Module):
    """KataGo-style global-pooling bias block ("Accelerating Self-Play
    Learning in Go", PAPERS.md): a 1×1 conv projects the trunk to
    ``pool_filters`` channels, their board-wide mean and max are
    concatenated (``2·pool_filters`` scalars — no spatial shape, so
    the block is size-generic like :class:`PointHead`), and a dense
    layer maps them back to one bias per trunk channel, broadcast over
    the board and added to the activations. This is what lets a net
    WITHOUT the handcrafted ladder planes see whole-board state (a
    running ladder is a global pattern a local conv stack cannot
    summarize) — the ladder-free configuration's architectural half."""

    pool_filters: int = 32
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        g = nn.Conv(self.pool_filters, (1, 1), padding="SAME",
                    dtype=self.dtype, name="pool_conv")(x)
        g = nn.relu(g)
        pooled = jnp.concatenate(
            [g.mean(axis=(1, 2)), g.max(axis=(1, 2))], axis=-1)
        bias = nn.Dense(x.shape[-1], dtype=self.dtype,
                        name="pool_dense")(pooled)
        return x + bias[:, None, None, :]


class ConvTrunk(nn.Module):
    """The AlphaGo conv trunk shared by policy and value nets: a
    width-``filter_width_1`` first layer then ``layers-2`` more of
    width ``filter_width_K``, ReLU, SAME padding (reference
    ``create_network`` trunk).

    ``global_pool=g > 0`` interleaves ``g`` :class:`GlobalPoolBias`
    blocks at evenly spaced depths (named ``gpool1..gpoolG``) — the
    ladder-free configuration's trunk. ``global_pool=0`` (default) is
    the exact pre-existing trunk: no extra modules, same param tree,
    bit-identical output."""

    layers: int = 12
    filters_per_layer: int = 128
    filter_width_1: int = 5
    filter_width_K: int = 3
    global_pool: int = 0
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = x.astype(self.dtype)
        convs = self.layers - 1
        # conv index (1-based) -> pooling block ordinal after it
        pool_after = {(j + 1) * convs // (self.global_pool + 1): j + 1
                      for j in range(self.global_pool)}
        for i in range(convs):
            w = self.filter_width_1 if i == 0 else self.filter_width_K
            x = nn.Conv(self.filters_per_layer, (w, w), padding="SAME",
                        dtype=self.dtype, name=f"conv{i + 1}")(x)
            x = nn.relu(x)
            j = pool_after.get(i + 1)
            if j is not None:
                x = GlobalPoolBias(dtype=self.dtype,
                                   name=f"gpool{j}")(x)
        return x


class PointHead(nn.Module):
    """1×1 conv → flatten → float32 logits ``[B, N]`` over board
    points. ``N`` comes from the input's H×W at trace time, never from
    a stored board size.

    ``head="bias"`` (legacy) adds the reference's per-position learned
    bias (its custom Keras ``Bias`` layer, as a plain ``[N]``
    parameter) — which locks the checkpoint to one board size.
    ``head="fcn"`` (default) omits it, leaving only the conv's own
    channel bias, so the params apply at any H×W. A FRESH net is
    bit-identical either way: the position bias initializes to
    zeros."""

    head: str = "fcn"
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        n = x.shape[1] * x.shape[2]
        x = nn.Conv(1, (1, 1), padding="SAME", dtype=self.dtype,
                    name="conv")(x)
        logits = x.reshape((x.shape[0], n)).astype(jnp.float32)
        if self.head == "bias":
            bias = self.param("position_bias",
                              nn.initializers.zeros, (n,))
            logits = logits + bias
        return logits


def stream_msgpack(f, state) -> None:
    """Write a state dict to ``f`` as Flax's msgpack, one leaf at a
    time: only the leaf being written is copied to the host."""
    packer = msgpack.Packer(default=serialization._msgpack_ext_pack,
                            strict_types=True)

    def walk(node):
        if isinstance(node, dict):
            f.write(packer.pack_map_header(len(node)))
            for key, value in node.items():
                f.write(packer.pack(key))
                walk(value)
        elif isinstance(node, (jax.Array, np.ndarray)):
            leaf = {"": np.asarray(node)}   # chunked if over 1 GiB,
            f.write(packer.pack(            # as to_bytes does
                serialization._chunk_array_leaves_in_place(leaf)[""]))
        else:
            f.write(packer.pack(node))

    walk(state)


def neuralnet(cls):
    """Class decorator registering a network for spec-based loading."""
    NEURALNETS[cls.__name__] = cls
    return cls


class NeuralNetBase:
    """Holds (module, params, preprocess) and the spec (de)serializer.

    Subclasses define ``create_network(**kwargs) -> flax.linen.Module``
    and evaluation helpers. ``self.spec_kwargs`` is everything needed to
    rebuild the module from JSON.
    """

    module = None  # flax module, set by subclass __init__
    #: ranks of a training batch's (inputs, targets): feature planes
    #: ``[B, s, s, F]`` and one move or outcome per position
    batch_ranks = (4, 1)

    def __init__(self, feature_list=DEFAULT_FEATURES, *, board: int = 19,
                 init_weights: bool = True, seed: int = 0, **kwargs):
        self.cfg = jaxgo.GoConfig(size=board)
        self.preprocess = Preprocess(feature_list, cfg=self.cfg)
        self.feature_list = tuple(feature_list)
        self.board = board
        self.spec_kwargs = dict(kwargs)
        self.module = self.create_network(
            board=board, input_planes=self.preprocess.output_dim, **kwargs)
        self.params = None
        if init_weights:
            dummy = jnp.zeros(
                (1, board, board, self.preprocess.output_dim), jnp.float32)
            self.params = self.module.init(jax.random.key(seed), dummy)
        self._apply = jax.jit(self.module.apply)

    @property
    def input_planes(self) -> int:
        """Feature planes a training corpus must carry for this net."""
        return self.preprocess.output_dim

    @property
    def num_outputs(self) -> int:
        """Size of a policy's output space: the board's points."""
        return self.board * self.board

    # ------------------------------------------------------------- forward

    def forward(self, planes: jax.Array) -> jax.Array:
        """Jitted apply on encoded planes ``[B, s, s, F]``."""
        return self._apply(self.params, planes)

    def forward_symmetric(self, planes: jax.Array) -> jax.Array:
        """Dihedral-ensembled forward (the AlphaGo paper's
        evaluation-time symmetry averaging): run all 8 transforms,
        map each output back, average. Subclasses define the mapping
        via ``_symmetric_spec``."""
        if getattr(self, "_apply_sym", None) is None:
            per_transform, finalize = self._symmetric_spec()
            self._apply_sym = jax.jit(make_symmetric_forward(
                self.module.apply, per_transform, finalize))
        return self._apply_sym(self.params, planes)

    def _symmetric_spec(self):
        """(per_transform(out, t), finalize(mean)) for
        :func:`make_symmetric_forward`; override per output type."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support symmetry "
            "ensembling")

    def _states_to_planes(self, states) -> jax.Array:
        """Host ``pygo.GameState`` list / single device ``GoState`` /
        batched ``GoState`` / list of either → ``[B, s, s, F]``."""
        if isinstance(states, jaxgo.GoState):
            if states.board.ndim == 2:  # already batched
                return self.preprocess.states_to_tensor(states)
            return self.preprocess.state_to_tensor(states)
        if isinstance(states, pygo.GameState):
            states = [states]
        # host BFS labeling skipped per state; one compiled batched
        # fill reseeds the whole wave (hot path: MCTS leaf evaluation)
        any_pygo = any(not isinstance(s, jaxgo.GoState) for s in states)
        dev = [s if isinstance(s, jaxgo.GoState)
               else jaxgo.from_pygo(self.cfg, s, with_labels=False)
               for s in states]
        batched = jax.tree.map(lambda *xs: jnp.stack(xs), *dev)
        if any_pygo:
            batched = jaxgo.seed_labels(self.cfg, batched)
        return self.preprocess.states_to_tensor(batched)

    @staticmethod
    def _pad_bucket(planes: jax.Array, min_bucket: int = 8):
        """Pad the batch axis up to the next power-of-two bucket.

        Host-facing eval batch sizes vary call to call (MCTS waves
        dedup to different leaf counts, game batches shrink as games
        finish); without bucketing every first-seen size costs a full
        XLA compile of the forward — 20–40s on TPU. Returns
        ``(padded_planes, real_batch)``; callers slice outputs back to
        ``real_batch``."""
        b = planes.shape[0]
        bucket = min_bucket
        while bucket < b:
            bucket *= 2
        if bucket == b:
            return planes, b
        pad = jnp.zeros((bucket - b,) + planes.shape[1:], planes.dtype)
        return jnp.concatenate([planes, pad]), b

    @staticmethod
    def _as_state_list(states):
        """Normalize eval inputs to a list of single-game states
        (splits a batched ``GoState`` into per-game views)."""
        if isinstance(states, pygo.GameState):
            return [states]
        if isinstance(states, jaxgo.GoState):
            if states.board.ndim == 1:
                return [states]
            b = states.board.shape[0]
            return [jax.tree.map(lambda x: x[i], states) for i in range(b)]
        return list(states)

    # ------------------------------------------------------ spec save/load

    def save_model(self, json_file: str, weights_file: str | None = None):
        """Write the JSON spec (+ weights beside it unless given)."""
        spec = {
            "class": type(self).__name__,
            "format": SPEC_FORMAT,
            "feature_list": list(self.feature_list),
            "board": self.board,
            "kwargs": self.spec_kwargs,
        }
        if weights_file is None:
            weights_file = os.path.splitext(json_file)[0] + ".flax.msgpack"
        spec["weights_file"] = os.path.relpath(
            weights_file, os.path.dirname(json_file) or ".")
        # weights first, spec second: a crash between the two leaves a
        # stale-but-loadable spec, never a spec pointing at a missing
        # or half-written weights file
        self.save_weights(weights_file)
        atomic_write_json(json_file, spec)

    def save_weights(self, weights_file: str):
        # atomic tmp+fsync+rename: concurrent readers (multi-host
        # opponent pools waiting on snapshot visibility) and post-crash
        # resumes must never see a half-written msgpack. Streamed a
        # leaf at a time (the bytes ``serialization.to_bytes`` would
        # build, without building them): a billion-parameter tree is
        # never on the host twice
        with atomic_writer(weights_file) as f:
            stream_msgpack(f, serialization.to_state_dict(self.params))

    def load_weights(self, weights_file: str):
        try:
            with open(weights_file, "rb") as f:
                # unpacked from the file as it is read: the tree, not
                # the file's bytes and then the tree
                state = msgpack.Unpacker(
                    f, ext_hook=serialization._msgpack_ext_unpack,
                    raw=False, max_buffer_size=0).unpack()
            self.params = serialization.from_state_dict(
                self.params,
                serialization._unchunk_array_leaves_in_place(state))
        except (ValueError, KeyError, msgpack.UnpackException) as e:
            # surface pytree mismatches with the likely causes instead
            # of a bare msgpack error; don't over-claim which one it is
            raise ValueError(
                f"{weights_file} does not match this architecture's "
                "parameter tree: the file may belong to a different "
                "network class/size, be corrupt or truncated, or have "
                "been exported under an older param-tree layout "
                f"(current model-spec format {SPEC_FORMAT}). "
                f"Underlying error: {e}") from e

    @staticmethod
    def load_model(json_file: str) -> "NeuralNetBase":
        """Rebuild any registered network from its JSON spec."""
        with open(json_file) as f:
            spec = json.load(f)
        fmt = spec.get("format", SPEC_FORMAT)
        if fmt != SPEC_FORMAT:
            raise ValueError(
                f"{json_file} is model-spec format {fmt}, this build "
                f"reads format {SPEC_FORMAT}: its weights use an "
                "incompatible parameter-tree layout — re-export the "
                "model with the matching framework version")
        cls = NEURALNETS.get(spec.get("class"))
        if cls is None:
            raise ValueError(
                f"unknown network class {spec.get('class')!r}; "
                f"registered: {sorted(NEURALNETS)}")
        spec = cls.migrate_spec(spec)
        net = cls(tuple(spec["feature_list"]), board=int(spec["board"]),
                  **spec.get("kwargs", {}))
        weights = spec.get("weights_file")
        if weights:
            path = os.path.join(os.path.dirname(json_file) or ".", weights)
            net.load_weights(path)
        return net

    @classmethod
    def migrate_spec(cls, spec: dict) -> dict:
        """Hook for same-format checkpoint migration: adjust an older
        spec (in place is fine) before the network is rebuilt —
        e.g. value/policy specs written before the ``head`` kwarg
        existed load with the legacy size-locked head. Default:
        identity."""
        return spec

    # ---------------------------------------------------- multi-size

    def size_generic(self) -> bool:
        """Whether this net's PARAM tree holds no size-locked shapes,
        i.e. one pytree applies at any board size. Subclasses with an
        FCN head override; the conservative default is False."""
        return False

    def at_board(self, board: int) -> "NeuralNetBase":
        """A facade of this net at another board size SHARING this
        net's params (by reference, no copy): same class, features and
        architecture kwargs, fresh ``GoConfig``/``Preprocess``/jitted
        apply at ``board``. The multi-size seam: a
        :class:`~rocalphago_tpu.multisize.MultiSizePool` builds one
        facade per active size over one FCN checkpoint, and the
        curriculum hands params from one stage's facade to the next.

        Params stay SHARED — assigning ``facade.params`` later
        rebinds only that facade; callers that train through a facade
        must copy the updated tree back themselves."""
        if board == self.board:
            return self
        if not self.size_generic():
            raise ValueError(
                f"{type(self).__name__} at board {self.board} has "
                "size-locked params (legacy dense/bias head) and "
                f"cannot be re-sized to {board} — rebuild or retrain "
                "with the FCN head (see docs/MULTISIZE.md)")
        clone = type(self)(self.feature_list, board=board,
                           init_weights=False, **self.spec_kwargs)
        clone.params = self.params
        return clone

    @staticmethod
    def create_network(**kwargs):
        raise NotImplementedError


def make_symmetric_forward(apply_fn, per_transform=None, finalize=None):
    """``(params, planes [B,s,s,F]) -> ensembled output``: transform
    the batch by each of the 8 dihedral group elements, apply the net,
    map each output back with ``per_transform(out, t)``, average, then
    ``finalize(mean)``."""
    from rocalphago_tpu.training.symmetries import transform_planes

    def sym(params, planes):
        def one(t):
            tp = jax.vmap(lambda x: transform_planes(x, t))(planes)
            out = apply_fn(params, tp)
            return per_transform(out, t) if per_transform else out

        mean = jax.vmap(one)(jnp.arange(8)).mean(axis=0)
        return finalize(mean) if finalize else mean

    return sym


@functools.partial(jax.jit, static_argnames=("temperature_is_one",))
def masked_probs(logits: jax.Array, legal: jax.Array,
                 temperature: jax.Array | float = 1.0,
                 temperature_is_one: bool = False) -> jax.Array:
    """Softmax over legal board points only, with optional temperature
    (probability exponentiation ``p^(1/T)`` as in the reference's
    ``ProbabilisticPolicyPlayer``). ``legal`` is bool ``[B, N]`` over
    board points; all-illegal rows return zeros."""
    neg = jnp.finfo(logits.dtype).min
    masked = jnp.where(legal, logits, neg)
    if not temperature_is_one:
        masked = masked / temperature
    p = jax.nn.softmax(masked, axis=-1)
    p = jnp.where(legal, p, 0.0)
    denom = p.sum(axis=-1, keepdims=True)
    return jnp.where(denom > 0, p / jnp.maximum(denom, 1e-30), 0.0)


def legal_moves_mask_host(state: pygo.GameState) -> np.ndarray:
    """Bool [N] legality over board points for a host GameState
    (sensible moves excluded at the agent layer, not here)."""
    n = state.size * state.size
    mask = np.zeros((n,), bool)
    for (x, y) in state.get_legal_moves(include_eyes=True):
        mask[x * state.size + y] = True
    return mask


class PointPolicyEval:
    """Host-facing evaluation for nets whose output is logits over
    board points — shared by ``CNNPolicy`` and ``CNNRollout`` (the
    reference's ``eval_state`` / ``batch_eval_state`` /
    ``_select_moves_and_normalize`` surface). Mixed into a
    :class:`NeuralNetBase` subclass."""

    def _symmetric_spec(self):
        """Inverse-map the point probabilities of each transform, then
        return ``log p̄`` — which behaves as logits under the masked
        softmax (renormalizing over the legal support recovers the
        averaged distribution)."""
        from rocalphago_tpu.training.symmetries import (
            inverse_transform_planes,
        )

        s = self.board

        def per_transform(logits, t):
            probs = jax.nn.softmax(logits, axis=-1)
            grids = probs.reshape(-1, s, s, 1)
            inv = jax.vmap(
                lambda g: inverse_transform_planes(g, t))(grids)
            return inv.reshape(-1, s * s)

        return per_transform, lambda mean: jnp.log(mean + 1e-30)

    def eval_state(self, state, moves=None):
        """Distribution over legal moves of one state →
        ``[((x, y), prob), ...]`` (the reference's
        ``_select_moves_and_normalize`` semantics). ``moves`` optionally
        restricts the support (an empty list means "no moves");
        it must contain only legal moves — entries are NOT re-checked
        against the rules."""
        return self.batch_eval_state(
            [state], [moves] if moves is not None else None)[0]

    def batch_eval_state(self, states, moves_lists=None,
                         symmetric: bool = False):
        """Lockstep evaluation of many states: one forward and one
        masked-softmax device call for the whole batch.

        ``moves_lists[i]``, when given, becomes the support for state
        ``i`` verbatim (callers pass pre-computed legal/sensible
        subsets; re-deriving legality here would double the host cost
        of the search hot path). ``symmetric`` ensembles the forward
        over the 8 board symmetries (8× device work)."""
        states = self._as_state_list(states)
        return self.dists_from_planes(
            states, self._states_to_planes(states), moves_lists,
            symmetric=symmetric)

    def dists_from_planes(self, states, planes, moves_lists=None,
                          symmetric: bool = False):
        """As :meth:`batch_eval_state`, from already-encoded ``planes``
        — the seam that lets a caller encode ONCE and share the planes
        between nets (the MCTS wave's policy/value fusion: the 48-plane
        encode dominates wave cost, so paying it twice halves sims/s)."""
        planes, b = self._pad_bucket(planes)
        logits = self.forward_symmetric(planes) if symmetric \
            else self.forward(planes)
        sizes, legal_rows = [], []
        for i, state in enumerate(states):
            size = state.size if isinstance(state, pygo.GameState) \
                else self.board
            if moves_lists is not None and moves_lists[i] is not None:
                # callers pass a subset of legal moves; building the
                # mask from it directly skips the per-point legality
                # scan (the expensive host computation)
                legal = np.zeros((size * size,), bool)
                for (x, y) in moves_lists[i]:
                    legal[x * size + y] = True
            else:
                legal = self._legal_for(state)
            sizes.append(size)
            legal_rows.append(legal)
        legal_b = np.stack(legal_rows)
        if logits.shape[0] > b:      # padded rows: all-illegal → zeros
            legal_b = np.concatenate(
                [legal_b, np.zeros((logits.shape[0] - b,
                                    legal_b.shape[1]), bool)])
        probs = np.asarray(masked_probs(logits, jnp.asarray(legal_b)))
        out = []
        for i, size in enumerate(sizes):
            out.append([((int(p) // size, int(p) % size),
                         float(probs[i, p]))
                        for p in np.flatnonzero(legal_b[i])])
        return out

    def _legal_for(self, state) -> np.ndarray:
        if isinstance(state, pygo.GameState):
            return legal_moves_mask_host(state)
        mask = np.asarray(jaxgo.legal_mask(self.cfg, state))
        return mask[:-1]
