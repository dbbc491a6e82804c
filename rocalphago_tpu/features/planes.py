"""Device-side 48-plane encoder: pure jitted function of engine state.

The reference encoder (``AlphaGo/preprocessing/preprocess.py``) loops
over board cells in Python and *simulates each candidate move* with
``state.copy() + do_move`` for the capture-size / self-atari /
liberties-after planes — its famous hot spot (SURVEY.md §3.2). Here the
same planes are **exact** but come from dense bitmap algebra on the
engine's :class:`~rocalphago_tpu.engine.jaxgo.GroupData`:

* a candidate's captures are its ≤4 deduped neighbor groups in atari —
  sizes come from ``gd.sizes``, captured stones from ``gd.member``;
* the merged own group after the move is ``{p} ∪ own neighbor groups``
  (bitmap OR), its liberties ``|dilate(M) ∩ new_empty|`` where
  ``new_empty`` adds the captured points — one [N,4,N] gather instead
  of N board simulations.

Everything vmaps over games; no per-cell Python anywhere.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from rocalphago_tpu.engine.jaxgo import (
    neighbor_analysis,
    GoConfig,
    GoState,
    GroupData,
    _dedup_mask,
    diagonals_for,
    group_data,
    legal_mask,
    neighbors_for,
)
from rocalphago_tpu.obs import scopes


class CandidateInfo(NamedTuple):
    """Per-candidate-move analysis (valid where the move is legal)."""

    capture_size: jax.Array     # int32 [N] opponent stones captured
    own_size_after: jax.Array   # int32 [N] own merged-group size
    libs_after: jax.Array       # int32 [N] own merged-group liberties
    legal: jax.Array            # bool  [N] board moves only (no pass)


@functools.lru_cache(maxsize=None)
def _packed_consts(size: int):
    """Trace-time constants of the packed-bitmap board representation
    (bit ``c % 32`` of word ``c // 32`` is cell ``c``): per-cell word
    index / bit value, the packed identity rows, and the not-col-0 /
    not-col-last masks the E/W bitstream shifts use."""
    import numpy as np

    n = size * size
    w = (n + 31) // 32
    cells = np.arange(n)
    word = cells // 32
    bit = np.uint32(1) << (cells % 32).astype(np.uint32)
    eye = np.zeros((n, w), np.uint32)
    eye[cells, word] = bit
    notcol0 = np.zeros((w,), np.uint32)
    notcol_last = np.zeros((w,), np.uint32)
    for c in cells:
        if c % size != 0:
            notcol0[c // 32] |= np.uint32(1) << np.uint32(c % 32)
        if c % size != size - 1:
            notcol_last[c // 32] |= np.uint32(1) << np.uint32(c % 32)
    # numpy, not jnp: these are cached across jit traces, and a jnp
    # constant materialized inside one trace may not escape to another
    return (word.astype(np.int32), bit, eye, notcol0, notcol_last)


def _packed_shift(x: jax.Array, k: int) -> jax.Array:
    """Shift packed bitstreams (uint32 [..., W]) toward HIGHER cell
    indices by ``k`` bits (negative = lower), zero-filled; requires
    ``0 < |k| < 32``."""
    if k > 0:
        prev = jnp.concatenate(
            [jnp.zeros_like(x[..., :1]), x[..., :-1]], axis=-1)
        return (x << k) | (prev >> (32 - k))
    nxt = jnp.concatenate(
        [x[..., 1:], jnp.zeros_like(x[..., :1])], axis=-1)
    return (x >> -k) | (nxt << (32 + k))


def _packed_dilate(size: int, x: jax.Array) -> jax.Array:
    """Packed-bitmap 4-neighborhood dilation: self ∪ N/S (bitstream
    shift by ±size, falls off the ends) ∪ E/W (shift by ±1, row edges
    masked so file-a/file-last never wrap)."""
    _, _, _, notcol0, notcol_last = _packed_consts(size)
    return (x
            | _packed_shift(x, size) | _packed_shift(x, -size)
            | (_packed_shift(x, 1) & notcol0)
            | (_packed_shift(x, -1) & notcol_last))


def candidate_info(cfg: GoConfig, state: GoState,
                   gd: GroupData) -> CandidateInfo:
    """Exact capture/merge/liberty analysis of every candidate move.

    The merged-group / captured-point bitmaps are PACKED (uint32 words
    over cells, built straight from ``gd.labels`` by one scatter-add —
    distinct bits of a word never collide, so add IS bitwise-or);
    dilation is bitstream shifts and the liberty count a population
    count. The dense [N, 4, N] member gather + boolean reductions this
    replaces were ~70% of the whole non-ladder encode on CPU
    (sequential profile, PR 6); ``gd.member`` is no longer read.
    """
    n = cfg.num_points
    board, me = state.board, state.turn
    empty = board == 0
    word, bitval, eye_p, _, _ = _packed_consts(cfg.size)
    w = eye_p.shape[-1]

    nbr_color, nbr_root, uniq, _ = neighbor_analysis(cfg, board, gd.labels)

    own_k = uniq & (nbr_color == me)
    cap_k = uniq & (nbr_color == -me) & (gd.lib_counts[nbr_root] == 1)

    capture_size = (cap_k * gd.sizes[nbr_root]).sum(axis=1)
    own_size_after = 1 + (own_k * gd.sizes[nbr_root]).sum(axis=1)

    # packed member rows per group (row N = the empty sentinel = 0)
    member_p = jnp.zeros((n + 1, w), jnp.uint32).at[gd.labels, word].add(
        jnp.where(~empty, bitval, jnp.uint32(0)))
    member_p = member_p.at[n].set(jnp.uint32(0))
    nbr_member_p = member_p[nbr_root]                    # [N, 4, W]
    own_sel = jnp.where(own_k[:, :, None], nbr_member_p, jnp.uint32(0))
    cap_sel = jnp.where(cap_k[:, :, None], nbr_member_p, jnp.uint32(0))
    merged = (eye_p | own_sel[:, 0] | own_sel[:, 1]
              | own_sel[:, 2] | own_sel[:, 3])           # [N, W]
    cap_pts = cap_sel[:, 0] | cap_sel[:, 1] | cap_sel[:, 2] | cap_sel[:, 3]

    empty_p = jnp.zeros((w,), jnp.uint32).at[word].add(
        jnp.where(empty, bitval, jnp.uint32(0)))
    new_empty = (empty_p[None, :] & ~eye_p) | cap_pts
    dilated = _packed_dilate(cfg.size, merged)
    libs_after = jax.lax.population_count(
        dilated & new_empty).sum(axis=1).astype(jnp.int32)

    legal = legal_mask(cfg, state, gd)[:n]
    return CandidateInfo(capture_size.astype(jnp.int32),
                         own_size_after.astype(jnp.int32),
                         libs_after, legal)


def true_eyes(cfg: GoConfig, state: GoState, owner) -> jax.Array:
    """bool [N]: empty points that are true eyes of ``owner`` (same
    diagonal rule as ``pygo.GameState.is_eye``)."""
    n = cfg.num_points
    nbrs = neighbors_for(cfg.size)
    diags = diagonals_for(cfg.size)
    board = state.board
    board_pad = jnp.concatenate([board, jnp.zeros((1,), board.dtype)])
    empty = board == 0

    valid_n = nbrs < n
    eyeish = empty & ((board_pad[nbrs] == owner) | ~valid_n).all(axis=1)
    valid_d = diags < n
    bad = (valid_d & (board_pad[diags] == -owner)).sum(axis=1)
    off_board = 4 - valid_d.sum(axis=1)
    return eyeish & jnp.where(off_board > 0, bad == 0, bad <= 1)


def _one_hot8(value: jax.Array, lo: int, active: jax.Array) -> jax.Array:
    """[N] int → [N, 8] one-hot of ``clip(value - lo, 0, 7)``, zeroed
    where ``active`` is False."""
    idx = jnp.clip(value - lo, 0, 7)
    return (jax.nn.one_hot(idx, 8, dtype=jnp.float32)
            * active[:, None].astype(jnp.float32))


def needs_member(features: tuple) -> bool:
    """Whether these features require ``group_data(with_member=True)``
    — callers precomputing a shared ``gd`` for :func:`encode` must
    match this. Always False since :func:`candidate_info` switched to
    packed bitmaps built straight from ``gd.labels``: no plane reads
    the dense ``gd.member`` rows anymore (superko's zxor is the only
    remaining consumer, and ``group_data`` handles that itself). Kept
    as the single source of truth for the convention."""
    del features
    return False


def needs_candidates(features: tuple) -> bool:
    """Whether these features need :func:`candidate_info` (the
    per-candidate-move capture/merge/liberty analysis)."""
    return any(f in ("capture_size", "self_atari_size",
                     "liberties_after") for f in features)


def encode_analysis(cfg: GoConfig, state: GoState, features: tuple,
                    gd: "GroupData | None" = None):
    """The per-state analysis every encode variant shares:
    ``(gd, ci, legal)`` — group data (built with member rows iff the
    candidate-simulation planes need them), the candidate-move info
    (None when unneeded) and the board-move legality mask. Factored
    out so the incremental encoder (:mod:`features.incremental`) and
    the from-scratch :func:`encode` analyse identically — bit-identity
    between the two paths starts here."""
    n = cfg.num_points
    if gd is None:
        gd = group_data(cfg, state.board,
                        with_member=needs_member(features),
                        with_zxor=cfg.enforce_superko,
                        labels=state.labels)
    ci = None
    with jax.named_scope(scopes.ENCODE_CANDIDATES):
        if needs_candidates(features):
            ci = candidate_info(cfg, state, gd)
            legal = ci.legal
        else:
            legal = legal_mask(cfg, state, gd)[:n]
    return gd, ci, legal


def assemble_planes(cfg: GoConfig, state: GoState, features: tuple,
                    gd: "GroupData", ci, legal, lad_cap, lad_esc,
                    lad_kw: dict) -> jax.Array:
    """Stack the requested plane groups → ``[size, size, F]``. The
    ladder planes are passed in when both were computed by a shared
    read (``ladder_planes`` / the incremental cached read); a
    single-plane request falls back to the per-plane reader here.
    Shared verbatim by :func:`encode` and ``features/incremental.py``
    so the two paths cannot drift plane-by-plane."""
    from rocalphago_tpu.features import ladders as _ladders

    # a single-plane ladder request chases here, under its own scope
    with jax.named_scope(scopes.ENCODE_LADDER):
        if "ladder_capture" in features and lad_cap is None:
            lad_cap = _ladders.ladder_capture_plane(
                cfg, state, gd, legal, **lad_kw)
        if "ladder_escape" in features and lad_esc is None:
            lad_esc = _ladders.ladder_escape_plane(
                cfg, state, gd, legal, **lad_kw)
    with jax.named_scope(scopes.ENCODE_PLANES):
        return _stack_planes(cfg, state, features, gd, ci, legal,
                             lad_cap, lad_esc)


def _stack_planes(cfg, state, features, gd, ci, legal, lad_cap,
                  lad_esc) -> jax.Array:
    n = cfg.num_points
    board, me = state.board, state.turn
    empty = board == 0
    has_stone = ~empty

    out = []
    for name in features:
        if name == "board":
            f = jnp.stack([(board == me), (board == -me), empty],
                          axis=-1).astype(jnp.float32)
        elif name == "ones":
            f = jnp.ones((n, 1), jnp.float32)
        elif name == "turns_since":
            age = state.step_count - 1 - state.stone_ages
            f = _one_hot8(age, 0, has_stone & (state.stone_ages >= 0))
        elif name == "liberties":
            libs = gd.lib_counts[gd.labels]
            f = _one_hot8(libs, 1, has_stone)
        elif name == "capture_size":
            f = _one_hot8(ci.capture_size, 0, legal)
        elif name == "self_atari_size":
            f = _one_hot8(ci.own_size_after, 1, legal & (ci.libs_after == 1))
        elif name == "liberties_after":
            f = _one_hot8(ci.libs_after, 1, legal)
        elif name == "ladder_capture":
            f = lad_cap.astype(jnp.float32)[:, None]
        elif name == "ladder_escape":
            f = lad_esc.astype(jnp.float32)[:, None]
        elif name == "sensibleness":
            f = (legal & ~true_eyes(cfg, state, me)).astype(
                jnp.float32)[:, None]
        elif name == "zeros":
            f = jnp.zeros((n, 1), jnp.float32)
        elif name == "color":
            # AlphaGo's value-net 49th plane: 1 iff black to move
            # (komi asymmetry; see pyfeatures module docstring)
            f = jnp.broadcast_to((me == 1).astype(jnp.float32), (n, 1))
        else:
            raise KeyError(f"unknown feature {name!r}")
        out.append(f)
    flat = jnp.concatenate(out, axis=-1)
    return flat.reshape(cfg.size, cfg.size, -1)


def encode(cfg: GoConfig, state: GoState,
           features: tuple = None,
           ladder_depth: int = 40,
           ladder_lanes: int = 16,
           ladder_chase_slots: int = 6,
           gd: "GroupData | None" = None) -> jax.Array:
    """Encode one game state → float32 ``[size, size, F]`` (NHWC).

    ``features`` is a tuple of plane-group names (static under jit);
    default is the full 48-plane AlphaGo set. Pass a precomputed ``gd``
    (built with ``with_member`` if the candidate-simulation planes are
    requested) to share one flood fill with the caller's own analysis
    — the self-play ply does this (encode + sensibleness per ply).

    When BOTH ladder planes are requested (the default set), they are
    computed by ONE shared, gated read (:func:`ladders.ladder_planes`:
    one candidate analysis, one pooled chase-slot set, one rung loop)
    — the encode-path overhaul; see docs/PERFORMANCE.md "Encode path".
    Sequential callers (self-play, MCTS root advance) should prefer
    the delta sibling ``features/incremental.py::encode_step``, which
    produces bit-identical planes while reusing prior ladder-chase
    verdicts across successive positions.
    """
    from rocalphago_tpu.features import ladders as _ladders
    from rocalphago_tpu.features.pyfeatures import DEFAULT_FEATURES

    if features is None:
        features = DEFAULT_FEATURES
    gd, ci, legal = encode_analysis(cfg, state, features, gd)

    # both ladder planes ride one shared gated chase; a single-plane
    # request keeps the cheaper per-plane read
    lad_cap = lad_esc = None
    lad_kw = dict(depth=ladder_depth, lanes=ladder_lanes,
                  chase_slots=ladder_chase_slots)
    if "ladder_capture" in features and "ladder_escape" in features:
        with jax.named_scope(scopes.ENCODE_LADDER):
            lad_cap, lad_esc = _ladders.ladder_planes(
                cfg, state, gd, legal, **lad_kw)
    return assemble_planes(cfg, state, features, gd, ci, legal,
                           lad_cap, lad_esc, lad_kw)


def batched_encoder(cfg: GoConfig, features: tuple, **encode_kwargs):
    """``(states, gd=None) -> planes [B, size, size, F]`` — the ONE
    definition of the vmapped encode every fused hot loop uses (the
    self-play ply, the device-search evaluation, the replay-gradient
    plies, the rollout leg). Callers that already hold a per-ply
    :func:`jaxgo.group_data` pass it to share the analysis (the
    shared-gd convention); ``gd=None`` recomputes inside. Encoder
    knobs (``ladder_depth``/``ladder_lanes``/``ladder_chase_slots``)
    thread through ``encode_kwargs``, so a call-site A/B or a future
    default change lands at every hot loop at once."""
    one = functools.partial(encode, cfg, features=features,
                            **encode_kwargs)
    with_gd = jax.vmap(lambda s, g: one(s, gd=g))
    no_gd = jax.vmap(lambda s: one(s))

    def enc(states: GoState, gd=None) -> jax.Array:
        return no_gd(states) if gd is None else with_gd(states, gd)

    return enc
