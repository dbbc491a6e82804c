"""TPU-native Go engine: pure-functional, fixed-shape, jit/vmap-able.

This replaces the reference's Python/Cython board (``AlphaGo/go.py::
GameState``; SURVEY.md §2a "the centerpiece of the rebuild") with a
design that maps onto XLA:

* game state is a pytree of fixed-shape arrays (:class:`GoState`);
* ``step(cfg, state, action)`` is a pure function — thousands of
  concurrent games run as ``jax.vmap(step)`` with zero host round-trips;
* connected groups come from an iterative min-label flood fill under
  ``lax.while_loop`` (no dynamic shapes);
* liberties are dense bitmaps ``[groups, points]`` built with four
  scatters — one matrix yields liberty counts, capture detection, and
  the feature encoder's exact capture-size / liberties-after planes
  without simulating any candidate move;
* positional superko is *exact and vectorized*: the Zobrist hash of the
  position after any candidate move is ``hash ^ z[p] ^ xor(captured
  groups)``, where per-group Zobrist XORs come from a GF(2) parity
  matmul that runs on the MXU.

Rules semantics are identical to :mod:`rocalphago_tpu.engine.pygo`
(differential-tested in ``tests/test_jaxgo.py``): suicide illegal,
simple ko always, optional positional superko, two passes end the game,
area scoring with komi.

Actions are flat indices ``0..N*N-1`` plus ``N*N`` for pass.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rocalphago_tpu.engine import zobrist as zobrist_tables

BLACK = 1
WHITE = -1


@dataclasses.dataclass(frozen=True)
class GoConfig:
    """Static engine parameters (hashable → usable as a jit static arg)."""

    size: int = 19
    komi: float = 7.5
    enforce_superko: bool = False
    # ring-buffer length for positional-superko hashes; >= max game
    # length gives exact superko (games are capped by move limits at the
    # agent layer, reference uses ~500)
    max_history: int = 512

    @property
    def num_points(self) -> int:
        return self.size * self.size

    @property
    def pass_action(self) -> int:
        return self.size * self.size


def default_komi(size: int) -> float:
    """Standard area-scoring komi per board size: 7.5 for 13×13 and
    up (the reference's and the zero papers' 19×19 value), 7.0 below
    (the CGOS 9×9 convention). Round-4 evidence for why this must be
    size-aware: a 9×9 zero run under the 19×19 default showed an 86%
    white win rate (``results/zero_scale_r4``) — most of that was the
    80-ply move cap truncating every game, but the komi default was
    the other half of the diagnosis (VERDICT r4 §weak 2;
    ``scripts/zero_balance.py`` measures both effects)."""
    return 7.5 if size >= 13 else 7.0


class GoState(NamedTuple):
    """One game. Batch by ``vmap``-ing the engine functions.

    All arrays are fixed-shape; ``N = size * size``.
    """

    board: jax.Array        # int8 [N]   0 empty, +1 black, -1 white
    turn: jax.Array         # int8 []    player to move (+1/-1)
    ko: jax.Array           # int32 []   point banned by simple ko, -1 none
    pass_count: jax.Array   # int8 []    consecutive passes
    done: jax.Array         # bool []
    step_count: jax.Array   # int32 []   moves played (incl. passes)
    hash: jax.Array         # uint32 [2] Zobrist hash of current position
    hash_history: jax.Array  # uint32 [H, 2] ring buffer of position hashes
    stone_ages: jax.Array   # int32 [N]  step at which stone placed, -1 empty
    prisoners: jax.Array    # int32 [2]  stones captured from [black, white]
    labels: jax.Array       # int32 [N]  carried group labeling: min flat
    #   index per group, sentinel N for empty — ALWAYS equal to
    #   compute_labels(board). step() maintains it incrementally
    #   (a move only adds one stone and removes whole captured groups,
    #   neither of which can split a group), so the per-move flood
    #   fill disappears from the hot loop; analysis consumers derive
    #   GroupData loop-free via group_data(..., labels=state.labels).


class GroupData(NamedTuple):
    """Whole-board group analysis — shared by step, legality and features.

    ``G = N + 1`` rows: one per possible group root (= min flat index of
    the group) plus a sentinel row ``N`` for empty/off-board.

    ``member`` and ``zxor`` are optional (``None`` unless requested):
    the hot step/legality path only needs the cheap [N,4]-scatter
    fields, while the feature encoder asks for the dense membership
    bitmap and superko legality for the per-group Zobrist XORs.
    """

    labels: jax.Array       # int32 [N]  group root per point (N for empty)
    sizes: jax.Array        # int32 [G]  stones per group
    lib_counts: jax.Array   # int32 [G]  distinct liberties per group
    member: jax.Array | None  # bool [G, N]  member[g, p]: stone p in group g
    zxor: jax.Array | None  # uint32 [G, 2] XOR of member stones' Zobrist keys


# --------------------------------------------------------------------------
# static per-size tables (host-side, cached)
# --------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _tables(size: int):
    """(neighbors [N,4], diagonals [N,4], zobrist [N,2,2]) as numpy.

    Neighbor/diagonal entries are ``N`` (sentinel) when off-board.
    Zobrist keys: ``zobrist[p, color_idx, 2xuint32]`` with color_idx
    0=black, 1=white; shared with the python oracle via
    :mod:`rocalphago_tpu.engine.zobrist` (fixed seed → identical
    hashes across engines and processes).
    """
    n = size * size
    neighbors = np.full((n, 4), n, dtype=np.int32)
    diagonals = np.full((n, 4), n, dtype=np.int32)
    for x in range(size):
        for y in range(size):
            p = x * size + y
            for k, (dx, dy) in enumerate(_NBR_SHIFTS):
                nx, ny = x + dx, y + dy
                if 0 <= nx < size and 0 <= ny < size:
                    neighbors[p, k] = nx * size + ny
            for k, (dx, dy) in enumerate(((1, 1), (1, -1), (-1, 1), (-1, -1))):
                nx, ny = x + dx, y + dy
                if 0 <= nx < size and 0 <= ny < size:
                    diagonals[p, k] = nx * size + ny
    return neighbors, diagonals, zobrist_tables.position_table(size)


def neighbors_for(size: int) -> jax.Array:
    return jnp.asarray(_tables(size)[0])


def diagonals_for(size: int) -> jax.Array:
    return jnp.asarray(_tables(size)[1])


def zobrist_for(size: int) -> jax.Array:
    return jnp.asarray(_tables(size)[2])


@functools.lru_cache(maxsize=1)
def _dense_engine() -> bool:
    """Dense (shift/matmul) vs scatter formulation of the per-ply group
    analysis.

    On TPU, scatter-adds with colliding indices and `[N,4]` index
    gathers serialize, while broadcast compares, 2-D grid shifts and
    small matmuls run at full vector/MXU width — the one on-chip A/B
    on record (ROADMAP.md's north star: 2026-08-01, before PR 1, a
    12x128 net's games at batch 1024, 19x19): dense 17,762 steps/s vs
    scatter 10,558, so dense is the TPU default. On CPU the scatter
    path wins (1444 cheap serial updates beat 131k-cell dense
    compares), so the default follows the backend platform; entry points report which one ran
    (:func:`engine_formulation`, the ``device`` event).

    Read once per process (trace-time; cached): override with
    ``ROCALPHAGO_ENGINE_DENSE=0/1`` **before the first engine trace**
    for A/B measurement — flipping it later in the same process has no
    effect on already-traced programs.
    """
    import os

    v = os.environ.get("ROCALPHAGO_ENGINE_DENSE", "")
    if v in ("0", "1"):
        return v == "1"
    return jax.default_backend() == "tpu"


def engine_formulation() -> str:
    """``"dense"`` or ``"scatter"`` — which group-analysis formulation
    this process traces (see :func:`_dense_engine`)."""
    return "dense" if _dense_engine() else "scatter"


def _shift2d(x: jax.Array, dx: int, dy: int, fill) -> jax.Array:
    """Read the value at ``(row+dx, col+dy)`` into each cell of the
    trailing 2-D grid (``fill`` off-board) — the gather-free neighbor
    access pattern shared by the dense group analysis and legality."""
    size = x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 2) + [(1, 1), (1, 1)]
    p = jnp.pad(x, pad, constant_values=fill)
    return p[..., 1 + dx:1 + dx + size, 1 + dy:1 + dy + size]


_NBR_SHIFTS = ((1, 0), (-1, 0), (0, 1), (0, -1))


def _color_idx(color) -> jax.Array:
    """±1 color → 0/1 index into the Zobrist table."""
    return ((1 - color) // 2).astype(jnp.int32)


# --------------------------------------------------------------------------
# state construction
# --------------------------------------------------------------------------


def new_state(cfg: GoConfig) -> GoState:
    n = cfg.num_points
    return GoState(
        board=jnp.zeros((n,), jnp.int8),
        turn=jnp.int8(BLACK),
        ko=jnp.int32(-1),
        pass_count=jnp.int8(0),
        done=jnp.bool_(False),
        step_count=jnp.int32(0),
        hash=jnp.zeros((2,), jnp.uint32),
        hash_history=jnp.zeros((cfg.max_history, 2), jnp.uint32),
        stone_ages=jnp.full((n,), -1, jnp.int32),
        prisoners=jnp.zeros((2,), jnp.int32),
        labels=jnp.full((n,), n, jnp.int32),
    )


def new_states(cfg: GoConfig, batch: int) -> GoState:
    """A batch of fresh games (leading axis on every leaf)."""
    one = new_state(cfg)
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (batch,) + x.shape), one)


def from_pygo(cfg: GoConfig, st, *, with_history: bool = True,
              with_labels: bool = True) -> GoState:
    """Bridge a host-side :class:`pygo.GameState` into engine state.

    Used at the GTP/SGF boundary where positions are built move-by-move
    on the host. Both engines share one Zobrist scheme
    (:mod:`rocalphago_tpu.engine.zobrist`), so the position hash and
    the superko history are carried over verbatim from the hashes pygo
    maintained incrementally (up to ``cfg.max_history``, most recent
    kept) — no host rehash. ``with_history=False`` skips the history
    transfer (correct whenever ``cfg.enforce_superko`` is off — e.g.
    the MCTS device-rollout path, which converts whole leaf waves per
    call).
    """
    board = np.asarray(st.board, dtype=np.int8).reshape(-1)

    # Place historical hashes so that the engine's future writes (at
    # slot ``step_count % H``, then ``step_count+1 % H``, ...) evict the
    # *oldest* entries first: newest-seen position sits at slot
    # ``(step_count - 1) % H``. ``_hash_history`` is insertion-ordered
    # (dict), so the suffix really is the most recent positions.
    hist = np.zeros((cfg.max_history, 2), np.uint32)
    if with_history:
        seen = [np.frombuffer(b, dtype=np.uint32)
                for b in st._hash_history.keys()]
        recent = seen[-cfg.max_history:]
        for i, h in enumerate(reversed(recent)):
            hist[(st.turns_played - 1 - i) % cfg.max_history] = h

    ko = -1 if st.ko is None else st.ko[0] * cfg.size + st.ko[1]
    passes = 0
    if st.history and st.history[-1] is None:
        passes = 2 if (len(st.history) > 1 and st.history[-2] is None) else 1

    # host-side min-root labeling (ascending scan ⇒ the BFS seed is the
    # group's min flat index), seeding the engine's carried labels.
    # ``with_labels=False`` skips it and leaves the field all-sentinel
    # (INVALID — callers batching many states must reseed with one
    # compiled fill via :func:`seed_labels` before any engine use).
    n = cfg.num_points
    lab = np.full(n, n, np.int32)
    if with_labels:
        nbrs_np = _tables(cfg.size)[0]
        for p in range(n):
            if board[p] != 0 and lab[p] == n:
                lab[p] = p
                stack = [p]
                while stack:
                    q = stack.pop()
                    for r in nbrs_np[q]:
                        if r < n and board[r] == board[p] and lab[r] == n:
                            lab[r] = p
                            stack.append(r)
    return GoState(
        board=jnp.asarray(board),
        turn=jnp.int8(st.current_player),
        ko=jnp.int32(ko),
        pass_count=jnp.int8(passes),
        done=jnp.bool_(st.is_end_of_game),
        step_count=jnp.int32(st.turns_played),
        hash=jnp.asarray(np.asarray(st.zobrist_hash, np.uint32)),
        hash_history=jnp.asarray(hist),
        stone_ages=jnp.asarray(
            np.asarray(st.stone_ages, np.int32).reshape(-1)),
        prisoners=jnp.asarray(
            np.array([st.num_black_prisoners, st.num_white_prisoners],
                     np.int32)),
        labels=jnp.asarray(lab),
    )


# --------------------------------------------------------------------------
# group analysis
# --------------------------------------------------------------------------


def compute_labels(cfg: GoConfig, board: jax.Array) -> jax.Array:
    """Connected-component root (min flat index) per point; N for empty.

    Min-label propagation over same-color neighbors as **2-D grid
    shifts** (pad + static slice — vector ops the TPU executes at full
    lane width, vs the index gathers of the naive formulation, which
    serialize): each ``while_loop`` trip runs several unrolled hook
    steps, then checks the fixed point, so convergence stays exact for
    any group shape while the per-trip launch/cond overhead is
    amortized ~8×. SURVEY.md §7 hard part #1.
    """
    n = cfg.num_points
    size = cfg.size
    b2 = board.reshape(size, size)
    stone = b2 != 0
    sentinel = jnp.int32(n)
    init = jnp.where(
        stone, jnp.arange(n, dtype=jnp.int32).reshape(size, size),
        sentinel)

    links = [(_shift2d(b2, dx, dy, 0) == b2) & stone
             for dx, dy in _NBR_SHIFTS]

    def hook(lab):
        for link, (dx, dy) in zip(links, _NBR_SHIFTS):
            nb = _shift2d(lab, dx, dy, sentinel)
            lab = jnp.minimum(lab, jnp.where(link, nb, sentinel))
        return lab

    def jump(lab):
        # pointer shortcutting (Shiloach–Vishkin): every point adopts
        # its current root's label, so the min propagates along the
        # already-discovered linkage exponentially — long snake groups
        # converge in O(log N) trips instead of O(diameter). Exactness
        # is unaffected (the while_loop still runs to fixpoint).
        flat = lab.reshape(-1)
        flat_pad = jnp.concatenate([flat, jnp.asarray([sentinel])])
        return jnp.minimum(flat, flat_pad[flat]).reshape(lab.shape)

    def body(carry):
        lab, _ = carry
        new = lab
        for _ in range(4):
            new = hook(new)
        new = jump(new)
        for _ in range(4):
            new = hook(new)
        new = jump(new)
        return new, lab

    def cond(carry):
        lab, prev = carry
        return jnp.any(lab != prev)

    lab, _ = lax.while_loop(cond, body, (hook(init), init))
    return lab.reshape(-1)


def neighbor_analysis(cfg: GoConfig, board: jax.Array, labels: jax.Array):
    """Per-point padded neighbor lookup shared by legality, stepping and
    the feature encoder: ``(nbr_color [N,4], nbr_root [N,4], uniq [N,4],
    valid [N,4])``. Off-board neighbors read color 0 and the sentinel
    root ``N``; ``uniq`` is True at the first occurrence of each root
    among a point's ≤4 neighbors (the dedup convention every caller
    must share)."""
    n = cfg.num_points
    nbrs = neighbors_for(cfg.size)
    board_pad = jnp.concatenate([board, jnp.zeros((1,), board.dtype)])
    lab_pad = jnp.concatenate([labels, jnp.full((1,), n, jnp.int32)])
    return (board_pad[nbrs], lab_pad[nbrs],
            jax.vmap(_dedup_mask)(lab_pad[nbrs]), nbrs < n)


def relabel_after_place(cfg: GoConfig, board: jax.Array,
                        labels: jax.Array, pt, color,
                        cap_mask: jax.Array) -> jax.Array:
    """Labels after placing ``color`` at ``pt`` (legality pre-checked)
    and removing the captured stones ``cap_mask`` — exact with zero
    flood fills, because a placement can only MERGE groups (min of
    min-rooted groups ∪ {pt} is the union's min flat index) and a
    capture removes whole groups (reset to the empty sentinel ``N``).
    The board itself is updated by the caller. Shared by the engine
    step and the ladder reader's carried chase analysis."""
    n = cfg.num_points
    nbrs = neighbors_for(cfg.size)
    board_pad = jnp.concatenate([board, jnp.zeros((1,), board.dtype)])
    lab_pad = jnp.concatenate([labels, jnp.full((1,), n, jnp.int32)])
    my = nbrs[pt]
    same = (my < n) & (board_pad[my] == color)
    roots = jnp.where(same, lab_pad[my], n)
    new_root = jnp.minimum(roots.min(), pt).astype(jnp.int32)
    merged = (labels[:, None] == jnp.where(
        same, roots, -2)[None, :]).any(axis=1)
    labels1 = jnp.where(merged, new_root, labels).at[pt].set(new_root)
    return jnp.where(cap_mask, n, labels1)


@functools.lru_cache(maxsize=None)
def _batched_fill(cfg: GoConfig):
    return jax.jit(jax.vmap(lambda bd: compute_labels(cfg, bd)))


def seed_labels(cfg: GoConfig, states: GoState) -> GoState:
    """Recompute the carried labels of a BATCHED state in one compiled
    device fill. Use at host→device wave boundaries (MCTS leaf
    conversion) together with ``from_pygo(..., with_labels=False)``:
    one vmapped fill beats a per-state interpreted host BFS."""
    return states._replace(labels=_batched_fill(cfg)(states.board))


def vgroup_data(cfg: GoConfig, *, with_member: bool = False,
                with_zxor: bool = False):
    """vmapped ``GoState → GroupData`` using the engine's carried
    labels — the loop-free per-ply analysis every batched game loop
    shares (self-play, rollouts, the value-corpus generator)."""
    return jax.vmap(lambda s: group_data(
        cfg, s.board, with_member=with_member, with_zxor=with_zxor,
        labels=s.labels))


def lib_counts_from_labels(cfg: GoConfig, board: jax.Array,
                           labels: jax.Array) -> jax.Array:
    """Loop-free liberty recount given ``labels``: int32 ``[N+1]``
    distinct-empty-point counts per group root (sentinel row ``N`` is
    0). Each empty point contributes one liberty to each *distinct*
    adjacent group via the deduped ``[N,4]`` scatter-add. Shared by
    :func:`group_data` and the ladder reader's carried incremental
    labeling (``features/ladders.py``)."""
    n = cfg.num_points
    empty = board == 0
    _, nbr_root, uniq, _ = neighbor_analysis(cfg, board, labels)
    contrib = empty[:, None] & uniq & (nbr_root < n)
    lib_counts = jnp.zeros((n + 1,), jnp.int32).at[
        jnp.where(contrib, nbr_root, n)].add(contrib.astype(jnp.int32))
    return lib_counts.at[n].set(0)


def group_data(cfg: GoConfig, board: jax.Array, *,
               with_member: bool = False,
               with_zxor: bool = False,
               labels: jax.Array | None = None) -> GroupData:
    """Group analysis of a board (one flood fill + small scatters).

    Liberty counts are *distinct* empty points per group, computed with
    a deduped [N,4] scatter-add (each empty point contributes once per
    distinct neighboring group) — no dense [G,N] intermediate in the
    hot path. Request ``with_member`` (feature encoder) or
    ``with_zxor`` (superko legality) explicitly.

    Pass ``labels`` (normally ``state.labels``, the engine's carried
    incremental labeling) to skip the flood fill entirely — the whole
    analysis is then loop-free scatters, which is how the self-play /
    training hot paths run.
    """
    n = cfg.num_points
    if labels is None:
        labels = compute_labels(cfg, board)
    empty = board == 0

    member = None
    zxor = None
    if _dense_engine():
        # scatter-free: membership by broadcast compare (empty points
        # carry the sentinel label N, so their row-n hits vanish under
        # ``& ~empty``), sizes by row reduce, distinct liberties by
        # dilating each group's stone mask one step (OR makes
        # distinctness free — no per-point dedup needed) and counting
        # empty cells under the dilation. All vector ops; the TPU
        # executes them at full lane width where the scatter
        # formulation below serializes on colliding indices.
        dense_member = (labels[None, :]
                        == jnp.arange(n + 1, dtype=jnp.int32)[:, None]
                        ) & (~empty)[None, :]                 # [N+1, N]
        sizes = dense_member.sum(axis=1, dtype=jnp.int32)
        m2 = dense_member.reshape(n + 1, cfg.size, cfg.size)
        dil = jnp.zeros_like(m2)
        for dx, dy in _NBR_SHIFTS:
            dil = dil | _shift2d(m2, dx, dy, False)
        lib_counts = (dil & empty.reshape(cfg.size, cfg.size)[None]).sum(
            axis=(1, 2), dtype=jnp.int32)
        if with_member or with_zxor:
            member = dense_member
    else:
        sizes = jnp.zeros((n + 1,), jnp.int32).at[labels].add(
            (~empty).astype(jnp.int32))
        lib_counts = lib_counts_from_labels(cfg, board, labels)
        if with_member or with_zxor:
            points = jnp.arange(n, dtype=jnp.int32)
            member = jnp.zeros((n + 1, n), jnp.bool_).at[
                labels, points].max(~empty)
            member = member.at[n].set(False)
    if with_zxor:
        # Per-group XOR of member Zobrist keys via GF(2) parity matmul
        # (rides the MXU; XLA has no segment-XOR).
        zob = zobrist_for(cfg.size)
        key_per_point = jnp.where(
            (board == BLACK)[:, None], zob[:, 0], zob[:, 1])  # uint32 [N,2]
        key_bits = _unpack_bits(key_per_point)                # bool [N,64]
        parity = (member.astype(jnp.int32) @ key_bits.astype(jnp.int32)) % 2
        zxor = _pack_bits(parity.astype(jnp.bool_))           # uint32 [G,2]
        if not with_member:
            member = None
    return GroupData(labels, sizes, lib_counts, member, zxor)


def _unpack_bits(words: jax.Array) -> jax.Array:
    """uint32 [..., W] → bool [..., W*32] (little-endian bit order)."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(*words.shape[:-1], -1).astype(jnp.bool_)


def _pack_bits(bits: jax.Array) -> jax.Array:
    """bool [..., W*32] → uint32 [..., W]."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    words = bits.reshape(*bits.shape[:-1], -1, 32).astype(jnp.uint32)
    return (words << shifts).sum(axis=-1, dtype=jnp.uint32)


def _xor_reduce_masked(keys: jax.Array, mask: jax.Array) -> jax.Array:
    """XOR of ``keys[i]`` (uint32 [..., 2]) where ``mask[i]`` — via bit
    parity, since XLA lacks a segment-XOR."""
    bits = _unpack_bits(keys) & mask[..., None]
    parity = bits.sum(axis=-2) % 2
    return _pack_bits(parity.astype(jnp.bool_))


def _dedup_mask(roots: jax.Array) -> jax.Array:
    """For a small [K] int vector: True at the first occurrence of each
    value (used to dedup ≤4 neighbor group roots)."""
    k = roots.shape[0]
    eq = roots[:, None] == roots[None, :]
    earlier = jnp.tril(jnp.ones((k, k), jnp.bool_), k=-1)
    return ~(eq & earlier).any(axis=1)


# --------------------------------------------------------------------------
# legality
# --------------------------------------------------------------------------


def legal_mask(cfg: GoConfig, state: GoState,
               gd: GroupData | None = None) -> jax.Array:
    """Boolean mask over the ``N+1`` actions (last = pass, always legal
    while the game is live).

    Matches ``pygo.GameState.is_legal`` exactly, including positional
    superko when ``cfg.enforce_superko`` (candidate hashes via the
    group-XOR trick — no per-candidate simulation).
    """
    n = cfg.num_points
    if gd is None:
        gd = group_data(cfg, state.board, with_zxor=cfg.enforce_superko,
                        labels=state.labels)
    board, me = state.board, state.turn
    empty = board == 0

    if _dense_engine() and not cfg.enforce_superko:
        # gather-free: a placement at an empty point is non-suicide iff
        # some neighbor is empty, OR an own group with ≥2 liberties, OR
        # an opponent group in atari — one OR-field dilated by the four
        # grid shifts replaces the [N,4] neighbor gathers (which
        # serialize on TPU). Superko needs per-slot capture roots, so
        # it keeps the gather formulation below.
        lib_at = gd.lib_counts[gd.labels]       # [N]: one small gather
        src = (empty | ((board == me) & (lib_at >= 2))
               | ((board == -me) & (lib_at == 1))
               ).reshape(cfg.size, cfg.size)
        not_suicide = jnp.zeros_like(src)
        for dx, dy in _NBR_SHIFTS:
            not_suicide = not_suicide | _shift2d(src, dx, dy, False)
        ok = empty & not_suicide.reshape(-1)
    else:
        nbr_color, nbr_root, uniq, valid_nbr = neighbor_analysis(
            cfg, board, gd.labels)
        nbr_libs = gd.lib_counts[nbr_root]

        has_empty_nbr = (valid_nbr & (nbr_color == 0)).any(axis=1)
        own_safe = (valid_nbr & (nbr_color == me)
                    & (nbr_libs >= 2)).any(axis=1)
        captures = valid_nbr & (nbr_color == -me) & (nbr_libs == 1)
        not_suicide = has_empty_nbr | own_safe | captures.any(axis=1)
        ok = empty & not_suicide
    ok = ok & (jnp.arange(n) != state.ko)

    if cfg.enforce_superko:
        zob = zobrist_for(cfg.size)
        ci = _color_idx(me)
        cap_xor = _xor_reduce_masked(
            gd.zxor[nbr_root], captures & uniq)      # [N, 2]
        cand = state.hash[None, :] ^ zob[:, ci, :] ^ cap_xor
        seen = (cand[:, None, :] == state.hash_history[None, :, :]).all(
            axis=-1).any(axis=1)
        ok = ok & ~seen

    live = ~state.done
    return jnp.concatenate([ok & live, jnp.ones((1,), jnp.bool_) & live])


# --------------------------------------------------------------------------
# eval signature (transposition key for the NN evaluation cache)
# --------------------------------------------------------------------------


def eval_signature(cfg: GoConfig, state: GoState) -> jax.Array:
    """uint32 [2] key under which the NN evaluation of ``state`` may be
    cached: equal signatures ⇒ identical feature planes ⇒ identical
    device outputs (bar a 64-bit hash collision).

    The planes (``features/planes.py``) are a function of the board,
    the player to move, the simple-ko point, the done flag, and the
    per-stone age *bucket* ``clip(step_count - 1 - stone_age, 0, 7)``
    (the ``turns_since`` one-hots saturate at 8 — absolute move number
    never appears); the terminal-value komi rescore reads only
    ``done`` and the score, both covered. So the signature is the
    carried position hash XOR one age-bucket key per stone XOR
    ko/turn/done keys — keys from an independent fixed-seed family
    (:func:`rocalphago_tpu.engine.zobrist.signature_tables`).

    NOT valid under ``cfg.enforce_superko``: there the sensible-move
    mask depends on the hash *history*, which is not part of the
    signature — the serve pool refuses to cache in that mode.
    """
    n = cfg.num_points
    tabs = zobrist_tables.signature_tables(cfg.size)
    age_t = jnp.asarray(tabs.age)
    bucket = jnp.clip(state.step_count - 1 - state.stone_ages, 0,
                      zobrist_tables.AGE_BUCKETS - 1)
    keys = age_t[jnp.arange(n), bucket]                       # [N, 2]
    occupied = (state.board != 0) & (state.stone_ages >= 0)
    sig = state.hash ^ _xor_reduce_masked(keys, occupied)
    sig = sig ^ jnp.asarray(tabs.ko)[state.ko + 1]
    turn_t = jnp.asarray(tabs.turn)
    sig = sig ^ jnp.where(state.turn == WHITE, turn_t,
                          jnp.zeros_like(turn_t))
    done_t = jnp.asarray(tabs.done)
    sig = sig ^ jnp.where(state.done, done_t, jnp.zeros_like(done_t))
    return sig


# --------------------------------------------------------------------------
# step
# --------------------------------------------------------------------------


def step(cfg: GoConfig, state: GoState, action: jax.Array,
         gd: GroupData | None = None) -> GoState:
    """Play ``action`` (flat index, ``N`` = pass) for the player to move.

    Pure function of (state, action); assumes the action is legal (use
    :func:`legal_mask` — sampling already needs it). Occupied-point
    actions degrade to a pass rather than corrupting state. A finished
    game is frozen: any action returns the state unchanged.

    Pass ``gd`` (the :func:`group_data` of ``state.board``) to reuse the
    analysis :func:`legal_mask` already computed — inside one jitted
    sample-and-step program this halves the per-move engine cost.
    """
    n = cfg.num_points
    new = lax.cond(
        state.done,
        lambda s: s,
        lambda s: lax.cond(
            (action >= n) | (s.board[jnp.minimum(action, n - 1)] != 0),
            functools.partial(_step_pass, cfg),
            functools.partial(_step_place, cfg, action=action, gd=gd),
            s),
        state)
    return new


def _step_pass(cfg: GoConfig, state: GoState) -> GoState:
    pc = state.pass_count + 1
    return state._replace(
        turn=-state.turn,
        ko=jnp.int32(-1),
        pass_count=pc,
        done=pc >= 2,
        step_count=state.step_count + 1,
        hash_history=state.hash_history.at[
            state.step_count % cfg.max_history].set(state.hash),
    )


def _step_place(cfg: GoConfig, state: GoState, action,
                gd: GroupData | None = None) -> GoState:
    n = cfg.num_points
    nbrs = neighbors_for(cfg.size)
    zob = zobrist_for(cfg.size)
    board, me = state.board, state.turn
    if gd is None:
        gd = group_data(cfg, board, labels=state.labels)

    my_nbrs = nbrs[action]                               # [4]
    nbr_color = jnp.concatenate(
        [board, jnp.zeros((1,), board.dtype)])[my_nbrs]
    nbr_root = jnp.concatenate(
        [gd.labels, jnp.full((1,), n, jnp.int32)])[my_nbrs]

    # opponent neighbor groups in atari (their single liberty is `action`)
    cap_roots = jnp.where(
        (nbr_color == -me) & (gd.lib_counts[nbr_root] == 1), nbr_root, -2)
    captured = (gd.labels[:, None] == cap_roots[None, :]).any(axis=1)
    num_captured = captured.sum(dtype=jnp.int32)

    # a one-hot select, not ``.at[action].set(me)``: under vmap at
    # 1,024 games the TPU's int8 scatter dropped every stone whose
    # action index lay beyond the first one to three 91-cell windows
    # (PERF.md §7 row 2; chip_smoke.py replays the engine on pygo)
    board2 = jnp.where(jnp.arange(n) == action, me,
                       jnp.where(captured, 0, board)).astype(board.dtype)

    # simple ko: lone new stone, exactly one capture, one liberty left
    placed_alone = ~(nbr_color == me).any()
    board2_pad = jnp.concatenate([board2, jnp.ones((1,), board2.dtype)])
    p_libs = (board2_pad[my_nbrs] == 0).sum(dtype=jnp.int32)
    ko_point = jnp.argmax(captured).astype(jnp.int32)
    ko = jnp.where(
        (num_captured == 1) & placed_alone & (p_libs == 1), ko_point, -1)

    ci = _color_idx(me)
    cap_keys = jnp.where((me == BLACK), zob[:, 1, :], zob[:, 0, :])
    new_hash = (state.hash ^ zob[action, ci, :]
                ^ _xor_reduce_masked(cap_keys, captured))

    prisoners = state.prisoners.at[_color_idx(-me)].add(num_captured)
    return state._replace(
        board=board2,
        turn=-me,
        ko=ko,
        pass_count=jnp.int8(0),
        step_count=state.step_count + 1,
        hash=new_hash,
        hash_history=state.hash_history.at[
            state.step_count % cfg.max_history].set(new_hash),
        stone_ages=jnp.where(captured, -1, state.stone_ages).at[action].set(
            state.step_count),
        prisoners=prisoners,
        labels=relabel_after_place(cfg, board, gd.labels, action, me,
                                   captured),
    )


# --------------------------------------------------------------------------
# scoring
# --------------------------------------------------------------------------


def _territory(cfg: GoConfig, board: jax.Array):
    """bool ``[N]`` masks ``(black's, white's)`` of the empty points in
    regions bordering exactly one color. Same flood-fill machinery as
    group labels, run on the empty graph."""
    n = cfg.num_points
    nbrs = neighbors_for(cfg.size)
    empty = board == 0

    # label empty regions: treat empty as the "color"
    region = compute_labels(cfg, jnp.where(empty, jnp.int8(9), jnp.int8(0)))
    board_pad = jnp.concatenate([board, jnp.zeros((1,), board.dtype)])
    nbr_color = board_pad[nbrs]
    touches_b_pt = empty & (nbr_color == BLACK).any(axis=1)
    touches_w_pt = empty & (nbr_color == WHITE).any(axis=1)
    touches_b = jnp.zeros((n + 1,), jnp.bool_).at[region].max(touches_b_pt)
    touches_w = jnp.zeros((n + 1,), jnp.bool_).at[region].max(touches_w_pt)
    return (empty & touches_b[region] & ~touches_w[region],
            empty & touches_w[region] & ~touches_b[region])


def area_scores(cfg: GoConfig, state: GoState) -> tuple[jax.Array, jax.Array]:
    """Area (Chinese) scores ``(black, white_plus_komi)`` — empty regions
    bordering exactly one color count for it."""
    board = state.board
    terr_b, terr_w = _territory(cfg, board)
    black = (board == BLACK).sum() + terr_b.sum()
    white = (board == WHITE).sum() + terr_w.sum()
    return black.astype(jnp.float32), white.astype(jnp.float32) + cfg.komi


def winner(cfg: GoConfig, state: GoState) -> jax.Array:
    """+1 black wins, -1 white wins, 0 draw."""
    b, w = area_scores(cfg, state)
    return jnp.sign(b - w).astype(jnp.int32)


def terminal_labels(cfg: GoConfig, state: GoState):
    """Auxiliary training targets from one TERMINAL position:
    ``(ownership int8 [N], score float32)``, black-positive.

    Ownership is the area-scoring verdict per point: a stone's own
    color, and for empty points the color of the single-color region
    they sit in (+1 black, -1 white, 0 contested/neutral — dame and
    seki-shared regions). Score is ``black − white`` with the komi
    inside white, so ``sign(score) == winner`` by construction — the
    parity the tests pin. One game's labels (vmap over a batch at the
    call site, e.g. the zero loop's game-end labelling).
    """
    board = state.board
    terr_b, terr_w = _territory(cfg, board)
    ownership = (board.astype(jnp.int8)
                 + terr_b.astype(jnp.int8) - terr_w.astype(jnp.int8))
    black = (board == BLACK).sum() + terr_b.sum()
    white = (board == WHITE).sum() + terr_w.sum()
    score = (black.astype(jnp.float32)
             - white.astype(jnp.float32) - cfg.komi)
    return ownership, score


# --------------------------------------------------------------------------
# convenience wrapper
# --------------------------------------------------------------------------


class GoEngine:
    """Jitted single-game and batched closures over a fixed config.

    ``step/legal_mask/...`` operate on one game; the ``v``-prefixed
    variants are ``vmap``-ed over a leading batch axis — the rebuild's
    self-play scaling axis (SURVEY.md §2b "environment parallelism").
    """

    def __init__(self, cfg: GoConfig):
        self.cfg = cfg
        self.init = jax.jit(functools.partial(new_state, cfg))
        self.step = jax.jit(functools.partial(step, cfg))
        self.legal_mask = jax.jit(
            lambda state: legal_mask(cfg, state))
        self.area_scores = jax.jit(functools.partial(area_scores, cfg))
        self.winner = jax.jit(functools.partial(winner, cfg))
        self.group_data = jax.jit(
            lambda board: group_data(cfg, board, with_member=True,
                                     with_zxor=True))
        self.vstep = jax.jit(jax.vmap(functools.partial(step, cfg)))
        self.vlegal_mask = jax.jit(
            jax.vmap(lambda state: legal_mask(cfg, state)))
        self.vwinner = jax.jit(jax.vmap(functools.partial(winner, cfg)))

    def init_batch(self, batch: int) -> GoState:
        return new_states(self.cfg, batch)
