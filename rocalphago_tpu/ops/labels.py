"""Region labelling ops: the Pallas TPU labeling kernel, plus the
terminal ownership/score labeller built on the same flood-fill
(:func:`terminal_labels` — the auxiliary-target source for the
KataGo-style ownership/score heads in ``models/value.py``).

Pallas TPU kernel: batched connected-component labeling.

The engine's hottest primitive is the whole-board flood fill behind
``jaxgo.compute_labels`` (group analysis for stepping, legality,
features, scoring). The XLA formulation is a convergence
``while_loop`` of min-propagation sweeps; this kernel is the
TPU-native alternative: 8 boards per grid cell, the whole fixpoint
iteration running over VMEM-resident boards with zero HBM round
trips between sweeps.

Design notes:

* the board is tiny (≤ 25×25), so each program holds it entirely in
  VMEM; the grid parallelizes over the batch, 8 boards per cell;
* min-propagation uses pad + static-slice shifts — pure VPU vector
  ops; there are NO gathers (TPU vector units have no efficient
  arbitrary gather, so the pointer-jumping trick the XLA path uses is
  deliberately omitted here);
* the loop is a ``while_loop`` with an in-kernel convergence check
  capped at a STATIC sweep bound that proves exactness: each sweep
  propagates the min label ≥1 step along group connectivity and the
  longest possible chain is N-1 (a serpentine group filling the
  board). The early exit is per grid cell — a hard board stalls only
  its own 8-board block, unlike the XLA path's batch-global fixpoint.

The kernel is exact but OPT-IN: the default engine path stays on the
XLA ``while_loop`` (early exit usually wins on sparse boards).
``benchmarks/bench_labels.py`` compares both and
``scripts/chip_kernels.py`` checks it on the chip (compiled by Mosaic
on the v5e and bit-equal to XLA at 19×19, PR 21); flipping the engine
over is a one-line change in ``jaxgo.compute_labels`` if measurements
favor the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def terminal_labels(cfg, state):
    """Auxiliary training targets from one TERMINAL position:
    ``(ownership int8 [N], score float32)``, black-positive.

    Ownership is the area-scoring verdict per point: a stone's own
    color, and for empty points the color of the single-color region
    they sit in (+1 black, -1 white, 0 contested/neutral — dame and
    seki-shared regions). Score is ``black − white`` with the komi
    inside white, so ``sign(score) == jaxgo.winner`` by construction
    — the parity the tests pin. Same flood-fill machinery as
    :func:`jaxgo.area_scores` run on the empty graph; one game's
    labels (vmap over a batch at the call site, e.g. the zero loop's
    game-end labelling).
    """
    from rocalphago_tpu.engine.jaxgo import (BLACK, WHITE,
                                             compute_labels,
                                             neighbors_for)

    n = cfg.num_points
    nbrs = neighbors_for(cfg.size)
    board = state.board
    empty = board == 0

    # label empty regions: treat empty as the "color" (area_scores'
    # exact construction, kept in step with it by the parity test)
    region = compute_labels(
        cfg, jnp.where(empty, jnp.int8(9), jnp.int8(0)))
    board_pad = jnp.concatenate(
        [board, jnp.zeros((1,), board.dtype)])
    nbr_color = board_pad[nbrs]
    touches_b_pt = empty & (nbr_color == BLACK).any(axis=1)
    touches_w_pt = empty & (nbr_color == WHITE).any(axis=1)
    touches_b = jnp.zeros((n + 1,), jnp.bool_).at[region].max(
        touches_b_pt)
    touches_w = jnp.zeros((n + 1,), jnp.bool_).at[region].max(
        touches_w_pt)

    terr_b = empty & touches_b[region] & ~touches_w[region]
    terr_w = empty & touches_w[region] & ~touches_b[region]
    ownership = (board.astype(jnp.int8)
                 + terr_b.astype(jnp.int8) - terr_w.astype(jnp.int8))
    black = (board == BLACK).sum() + terr_b.sum()
    white = (board == WHITE).sum() + terr_w.sum()
    score = (black.astype(jnp.float32)
             - white.astype(jnp.float32) - cfg.komi)
    return ownership, score


def _sweeps_for(num_points: int) -> int:
    """Static sweep count that PROVES convergence: min labels advance
    ≥1 connectivity step per sweep and the longest chain is N-1."""
    return num_points


# Boards packed per grid cell. NOT a tiling requirement (the block's
# trailing dims are the full (size, size) board, which Mosaic accepts
# as-is); packing amortizes per-cell launch overhead — measured 1.6×
# over one board per cell on a real v5e chip at batch 256.
_BOARDS_PER_CELL = 8


def _label_kernel(board_ref, out_ref, *, size: int, sweeps: int):
    n = size * size
    # (bpc, size, size); no reshapes in-kernel, and widen int8 → int32
    # immediately — Mosaic lacks sub-word vector compares on this target
    board = board_ref[...].astype(jnp.int32)
    stone = board != 0
    sentinel = jnp.int32(n)
    iota = (jax.lax.broadcasted_iota(jnp.int32, (1, size, size), 1) * size
            + jax.lax.broadcasted_iota(jnp.int32, (1, size, size), 2))
    init = jnp.where(stone, iota, sentinel)

    def shifted(x, dx, dy, fill):
        p = jnp.pad(x, ((0, 0), (1, 1), (1, 1)), constant_values=fill)
        return p[:, 1 + dx:1 + dx + size, 1 + dy:1 + dy + size]

    links = [(shifted(board, dx, dy, 0) == board) & stone
             for dx, dy in ((1, 0), (-1, 0), (0, 1), (0, -1))]

    def sweep(lab):
        for link, (dx, dy) in zip(links, ((1, 0), (-1, 0), (0, 1),
                                          (0, -1))):
            nb = shifted(lab, dx, dy, sentinel)
            lab = jnp.minimum(lab, jnp.where(link, nb, sentinel))
        return lab

    # Fixpoint with an in-kernel convergence check: the ``sweeps``
    # static bound guarantees exactness, the early exit makes sparse
    # boards (the common case) converge in ~size sweeps instead of N.
    # The check is per grid cell — a hard board only stalls its own
    # 8-board block, not the whole batch the way the XLA path's
    # batch-global while_loop does.
    def cond(state):
        i, lab, changed = state
        return changed & (i < sweeps)

    def body(state):
        i, lab, _ = state
        new = sweep(lab)
        return i + 1, new, jnp.any(new != lab)

    _, lab, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), init, jnp.bool_(True)))
    out_ref[...] = lab


@functools.partial(jax.jit, static_argnames=("size", "interpret"))
def pallas_labels(boards: jax.Array, size: int,
                  interpret: bool = False) -> jax.Array:
    """Connected-component root (min flat index) per point for a BATCH
    of boards: int8 ``[B, N]`` → int32 ``[B, N]`` (``N`` = sentinel
    for empty points). Semantics identical to
    ``jaxgo.compute_labels`` vmapped over the batch.

    ``interpret=True`` runs the kernel in the Pallas interpreter — the
    CI path on CPU-only hosts (tests/test_ops.py differential-checks
    it against the XLA implementation).
    """
    batch, n = boards.shape
    if n != size * size:
        raise ValueError(f"boards have {n} points, size² is {size * size}")
    bpc = _BOARDS_PER_CELL
    padded = -batch % bpc
    if padded:
        boards = jnp.pad(boards, ((0, padded), (0, 0)))
    grids = boards.reshape(batch + padded, size, size)
    kernel = functools.partial(_label_kernel, size=size,
                               sweeps=_sweeps_for(n))
    out = pl.pallas_call(
        kernel,
        grid=((batch + padded) // bpc,),
        in_specs=[pl.BlockSpec((bpc, size, size), lambda b: (b, 0, 0))],
        out_specs=pl.BlockSpec((bpc, size, size), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((batch + padded, size, size),
                                       jnp.int32),
        interpret=interpret,
    )(grids)
    return out.reshape(batch + padded, n)[:batch]
