"""Jitted ladder reading for the ladder_capture / ladder_escape planes.

The reference reads ladders with a recursive Python search around
``AlphaGo/preprocessing/preprocess.py``. Recursion with data-dependent
branching doesn't map to XLA, so the TPU design (SURVEY.md §7 hard part
#2) is:

* **candidate compaction** — only (move, prey-group) pairs satisfying
  the ladder precondition are simulated. ``jnp.nonzero(size=K)``
  compacts them into a fixed ``K`` lanes (static shape; overflow beyond
  ``K`` truncates — real boards have few simultaneous ladders);
* **two-ply lockstep reading** — one ``lax.while_loop`` iteration plays
  a full ladder rung: each chaser option (the prey's two liberties) is
  scored by the *forced escaper response* (extend at the last liberty,
  or counter-capture an adjacent chasing group in atari), and the
  chaser takes the best outcome. This 2-ply evaluation is what makes
  the read exact on standard ladder zigzags, where a 1-ply greedy
  chaser picks the wrong side; it remains an approximation vs the
  oracle's full branching on pathological shapes (tests use positions
  where both agree);
* ko inside the read is ignored (as in the reference's reader);
* **shared, gated chase slots** — the full encoder reads BOTH planes
  through :func:`ladder_planes`: one candidate analysis, slot entry
  gated on a live undecided chase (prey back at exactly 2 liberties
  after the opening), and one pooled rung loop whose lanes mix
  capture (opponent) and escape (own) prey — the chase is
  prey-color-agnostic. See docs/PERFORMANCE.md "Encode path" for the
  gating model and the measured defaults.

All functions are pure and vmap over games.
"""

from __future__ import annotations

from typing import NamedTuple

import os

import jax
import jax.numpy as jnp
from jax import lax

from rocalphago_tpu.engine.jaxgo import (
    GoConfig,
    GoState,
    GroupData,
    _dedup_mask,
    lib_counts_from_labels,
    neighbor_analysis,
    neighbors_for,
    relabel_after_place,
)

# per-option ladder outcomes, ordered so the chaser minimises
_CAPTURED, _CONTINUE, _ESCAPED = 0, 1, 2

def _phase1_depth() -> int:
    """Two-phase chase schedule knob (see _compacted_chase): phase 1
    reads all slots to this many rungs lockstep; still-live lanes
    then finish one at a time at 1/slots the loop width. Most lanes
    settle within a few rungs. MEASURED DEFAULT 2 (the
    ``jaxgo._dense_engine`` discipline): a CPU A/B (CHANGES.md PR 5)
    on dense 19×19 mid-games, batch 16, shared gating —
    depth 2 won both slot sweeps (91.0 pos/s vs 77.1 @ 1 / 81.4 @ 4
    at 4 slots; 73.9 vs 73.3 / 71.3 at the default 6 — within the
    run-to-run ~10% noise there), 8 pays extra lockstep rungs
    whenever any lane runs deep (62.2), and 40 recovers the old
    single-phase fixed-rung read (21.8 — the baseline; CHANGES.md
    PR 5). On the chip: not measured (ROADMAP S1 decides). Read from
    ``$ROCALPHAGO_LADDER_PHASE1`` at TRACE time (same policy as
    ``_ladder_gating``) so A/B sweeps can flip it per run. Floor 1: a while_loop body always runs once for live
    lanes, so a "depth-0" phase 1 would still play a rung and
    over-read by one."""
    return max(1, int(os.environ.get("ROCALPHAGO_LADDER_PHASE1", "2")))


def _ladder_gating() -> str:
    """Which slot-gating formulation :func:`ladder_planes` traces:
    ``"shared"`` (default) pools BOTH planes' gated chase candidates
    into ONE compacted slot set and ONE lockstep rung loop;
    ``"split"`` keeps the legacy per-plane chases (two loops of
    ``chase_slots`` each — the pre-overhaul formulation, kept as the
    A/B baseline). MEASURED DEFAULT: shared wins the CPU A/B (the
    two planes' rung loops merge, so a deep chase pays its trips
    once instead of once per plane — CHANGES.md PR 5; on the chip:
    not measured). Read from
    ``$ROCALPHAGO_LADDER_GATE`` at trace time."""
    v = os.environ.get("ROCALPHAGO_LADDER_GATE", "shared")
    return "split" if v in ("split", "0", "off") else "shared"


def _place(cfg: GoConfig, board, gd: GroupData, action, color):
    """Light move application using the *pre-move* group analysis:
    resolves captures, flags suicide/occupied as invalid (board
    unchanged). Ko is deliberately not tracked."""
    n = cfg.num_points
    nbrs = neighbors_for(cfg.size)
    board_pad = jnp.concatenate([board, jnp.zeros((1,), board.dtype)])
    lab_pad = jnp.concatenate([gd.labels, jnp.full((1,), n, jnp.int32)])
    my_nbrs = nbrs[action]
    nbr_color = board_pad[my_nbrs]
    nbr_root = lab_pad[my_nbrs]
    valid = my_nbrs < n
    uniq = _dedup_mask(nbr_root)

    cap_k = valid & uniq & (nbr_color == -color) & (
        gd.lib_counts[nbr_root] == 1)
    captured = (gd.labels[:, None] == jnp.where(
        cap_k, nbr_root, -2)[None, :]).any(axis=1)

    has_empty = (valid & (nbr_color == 0)).any()
    own_safe = (valid & (nbr_color == color) & (
        gd.lib_counts[nbr_root] >= 2)).any()
    ok = (board[action] == 0) & (has_empty | own_safe | cap_k.any())

    new_board = jnp.where(captured, 0, board).at[action].set(color)
    return jnp.where(ok, new_board, board), ok, captured & ok





def _relabel_place(cfg: GoConfig, board, labels, pt, color, cap_mask,
                   enabled):
    """Incremental group labels after placing ``color`` at ``pt``
    (legality pre-checked by the caller) and removing the captured
    stones ``cap_mask``.

    Exact with ZERO flood fills: ladder reading only ever *adds* one
    stone at a time and removes whole captured groups, and neither
    operation can split a group — so the min-flat-index labeling of
    :func:`jaxgo.compute_labels` is maintained by pure mask algebra:
    the new stone unions its same-color neighbor groups under
    ``min(pt, their roots)`` (the min of a union of min-rooted groups),
    and captured points revert to the empty sentinel ``N``.

    ``enabled=False`` returns the inputs unchanged (vital under vmap:
    disabled lanes must not corrupt their carried analysis).
    """
    labels1 = relabel_after_place(cfg, board, labels, pt, color,
                                  cap_mask)
    board1 = jnp.where(cap_mask, jnp.int8(0), board).at[pt].set(color)
    return (jnp.where(enabled, board1, board),
            jnp.where(enabled, labels1, labels))


def _dilate2d(size: int, m):
    """bool [size, size] → self ∪ 4-neighborhood, via pad + static
    slices (pure vector ops, same trick as ``compute_labels``)."""
    p = jnp.pad(m, 1)
    return (m | p[2:, 1:-1] | p[:-2, 1:-1]
            | p[1:-1, 2:] | p[1:-1, :-2])


def _local_prey_libs(cfg: GoConfig, board, prey_pt):
    """Liberty count of the group at ``prey_pt`` — EXACT, via a local
    connected-component fill (dilate-within-color to fixpoint) instead
    of the whole-board labeling; converges in group-diameter steps
    (4 unrolled per trip). No production call sites remain (the
    ladder_escape opening now uses the incremental relabel +
    loop-free recount); kept as the independent fill-based oracle
    that ``tests/test_features.py`` checks the
    ``_escaper_response_fast`` algebra against."""
    size = cfg.size
    color = board[prey_pt]
    own = (board == color).reshape(size, size)
    seed = jnp.zeros((size, size), jnp.bool_).at[
        prey_pt // size, prey_pt % size].set(color != 0)

    def body(carry):
        mask, _ = carry
        new = mask
        for _ in range(4):
            new = _dilate2d(size, new) & own
        return new | mask, mask

    mask, _ = lax.while_loop(lambda c: (c[0] != c[1]).any(), body,
                             (seed, jnp.zeros_like(seed)))
    libs = _dilate2d(size, mask) & (board == 0).reshape(size, size)
    return jnp.where(color == 0, 0, libs.sum().astype(jnp.int32))


def _escaper_response_full(cfg: GoConfig, b1, prey_pt, prey_color,
                           prey_mask, gd0, c_pt, cap0):
    """Best forced response of a prey left in atari by the chaser's
    move at ``c_pt``: extend at the last liberty, or counter-capture an
    adjacent chasing group in atari. Unlike a recompute-everything
    formulation, this derives the whole 2-ply analysis from the rung's
    single pre-move analysis ``gd0`` — ZERO extra flood fills.

    Exactness (case by case, ``chaser = -prey_color``):

    * the chaser's move cannot change the PREY group's membership
      (it fills a liberty or captures other prey-colored groups), so
      ``prey_mask`` from ``gd0`` is valid on ``b1``;
    * chaser groups touching ``c_pt`` merged into one group ``Gc``;
      its mask/liberties are computed directly;
    * a chaser group adjacent to a stone the chaser's move captured
      (``cap0``) GAINED at least one liberty, so it has ≥2 now and can
      be neither a counter-capture target nor capturable — excluding
      them outright is exact;
    * every other chaser group is untouched, so ``gd0`` lib counts
      hold, and a 1-liberty group's last liberty is any empty point
      adjacent to it;
    * prey-colored groups surviving on ``b1`` are unchanged, so merges
      from an extension are unions of ``gd0`` label masks.

    Returns ``(preyL1, libs_after_best, board_after_best, resp_pt,
    resp_cap, resp_made)`` where ``preyL1`` is the prey's liberty
    count on ``b1`` (callers gate on it); libs_after_best is -1 when
    no legal response exists (then ``resp_made`` is False and the
    board is returned unchanged). ``resp_pt``/``resp_cap`` are the
    chosen response move and the chaser stones it captured — exactly
    the inputs :func:`_relabel_place` needs to carry the incremental
    labeling past the response.
    """
    n = cfg.num_points
    size = cfg.size
    nbrs = neighbors_for(size)
    chaser = -prey_color
    lab_pad0 = jnp.concatenate(
        [gd0.labels, jnp.full((1,), n, jnp.int32)])
    b1_pad = jnp.concatenate([b1, jnp.zeros((1,), b1.dtype)])
    empty1 = b1 == 0

    def dil(mask):
        return _dilate2d(size, mask.reshape(size, size)).reshape(-1)

    dil_prey = dil(prey_mask)
    prey_libs1 = empty1 & dil_prey
    preyL1 = prey_libs1.sum().astype(jnp.int32)
    ext_pt = jnp.argmax(prey_libs1).astype(jnp.int32)

    # the merged chaser group around c_pt
    c_nbr_roots = lab_pad0[nbrs[c_pt]]
    c_nbr_chaser = b1_pad[nbrs[c_pt]] == chaser
    gc_mask = (gd0.labels[:, None] == jnp.where(
        c_nbr_chaser, c_nbr_roots, -2)[None, :]).any(axis=1)
    gc_mask = gc_mask.at[c_pt].set(True)
    gc_pad = jnp.concatenate([gc_mask, jnp.zeros((1,), jnp.bool_)])
    gc_nlibs = (empty1 & dil(gc_mask)).sum()

    # chaser groups that gained a liberty from the chaser-move capture
    gained_pt = (b1 == chaser) & dil(cap0)
    gained_root = jnp.zeros((n + 1,), jnp.bool_).at[gd0.labels].max(
        gained_pt)

    # counter-capture target: first (lowest-index) chaser stone
    # adjacent to the prey whose group is in atari on b1
    adj_prey = (b1 == chaser) & dil(prey_mask)
    atari_pts = adj_prey & jnp.where(
        gc_mask, gc_nlibs == 1,
        (gd0.lib_counts[gd0.labels] == 1) & ~gained_root[gd0.labels])
    have_cap = atari_pts.any()
    target = jnp.argmax(atari_pts).astype(jnp.int32)
    target_mask = jnp.where(gc_mask[target], gc_mask,
                            gd0.labels == gd0.labels[target])
    cap_pt = jnp.argmax(empty1 & dil(target_mask)).astype(jnp.int32)

    def try_move(pt, enabled):
        onehot = jnp.zeros((n,), jnp.bool_).at[pt].set(True)
        pt_nbr_roots = lab_pad0[nbrs[pt]]
        pt_nbr_chaser = b1_pad[nbrs[pt]] == chaser
        pt_nbr_in_gc = gc_pad[nbrs[pt]]
        valid = nbrs[pt] < n
        # chaser groups captured by the response: adjacent, in atari
        # (their last liberty must then be pt itself)
        old_cap_k = (valid & pt_nbr_chaser & ~pt_nbr_in_gc
                     & (gd0.lib_counts[pt_nbr_roots] == 1)
                     & ~gained_root[pt_nbr_roots])
        esc_cap = (gd0.labels[:, None] == jnp.where(
            old_cap_k, pt_nbr_roots, -2)[None, :]).any(axis=1)
        gc_capped = (valid & pt_nbr_chaser & pt_nbr_in_gc).any() \
            & (gc_nlibs == 1)
        esc_cap = esc_cap | (gc_capped & gc_mask)
        # the played stone's cluster: {pt} ∪ surviving own-color
        # neighbor groups. It joins the PREY's component only when pt
        # itself is adjacent to the prey (two distinct same-color
        # groups are never orthogonally adjacent, so a merge partner
        # cannot bridge them) — a counter-capture played away from the
        # prey must not donate its own liberties to the prey's count.
        merge_k = valid & (b1_pad[nbrs[pt]] == prey_color)
        merge_mask = (gd0.labels[:, None] == jnp.where(
            merge_k, pt_nbr_roots, -2)[None, :]).any(axis=1)
        cluster = onehot | merge_mask
        empty2 = (empty1 & ~onehot) | esc_cap
        comp = jnp.where(dil_prey[pt], prey_mask | cluster, prey_mask)
        L2 = (empty2 & dil(comp)).sum().astype(jnp.int32)
        # move legality = the played stone's own group keeps a liberty
        legal = (empty2 & dil(cluster)).any()
        okm = enabled & empty1[pt] & legal
        b2 = jnp.where(esc_cap, jnp.int8(0), b1).at[pt].set(prey_color)
        return (jnp.where(okm, L2, -1), jnp.where(okm, b2, b1),
                esc_cap & okm)

    L1, B1, C1 = try_move(ext_pt, preyL1 >= 1)
    L2, B2, C2 = try_move(cap_pt, have_cap)
    take1 = L1 >= L2
    respL = jnp.where(take1, L1, L2)
    return (preyL1, respL, jnp.where(take1, B1, B2),
            jnp.where(take1, ext_pt, cap_pt),
            jnp.where(take1[None], C1, C2), respL >= 0)


def _escaper_response_fast(cfg: GoConfig, b1, prey_pt, prey_color,
                           prey_mask, gd0, c_pt, cap0):
    """3-tuple view of :func:`_escaper_response_full` —
    ``(preyL1, libs_after_best, board_after_best)``."""
    preyL1, respL, b2, _, _, _ = _escaper_response_full(
        cfg, b1, prey_pt, prey_color, prey_mask, gd0, c_pt, cap0)
    return preyL1, respL, b2


def _foot_mode() -> str:
    """Which footprint expansion :func:`_chase_read_regions` traces:
    ``"tight"`` (default) derives the region from the actual reads of
    the 2-ply algebra (see that function's derivation), ``"wide"``
    keeps the pre-tightening blanket (``dilate²`` of everything plus a
    second group pass over it) as the A/B baseline and safety valve.
    Both are sound over-approximations; wide is strictly larger, so it
    only costs reuse. Read from ``$ROCALPHAGO_LADDER_FOOT`` at trace
    time (same policy as the other ladder knobs). MEASURED: tight cuts
    the footprint-churn re-chase cascade that capped incremental
    encode at ~2.1–2.3× on CPU — CHANGES.md PR 19."""
    v = os.environ.get("ROCALPHAGO_LADDER_FOOT", "tight")
    return "wide" if v in ("wide", "0", "off") else "tight"


def _chase_read_region(cfg: GoConfig, board, labels, core):
    """Sound over-approximation of the board cells a chase's (or an
    opening's) analysis can read, radiating from the accumulated
    ``core`` — the union over plies of the prey's group mask plus
    every cell the simulation played on or captured.

    This is the dependency footprint of the incremental encoder's
    per-lane cache (``features/incremental.py``): a cached opening
    outcome / chase verdict stays valid exactly while no cell of its
    recorded region changes — the standard memoization-with-read-set
    induction (each ply of a re-run read would see only unchanged
    cells, so it makes identical decisions). Crucially it is evaluated
    ONCE per recorded lane against the ENCODE-TIME board — not per
    rung against the simulation boards — which is sound because the
    simulation's own moves are all in ``core``: a group on a simulated
    board is original groups bridged by played cells, so "groups
    touching X on the simulated board" is covered by "groups touching
    ``dilate(X ∪ core)`` on the real board" plus ``core`` itself.

    Derivation of the TIGHT region (default; every read of
    :func:`_place` / :func:`_escaper_response_full` / the rung body is
    accounted for — the wide pre-tightening blanket is kept behind
    ``$ROCALPHAGO_LADDER_FOOT=wide``):

    * ``D2 = dilate²(core)`` — the prey's liberty points are 1 step
      from ``core``, both chaser options and the extension response
      read their own 4-neighborhoods at those points (2 steps), and
      simulated-merge bridging needs no extra step because the
      bridging played cells are themselves in ``core``;
    * ``grp1`` — WHOLE groups with a stone in ``D2``: every group
      whose liberty count, membership or capture the algebra consults
      at the first level (chaser groups at the options, merge
      partners, atari/counter-capture targets) touches the prey or a
      played/option point, i.e. has a stone within ``D2``. Liberty
      counts are group-global, so the whole extent matters, and their
      liberties live in ``dilate(grp1)``;
    * ``R2 = dilate²(grp1)`` — the counter-capture response plays at
      a liberty of a ``grp1`` target (1 step off it) and reads that
      point's own neighborhood (1 more step);
    * ``grp2`` — whole groups with a stone in ``R2 ∪ D2``: the groups
      the counter-capture's legality/merge/capture checks consult
      around its response point, plus (re-)covering the first level;
      their liberty reads live in ``dilate(grp2)``.

    The wide blanket additionally dilates the ENTIRE first ring by two
    (``dilate⁴(core)``) before the second group pass — for a long
    chase path that near-doubles the band around the whole path, which
    is exactly the footprint-churn cascade the incremental encoder
    measured as its limiter. Over-approximation only costs reuse,
    never correctness; tight ⊂ wide by construction."""
    return _chase_read_regions(cfg, board, labels, core[None, :])[0]


def _chase_read_regions(cfg: GoConfig, board, labels, cores):
    """Batched :func:`_chase_read_region`: ``cores`` bool [W, N] →
    footprints bool [W, N], all lanes against the one shared board.

    This runs on EVERY recording ply of the incremental encoder, so
    it is written for CPU op-dispatch cost, not elegance: the 2-D
    dilations are batched pad+slice shifts (no vmap), and the
    whole-group reads ("any core cell in group ρ?") are ONE f32
    matmul against the label one-hot table instead of a vmapped
    scatter-max per lane — bitwise the same result (distinct labels
    hit distinct columns; > 0 recovers the OR), an order of magnitude
    fewer op dispatches."""
    n = cfg.num_points
    size = cfg.size
    w = cores.shape[0]

    def dilate(m, k):
        m2 = m.reshape(w, size, size)
        for _ in range(k):
            p = jnp.pad(m2, ((0, 0), (1, 1), (1, 1)))
            m2 = (m2 | p[:, 2:, 1:-1] | p[:, :-2, 1:-1]
                  | p[:, 1:-1, 2:] | p[:, 1:-1, :-2])
        return m2.reshape(w, n)

    stones = board != 0
    # [N, N+1] one-hot of each stone's group root (empty cells hit the
    # sentinel column n, which no real read consults)
    label_oh = (jnp.where(stones, labels, n)[:, None]
                == jnp.arange(n + 1)[None, :]).astype(jnp.float32)

    def groups_touching(region):
        touched = (region & stones[None, :]).astype(jnp.float32) \
            @ label_oh                                   # [W, N+1]
        return (jnp.take(touched, labels, axis=1) > 0.5) \
            & stones[None, :]

    region = dilate(cores, 2)
    grp1 = groups_touching(region)
    if _foot_mode() == "wide":
        ring = dilate(region | grp1, 2)
        grp2 = groups_touching(ring)
        return ring | grp2 | dilate(grp2, 1)
    ring = dilate(grp1, 2)                  # counter-capture ring
    grp2 = groups_touching(ring | region)
    return region | grp1 | ring | grp2 | dilate(grp2, 1)


def _chase(cfg: GoConfig, board0, labels0, prey_pt, depth: int,
           enabled=True, return_state: bool = False,
           collect_core: bool = False, core0=None):
    """Chaser to move against a two-liberty prey; True if prey is
    ladder-captured. Each iteration = one full rung (chaser move +
    forced escaper response).

    ``return_state=True`` additionally returns ``(unresolved, board,
    labels)`` — the lanes that hit the ``depth`` cap mid-chase and
    the position they stopped at. The chase state is fully (board,
    labels, prey_pt), so a capped chase RESUMES exactly by calling
    :func:`_chase` again on the returned position with the remaining
    depth (the two-phase schedule in :func:`_compacted_chase`).

    ZERO flood fills anywhere in the loop: the caller seeds the
    group labeling (``labels0``, from the plane-level analysis plus
    :func:`_relabel_place` for the opening moves) and each rung
    carries it forward with the same incremental relabeling — sound
    because a chase only adds single stones and removes whole captured
    groups, neither of which can split a group. Liberty counts are
    recomputed loop-free from the labels (:func:`jaxgo.lib_counts_from_labels`).
    Previous designs refilled the whole board once (originally seven
    times) per rung; under vmap every lane/game stalls on the slowest
    lane's fill, which made ladders ~99% of the 48-plane encode.

    ``enabled=False`` starts the loop already done — vital under
    ``vmap`` over candidate lanes, where the while_loop runs until
    EVERY lane converges: without the gate, empty/garbage lanes chase
    to full ``depth`` on every call, making typical positions pay the
    worst case.

    ``collect_core=True`` additionally accumulates the chase's read
    CORE (bool [N]; seeded from ``core0``): the union over rungs of
    the prey's group mask plus every cell the rung changed (played
    stones and captures) — pure ORs of masks each rung computes
    anyway, so collection is ~free. The caller expands the final core
    ONCE with :func:`_chase_read_region` into the dependency footprint
    the incremental encoder's verdict cache invalidates on (see that
    function's soundness note for why a single end-of-chase expansion
    against the encode-time board covers every rung's reads). Appended
    to the return tuple (``captured, core`` / ``captured, unresolved,
    board, labels, core``)."""
    n = cfg.num_points
    nbrs = neighbors_for(cfg.size)
    prey_color = board0[prey_pt].astype(jnp.int8)

    class Carry(NamedTuple):
        board: jax.Array
        labels: jax.Array
        done: jax.Array
        captured: jax.Array
        rung: jax.Array
        settled: jax.Array      # done by OUTCOME (vs the depth cap)
        core: jax.Array         # bool [N] accumulated read core
        #   (all-False and never updated unless collect_core)

    def option_outcome(board, gd, prey_mask, lib_pt, enabled):
        """Chaser fills ``lib_pt``; returns (outcome, relabeling
        inputs for both plies). Pure mask algebra — no fills."""
        b1, ok, cap0 = _place(cfg, board, gd, lib_pt, -prey_color)
        preyL, respL, _, resp_pt, resp_cap, resp_made = \
            _escaper_response_full(cfg, b1, prey_pt, prey_color,
                                   prey_mask, gd, lib_pt, cap0)
        resp_logic = jnp.where(
            respL <= 1, _CAPTURED,
            jnp.where(respL >= 3, _ESCAPED, _CONTINUE))
        # an option only matters if it's a legal move that keeps atari
        outcome = jnp.where(enabled & ok & (preyL == 1),
                            resp_logic, _ESCAPED)
        return outcome, (lib_pt, cap0, resp_pt, resp_cap, resp_made)

    def body(c: Carry) -> Carry:
        board, labels = c.board, c.labels
        lib_counts = lib_counts_from_labels(cfg, board, labels)
        gd = GroupData(labels, None, lib_counts, None, None)
        lab_pad = jnp.concatenate(
            [labels, jnp.full((1,), n, jnp.int32)])
        root = labels[prey_pt]
        prey_alive = board[prey_pt] == prey_color
        L = jnp.where(prey_alive, lib_counts[root], 0)
        prey_mask = labels == root
        empty = board == 0
        lib_pts = empty & (lab_pad[nbrs] == root).any(axis=1)
        l1 = jnp.argmax(lib_pts).astype(jnp.int32)
        l2 = jnp.argmax(lib_pts & (jnp.arange(n) != l1)).astype(jnp.int32)

        o1, u1 = option_outcome(board, gd, prey_mask, l1, L == 2)
        o2, u2 = option_outcome(board, gd, prey_mask, l2, L == 2)
        pick1 = o1 <= o2
        o = jnp.where(pick1, o1, o2)
        c_pt, cap0, resp_pt, resp_cap, resp_made = jax.tree.map(
            lambda a, b: jnp.where(pick1, a, b), u1, u2)

        # prey already captured / in atari / safe before we move
        pre = jnp.where(
            ~prey_alive, _CAPTURED,
            jnp.where(L >= 3, _ESCAPED,
                      jnp.where(L == 1, _CAPTURED, -1)))
        o = jnp.where(pre >= 0, pre, o)
        # ~done: a lane stopped by the depth cap must FREEZE — its
        # exit board is the phase-2 resume point (return_state), so
        # free extra plies here would double-count reading depth and
        # could settle an outcome the frozen `captured` never sees
        advance = (pre < 0) & (o == _CONTINUE) & ~c.done

        board1, labels1 = _relabel_place(
            cfg, board, labels, c_pt, -prey_color, cap0, advance)
        board2, labels2 = _relabel_place(
            cfg, board1, labels1, resp_pt, prey_color, resp_cap,
            advance & resp_made)

        core = c.core
        if collect_core:
            # this rung's reads radiate from the prey (masked to
            # stones — a dead prey's sentinel root would select every
            # empty cell; the rung then stops on prey_pt alone) and
            # from the cells it changed (played stones + captures =
            # the rung's board diff)
            add = ((prey_mask & (board != 0))
                   | (jnp.arange(n) == prey_pt)
                   | (board2 != board))
            core = jnp.where(~c.done, core | add, core)

        out_of_depth = c.rung + 1 >= depth
        return Carry(
            board=board2,
            labels=labels2,
            done=c.done | (o != _CONTINUE) | out_of_depth,
            captured=jnp.where(c.done, c.captured, o == _CAPTURED),
            rung=c.rung + 1,
            settled=c.settled | (~c.done & (o != _CONTINUE)),
            core=core,
        )

    core_init = (jnp.zeros((n,), jnp.bool_) if core0 is None
                 else jnp.asarray(core0))
    init = Carry(board0, labels0, ~jnp.asarray(enabled, jnp.bool_),
                 jnp.bool_(False), jnp.int32(0),
                 ~jnp.asarray(enabled, jnp.bool_), core_init)
    final = lax.while_loop(lambda c: ~c.done, body, init)
    captured = final.captured & jnp.asarray(enabled, jnp.bool_)
    if not return_state:
        return (captured, final.core) if collect_core else captured
    unresolved = ~final.settled & jnp.asarray(enabled, jnp.bool_)
    if collect_core:
        return captured, unresolved, final.board, final.labels, \
            final.core
    return captured, unresolved, final.board, final.labels


def _compacted_chase(cfg: GoConfig, boards, labels, prey_pts,
                     need_chase, depth: int, slots: int):
    """Run the chase for the lanes flagged ``need_chase``, first
    compacted into ``slots`` slots (bool [K] → results bool [K]).

    After the opening filter, typically 0–2 of the K candidate lanes
    actually need a chase; compacting them means the expensive rung
    loop runs ``slots`` wide instead of ``K`` wide (the loop's
    per-trip cost is proportional to its width, and under the
    encoder's vmap every board pays every trip). Overflow beyond
    ``slots`` truncates — the same bounded-capacity contract as
    ``_candidate_lanes``; callers must map uncovered lanes to the
    conservative plane value. Lanes may mix prey colors (the pooled
    capture+escape set from :func:`ladder_planes`): the chase reads
    each lane's prey color from its board. Returns ``(captured [K],
    covered [K])`` where ``covered`` marks lanes whose chase actually
    ran."""
    k = need_chase.shape[0]
    slot_idx = _compact_indices(need_chase, slots, k)
    valid = slot_idx < k
    safe = jnp.where(valid, slot_idx, 0)
    if os.environ.get("ROCALPHAGO_DEBUG_LADDER_OVERFLOW") == "1":
        # runtime signal for the silent truncation contract (advisor
        # r2): flag positions whose live chases exceed capacity so a
        # user encoding dense ladder problems knows to raise
        # ``ladder_chase_slots``. Trace-time opt-in — zero cost off.
        # host-side condition: under the encoder's vmap a lax.cond
        # lowers to both-branches select, which would print for every
        # board; the callback sees each board's own count instead
        def _warn(c):
            if int(c) > slots:
                print(f"ladders: {int(c)} live chases > {slots} "
                      "chase slots — truncating (raise "
                      "ladder_chase_slots)")

        jax.debug.callback(_warn, need_chase.sum())
    # TWO-PHASE schedule. The vmapped while_loop locksteps every lane
    # of every board in the batch: ONE deep chase anywhere makes all
    # B×slots lanes pay its full trip count through the expensive
    # two-ply body. Measured on random 19×19 mid-games, typical lanes
    # settle in ≤9 rungs while a stray lane runs to the 40 cap — so
    # phase 1 reads everyone to a short cap lockstep, then the
    # still-live lanes finish ONE AT A TIME as scalar chases (resume
    # is exact: the chase state is (board, labels, prey_pt)). Each
    # scalar loop runs at 1/slots the width, and a loop whose lane
    # doesn't exist exits in zero trips — so typical boards pay
    # nothing for the tail, EVERY slotted lane is still read to full
    # depth (the slots-restore-exactness contract), and the worst case
    # (all slots deep) costs what the single lockstep loop did.
    d1 = min(_phase1_depth(), depth)
    prey = prey_pts[safe]
    captured, unres, b_end, lab_end = jax.vmap(
        lambda b, l, p, v: _chase(cfg, b, l, p, d1, enabled=v,
                                  return_state=True))(
            boards[safe], labels[safe], prey, valid)
    if depth > d1:
        deep_idx = _compact_indices(unres, slots, slots)
        for s in range(slots):
            idx = deep_idx[s]
            live = idx < slots
            at = jnp.where(live, idx, 0)
            cap_s = _chase(cfg, b_end[at], lab_end[at], prey[at],
                           depth - d1, enabled=live)
            captured = captured.at[idx].set(cap_s, mode="drop")
    scatter = jnp.zeros((k,), jnp.bool_)
    return (scatter.at[slot_idx].set(captured & valid, mode="drop"),
            scatter.at[slot_idx].set(valid, mode="drop"))


def _compact_indices(mask, size: int, fill_value):
    """First ``size`` set indices of a 1-D bool mask, ascending,
    padded with ``fill_value`` — the shared compaction primitive of
    the candidate/slot machinery (here and the incremental refresh
    scheduler).

    Kept as ``jnp.nonzero(size=..., fill_value=...)`` BY MEASUREMENT:
    a scatter-free rewrite (log-depth ``associative_scan`` ranks +
    per-slot argmax gather over the ``[size, N]`` rank-match matrix)
    looked faster in profiler traces of the warm no-churn floor, but
    regressed the real 19x19 trajectory benchmark from ~2.5 ms to
    ~4.3 ms per position — XLA:CPU's sized-nonzero lowering beats the
    dense comparison matrix once chases actually run. Trace spans
    overweight the serial while-loops; trust the wall-clock bench
    (docs/PERFORMANCE.md "Incremental encode")."""
    return jnp.nonzero(mask, size=size,
                       fill_value=fill_value)[0].astype(jnp.int32)


def _candidate_lanes(cfg: GoConfig, state: GoState, gd: GroupData,
                     legal, prey_libs: int, prey_is_opp: bool,
                     lanes: int, analysis=None):
    """Compact (move, prey) pairs matching the precondition into K
    lanes. Returns (move_pt [K], prey_pt [K], valid [K]).

    This is the first gating stage (docs/PERFORMANCE.md "Encode
    path"): only strings at the exact ladder precondition — opponent
    strings at 2 liberties (capture) or own strings in atari (escape)
    — generate lanes at all. EXACT by the planes' definitions: a
    ladder capture starts by filling one of a 2-liberty group's
    liberties (a 1-liberty group is a plain capture, ≥3 can't be
    laddered this ply), and a ladder escape extends an atari group at
    its last liberty. Pass ``analysis`` (a
    :func:`jaxgo.neighbor_analysis` result) to share one neighbor
    lookup between both planes' enumerations."""
    n = cfg.num_points
    nbrs = neighbors_for(cfg.size)
    if analysis is None:
        analysis = neighbor_analysis(cfg, state.board, gd.labels)
    nbr_color, nbr_root, uniq, _ = analysis

    want = -state.turn if prey_is_opp else state.turn
    cand = (legal[:, None] & uniq & (nbr_color == want)
            & (gd.lib_counts[nbr_root] == prey_libs))   # [N, 4]
    flat_idx = _compact_indices(cand.reshape(-1), lanes, 4 * n)
    valid = flat_idx < 4 * n
    safe = jnp.where(valid, flat_idx, 0)
    move_pt = (safe // 4).astype(jnp.int32)
    prey_pt = nbrs[move_pt, safe % 4]
    return move_pt, prey_pt, valid


def _capture_opening(cfg: GoConfig, state: GoState, gd: GroupData,
                     move_pt, prey_pt, valid):
    """Vmapped capture opening over the candidate lanes: play the
    chaser's first move, score the prey's forced response, and carry
    the incremental labeling through both plies. Returns ``(boards
    [K,N], labels [K,N], need_chase [K], direct [K])`` — the second
    gating stage: ONLY lanes whose response leaves the prey back at
    exactly 2 liberties (``respL == 2`` — a live, undecided chase)
    enter the chase slots. Exact: ``respL <= 1`` is a capture decided
    with no chase (``direct``), ``respL >= 3`` is a clean escape, and
    both are classified here without consuming a slot."""
    me = state.turn

    def opening(mv, pr, ok):
        board1, placed, cap0 = _place(cfg, state.board, gd, mv, me)
        # prey is now in atari; its forced response decides the
        # opening — derived from the plane-level gd, no refill
        prey_mask = gd.labels == gd.labels[pr]
        _, respL, _, resp_pt, resp_cap, resp_made = \
            _escaper_response_full(
                cfg, board1, pr, -me, prey_mask, gd, mv, cap0)
        need_chase = ok & placed & (respL == 2)
        # carry the incremental labeling through both opening plies so
        # the chase starts with a valid analysis and never refills
        b1r, lab1 = _relabel_place(
            cfg, state.board, gd.labels, mv, me, cap0, ok & placed)
        b2r, lab2 = _relabel_place(
            cfg, b1r, lab1, resp_pt, -me, resp_cap,
            need_chase & resp_made)
        direct = ok & placed & (respL <= 1)   # captured with no chase
        return b2r, lab2, need_chase, direct

    return jax.vmap(opening)(move_pt, prey_pt, valid)


def _escape_opening(cfg: GoConfig, state: GoState, gd: GroupData,
                    move_pt, prey_pt, valid):
    """Vmapped escape opening: extend the atari group at its last
    liberty and recount. Second gating stage for the escape plane:
    only extensions that land on exactly 2 liberties (``L == 2`` — an
    undecided ladder) enter the chase slots; ``L >= 3`` is a decided
    escape (``direct``), ``L <= 1`` a decided failure — both
    classified slot-free."""
    me = state.turn

    def opening(mv, pr, ok):
        board1, placed, cap0 = _place(cfg, state.board, gd, mv, me)
        # own extension may merge groups — the incremental relabel
        # handles the merge exactly, and the loop-free liberty recount
        # replaces the old per-lane local fill
        b1r, lab1 = _relabel_place(
            cfg, state.board, gd.labels, mv, me, cap0, ok & placed)
        libs1 = lib_counts_from_labels(cfg, b1r, lab1)
        L = jnp.where(b1r[pr] == me, libs1[lab1[pr]], 0)
        need_chase = ok & placed & (L == 2)
        direct = ok & placed & (L >= 3)       # escaped with no chase
        return b1r, lab1, need_chase, direct

    return jax.vmap(opening)(move_pt, prey_pt, valid)


def ladder_capture_plane(cfg: GoConfig, state: GoState, gd: GroupData,
                         legal, depth: int = 40, lanes: int = 16,
                         chase_slots: int = 6) -> jax.Array:
    """bool [N]: legal moves that ladder-capture an adjacent two-liberty
    opponent group. Single-plane entry point (tests, one-plane
    encodes); the full encoder computes both planes through
    :func:`ladder_planes`, which shares the candidate analysis and the
    chase between them."""
    n = cfg.num_points
    move_pt, prey_pt, valid = _candidate_lanes(
        cfg, state, gd, legal, prey_libs=2, prey_is_opp=True, lanes=lanes)
    b2r, lab2, need_chase, direct = _capture_opening(
        cfg, state, gd, move_pt, prey_pt, valid)
    chased, _ = _compacted_chase(cfg, b2r, lab2, prey_pt, need_chase,
                                 depth, chase_slots)
    captured = direct | (need_chase & chased)
    return jnp.zeros((n,), jnp.bool_).at[move_pt].max(captured & valid)


def ladder_escape_plane(cfg: GoConfig, state: GoState, gd: GroupData,
                        legal, depth: int = 40, lanes: int = 16,
                        chase_slots: int = 6) -> jax.Array:
    """bool [N]: legal moves that rescue an own group in atari from a
    ladder (extension at its last liberty that survives the read).
    Single-plane entry point — see :func:`ladder_capture_plane`."""
    n = cfg.num_points
    move_pt, prey_pt, valid = _candidate_lanes(
        cfg, state, gd, legal, prey_libs=1, prey_is_opp=False, lanes=lanes)
    b1r, lab1, need_chase, direct = _escape_opening(
        cfg, state, gd, move_pt, prey_pt, valid)
    chased, covered = _compacted_chase(cfg, b1r, lab1, prey_pt,
                                       need_chase, depth, chase_slots)
    # overflow lanes (chase needed but no slot) must stay conservative
    # False — an unread escape is not asserted
    escaped = direct | (need_chase & covered & ~chased)
    return jnp.zeros((n,), jnp.bool_).at[move_pt].max(escaped & valid)


def ladder_planes(cfg: GoConfig, state: GoState, gd: GroupData,
                  legal, depth: int = 40, lanes: int = 16,
                  chase_slots: int = 6):
    """Both ladder planes from ONE shared read:
    ``(ladder_capture [N], ladder_escape [N])``.

    The encode-path overhaul (docs/PERFORMANCE.md "Encode path").
    Ladder work scales with the number of GENUINELY CHASEABLE strings,
    not with the board, via three gates and one shared loop:

    1. **candidate gating** (:func:`_candidate_lanes`) — only strings
       at the ladder precondition (opponent strings at 2 liberties /
       own strings in atari) generate lanes; one
       :func:`jaxgo.neighbor_analysis` serves both planes. Exact by
       definition of the planes.
    2. **slot gating** (the openings) — a lane consumes a chase slot
       ONLY when its opening leaves a live, undecided chase (prey back
       at exactly 2 liberties). Decided openings (direct capture,
       clean escape, illegal move) are classified slot-free — exact,
       because a prey at ≤1 liberties after the forced response is
       captured outright and one at ≥3 can no longer be laddered by
       the 2-ply reader.
    3. **shared chase slots** — both planes' surviving candidates are
       pooled into ONE ``chase_slots``-wide compacted chase (the chase
       is prey-color-agnostic: :func:`_chase` reads the prey's color
       from its board, so capture lanes — opponent prey — and escape
       lanes — own prey — share lanes of the same ``lax.while_loop``).
       One lockstep rung loop + one scalar deep tail replace the two
       per-plane loops, so a deep ladder pays its trips once, not once
       per plane. The loop EXITS EARLY the trip every pooled chase has
       resolved (``_chase``'s ``done`` reduction — with zero live
       chases it runs zero trips).

    Truncation contract: capacity is SHARED — capture candidates fill
    slots first (compaction order), escape candidates take what's
    left; overflow beyond ``chase_slots`` reads the conservative False
    on both planes (never a spurious capture or escape). With slots ≥
    live chases the pooled read is BIT-IDENTICAL to the split
    formulation (tests/test_features.py::TestSharedGating).

    ``$ROCALPHAGO_LADDER_GATE=split`` traces the legacy per-plane
    formulation instead (two independent ``chase_slots``-wide chases)
    — the A/B baseline and the reference of ``TestSharedGating``.
    """
    n = cfg.num_points
    analysis = neighbor_analysis(cfg, state.board, gd.labels)
    cap_mv, cap_pr, cap_ok = _candidate_lanes(
        cfg, state, gd, legal, prey_libs=2, prey_is_opp=True,
        lanes=lanes, analysis=analysis)
    esc_mv, esc_pr, esc_ok = _candidate_lanes(
        cfg, state, gd, legal, prey_libs=1, prey_is_opp=False,
        lanes=lanes, analysis=analysis)
    cap_b, cap_l, cap_need, cap_direct = _capture_opening(
        cfg, state, gd, cap_mv, cap_pr, cap_ok)
    esc_b, esc_l, esc_need, esc_direct = _escape_opening(
        cfg, state, gd, esc_mv, esc_pr, esc_ok)

    if _ladder_gating() == "split":
        # legacy baseline: two independent chases, chase_slots each
        cap_chased, _ = _compacted_chase(
            cfg, cap_b, cap_l, cap_pr, cap_need, depth, chase_slots)
        esc_chased, esc_cov = _compacted_chase(
            cfg, esc_b, esc_l, esc_pr, esc_need, depth, chase_slots)
    else:
        chased, covered = _compacted_chase(
            cfg, jnp.concatenate([cap_b, esc_b]),
            jnp.concatenate([cap_l, esc_l]),
            jnp.concatenate([cap_pr, esc_pr]),
            jnp.concatenate([cap_need, esc_need]), depth, chase_slots)
        cap_chased, esc_chased = chased[:lanes], chased[lanes:]
        esc_cov = covered[lanes:]

    captured = cap_direct | (cap_need & cap_chased)
    # overflow lanes (chase needed but no slot) stay conservative
    # False on both planes — an unread chase asserts nothing
    escaped = esc_direct | (esc_need & esc_cov & ~esc_chased)
    return (jnp.zeros((n,), jnp.bool_).at[cap_mv].max(
                captured & cap_ok),
            jnp.zeros((n,), jnp.bool_).at[esc_mv].max(
                escaped & esc_ok))
