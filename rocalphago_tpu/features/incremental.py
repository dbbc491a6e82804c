"""Incremental 48-plane encoding: update from the move delta.

Self-play and MCTS visit SUCCESSIVE positions, so almost all of each
48-plane tensor's expensive analysis is unchanged ply-to-ply — yet the
from-scratch encoder re-reads every ladder every time, and the ladder
work (candidate openings + chases) dominates sequential encode cost
(CPU profiles, CHANGES.md PR 5/6). This module is
the delta path: an :class:`EncodeCache` carried through the sequential
hot loops (a jit-compatible pytree) and an :func:`encode_step` that
recomputes only what a move can change:

* the cheap planes (board/liberties/turns-since aging, the
  candidate-simulation planes — all loop-free vector work over the
  played point, captured strings and the liberty frontier of adjacent
  strings) ride the exact same :func:`planes.encode_analysis` +
  :func:`planes.assemble_planes` code as the from-scratch path; on
  CPU their cost is op-dispatch-bound, so "recompute the dense vector
  pass" IS the cheapest correct delta (masking a vector op saves
  nothing — see docs/PERFORMANCE.md "Incremental encode");
* the two LADDER planes — the cost center — ride a per-lane outcome
  cache: every candidate lane's OPENING verdict (live chase needed /
  decided directly) and, when a pooled chase ran, its chase VERDICT
  are recorded together with one read FOOTPRINT (the chase's
  accumulated core expanded once by
  :func:`ladders._chase_read_region`) AND the record-time board. A
  cached outcome is consulted exactly while the CURRENT board matches
  the entry's recorded board on every footprint cell — a stone only
  flips a distant ladder if it lands on that ladder's recorded read
  region (the footprint rule). Unrelated stone churn therefore never
  KILLS an entry: the per-ply test is two-tier — a coarse per-board-
  region bitmask key (``REGION_BLOCK``² cell blocks packed into one
  uint32) cheaply clears entries whose footprint regions saw no churn
  at all, and only region-suspect entries pay the cell-exact
  comparison against their recorded board. An entry that fails the
  cell test goes DORMANT rather than dying — it revives the ply the
  board drifts back to its recorded footprint state (common around
  short capture/recapture exchanges), because the comparison is
  absolute, not a one-ply delta.

On the single-state sequential path (GTP root advance, ``Preprocess``
``advance``, ``bench_encode --trajectory``) the expensive blocks sit
behind ``lax.switch``/``lax.cond``, so a fully-warm ply pays only the
vector floor plus the candidate scan: openings run compacted to
``refresh_slots`` lanes only for lanes whose cache entry is missing or
invalidated (with a full-width fallback when more than
``refresh_slots`` lanes are dirty at once — correctness never depends
on the compaction), and the pooled chase plus footprint expansion run
only when some slotted lane lacks a valid verdict. Under ``vmap``
(:func:`batched_delta_encoder`) those conds lower to selects that
execute both branches, so the batched carry passes ``refresh_slots=0``
— openings always run full-width (same vector cost as the from-scratch
read) and the win is the verdict reuse itself, which cuts the
batch-lockstep rung-loop trips that dominate batched encode.

BIT-IDENTITY CONTRACT: ``encode_step`` produces exactly the planes of
``planes.encode`` at every ply, warm or cold — the delta path must
never be "approximately" right. The mechanism: candidate enumeration,
slot assignment and overflow truncation are recomputed fresh each ply
by the SAME code as the from-scratch shared-gated read, so the read's
COVERAGE is identical; a cached opening outcome / chase verdict is
only consulted where the memoization induction proves it equal to the
fresh computation (no footprint cell changed ⇒ each ply of a re-run
read sees only unchanged cells ⇒ identical decisions). Pinned by
``tests/test_incremental.py``: trajectory fuzz (multi-stone captures,
ko, edge/corner ladders, passes) asserting bit-identity against the
from-scratch ``Preprocess`` at every ply with the ``pyfeatures``
oracle as the independent check.

The cached read always traces the default SHARED chase formulation;
the ``ROCALPHAGO_LADDER_GATE=split`` A/B knob applies to the
from-scratch path only.

COLD / INVALIDATED caches are not an error path: a cold cache simply
has no valid entries, so every lane refreshes and every live chase
runs (and records), which IS the from-scratch shared read plus
footprint bookkeeping. Host boundaries (``Preprocess.advance``, the
GTP root advance) still reset the cache explicitly on new games /
rewinds / board switches — see ``features/api.py`` — and count the
reason (``encode_cache_resets_total{reason=...}``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from rocalphago_tpu.engine.jaxgo import (
    GoConfig,
    GoState,
    neighbor_analysis,
    step,
)
from rocalphago_tpu.features.ladders import (
    _candidate_lanes,
    _compact_indices,
    _capture_opening,
    _chase,
    _chase_read_regions,
    _escape_opening,
    _phase1_depth,
)
from rocalphago_tpu.features.planes import (
    assemble_planes,
    encode_analysis,
)
from rocalphago_tpu.obs import scopes

#: default outcome-ring capacity. Ring retention must comfortably
#: exceed the reuse distance or the cache sits in an eviction-forced
#: refresh equilibrium (measured on dense 19×19 random tails: a
#: 48-entry ring rotated itself dry and refreshes pinned at the
#: record width; 128 leaves invalidation, not eviction, as the
#: limiting factor). [V, N] bools are small (46 KB at 19×19).
VERDICT_SLOTS = 128

#: how many dirty CAPTURE / ESCAPE lanes one encode refreshes
#: compacted (and records). More lanes than this dirty at once falls
#: back to that kind's full-width opening pass — correctness never
#: depends on the compaction. Segregated by kind so each opening
#: algebra runs once at its own width instead of both running over
#: one mixed set. MEASURED DEFAULT (8, 4): the 19×19 random-tail A/B
#: on CPU (CHANGES.md PR 6) ran ~2350 µs/pos at (8, 4) vs
#: ~2600 at (12, 6) and ~2500 at (4, 2) — wide enough that full-width
#: fallbacks stay rare (13 in a 100-ply dense tail), narrow enough
#: that the per-ply record/expansion work stops paying for idle lanes.
REFRESH_SLOTS = (8, 4)

# stats vector layout (int32 [9], accumulated on device; host
# boundaries snapshot it into the obs registry — see features/api.py,
# which iterates STAT_FIELDS generically, so new fields flow straight
# to ``encode_incr_<field>_total`` counters). The last three are the
# invalidation-cascade view: ``foot_hits`` counts region-coarse key
# hits (entries whose footprint REGIONS saw churn and paid the
# cell-exact test), ``entries_invalidated`` the subset that actually
# failed it and went dormant, ``verdict_flips`` the chases forced by
# a dormant entry's cached verdict (re-chases of known ladders — the
# cascade's cost), and ``entries_revived`` dormant entries whose
# footprint drifted back to its recorded state.
(STAT_ENCODES, STAT_REFRESHED, STAT_CHASES, STAT_REUSED,
 STAT_INVALIDATED, STAT_FALLBACKS, STAT_FOOT_HITS, STAT_FLIPS,
 STAT_REVIVED) = range(9)
STAT_FIELDS = ("encodes", "lanes_refreshed", "chases_run",
               "verdicts_reused", "entries_invalidated",
               "refresh_fallbacks", "foot_hits", "verdict_flips",
               "entries_revived")

#: side length of the square cell blocks the coarse footprint keys
#: quantize the board into. One uint32 bit per block: 4 → 25 regions
#: at 19×19 (the bitmask folds mod 32 on boards that would exceed 32
#: regions — still sound, just coarser).
REGION_BLOCK = 4


def _region_ids(cfg: GoConfig):
    """int32 [N]: each cell's coarse-region bit position (< 32)."""
    size = cfg.size
    per_row = -(-size // REGION_BLOCK)
    flat = jnp.arange(cfg.num_points)
    rid = ((flat // size) // REGION_BLOCK) * per_row \
        + (flat % size) // REGION_BLOCK
    return rid % 32


def _region_bits(cfg: GoConfig, cells):
    """Pack a cell mask (bool [..., N]) into its coarse-region
    bitmask (uint32 [...]): bit r set iff any cell of region r is
    set. Two footprints can interact only if their bitmasks AND —
    the cheap first tier of the invalidation test."""
    onehot = _region_ids(cfg)[:, None] == jnp.arange(32)[None, :]
    hit = (cells[..., :, None] & onehot).any(axis=-2)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    # regions are distinct bits, so the sum IS the bitwise OR
    return (hit * weights).sum(axis=-1, dtype=jnp.uint32)


def enabled(default: bool) -> bool:
    """Resolve the one incremental-encode knob,
    ``ROCALPHAGO_ENCODE_INCR``: unset → the calling path's measured
    default (sequential single-state paths pass True, the batched
    self-play loop passes False — see
    ``selfplay.incremental_default``), ``"1"``/``"0"`` → force
    on/off everywhere (the bench A/B lever). Read at trace/build
    time, like the ladder knobs."""
    import os

    v = os.environ.get("ROCALPHAGO_ENCODE_INCR", "")
    if v == "":
        return default
    return v == "1"


class EncodeCache(NamedTuple):
    """Delta-encode carry: the previous board + the per-lane ladder
    outcome ring with the dependency metadata needed to invalidate it.

    All arrays are fixed-shape (``N = size²``, ``V = ring capacity``);
    the cache is a pytree — vmap it over games for the batched
    self-play carry (:func:`init_caches`). An entry is keyed by the
    lane identity ``(move, prey root, prey color, lane kind)`` and
    holds the opening outcome (``need``/``direct``), the pooled-chase
    verdict when one ran (``verdict`` valid iff ``has_verdict``), and
    the dependency guard: the read footprint, its coarse-region
    bitmask key, and the record-time board the footprint cells are
    revalidated against (an entry is CONSULTED while the current
    board matches ``entry_board`` on every ``entry_foot`` cell — a
    mismatched entry is dormant, not dead, and revives if the board
    drifts back)."""

    board: jax.Array            # int8 [N]  board at the last encode
    entry_key: jax.Array        # int32 [V] packed lane key: move |
    #   prey_root << 10 | (prey_color + 1) << 20 | kind << 22
    #   (-1 = never written; packed keys are always >= 0)
    entry_need: jax.Array       # bool [V]  opening → live chase needed
    entry_direct: jax.Array     # bool [V]  opening → decided directly
    entry_verdict: jax.Array    # bool [V]  chase verdict (captured)
    entry_has_verdict: jax.Array  # bool [V]
    entry_valid: jax.Array      # bool [V]  slot written & not superseded
    entry_foot: jax.Array       # bool [V, N] recorded read footprint
    entry_board: jax.Array      # int8 [V, N] board at record time —
    #   only its entry_foot cells are ever consulted
    entry_footmask: jax.Array   # uint32 [V] coarse-region key of foot
    entry_clean: jax.Array      # bool [V] footprint regions unchurned
    #   since the last passing cell test (clean ⇒ board matches
    #   entry_board on entry_foot — the cell test is skipped)
    entry_live: jax.Array       # bool [V] last ply's consult verdict
    #   (valid & cell-test pass) — transition bookkeeping for the
    #   invalidated/revived stats
    ptr: jax.Array              # int32 []  ring write pointer
    stats: jax.Array            # int32 [9] see STAT_FIELDS


def init_cache(cfg: GoConfig,
               verdict_slots: int = VERDICT_SLOTS) -> EncodeCache:
    """A cold cache: no valid entries, empty previous board (which is
    also exactly right for a fresh game)."""
    n = cfg.num_points
    v = verdict_slots
    return EncodeCache(
        board=jnp.zeros((n,), jnp.int8),
        entry_key=jnp.full((v,), -1, jnp.int32),
        entry_need=jnp.zeros((v,), jnp.bool_),
        entry_direct=jnp.zeros((v,), jnp.bool_),
        entry_verdict=jnp.zeros((v,), jnp.bool_),
        entry_has_verdict=jnp.zeros((v,), jnp.bool_),
        entry_valid=jnp.zeros((v,), jnp.bool_),
        entry_foot=jnp.zeros((v, n), jnp.bool_),
        entry_board=jnp.zeros((v, n), jnp.int8),
        entry_footmask=jnp.zeros((v,), jnp.uint32),
        entry_clean=jnp.zeros((v,), jnp.bool_),
        entry_live=jnp.zeros((v,), jnp.bool_),
        ptr=jnp.int32(0),
        stats=jnp.zeros((len(STAT_FIELDS),), jnp.int32),
    )


def init_caches(cfg: GoConfig, batch: int,
                verdict_slots: int = VERDICT_SLOTS) -> EncodeCache:
    """A batch of cold caches (leading axis on every leaf) — the
    self-play loop's carry sibling of ``jaxgo.new_states``."""
    one = init_cache(cfg, verdict_slots)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (batch,) + x.shape), one)


def ladder_planes_cached(cfg: GoConfig, state: GoState, gd, legal,
                         cache: EncodeCache, depth: int = 40,
                         lanes: int = 16, chase_slots: int = 6,
                         refresh_slots=REFRESH_SLOTS):
    """Both ladder planes through the per-lane outcome cache:
    ``(ladder_capture [N], ladder_escape [N], cache')``.

    Same three gates as ``ladders.ladder_planes`` (candidate gating,
    slot gating, shared pooled chase slots) — candidate enumeration,
    slot assignment and overflow truncation are recomputed fresh, so
    the read's COVERAGE is bit-identical to the from-scratch shared
    formulation. The deltas: a lane whose ``(move, prey root, prey
    color, kind)`` matches a still-valid entry reuses the recorded
    opening outcome (skipping its opening algebra) and, when the
    entry carries a chase verdict, reuses that too while still
    CONSUMING its chase slot (coverage parity); only dirty lanes run
    openings (compacted per kind to ``refresh_slots = (capture,
    escape)`` widths; that kind's full-width fallback beyond) and
    only slotted lanes without a valid verdict chase.

    ``refresh_slots=0`` disables the compaction branches entirely
    (openings always full-width, gated to the refresh lanes) — the
    right trace under ``vmap``, where ``lax.switch`` would execute
    every branch anyway.

    Invalidation is two-tier and ABSOLUTE (not a one-ply delta):

    1. coarse-region keys — the one-ply churn ``board !=
       cache.board`` is packed into a per-region uint32 bitmask
       (:func:`_region_bits`); entries whose footprint-region key
       doesn't intersect it provably still match their recorded
       board (the carried ``entry_clean`` invariant) and skip tier 2;
    2. cell-exact revalidation — region-suspect entries compare the
       CURRENT board against their RECORD-TIME board on their exact
       footprint cells. A match means every re-run of the recorded
       read would see identical cells (the memoization induction), so
       the entry is consulted as if untouched — churn in the region's
       slop cells, or churn that has since reverted (capture /
       recapture), costs nothing. Only a genuine footprint mismatch
       makes the entry DORMANT: unmatched by lookups, so its lane
       re-opens (and re-chases if still live) and re-records — but
       the entry itself persists until superseded and revives if the
       board drifts back to its recorded footprint state.
    """
    n = cfg.num_points
    v = cache.entry_key.shape[0]
    k = 2 * lanes
    wc, we = refresh_slots if refresh_slots else REFRESH_SLOTS
    wc, we = min(wc, lanes), min(we, lanes)
    rec = wc + we
    if v < rec:
        raise ValueError(
            f"outcome ring ({v}) must hold at least one encode's "
            f"record width ({rec})")
    iota = jnp.arange(n)

    # --- 1. candidates: fresh every ply, same code as from-scratch ---
    analysis = neighbor_analysis(cfg, state.board, gd.labels)
    cap_mv, cap_pr, cap_ok = _candidate_lanes(
        cfg, state, gd, legal, prey_libs=2, prey_is_opp=True,
        lanes=lanes, analysis=analysis)
    esc_mv, esc_pr, esc_ok = _candidate_lanes(
        cfg, state, gd, legal, prey_libs=1, prey_is_opp=False,
        lanes=lanes, analysis=analysis)
    mv = jnp.concatenate([cap_mv, esc_mv])
    pr = jnp.concatenate([cap_pr, esc_pr])
    ok = jnp.concatenate([cap_ok, esc_ok])
    kind = jnp.concatenate([jnp.zeros((lanes,), jnp.int8),
                            jnp.ones((lanes,), jnp.int8)])
    pr_safe = jnp.minimum(pr, n - 1)       # garbage lanes: ok=False
    prey_root = gd.labels[pr_safe]
    prey_color = state.board[pr_safe]
    lane_key = (mv | (prey_root << 10)
                | ((prey_color.astype(jnp.int32) + 1) << 20)
                | (kind.astype(jnp.int32) << 22))

    # --- 2. invalidate + look up: tier 1, the coarse-region keys —
    # one uint32 AND against the ply's churn bitmask clears entries
    # whose footprint regions saw nothing (entry_clean invariant:
    # clean ⇒ board still matches entry_board on entry_foot) ---
    changed = state.board != cache.board
    churn_bits = _region_bits(cfg, changed)
    region_hit = (cache.entry_footmask & churn_bits) != 0
    clean = cache.entry_clean & ~region_hit
    suspect = cache.entry_valid & ~clean
    foot_hits = (cache.entry_valid & region_hit).sum(dtype=jnp.int32)

    # tier 2, cell-exact revalidation of the suspects: absolute
    # comparison against the RECORD-TIME board restricted to the
    # recorded footprint — region slop and reverted churn pass and
    # cost nothing; a genuine mismatch makes the entry dormant (it
    # revives if the board drifts back). Skipped entirely on plies
    # with no suspects (the common warm ply).
    def cell_test(_):
        return ((state.board[None, :] != cache.entry_board)
                & cache.entry_foot).any(axis=-1)

    cellbad = suspect & lax.cond(
        suspect.any(), cell_test,
        lambda _: jnp.zeros((v,), jnp.bool_), None)
    live = cache.entry_valid & ~cellbad
    entry_clean = cache.entry_valid & ~cellbad
    invalidated = (cache.entry_live & ~live).sum(dtype=jnp.int32)
    revived = (live & cache.entry_valid
               & ~cache.entry_live).sum(dtype=jnp.int32)

    keymatch = cache.entry_key[None, :] == lane_key[:, None]   # [K, V]
    match = live[None, :] & keymatch
    hit = match.any(axis=-1) & ok
    ent = jnp.argmax(match, axis=-1)
    c_need = cache.entry_need[ent] & hit
    c_direct = cache.entry_direct[ent] & hit
    c_has = cache.entry_has_verdict[ent] & hit
    c_verdict = cache.entry_verdict[ent]
    # a lane whose key matches only a DORMANT verdict entry is a
    # verdict flip when it actually re-chases (the cascade stat)
    dormant_verdict = ((cache.entry_valid & ~live
                        & cache.entry_has_verdict)[None, :]
                       & keymatch).any(axis=-1)

    # --- 3. refresh set: unknown opening, or a verdict gap (a hit
    # lane that needs a chase but has no recorded verdict must re-open
    # so the chase has its opening board) — UNLESS the gap lane
    # certainly cannot win a chase slot this ply: lanes that are
    # certainly needing (hit with a cached need) and ahead of it in
    # lane order already fill the slots. Without that guard a
    # persistent overflow lane (need, no slot, hence never a verdict)
    # would drag the opening pass into every otherwise-warm ply.
    # Sound: certain-need lanes are a SUBSET of the actual need lanes,
    # so "certain rank ≥ slots" implies "actual rank ≥ slots" = no
    # slot = no chase = its opening board is never consumed. Compacted
    # PER KIND so each opening algebra runs once at its own width. ---
    certain_before = jnp.cumsum(
        jnp.concatenate([jnp.zeros((1,), jnp.int32),
                         (hit & c_need).astype(jnp.int32)[:-1]]))
    gap = c_need & ~c_has & (certain_before < chase_slots)
    refresh = ok & (~hit | gap)
    nref = refresh.sum(dtype=jnp.int32)

    def kind_openings(opening_fn, kmv, kpr, kref, w):
        """One kind's openings over its refresh lanes: compacted to
        ``w`` when they fit, that kind's full width beyond (the
        fallback that keeps compaction a pure optimization), skipped
        when clean. Returns full-width rows + the compact index."""
        nk = kref.sum(dtype=jnp.int32)
        idx = _compact_indices(kref, w, lanes)
        valid = idx < lanes
        safe = jnp.where(valid, idx, 0)
        zb = jnp.broadcast_to(state.board, (lanes, n))
        zl = jnp.broadcast_to(gd.labels, (lanes, n))
        zf = jnp.zeros((lanes,), jnp.bool_)

        def none(_):
            return zb, zl, zf, zf

        def compact(_):
            bw, lw, nw, dw = opening_fn(
                cfg, state, gd, kmv[safe], kpr[safe],
                valid & kref[safe])
            return (zb.at[idx].set(bw, mode="drop"),
                    zl.at[idx].set(lw, mode="drop"),
                    zf.at[idx].set(nw, mode="drop"),
                    zf.at[idx].set(dw, mode="drop"))

        def full(_):
            return opening_fn(cfg, state, gd, kmv, kpr, kref)

        if refresh_slots:
            branch = (nk > 0).astype(jnp.int32) + \
                (nk > w).astype(jnp.int32)
            out = lax.switch(branch, (none, compact, full), None)
        else:
            out = full(None)
        return out + (idx, valid, nk)

    cb, cl, cn, cd, cridx, crvalid, ncap = kind_openings(
        _capture_opening, cap_mv, cap_pr, refresh[:lanes], wc)
    eb, el, en, ed, eridx, ervalid, nesc = kind_openings(
        _escape_opening, esc_mv, esc_pr, refresh[lanes:], we)
    boards_f = jnp.concatenate([cb, eb])
    labels_f = jnp.concatenate([cl, el])
    need_f = jnp.concatenate([cn, en])
    direct_f = jnp.concatenate([cd, ed])
    ridx = jnp.concatenate([cridx, eridx + lanes])
    ridx = jnp.where(jnp.concatenate([crvalid, ervalid]), ridx, k)
    rvalid = ridx < k
    rsafe = jnp.where(rvalid, ridx, 0)
    fellback = (ncap > wc) | (nesc > we)

    zero_f = jnp.zeros((k,), jnp.bool_)
    need = jnp.where(hit, c_need, need_f) & ok
    direct = jnp.where(hit, c_direct, direct_f) & ok

    # --- 4. slot assignment over ALL need-lanes (coverage parity with
    # the from-scratch shared pool: hit lanes consume slots too) ---
    slot_idx = _compact_indices(need, chase_slots, k)
    svalid = slot_idx < k
    ssafe = jnp.where(svalid, slot_idx, 0)
    covered = zero_f.at[slot_idx].set(svalid, mode="drop")
    run = svalid & ~(hit & c_has)[ssafe]
    any_run = run.any()

    # --- 5. pooled chase, only when some slotted lane lacks a verdict.
    # Lanes with reused verdicts enter disabled (zero trips). Collects
    # each chase's read CORE, seeded with the opening's board diff.
    # The verdict cache usually leaves only 1–2 lanes actually running
    # — those skip the slots-wide lockstep phase entirely and chase
    # scalar at full depth (the schedule is internal: verdicts are
    # identical either way); 3+ running lanes take the same two-phase
    # schedule as ladders._compacted_chase. ---
    d1 = min(_phase1_depth(), depth)

    def chase_block(_):
        prey = pr_safe[ssafe]
        boards_s = boards_f[ssafe]
        labels_s = labels_f[ssafe]
        open_core = ((gd.labels[None, :] == prey_root[ssafe][:, None])
                     & (state.board != 0)[None, :]
                     | (iota[None, :] == mv[ssafe][:, None])
                     | (boards_s != state.board[None, :]))
        zero_cap = jnp.zeros((chase_slots,), jnp.bool_)
        zero_core = jnp.zeros((chase_slots, n), jnp.bool_)

        def narrow(_):
            widx = _compact_indices(run, 2, chase_slots)
            capt, core = zero_cap, zero_core
            for j in range(2):
                live = widx[j] < chase_slots
                at = jnp.where(live, widx[j], 0)
                cap_j, core_j = _chase(
                    cfg, boards_s[at], labels_s[at], prey[at], depth,
                    enabled=live, collect_core=True,
                    core0=open_core[at])
                capt = capt.at[widx[j]].set(cap_j, mode="drop")
                core = core.at[widx[j]].set(core_j, mode="drop")
            return capt, core

        def wide(_):
            captured, unres, b_end, lab_end, core = jax.vmap(
                lambda b, l, p, en, c0: _chase(
                    cfg, b, l, p, d1, enabled=en, return_state=True,
                    collect_core=True, core0=c0))(
                    boards_s, labels_s, prey, run, open_core)
            if depth > d1:
                deep_idx = _compact_indices(unres, chase_slots,
                                            chase_slots)
                for s in range(chase_slots):
                    idx = deep_idx[s]
                    live = idx < chase_slots
                    at = jnp.where(live, idx, 0)
                    cap_s, core_s = _chase(
                        cfg, b_end[at], lab_end[at], prey[at],
                        depth - d1, enabled=live, collect_core=True,
                        core0=core[at])
                    captured = captured.at[idx].set(cap_s,
                                                    mode="drop")
                    core = core.at[idx].set(core_s, mode="drop")
            return captured, core

        captured, core = lax.cond(
            run.sum(dtype=jnp.int32) <= 2, narrow, wide, None)
        return captured & run, core & run[:, None]

    chased_s, core_s = lax.cond(
        any_run, chase_block,
        lambda _: (jnp.zeros((chase_slots,), jnp.bool_),
                   jnp.zeros((chase_slots, n), jnp.bool_)), None)
    chased = zero_f.at[slot_idx].set(chased_s, mode="drop")
    ran = zero_f.at[slot_idx].set(run, mode="drop")
    chase_core = jnp.zeros((k, n), jnp.bool_).at[slot_idx].set(
        core_s, mode="drop")

    # --- 6. planes: the from-scratch formulas, verdicts from cache or
    # chase (an uncovered overflow lane reads the conservative False
    # on both planes either way) ---
    verdict = jnp.where(hit & c_has, c_verdict, chased)
    captured_lane = direct[:lanes] | (
        need[:lanes] & covered[:lanes] & verdict[:lanes])
    escaped_lane = direct[lanes:] | (
        need[lanes:] & covered[lanes:] & ~verdict[lanes:])
    plane_cap = jnp.zeros((n,), jnp.bool_).at[cap_mv].max(
        captured_lane & cap_ok)
    plane_esc = jnp.zeros((n,), jnp.bool_).at[esc_mv].max(
        escaped_lane & esc_ok)

    # --- 7. record the refreshed lanes (first `rec` in lane order —
    # beyond that is only a reuse loss, never a correctness one).
    # One footprint expansion per recorded lane over the merged
    # opening+chase core, against the encode-time board. ---
    any_rec = rvalid.any()

    def expand_block(_):
        open_core_w = ((gd.labels[None, :]
                        == prey_root[rsafe][:, None])
                       & (state.board != 0)[None, :]
                       | (iota[None, :] == mv[rsafe][:, None])
                       | (boards_f[rsafe] != state.board[None, :]))
        core_w = (open_core_w | chase_core[rsafe]) & rvalid[:, None]
        foot = _chase_read_regions(cfg, state.board, gd.labels,
                                   core_w)
        return foot, _region_bits(cfg, foot)

    foot_w, footbits_w = lax.cond(
        any_rec, expand_block,
        lambda _: (jnp.zeros((rec, n), jnp.bool_),
                   jnp.zeros((rec,), jnp.uint32)), None)

    # entries superseded by a re-recorded lane die before the ring
    # write — dormant twins included, else a later revival could
    # shadow the fresher entry (either would be correct — each entry
    # is a self-contained memoization — but one canonical entry per
    # key keeps the ring honest)
    rec_lane = zero_f.at[ridx].set(True, mode="drop")
    superseded = (keymatch & rec_lane[:, None]).any(axis=0)

    dest = jnp.where(rvalid, (cache.ptr + jnp.arange(rec)) % v, v)
    n_new = rvalid.sum(dtype=jnp.int32)
    new_cache = cache._replace(
        board=state.board,
        entry_key=cache.entry_key.at[dest].set(
            lane_key[rsafe], mode="drop"),
        entry_need=cache.entry_need.at[dest].set(
            need_f[rsafe], mode="drop"),
        entry_direct=cache.entry_direct.at[dest].set(
            direct_f[rsafe], mode="drop"),
        entry_verdict=cache.entry_verdict.at[dest].set(
            chased[rsafe], mode="drop"),
        entry_has_verdict=cache.entry_has_verdict.at[dest].set(
            ran[rsafe], mode="drop"),
        entry_valid=(cache.entry_valid & ~superseded).at[dest].set(
            rvalid, mode="drop"),
        entry_foot=cache.entry_foot.at[dest].set(
            foot_w, mode="drop"),
        entry_board=cache.entry_board.at[dest].set(
            jnp.broadcast_to(state.board, (rec, n)), mode="drop"),
        entry_footmask=cache.entry_footmask.at[dest].set(
            footbits_w, mode="drop"),
        entry_clean=(entry_clean & ~superseded).at[dest].set(
            rvalid, mode="drop"),
        entry_live=(live & ~superseded).at[dest].set(
            rvalid, mode="drop"),
        ptr=(cache.ptr + n_new) % v,
        # one vector add, not nine scalar scatters — the warm path is
        # op-dispatch-bound on CPU (STAT_* layout)
        stats=cache.stats + jnp.stack(
            [jnp.int32(0),
             nref,
             run.sum(dtype=jnp.int32),
             (svalid & (hit & c_has)[ssafe]).sum(dtype=jnp.int32),
             invalidated,
             fellback.astype(jnp.int32),
             foot_hits,
             (run & dormant_verdict[ssafe]).sum(dtype=jnp.int32),
             revived]),
    )
    return plane_cap, plane_esc, new_cache


def encode_step(cfg: GoConfig, state: GoState, cache: EncodeCache,
                features: tuple = None,
                ladder_depth: int = 40, ladder_lanes: int = 16,
                ladder_chase_slots: int = 6,
                refresh_slots=REFRESH_SLOTS,
                gd=None):
    """Encode ``state`` against the cache of the PREVIOUS position →
    ``(planes [size, size, F], cache')``.

    Bit-identical to ``planes.encode(cfg, state, ...)`` at every call
    (see the module docstring's contract); the cache only modulates
    how much ladder work actually runs. The O(N) aging pass for the
    turns-since planes, the board/liberty planes and the
    candidate-simulation planes ride the exact same
    ``encode_analysis`` + ``assemble_planes`` code as the from-scratch
    path. Feature sets without both ladder planes get no reuse
    (nothing expensive to reuse) but keep the carry contract.
    """
    from rocalphago_tpu.features.pyfeatures import DEFAULT_FEATURES

    if features is None:
        features = DEFAULT_FEATURES
    gd, ci, legal = encode_analysis(cfg, state, features, gd)
    lad_kw = dict(depth=ladder_depth, lanes=ladder_lanes,
                  chase_slots=ladder_chase_slots)
    lad_cap = lad_esc = None
    if "ladder_capture" in features and "ladder_escape" in features:
        with jax.named_scope(scopes.ENCODE_LADDER):
            lad_cap, lad_esc, cache = ladder_planes_cached(
                cfg, state, gd, legal, cache,
                refresh_slots=refresh_slots, **lad_kw)
    else:
        cache = cache._replace(board=state.board)
    cache = cache._replace(
        stats=cache.stats.at[STAT_ENCODES].add(1))
    planes = assemble_planes(cfg, state, features, gd, ci, legal,
                             lad_cap, lad_esc, lad_kw)
    return planes, cache


def encode_delta(cfg: GoConfig, prev_state: GoState,
                 cache: EncodeCache, move, features: tuple = None,
                 **encode_kwargs):
    """Play ``move`` (flat index, ``N`` = pass) on ``prev_state`` and
    delta-encode the successor → ``(planes, cache')``.

    Convenience form of the carry contract for callers that hold the
    previous position and the move; callers that already stepped the
    engine (the fused self-play ply) call :func:`encode_step` on the
    successor directly — the two are equivalent because the cache
    diffs boards, not moves.
    """
    new_state = step(cfg, prev_state, jnp.asarray(move, jnp.int32))
    return encode_step(cfg, new_state, cache, features=features,
                       **encode_kwargs)


def batched_delta_encoder(cfg: GoConfig, features: tuple,
                          **encode_kwargs):
    """``(states, caches, gd=None) -> (planes [B, s, s, F], caches')``
    — the delta sibling of ``planes.batched_encoder``, for the fused
    sequential hot loops (the self-play ply carry). Callers holding a
    per-ply ``jaxgo.group_data`` pass it to share the analysis, same
    convention as the from-scratch encoder.

    Traces with ``refresh_slots=0`` (full-width openings, no host
    branches) unless overridden: under ``vmap`` the single-state
    path's ``lax.switch`` branches all execute as selects, so the
    compaction would cost MORE than it saves — the batched win is the
    verdict reuse cutting the lockstep rung-loop trips."""
    encode_kwargs.setdefault("refresh_slots", 0)
    one = functools.partial(encode_step, cfg, features=features,
                            **encode_kwargs)
    with_gd = jax.vmap(lambda s, c, g: one(s, c, gd=g))
    no_gd = jax.vmap(lambda s, c: one(s, c))

    def enc(states: GoState, caches: EncodeCache, gd=None):
        return (no_gd(states, caches) if gd is None
                else with_gd(states, caches, gd))

    return enc
