"""Feature-encoder tests: device planes vs the host oracle.

Follows the reference's plane-by-plane assertion strategy
(``tests/test_preprocessing.py``, SURVEY.md §4) plus random-game
differentials against the simulate-every-candidate oracle.
"""

import numpy as np
import pytest

from rocalphago_tpu.engine import jaxgo, pygo
from rocalphago_tpu.engine.jaxgo import GoConfig
from rocalphago_tpu.features import (
    DEFAULT_FEATURES,
    VALUE_FEATURES,
    Preprocess,
    output_planes,
    pyfeatures,
)
from rocalphago_tpu.features import planes as jplanes

# the 49-plane value set minus the ladder planes, so the random-game
# differential covers the color plane too
NON_LADDER = tuple(f for f in VALUE_FEATURES
                   if not f.startswith("ladder"))


def plane_slices(features):
    out, off = {}, 0
    for f in features:
        k = pyfeatures.FEATURE_PLANES[f]
        out[f] = slice(off, off + k)
        off += k
    return out


@pytest.mark.parametrize("size", [5, 9])
def test_nonladder_planes_match_oracle(size):
    cfg = GoConfig(size=size, komi=5.5)
    pre = Preprocess(NON_LADDER, cfg=cfg)
    rng = np.random.default_rng(size)
    sl = plane_slices(NON_LADDER)

    pst = pygo.GameState(size=size, komi=5.5)
    checks = 0
    for move_i in range(60):
        legal = pst.get_legal_moves()
        if not legal:
            break
        pst.do_move(legal[rng.integers(len(legal))])
        if pst.is_end_of_game:
            break
        if move_i % 7 == 3:
            jst = jaxgo.from_pygo(cfg, pst)
            got = np.asarray(pre.state_to_tensor(jst))[0]
            want = pyfeatures.state_to_planes(pst, NON_LADDER)
            for name in NON_LADDER:
                g, w = got[:, :, sl[name]], want[:, :, sl[name]]
                assert np.array_equal(g, w), (
                    f"plane {name} diverged at move {move_i}:\n"
                    f"board=\n{pst.board}\n"
                    f"got=\n{g.argmax(-1) * (g.sum(-1) > 0)}\n"
                    f"want=\n{w.argmax(-1) * (w.sum(-1) > 0)}")
            checks += 1
    assert checks >= 3


class TestLadders:
    """Curated ladder shapes where greedy and full-branching reads agree."""

    def ladder_position(self, breaker=None):
        """B to move; W stone at (2,2) flanked by B on three sides has
        two liberties; the ladder toward the lower-right works unless a
        breaker stone on the path helps W."""
        st = pygo.GameState(size=9, komi=5.5)
        st.do_move((1, 2), pygo.BLACK)
        st.do_move((2, 2), pygo.WHITE)
        st.do_move((2, 1), pygo.BLACK)
        st.do_move((8, 8), pygo.WHITE)
        st.do_move((3, 1), pygo.BLACK)
        if breaker:
            st.do_move(breaker, pygo.WHITE)
        st.current_player = pygo.BLACK
        return st

    def encode_plane(self, st, name):
        cfg = GoConfig(size=9, komi=5.5)
        pre = Preprocess((name,), cfg=cfg)
        jst = jaxgo.from_pygo(cfg, st)
        return np.asarray(pre.state_to_tensor(jst))[0, :, :, 0]

    def test_working_ladder_capture(self):
        st = self.ladder_position()
        # oracle: starting the ladder at either liberty works from (2,3)
        # (the standard attack keeping W at one liberty)
        assert pyfeatures.is_ladder_capture(st, (2, 3))
        plane = self.encode_plane(st, "ladder_capture")
        assert plane[2, 3] == 1.0

    def test_broken_ladder_not_capture(self):
        st = self.ladder_position(breaker=(6, 6))  # W stone on the path
        assert not pyfeatures.is_ladder_capture(st, (2, 3))
        plane = self.encode_plane(st, "ladder_capture")
        assert plane[2, 3] == 0.0

    def test_ladder_escape(self):
        # W in atari; escape works only with the breaker present
        st = self.ladder_position()
        st.do_move((2, 3), pygo.BLACK)  # atari
        st.current_player = pygo.WHITE
        assert not pyfeatures.is_ladder_escape(st, (3, 2))
        plane = self.encode_plane(st, "ladder_escape")
        assert plane[3, 2] == 0.0

        st2 = self.ladder_position(breaker=(6, 6))
        st2.do_move((2, 3), pygo.BLACK)
        st2.current_player = pygo.WHITE
        assert pyfeatures.is_ladder_escape(st2, (3, 2))
        plane2 = self.encode_plane(st2, "ladder_escape")
        assert plane2[3, 2] == 1.0


class TestLadderDifferential:
    """Randomized device-vs-oracle ladder hardening (round-1 weakness:
    ladders were only checked on 3 hand-built shapes).

    The device reader is a 2-ply forced-response approximation of the
    oracle's full-branching read (``features/ladders.py`` docstring),
    so two guarantees are asserted: EXACT agreement on a family of
    standard zigzag ladders (the shape the feature exists for), and a
    bounded disagreement rate on unrestricted random positions
    (measured ~0.1–0.3%% of cells; bound set at 1%%)."""

    LADDER_FEATURES = ("ladder_capture", "ladder_escape")

    def _encode_both(self, cfg, pre, st):
        jst = jaxgo.from_pygo(cfg, st)
        dev = np.asarray(pre.state_to_tensor(jst))[0]
        ora = pyfeatures.state_to_planes(st, self.LADDER_FEATURES)
        return dev, ora

    @pytest.mark.parametrize("dx,dy",
                             [(0, 0), (1, 2), (2, 1), (2, 2), (1, 1),
                              (0, 2)])
    def test_zigzag_family_is_exact(self, dx, dy):
        """Shifted standard ladders: W prey flanked on three sides,
        chased toward the far corner — device planes must equal the
        oracle everywhere, both with the working ladder and with a
        breaker stone on the path. (W's tempo stone sits off-path with
        4 liberties so the only ladder candidate is the real prey —
        lone 2-liberty stones elsewhere are exactly the shapes where
        the 2-ply reader is allowed to diverge, covered by the rate
        test below.)"""
        cfg = GoConfig(size=9, komi=5.5)
        pre = Preprocess(self.LADDER_FEATURES, cfg=cfg)
        for breaker in (None, (4 + dx, 4 + dy)):
            st = pygo.GameState(size=9, komi=5.5)
            st.do_move((1 + dx, 2 + dy), pygo.BLACK)
            st.do_move((2 + dx, 2 + dy), pygo.WHITE)
            st.do_move((2 + dx, 1 + dy), pygo.BLACK)
            st.do_move((7, 1), pygo.WHITE)   # tempo, 4 libs, off-path
            st.do_move((3 + dx, 1 + dy), pygo.BLACK)
            if breaker and st.board[breaker] == 0:
                st.do_move(breaker, pygo.WHITE)
            st.current_player = pygo.BLACK
            dev, ora = self._encode_both(cfg, pre, st)
            assert np.array_equal(dev, ora), (
                f"zigzag at offset ({dx},{dy}) breaker={breaker} "
                f"diverged:\nboard=\n{st.board}")
            # semantics, not just agreement: the ladder works without
            # the breaker and fails with it
            n_captures = int(ora[:, :, 0].sum())
            assert n_captures == (0 if breaker else 1)

    @pytest.mark.slow
    def test_escaper_response_algebra_self_consistent(self):
        """Property check of the loop-free rung algebra: for random
        chase openings, the reported response liberty count must equal
        an independent local-fill measurement of the prey group on the
        returned board (regression: a counter-capture played AWAY from
        the prey once donated its own liberties to the prey's count)."""
        import jax.numpy as jnp

        from rocalphago_tpu.engine.jaxgo import group_data
        from rocalphago_tpu.features import ladders

        cfg = GoConfig(size=7, komi=5.5)
        rng = np.random.default_rng(7)
        checked = 0
        for _ in range(60):
            st = pygo.GameState(size=7, komi=5.5)
            for _ in range(int(rng.integers(6, 26))):
                legal = st.get_legal_moves(include_eyes=False)
                if not legal or st.is_end_of_game:
                    break
                st.do_move(legal[rng.integers(len(legal))])
            if st.is_end_of_game:
                continue
            jst = jaxgo.from_pygo(cfg, st)
            gd = group_data(cfg, jst.board, with_member=False,
                            with_zxor=False)
            # find a 2-liberty opponent group and one of its liberties
            me = int(jst.turn)
            opp = (np.asarray(jst.board) == -me)
            labels = np.asarray(gd.labels)
            libcounts = np.asarray(gd.lib_counts)
            roots = {labels[p] for p in np.flatnonzero(opp)
                     if libcounts[labels[p]] == 2}
            for root in sorted(roots)[:2]:
                prey_pt = int(np.flatnonzero(labels == root)[0])
                prey_mask = jnp.asarray(labels == root)
                empty = np.asarray(jst.board) == 0
                dil = np.asarray(ladders._dilate2d(
                    7, jnp.asarray(labels == root).reshape(7, 7))
                ).reshape(-1)
                libs = np.flatnonzero(empty & dil)
                if not len(libs):
                    continue
                c = int(libs[0])
                b1, ok, cap0 = ladders._place(
                    cfg, jst.board, gd, jnp.int32(c), jnp.int8(me))
                if not bool(ok):
                    continue
                preyL, respL, b2 = ladders._escaper_response_fast(
                    cfg, b1, jnp.int32(prey_pt), jnp.int8(-me),
                    prey_mask, gd, jnp.int32(c), cap0)
                if int(respL) < 0:
                    continue
                oracle = int(ladders._local_prey_libs(
                    cfg, b2, jnp.int32(prey_pt)))
                assert int(respL) == oracle, (
                    f"algebraic respL {int(respL)} != local-fill "
                    f"{oracle}\nboard:\n"
                    f"{np.asarray(b2).reshape(7, 7)}")
                checked += 1
        assert checked >= 10

    @pytest.mark.slow
    def test_random_position_disagreement_rate_bounded(self):
        rng_master = np.random.default_rng(20260729)
        cells = disagreements = 0
        for size in (7, 9):
            cfg = GoConfig(size=size, komi=5.5)
            pre = Preprocess(self.LADDER_FEATURES, cfg=cfg)
            for case in range(10):
                rng = np.random.default_rng(rng_master.integers(2**31))
                st = pygo.GameState(size=size, komi=5.5)
                for _ in range(int(rng.integers(8, 33))):
                    legal = st.get_legal_moves(include_eyes=False)
                    if not legal or st.is_end_of_game:
                        break
                    st.do_move(legal[rng.integers(len(legal))])
                if st.is_end_of_game:
                    continue
                dev, ora = self._encode_both(cfg, pre, st)
                disagreements += int((dev != ora).sum())
                cells += dev.size
        assert cells > 0
        rate = disagreements / cells
        assert rate < 0.01, (
            f"device ladder reader disagrees with the full-branching "
            f"oracle on {rate:.2%} of cells (bound 1%)")

    @pytest.mark.slow   # compiles a 19×19 encode (~20 s of a tier-1
    # with no spare time); chip_smoke.py's verify phase holds the
    # same 1% bound at 19×19 on the chip on every PR
    def test_dense_19x19_disagreement_rate_bounded(self):
        """Crowded 19×19 boards are where the bounded chase-slot
        capacity could bite (uniform-random 200-ply boards carry 2–11
        active capture chases/board — past the default 6 POOLED slots
        both planes now share): assert the rate vs the full-branching
        oracle stays under the same 1% bound there. Measured ~0.5%
        at bounded capacity vs 0.49% with effectively unlimited
        slots, i.e. the truncation itself adds ~0.05% — positions
        this dense are far beyond anything a policy-guided game
        produces."""
        cfg = GoConfig(size=19, komi=7.5)
        pre = Preprocess(self.LADDER_FEATURES, cfg=cfg)
        rng = np.random.default_rng(20260730)
        cells = disagreements = 0
        for case in range(3):
            st = pygo.GameState(size=19, komi=7.5)
            for _ in range(200):
                legal = st.get_legal_moves(include_eyes=False)
                if not legal or st.is_end_of_game:
                    break
                st.do_move(legal[rng.integers(len(legal))])
            dev, ora = self._encode_both(cfg, pre, st)
            disagreements += int((dev != ora).sum())
            cells += dev.size
        rate = disagreements / cells
        assert rate < 0.01, (
            f"dense-board ladder disagreement {rate:.2%} (bound 1%)")


@pytest.mark.slow
class TestLadderOverflow:
    """Adversarial ``chase_slots`` overflow (VERDICT r2 weak #6): a
    crafted board with MORE simultaneous live ladder chases than the
    slot capacity (here 4; the shipped default is 6 POOLED across
    both planes) must degrade gracefully — truncation drops chases
    in board row-major candidate order and every dropped cell reads
    the conservative False (never a spurious capture/escape) — and
    raising ``ladder_chase_slots`` must restore exactness."""

    # six independent standard ladder seeds along the anti-diagonal:
    # each W stone is flanked by B on three sides (two liberties, B to
    # move) and its chase path runs toward the lower-right, parallel
    # to and clear of every other seed's path
    SEEDS = [(1, 16), (4, 13), (7, 10), (10, 7), (13, 4), (16, 1)]
    FEATURES = ("ladder_capture", "ladder_escape")

    def _board(self):
        st = pygo.GameState(size=19, komi=7.5)
        for r, c in self.SEEDS:
            st.do_move((r - 1, c), pygo.BLACK)
            st.do_move((r, c), pygo.WHITE)
            st.do_move((r, c - 1), pygo.BLACK)
            st.do_move((r + 1, c - 1), pygo.BLACK)
        st.current_player = pygo.BLACK
        return st

    def _encode(self, st, slots):
        cfg = GoConfig(size=19, komi=7.5)
        pre = Preprocess(self.FEATURES, cfg=cfg,
                         ladder_chase_slots=slots)
        return np.asarray(
            pre.state_to_tensor(jaxgo.from_pygo(cfg, st)))[0]

    def test_overflow_degrades_conservatively_and_slots_restore(self):
        st = self._board()
        ora = pyfeatures.state_to_planes(st, self.FEATURES)
        # the construction really overflows: one working ladder
        # capture per seed, all simultaneously live
        assert int(ora[:, :, 0].sum()) == len(self.SEEDS)

        dev4 = self._encode(st, slots=4)
        # graceful: every asserted cell is oracle-true (truncation
        # only ever under-reports) ...
        assert not ((dev4 == 1) & (ora == 0)).any()
        # ... and exactly the 4 covered chases (row-major candidate
        # order) are reported — the 2 dropped seeds read False
        assert int(dev4[:, :, 0].sum()) == 4

        dev16 = self._encode(st, slots=16)
        np.testing.assert_array_equal(dev16, ora)


class TestAPI:
    def test_output_dim_default_is_48(self):
        assert output_planes(DEFAULT_FEATURES) == 48

    def test_value_features_is_49(self):
        assert output_planes(VALUE_FEATURES) == 49

    def test_color_plane_tracks_player_to_move(self):
        cfg = GoConfig(size=5)
        pre = Preprocess(("color",), cfg=cfg)
        pst = pygo.GameState(size=5)
        t = np.asarray(pre.state_to_tensor(jaxgo.from_pygo(cfg, pst)))
        assert t.all()          # black to move → all ones
        pst.do_move((2, 2))
        t = np.asarray(pre.state_to_tensor(jaxgo.from_pygo(cfg, pst)))
        assert not t.any()      # white to move → all zeros
        assert np.array_equal(
            t[0], pyfeatures.state_to_planes(pst, ("color",)))

    def test_state_to_tensor_shapes(self):
        cfg = GoConfig(size=5)
        pre = Preprocess(("board", "ones", "liberties"), cfg=cfg)
        assert pre.output_dim == 12
        eng = jaxgo.GoEngine(cfg)
        t = pre.state_to_tensor(eng.init())
        assert t.shape == (1, 5, 5, 12)
        batch = pre.states_to_tensor(eng.init_batch(4))
        assert batch.shape == (4, 5, 5, 12)

    def test_unknown_feature_rejected(self):
        with pytest.raises(KeyError):
            Preprocess(("board", "nope"))

    def test_fresh_board_planes(self):
        cfg = GoConfig(size=5)
        pre = Preprocess(NON_LADDER, cfg=cfg)
        eng = jaxgo.GoEngine(cfg)
        t = np.asarray(pre.state_to_tensor(eng.init()))[0]
        sl = plane_slices(NON_LADDER)
        assert t[:, :, sl["board"]][:, :, 2].all()       # all empty
        assert t[:, :, sl["ones"]].all()
        assert not t[:, :, sl["zeros"]].any()
        assert t[:, :, sl["sensibleness"]].all()         # every move fine
        cap0 = t[:, :, sl["capture_size"]][:, :, 0]
        assert cap0.all()                                # 0 captures, legal
        la = t[:, :, sl["liberties_after"]]
        assert la[0, 0, 1] == 1.0   # corner stone: 2 libs
        assert la[2, 2, 3] == 1.0   # center stone: 4 libs


class TestSharedGating:
    """The encode-path overhaul's pooled chase
    (``ladders.ladder_planes``: one candidate analysis, slot entry
    gated on a live undecided chase, ONE rung loop whose lanes mix
    capture and escape prey) vs the legacy split formulation
    (``ROCALPHAGO_LADDER_GATE=split`` — two independent per-plane
    chases). Contract under test: with slots ≥ live chases the pooled
    read is BIT-IDENTICAL to split (gating is provably exact there:
    candidate and slot gates only discard lanes whose outcome is
    decided without a chase), and on overflow capture lanes fill the
    pooled capacity first while every dropped lane stays a
    conservative False."""

    FEATURES = ("ladder_capture", "ladder_escape")
    # 2 random-board chases + the curated single ladders fit well
    # inside the default pooled capacity, so shared must equal split
    SLOTS = 6
    N_RANDOM = 4

    @staticmethod
    def _batch(cfg, boards):
        import jax
        import jax.numpy as jnp

        states = [jaxgo.from_pygo(cfg, st) for st in boards]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    @classmethod
    def _encode_batch(cls, cfg, boards, gate, slots):
        import os

        os.environ["ROCALPHAGO_LADDER_GATE"] = gate
        try:
            pre = Preprocess(cls.FEATURES, cfg=cfg,
                             ladder_chase_slots=slots)
            return np.asarray(
                pre.states_to_tensor(cls._batch(cfg, boards)))
        finally:
            os.environ.pop("ROCALPHAGO_LADDER_GATE", None)

    @staticmethod
    def _edge_boards():
        """Adversarial first-line shapes: a prey chased ALONG the top
        edge and one a step from the corner (the greedy chaser's
        known-divergent family — ``ladders.py`` module docstring);
        the W tempo stone sits in the center with 4 liberties so the
        edge prey is the only candidate."""
        for col in (3, 6):
            st = pygo.GameState(size=9, komi=5.5)
            st.do_move((0, col - 1), pygo.BLACK)
            st.do_move((0, col), pygo.WHITE)
            st.do_move((1, col - 1), pygo.BLACK)
            st.do_move((5, 5), pygo.WHITE)      # tempo, off-path
            st.current_player = pygo.BLACK
            yield st

    @pytest.fixture(scope="class")
    def encoded(self):
        """One shared and one split encode of the whole board family
        (random mid-games, curated working/broken ladder, edge/corner
        ladders) — two traces total, consumed by both tier-1 tests.
        Returns ``(boards, shared [B,9,9,2], split [B,9,9,2])``."""
        rng = np.random.default_rng(20260804)
        boards = []
        for _ in range(self.N_RANDOM):
            st = pygo.GameState(size=9, komi=5.5)
            for _ in range(int(rng.integers(10, 41))):
                legal = st.get_legal_moves(include_eyes=False)
                if not legal or st.is_end_of_game:
                    break
                st.do_move(legal[rng.integers(len(legal))])
            if not st.is_end_of_game:
                boards.append(st)
        tl = TestLadders()
        boards += [tl.ladder_position(),
                   tl.ladder_position(breaker=(6, 6))]
        boards += list(self._edge_boards())
        cfg = GoConfig(size=9, komi=5.5)
        shared = self._encode_batch(cfg, boards, "shared", self.SLOTS)
        split = self._encode_batch(cfg, boards, "split", self.SLOTS)
        return boards, shared, split

    def test_bit_identity_when_capacity_covers(self, encoded):
        """With slots ≥ live chases, pooling cannot change any lane's
        outcome (per-lane chases are independent; the gates only
        discard decided lanes): shared and split planes must be equal
        bit-for-bit, and the known working-ladder capture must be
        asserted by both (non-vacuity)."""
        boards, shared, split = encoded
        np.testing.assert_array_equal(shared, split)
        work_i = len(boards) - 4    # the curated working ladder
        assert shared[work_i, 2, 3, 0] == 1.0

    def test_edge_ladders_sound_vs_oracle(self, encoded):
        """On the edge/corner family the 2-ply greedy reader may
        UNDER-read (it can block on the first line instead of turning
        the ladder — the documented approximation), but it must stay
        SOUND: every asserted capture/escape cell is oracle-true.
        The unrestricted disagreement RATE has its own bound test
        (``TestLadderDifferential``)."""
        boards, shared, _ = encoded
        for i in (len(boards) - 2, len(boards) - 1):
            st = boards[i]
            ora = pyfeatures.state_to_planes(st, self.FEATURES)
            assert int(ora[:, :, 0].sum()) >= 1   # a real ladder
            spurious = (shared[i] == 1) & (ora == 0)
            assert not spurious.any(), (
                f"edge board {i}: device asserted oracle-false cells "
                f"at {np.argwhere(spurious)}\nboard:\n{st.board}")

    @pytest.mark.slow
    def test_overflow_capture_lanes_fill_first(self):
        """Pooled-capacity truncation contract on the 6-ladder
        overflow board: at 4 shared slots exactly the first 4 capture
        chases (compaction order — capture lanes precede escape
        lanes) are read, dropped lanes stay conservative False, and
        raising the pooled capacity restores exactness."""
        st = TestLadderOverflow()._board()
        cfg = GoConfig(size=19, komi=7.5)
        ora = pyfeatures.state_to_planes(st, self.FEATURES)
        dev4 = self._encode_batch(cfg, [st], "shared", 4)[0]
        assert not ((dev4 == 1) & (ora == 0)).any()
        assert int(dev4[:, :, 0].sum()) == 4
        dev16 = self._encode_batch(cfg, [st], "shared", 16)[0]
        np.testing.assert_array_equal(dev16, ora)


def test_warm_encode_compiles_nothing():
    """Compile-cache smoke (encode-overhaul satellite): a warm second
    batched encode of the same shapes must not grow the
    ``jax_compiles_total{entry="encode.batch"}`` counter that
    ``features/api.py`` records through ``obs/jaxobs.py`` — repeat
    encodes ride the jit cache (and, across processes, the persistent
    compile cache ``runtime/compilecache.py`` points every CLI at)."""
    from rocalphago_tpu.obs import registry as obs_registry

    cfg = GoConfig(size=5)
    pre = Preprocess(("board", "ladder_capture", "ladder_escape"),
                     cfg=cfg)
    states = jaxgo.GoEngine(cfg).init_batch(3)
    key = 'jax_compiles_total{entry="encode.batch"}'

    pre.states_to_tensor(states)
    before = obs_registry.REGISTRY.snapshot()["counters"].get(key, 0)
    assert before >= 1              # the cold call really was tracked
    pre.states_to_tensor(states)
    after = obs_registry.REGISTRY.snapshot()["counters"].get(key, 0)
    assert after == before          # warm run: zero compile growth
    assert pre._batch.compiles == 1 and pre._batch.calls == 2


@pytest.mark.slow
class TestTwoPhaseChaseEquivalence:
    """The two-phase chase schedule (round 4) must be BIT-IDENTICAL
    to the single lockstep chase: phase 2 resumes each capped lane
    from its frozen exit state, so splitting the read cannot change
    any outcome. ``ROCALPHAGO_LADDER_PHASE1=<depth>`` recovers the
    single-phase program exactly (d1 = min(knob, depth) = depth →
    no deep tail), giving a direct differential."""

    @staticmethod
    def _positions():
        """Random mid-games PLUS the constructed 6-ladder overflow
        board — its chases cross the whole 19×19 board, so lanes
        provably survive past phase 1 and the resume path does real
        work (not just the all-lanes-settled trivial case)."""
        rng = np.random.default_rng(20260731)
        for size, plies in ((9, 40), (19, 160)):
            st = pygo.GameState(size=size, komi=5.5)
            for _ in range(plies):
                legal = st.get_legal_moves(include_eyes=False)
                if not legal or st.is_end_of_game:
                    break
                st.do_move(legal[rng.integers(len(legal))])
            yield size, st
        deep = TestLadderOverflow()._board()
        yield 19, deep

    def test_two_phase_equals_single_phase(self, monkeypatch):
        for size, st in self._positions():
            cfg = GoConfig(size=size, komi=7.5)
            st.komi = 7.5
            jst = jaxgo.from_pygo(cfg, st)

            monkeypatch.setenv("ROCALPHAGO_LADDER_PHASE1", "4")
            two = np.asarray(Preprocess(
                ("ladder_capture", "ladder_escape"), cfg=cfg,
                ladder_depth=40).state_to_tensor(jst))[0]
            # a huge knob forces d1 = min(knob, depth) = depth: the
            # exact single-phase program, whatever the default depth
            monkeypatch.setenv("ROCALPHAGO_LADDER_PHASE1", "100000")
            one = np.asarray(Preprocess(
                ("ladder_capture", "ladder_escape"), cfg=cfg,
                ladder_depth=40).state_to_tensor(jst))[0]
            np.testing.assert_array_equal(two, one)
