"""Differential tests: JAX engine vs the pure-Python oracle.

This is the oracle strategy SURVEY.md §4 prescribes for the vectorized
engine (as upstream validated its Cython branch): play random games,
compare the full legality mask, board, ko, termination, and final score
at every step.
"""

import numpy as np
import pytest

from rocalphago_tpu.engine import jaxgo, pygo
from rocalphago_tpu.engine.jaxgo import GoConfig, GoEngine, compute_labels


def py_board_flat(st: pygo.GameState) -> np.ndarray:
    return np.asarray(st.board, dtype=np.int8).reshape(-1)


def py_legal_points(st: pygo.GameState) -> np.ndarray:
    n = st.size * st.size
    mask = np.zeros(n, dtype=bool)
    for x in range(st.size):
        for y in range(st.size):
            mask[x * st.size + y] = st.is_legal((x, y))
    return mask


@pytest.mark.parametrize(
    "size,superko",
    [(5, False),
     # the 5×5 no-superko case stays in the fast tier so the default
     # edit-test loop keeps ONE engine-vs-pygo differential; the
     # superko variant and the 9×9 runs cover the same code paths
     # over longer games — kept in CI's full run, deselected from the
     # fast tier (suite wall-time)
     pytest.param(5, True, marks=pytest.mark.slow),
     pytest.param(9, False, marks=pytest.mark.slow),
     pytest.param(9, True, marks=pytest.mark.slow)])
def test_random_game_differential(size, superko):
    cfg = GoConfig(size=size, komi=5.5, enforce_superko=superko,
                   max_history=256)
    eng = GoEngine(cfg)
    rng = np.random.default_rng(size * 10 + superko)

    for game in range(3):
        jst = eng.init()
        pst = pygo.GameState(size=size, komi=5.5, enforce_superko=superko)
        for move_i in range(180):
            jmask = np.asarray(eng.legal_mask(jst))
            pmask = py_legal_points(pst)
            assert jmask[:-1].tolist() == pmask.tolist(), (
                f"legality diverged at move {move_i} (game {game}):\n"
                f"jax={np.flatnonzero(jmask[:-1] != pmask)}\n"
                f"board=\n{pst.board}\nko={pst.ko}")
            assert bool(jmask[-1])  # pass legal while live

            legal_idx = np.flatnonzero(pmask)
            # bias towards board moves; occasionally pass
            if len(legal_idx) == 0 or rng.random() < 0.03:
                action = size * size
                pst.do_move(pygo.PASS_MOVE)
            else:
                action = int(rng.choice(legal_idx))
                pst.do_move(divmod(action, size))
            jst = eng.step(jst, np.int32(action))

            assert py_board_flat(pst).tolist() == np.asarray(
                jst.board).tolist(), f"board diverged at move {move_i}"
            # carried incremental labels must ALWAYS equal a fresh fill
            # (sampled every 8th move — a divergence persists until the
            # next capture of the affected group, so sampling catches it)
            if move_i % 8 == 0 or pst.is_end_of_game:
                assert np.asarray(jst.labels).tolist() == np.asarray(
                    compute_labels(cfg, jst.board)).tolist(), (
                    f"carried labels diverged by move {move_i}")
            pko = -1 if pst.ko is None else pst.ko[0] * size + pst.ko[1]
            assert int(jst.ko) == pko, f"ko diverged at move {move_i}"
            assert bool(jst.done) == pst.is_end_of_game
            if pst.is_end_of_game:
                break

        pb, pw = pst.get_scores()
        jb, jw = eng.area_scores(jst)
        assert float(jb) == pb and float(jw) == pw
        jwin = int(eng.winner(jst))
        assert jwin == pst.get_winner()


def test_dense_engine_parity_differential(monkeypatch):
    """The dense (shift/matmul) group-analysis formulation — the TPU
    default, which CPU CI otherwise never executes — must match pygo
    move-for-move exactly like the scatter path does, and must agree
    with the scatter path on the full GroupData contract."""
    from rocalphago_tpu.engine.jaxgo import group_data

    monkeypatch.setenv("ROCALPHAGO_ENGINE_DENSE", "1")
    jaxgo._dense_engine.cache_clear()
    try:
        assert jaxgo._dense_engine()
        cfg = GoConfig(size=5, komi=5.5)
        eng = GoEngine(cfg)  # fresh closures → traces the dense branch
        rng = np.random.default_rng(7)
        jst = eng.init()
        pst = pygo.GameState(size=5, komi=5.5)
        for move_i in range(120):
            jmask = np.asarray(eng.legal_mask(jst))
            assert jmask[:-1].tolist() == py_legal_points(pst).tolist(), (
                f"dense legality diverged at move {move_i}")
            legal_idx = np.flatnonzero(jmask[:-1])
            if len(legal_idx) == 0 or rng.random() < 0.03:
                action = cfg.num_points
                pst.do_move(pygo.PASS_MOVE)
            else:
                action = int(rng.choice(legal_idx))
                pst.do_move(divmod(action, cfg.size))
            jst = eng.step(jst, np.int32(action))
            assert py_board_flat(pst).tolist() == np.asarray(
                jst.board).tolist()
            if move_i % 10 == 0:
                dense = group_data(cfg, jst.board, with_member=True,
                                   with_zxor=True, labels=jst.labels)
                monkeypatch.setenv("ROCALPHAGO_ENGINE_DENSE", "0")
                jaxgo._dense_engine.cache_clear()
                scat = group_data(cfg, jst.board, with_member=True,
                                  with_zxor=True, labels=jst.labels)
                monkeypatch.setenv("ROCALPHAGO_ENGINE_DENSE", "1")
                jaxgo._dense_engine.cache_clear()
                for a, b, name in [
                        (dense.sizes, scat.sizes, "sizes"),
                        (dense.lib_counts, scat.lib_counts, "lib_counts"),
                        (dense.member, scat.member, "member"),
                        (dense.zxor, scat.zxor, "zxor")]:
                    assert np.asarray(a).tolist() == np.asarray(
                        b).tolist(), f"{name} diverged at move {move_i}"
            if pst.is_end_of_game:
                break
    finally:
        jaxgo._dense_engine.cache_clear()  # monkeypatch restored the env


def random_boards(size, batch, moves, seed):
    rng = np.random.default_rng(seed)
    out = np.zeros((batch, size * size), np.int8)
    for i in range(batch):
        st = pygo.GameState(size=size, komi=5.5)
        for _ in range(moves):
            legal = st.get_legal_moves(include_eyes=False)
            if not legal or st.is_end_of_game:
                break
            st.do_move(legal[rng.integers(len(legal))])
        out[i] = py_board_flat(st)
    return out


def single_file_snake(size: int):
    """A 1-wide boustrophedon snake: even rows full, odd rows a single
    connector stone at alternating ends — ONE group whose label must
    propagate along the whole path (the longest chain constructible on
    a board), the stress case for the labeller's propagation bound."""
    b = np.zeros((size, size), np.int8)
    for x in range(size):
        if x % 2 == 0:
            b[x, :] = 1
        else:
            b[x, size - 1 if (x // 2) % 2 == 0 else 0] = 1
    return b.reshape(-1)


def worst_case_boards(size):
    """The snake (on 19×19 a chain of ~190 stones), a solid board and
    the snake in the other color."""
    solid = np.ones((size * size,), np.int8)
    return np.stack([single_file_snake(size), solid,
                     -single_file_snake(size)]).astype(np.int8)


def host_groups(board, size):
    """Host flood fill: ``(labels, sizes, libs)`` — a point's label is
    the min flat index of its group (N for empty); sizes and distinct
    liberties are indexed by that root."""
    n = size * size
    labels = np.full(n, n, np.int32)
    sizes, libs = np.zeros(n + 1, np.int32), np.zeros(n + 1, np.int32)
    for p in range(n):      # ascending: p is the min of a new group
        if board[p] == 0 or labels[p] != n:
            continue
        group, frontier, lib = {p}, [p], set()
        while frontier:
            x, y = divmod(frontier.pop(), size)
            for nx, ny in ((x + 1, y), (x - 1, y), (x, y + 1), (x, y - 1)):
                if not (0 <= nx < size and 0 <= ny < size):
                    continue
                r = nx * size + ny
                if board[r] == 0:
                    lib.add(r)
                elif board[r] == board[p] and r not in group:
                    group.add(r)
                    frontier.append(r)
        labels[list(group)] = p
        sizes[p], libs[p] = len(group), len(lib)
    return labels, sizes, libs


@pytest.mark.parametrize("size,moves", [
    (9, 0), (9, 10), (9, 30), (9, 60), (9, None), (19, None)],
    ids=lambda v: "snake" if v is None else str(v))
def test_labels_and_group_data_match_host_flood_fill(
        size, moves, monkeypatch):
    """The labeller that runs on the chip against a host oracle, on
    whole boards: sparse and dense random positions, and the longest
    chains a board can hold — the input that stresses
    ``compute_labels``' propagation (hooks + pointer jumps to a fixed
    point). ``group_data``'s sizes and liberty counts must equal the
    host's under BOTH formulations (dense: the TPU's; scatter: the
    CPU's)."""
    import jax

    boards = (worst_case_boards(size) if moves is None
              else random_boards(size, 6, moves, seed=moves))
    cfg = GoConfig(size=size)
    want = [host_groups(b, size) for b in boards]
    got = np.asarray(jax.vmap(lambda b: compute_labels(cfg, b))(boards))
    for row, (labels, _, _) in zip(got, want):
        np.testing.assert_array_equal(row, labels)
    try:
        for dense in ("1", "0"):
            monkeypatch.setenv("ROCALPHAGO_ENGINE_DENSE", dense)
            jaxgo._dense_engine.cache_clear()
            gd = jax.jit(jax.vmap(     # a fresh trace per formulation
                lambda b: jaxgo.group_data(cfg, b)))(boards)
            for i, (_, sizes, libs) in enumerate(want):
                np.testing.assert_array_equal(
                    np.asarray(gd.sizes[i]), sizes, f"dense={dense}")
                np.testing.assert_array_equal(
                    np.asarray(gd.lib_counts[i]), libs, f"dense={dense}")
    finally:
        monkeypatch.undo()
        jaxgo._dense_engine.cache_clear()


class TestUnit:
    def setup_method(self):
        self.cfg = GoConfig(size=5, komi=0.0)
        self.eng = GoEngine(self.cfg)

    def test_fresh_state(self):
        st = self.eng.init()
        mask = np.asarray(self.eng.legal_mask(st))
        assert mask.all()
        assert int(st.turn) == jaxgo.BLACK

    def test_capture_and_prisoners(self):
        st = self.eng.init()
        # B surrounds W at (1,1): flat idx = x*5+y
        for a in [5, 6, 1, 24, 11, 23, 7]:
            st = self.eng.step(st, np.int32(a))
        board = np.asarray(st.board).reshape(5, 5)
        assert board[1, 1] == 0  # captured
        assert np.asarray(st.prisoners).tolist() == [0, 1]

    def test_ko_banned_then_cleared(self):
        st = self.eng.init()
        seq = [(1, 0), (2, 0), (0, 1), (3, 1), (1, 2), (2, 2), (4, 4), (1, 1)]
        for x, y in seq:
            st = self.eng.step(st, np.int32(x * 5 + y))
        st = self.eng.step(st, np.int32(2 * 5 + 1))  # B captures → ko
        assert int(st.ko) == 1 * 5 + 1
        mask = np.asarray(self.eng.legal_mask(st))
        assert not mask[1 * 5 + 1]
        st = self.eng.step(st, np.int32(4 * 5 + 0))  # W elsewhere
        assert int(st.ko) == -1

    def test_two_passes_end_and_freeze(self):
        st = self.eng.init()
        st = self.eng.step(st, np.int32(12))
        st = self.eng.step(st, np.int32(25))
        st = self.eng.step(st, np.int32(25))
        assert bool(st.done)
        frozen = self.eng.step(st, np.int32(3))
        assert np.asarray(frozen.board).tolist() == np.asarray(
            st.board).tolist()
        assert not np.asarray(self.eng.legal_mask(st)).any()

    def test_occupied_action_degrades_to_pass(self):
        st = self.eng.init()
        st = self.eng.step(st, np.int32(12))
        st2 = self.eng.step(st, np.int32(12))  # W "plays" occupied point
        assert int(st2.turn) == jaxgo.BLACK
        assert int(st2.pass_count) == 1

    def test_vmap_batch(self):
        batch = 8
        sts = self.eng.init_batch(batch)
        actions = np.arange(batch, dtype=np.int32)
        sts = self.eng.vstep(sts, actions)
        boards = np.asarray(sts.board)
        for i in range(batch):
            assert boards[i, i] == jaxgo.BLACK
        masks = np.asarray(self.eng.vlegal_mask(sts))
        assert masks.shape == (batch, 26)
        for i in range(batch):
            assert not masks[i, i]

    # Found by seeded search over random 5x5 games: after this sequence,
    # flat action 19 recreates an earlier whole-board position while
    # simple ko does NOT ban it — a superko-only ban, exercising the
    # candidate-hash group-XOR path deterministically.
    SUPERKO_SEQ = [21, 15, 11, 5, 7, 0, 2, 1, 6, 22, 17, 23, 13, 16, 24,
                   18, 12, 10, 9, 20, 4, 21, 14, 3, 8, 19, 24, 22, 16, 0,
                   20, 19, 21, 5, 1, 23, 3, 18, 10, 0, 15, 5, 9, 10, 1, 2,
                   4, 3, 16, 14, 15, 8, 13, 20, 9, 11, 21, 17, 12, 6, 24,
                   19, 23, 17, 22, 14, 20, 4, 18, 1, 9, 19, 17, 14, 9]
    SUPERKO_BANNED = 19

    def test_superko_only_ban(self):
        cfg = GoConfig(size=5, komi=5.5, enforce_superko=True,
                       max_history=128)
        eng = GoEngine(cfg)
        st = eng.init()
        pst = pygo.GameState(size=5, komi=5.5, enforce_superko=True)
        for a in self.SUPERKO_SEQ:
            st = eng.step(st, np.int32(a))
            pst.do_move(divmod(a, 5))
        banned = self.SUPERKO_BANNED
        # oracle agrees this is a superko-only ban
        assert pst.is_positional_superko(divmod(banned, 5))
        assert pst.ko != divmod(banned, 5)
        assert not pst.is_suicide(divmod(banned, 5))
        assert not np.asarray(eng.legal_mask(st))[banned]

        # without superko enforcement the same move is legal
        cfg2 = GoConfig(size=5, komi=5.5, enforce_superko=False)
        eng2 = GoEngine(cfg2)
        st2 = eng2.init()
        for a in self.SUPERKO_SEQ:
            st2 = eng2.step(st2, np.int32(a))
        assert np.asarray(eng2.legal_mask(st2))[banned]
