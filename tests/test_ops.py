"""Pallas kernel differential tests (interpret mode on CPU CI; the
same check at 19×19 with ``interpret=False`` is
``scripts/chip_kernels.py``, run on the chip).

The kernel must be drop-in exact against its XLA twin; adversarial
shapes (the serpentine worst case that maximizes label-propagation
distance) are included so the static sweep bound is exercised, not
just typical sparse boards.
"""

import jax
import numpy as np
import pytest

from rocalphago_tpu.engine import pygo
from rocalphago_tpu.engine.jaxgo import GoConfig, compute_labels
from rocalphago_tpu.ops import pallas_labels

SIZE = 9
N = SIZE * SIZE


def xla_labels(boards):
    cfg = GoConfig(size=SIZE)
    return jax.vmap(lambda b: compute_labels(cfg, b))(boards)


def random_boards(batch, moves, seed):
    rng = np.random.default_rng(seed)
    out = np.zeros((batch, N), np.int8)
    for i in range(batch):
        st = pygo.GameState(size=SIZE, komi=5.5)
        for _ in range(moves):
            legal = st.get_legal_moves(include_eyes=False)
            if not legal or st.is_end_of_game:
                break
            st.do_move(legal[rng.integers(len(legal))])
        out[i] = np.asarray(st.board, np.int8).reshape(-1)
    return out


def single_file_snake(size: int):
    """A 1-wide boustrophedon snake: even rows full, odd rows a single
    connector stone at alternating ends — ONE group whose label must
    propagate along the whole path (the longest chain constructible on
    a board), the stress case for the kernel's static sweep bound."""
    b = np.zeros((size, size), np.int8)
    for x in range(size):
        if x % 2 == 0:
            b[x, :] = 1
        else:
            b[x, size - 1 if (x // 2) % 2 == 0 else 0] = 1
    return b.reshape(-1)


@pytest.mark.parametrize("moves", [0, 10, 30, 60])
def test_pallas_labels_match_xla_on_random_boards(moves):
    boards = random_boards(6, moves, seed=moves)
    got = np.asarray(pallas_labels(boards, SIZE, interpret=True))
    want = np.asarray(xla_labels(boards))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("size", [SIZE, 19])
def test_pallas_labels_serpentine_worst_case(size):
    """Longest-chain snakes (on 19×19 the path is ~190 stones) plus a
    solid board must label exactly — these exercise the static sweep
    bound far beyond typical sparse positions."""
    solid = np.ones((size * size,), np.int8)
    boards = np.stack([single_file_snake(size), solid,
                       -single_file_snake(size)]).astype(np.int8)
    got = np.asarray(pallas_labels(boards, size, interpret=True))
    cfg = GoConfig(size=size)
    want = np.asarray(
        jax.vmap(lambda b: compute_labels(cfg, b))(boards))
    np.testing.assert_array_equal(got, want)
    # each snake really is one group rooted at its min index
    for row in (0, 2):
        snake = got[row]
        stones = boards[row] != 0
        assert (snake[stones] == snake[stones].min()).all()
