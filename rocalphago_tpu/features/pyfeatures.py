"""Host-side oracle feature encoder (slow, obviously-correct).

Computes the AlphaGo 48-plane set from a :class:`pygo.GameState` by
literal candidate-move simulation (``copy()`` + ``do_move``), the way
the reference's ``AlphaGo/preprocessing/preprocess.py::Preprocess``
does. Exists purely as the correctness oracle for the vectorized
device encoder (:mod:`rocalphago_tpu.features.planes`) — plane-by-plane
comparison in ``tests/test_features.py`` — and is not on any hot path.

Plane layout (48 total, in ``DEFAULT_FEATURES`` order):

========================  ======  =====================================
feature                   planes  semantics (all relative to player to
                                  move)
========================  ======  =====================================
board                     3       own stones / opponent stones / empty
ones                      1       constant 1
turns_since               8       age of stone: 0..6, 7+
liberties                 8       group liberties: 1..7, 8+
capture_size              8       opponent stones a legal move would
                                  capture: 0..6, 7+
self_atari_size           8       own-group size if the move leaves it
                                  in self-atari: 1..7, 8+
liberties_after           8       own-group liberties after the move:
                                  1..7, 8+
ladder_capture            1       move is a working ladder capture
ladder_escape             1       move is a working ladder escape
sensibleness              1       legal and does not fill own true eye
zeros                     1       constant 0
========================  ======  =====================================

One extra plane-group exists beyond the 48: ``color`` (1 plane,
constant 1 when black is to move) — the AlphaGo *value* network's 49th
input plane. Komi breaks color symmetry, so without it a value net
cannot distinguish a position from its color-swapped mirror (outcomes
differ by 2·komi). ``VALUE_FEATURES`` is the 49-plane value-net set.
"""

from __future__ import annotations

import os

import numpy as np

from rocalphago_tpu.engine import pygo

DEFAULT_FEATURES = (
    "board", "ones", "turns_since", "liberties", "capture_size",
    "self_atari_size", "liberties_after", "ladder_capture",
    "ladder_escape", "sensibleness", "zeros",
)

# the value net's 49-plane input: the 48 policy planes + player color
VALUE_FEATURES = DEFAULT_FEATURES + ("color",)

#: the two handcrafted ladder plane groups — ~88% of encode cost on
#: CPU, 94% of a self-play ply on the chip (PERF.md §5, by hand) —
#: the target of the ladder-free
#: self-play configuration (docs/PERFORMANCE.md "Ladder-free encode")
LADDER_FEATURES = ("ladder_capture", "ladder_escape")


def ladder_planes_enabled() -> bool:
    """ROCALPHAGO_LADDER_PLANES: ``off``/``0`` drops both handcrafted
    ladder planes from NEW feature specs (the KataGo route: the net
    recovers the signal via global pooling + aux heads instead of the
    encoder paying for it every position). Default on — the shipped
    48/49-plane encoding. Read where specs are BORN (models/specs.py
    CLI, fresh-net defaults); nets loaded from a saved spec keep the
    feature list they were trained with regardless of this knob."""
    return os.environ.get("ROCALPHAGO_LADDER_PLANES", "on") \
        not in ("off", "0")


def active_features(features) -> tuple:
    """``features`` minus the ladder plane groups when
    ``ROCALPHAGO_LADDER_PLANES=off`` — unchanged (same tuple) when the
    knob is on, so the defaults-on path is bit-identical."""
    if ladder_planes_enabled():
        return tuple(features)
    return tuple(f for f in features if f not in LADDER_FEATURES)


def default_features() -> tuple:
    """Knob-aware policy feature set (48 planes, 46 ladder-free)."""
    return active_features(DEFAULT_FEATURES)


def value_features() -> tuple:
    """Knob-aware value feature set (49 planes, 47 ladder-free)."""
    return active_features(VALUE_FEATURES)

FEATURE_PLANES = {
    "board": 3, "ones": 1, "turns_since": 8, "liberties": 8,
    "capture_size": 8, "self_atari_size": 8, "liberties_after": 8,
    "ladder_capture": 1, "ladder_escape": 1, "sensibleness": 1,
    "zeros": 1, "color": 1,
}


def output_planes(features=DEFAULT_FEATURES) -> int:
    return sum(FEATURE_PLANES[f] for f in features)


def _one_hot8(plane_stack, x, y, value, lo):
    """Set plane ``clip(value - lo, 0, 7)`` at (x, y)."""
    plane_stack[x, y, min(max(value - lo, 0), 7)] = 1.0


def state_to_planes(st: pygo.GameState,
                    features=DEFAULT_FEATURES,
                    ladder_depth: int = 40) -> np.ndarray:
    """Encode ``st`` → float32 ``[size, size, F]`` (NHWC, TPU layout)."""
    size, me = st.size, st.current_player
    legal = {m for m in st.get_legal_moves(include_eyes=True)}
    out = []
    for name in features:
        f = np.zeros((size, size, FEATURE_PLANES[name]), np.float32)
        if name == "board":
            f[:, :, 0] = st.board == me
            f[:, :, 1] = st.board == -me
            f[:, :, 2] = st.board == 0
        elif name == "ones":
            f[:, :, 0] = 1.0
        elif name == "turns_since":
            for x in range(size):
                for y in range(size):
                    if st.board[x, y] != 0 and st.stone_ages[x, y] >= 0:
                        age = st.turns_played - 1 - st.stone_ages[x, y]
                        _one_hot8(f, x, y, age, 0)
        elif name == "liberties":
            for x in range(size):
                for y in range(size):
                    if st.board[x, y] != 0:
                        _one_hot8(f, x, y, st.liberty_count((x, y)), 1)
        elif name in ("capture_size", "self_atari_size", "liberties_after"):
            for (x, y) in legal:
                sim = st.copy()
                before = (sim.num_white_prisoners if me == pygo.BLACK
                          else sim.num_black_prisoners)
                sim.do_move((x, y))
                if name == "capture_size":
                    after = (sim.num_white_prisoners if me == pygo.BLACK
                             else sim.num_black_prisoners)
                    _one_hot8(f, x, y, after - before, 0)
                else:
                    stones, libs = sim.get_group((x, y))
                    if name == "liberties_after":
                        _one_hot8(f, x, y, len(libs), 1)
                    elif len(libs) == 1:
                        _one_hot8(f, x, y, len(stones), 1)
        elif name == "ladder_capture":
            for (x, y) in legal:
                if is_ladder_capture(st, (x, y), ladder_depth):
                    f[x, y, 0] = 1.0
        elif name == "ladder_escape":
            for (x, y) in legal:
                if is_ladder_escape(st, (x, y), ladder_depth):
                    f[x, y, 0] = 1.0
        elif name == "sensibleness":
            for (x, y) in legal:
                if not st.is_eye((x, y), me):
                    f[x, y, 0] = 1.0
        elif name == "zeros":
            pass
        elif name == "color":
            f[:, :, 0] = 1.0 if me == pygo.BLACK else 0.0
        else:
            raise KeyError(f"unknown feature {name!r}")
        out.append(f)
    return np.concatenate(out, axis=-1)


# ---------------------------------------------------------------- ladders


def _adjacent_groups(st: pygo.GameState, stones, color):
    """Distinct groups of ``color`` orthogonally adjacent to ``stones``
    (as a list of (stones, liberties) with duplicates removed)."""
    seen, out = set(), []
    for s in stones:
        for nb in st.get_neighbors(s):
            if st.board[nb] == color and nb not in seen:
                g_stones, g_libs = st.get_group(nb)
                seen |= g_stones
                out.append((g_stones, g_libs))
    return out


def ladder_captured(st: pygo.GameState, prey_point, depth: int) -> bool:
    """Full-branching depth-limited ladder read: is the group at
    ``prey_point`` captured with ``st.current_player`` to move?"""
    if depth <= 0:
        return False
    if st.board[prey_point] == 0:
        return True
    prey_color = st.board[prey_point]
    stones, libs = st.get_group(prey_point)
    to_move = st.current_player

    if to_move == prey_color:  # escaper
        if len(libs) >= 3:
            return False
        options = [lib for lib in libs if st.is_legal(lib)]
        for g_stones, g_libs in _adjacent_groups(st, stones, -prey_color):
            if len(g_libs) == 1:
                (cap,) = g_libs
                if st.is_legal(cap):
                    options.append(cap)
        for move in options:
            sim = st.copy()
            sim.do_move(move)
            if not ladder_captured(sim, prey_point, depth - 1):
                return False
        return True
    else:  # chaser
        if len(libs) >= 3:
            return False
        if len(libs) == 1:
            (last,) = libs
            return st.is_legal(last)
        for lib in libs:
            if st.is_legal(lib):
                sim = st.copy()
                sim.do_move(lib)
                if ladder_captured(sim, prey_point, depth - 1):
                    return True
        return False


def is_ladder_capture(st: pygo.GameState, action, depth: int = 40) -> bool:
    """Playing ``action`` starts a working ladder on an adjacent
    opponent group that currently has exactly two liberties."""
    me = st.current_player
    for nb in st.get_neighbors(action):
        if st.board[nb] == -me:
            _, libs = st.get_group(nb)
            if len(libs) == 2 and action in libs:
                sim = st.copy()
                sim.do_move(action)
                if ladder_captured(sim, nb, depth):
                    return True
    return False


def is_ladder_escape(st: pygo.GameState, action, depth: int = 40) -> bool:
    """Playing ``action`` rescues an own group in atari from a ladder
    (extension at its last liberty that then survives the read)."""
    me = st.current_player
    for nb in st.get_neighbors(action):
        if st.board[nb] == me:
            _, libs = st.get_group(nb)
            if len(libs) == 1 and action in libs:
                sim = st.copy()
                sim.do_move(action)
                if not ladder_captured(sim, nb, depth):
                    return True
    return False
