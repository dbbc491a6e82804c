"""SGF corpus → training-data converter (device-batched encoding).

Parity: ``AlphaGo/preprocessing/game_converter.py::GameConverter``
(``convert_game``, ``sgfs_to_hdf5``, the ``run_game_converter`` CLI with
``--features/--directory/--recurse/--outfile``; SURVEY.md §3.4). The
reference encodes positions one at a time in host Python; here games are
replayed on host (rules bookkeeping) but positions are *encoded on
device in fixed-size batches* through the jitted 48-plane encoder — the
expensive planes (candidate analysis, ladders) run vectorized.

Native output is sharded ``.npz`` (uint8 NHWC states + int32 flat
actions + JSON manifest) for the prefetching input pipeline; an HDF5
writer in the reference's layout (uint8 NCHW ``states``/``actions``
datasets) is kept for interchange.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import warnings

import numpy as np

from rocalphago_tpu.data import native, sgf as sgflib
from rocalphago_tpu.engine import pygo
from rocalphago_tpu.engine.jaxgo import GoConfig, GoState, seed_labels
from rocalphago_tpu.features import DEFAULT_FEATURES, Preprocess

_ENCODE_BATCH = 128  # static batch for the jitted encoder (padded)


def pack_states(cfg: GoConfig, boards, turns, kos, steps, ages) -> GoState:
    """Assemble a batched GoState from raw numpy fields (hash/history
    zeroed — converters run with superko off, so legality inside the
    encoder never consults them). The carried labels are seeded with
    one compiled batched fill (:func:`jaxgo.seed_labels`)."""
    import jax.numpy as jnp
    b = len(boards)
    n = cfg.num_points
    state = GoState(
        board=jnp.asarray(np.asarray(boards, np.int8)),
        turn=jnp.asarray(np.asarray(turns, np.int8)),
        ko=jnp.asarray(np.asarray(kos, np.int32)),
        pass_count=jnp.zeros((b,), jnp.int8),
        done=jnp.zeros((b,), jnp.bool_),
        step_count=jnp.asarray(np.asarray(steps, np.int32)),
        hash=jnp.zeros((b, 2), jnp.uint32),
        hash_history=jnp.zeros((b, cfg.max_history, 2), jnp.uint32),
        stone_ages=jnp.asarray(np.asarray(ages, np.int32)),
        prisoners=jnp.zeros((b, 2), jnp.int32),
        labels=jnp.full((b, n), n, jnp.int32),
    )
    return seed_labels(cfg, state)


class GameConverter:
    """Replay SGF games and emit (encoded state, expert action) pairs."""

    def __init__(self, feature_list=DEFAULT_FEATURES, board_size: int = 19,
                 ladder_depth: int = 40, ladder_lanes: int = 16,
                 ladder_chase_slots: int = 4):
        self.board_size = board_size
        self.cfg = GoConfig(size=board_size, enforce_superko=False,
                            max_history=8)
        self.pre = Preprocess(feature_list, cfg=self.cfg,
                              ladder_depth=ladder_depth,
                              ladder_lanes=ladder_lanes,
                              ladder_chase_slots=ladder_chase_slots)
        self.feature_list = tuple(feature_list)

    # ------------------------------------------------------------ encoding

    def _encode_fields(self, fields):
        """fields: list of (board, turn, ko, step, ages) → [n,s,s,F]
        uint8, padding the jit batch to a static size."""
        out = []
        for i in range(0, len(fields), _ENCODE_BATCH):
            chunk = fields[i:i + _ENCODE_BATCH]
            pad = _ENCODE_BATCH - len(chunk)
            rows = chunk + [chunk[-1]] * pad
            st = pack_states(self.cfg, *map(list, zip(*rows)))
            t = np.asarray(self.pre.states_to_tensor(st))
            out.append(t[:len(chunk)])
        planes = np.concatenate(out, axis=0)
        return (planes > 0.5).astype(np.uint8)

    def convert_game(self, sgf_text: str, include_passes: bool = False):
        """One game → (states uint8 [n,s,s,F] NHWC, actions int32 [n]).

        Positions whose move is a pass are dropped unless
        ``include_passes`` (the policy output space is board points, as
        in the reference; pass handling lives at the agent layer).
        Rules replay runs through the native C++ replayer when built
        (exact pygo parity; see ``native/goreplay.cpp``), else pygo.
        """
        game = sgflib.parse(sgf_text)
        if game.size != self.board_size:
            raise sgflib.SGFError(
                f"board size {game.size} != converter size "
                f"{self.board_size}")
        if native.available():
            return self._convert_game_native(game, include_passes)
        n = self.cfg.num_points
        fields, actions = [], []
        for st, move, player in sgflib.replay(game):
            if move is None and not include_passes:
                continue
            if player != st.current_player:
                # out-of-turn move (free placement SGF) — skip position
                continue
            # snapshot with copies: pygo mutates stone_ages in place as
            # the generator advances, so a view here would silently
            # give every position the END-of-game ages (caught by the
            # native-replayer differential test)
            fields.append((
                np.array(st.board, np.int8).reshape(-1),
                np.int8(st.current_player),
                np.int32(-1 if st.ko is None
                         else st.ko[0] * game.size + st.ko[1]),
                np.int32(st.turns_played),
                np.array(st.stone_ages, np.int32).reshape(-1),
            ))
            actions.append(n if move is None
                           else move[0] * game.size + move[1])
        if not fields:
            return (np.zeros((0, game.size, game.size,
                              self.pre.output_dim), np.uint8),
                    np.zeros((0,), np.int32))
        return (self._encode_fields(fields),
                np.asarray(actions, np.int32))

    def _convert_game_native(self, game, include_passes: bool):
        size = game.size
        n = self.cfg.num_points
        flat = lambda p: p[0] * size + p[1]  # noqa: E731
        moves = np.asarray([n if mv is None else flat(mv)
                            for _, mv in game.moves], np.int32)
        colors = np.asarray([c for c, _ in game.moves], np.int8)
        boards, to_move, kos, steps, ages = native.replay_arrays(
            size, [flat(p) for p in game.setup_black],
            [flat(p) for p in game.setup_white], moves, colors)
        keep = [t for t in range(len(moves))
                if (include_passes or moves[t] != n)
                and colors[t] == to_move[t]]
        if not keep:
            return (np.zeros((0, size, size, self.pre.output_dim),
                             np.uint8), np.zeros((0,), np.int32))
        fields = [(boards[t], np.int8(to_move[t]), np.int32(kos[t]),
                   np.int32(steps[t]), ages[t]) for t in keep]
        return (self._encode_fields(fields),
                np.asarray([moves[t] for t in keep], np.int32))

    # ------------------------------------------------------------- corpora

    @staticmethod
    def _iter_sgf_files(directory: str, recurse: bool):
        if recurse:
            for root, _, names in sorted(os.walk(directory)):
                for name in sorted(names):
                    if name.lower().endswith(".sgf"):
                        yield os.path.join(root, name)
        else:
            for name in sorted(os.listdir(directory)):
                if name.lower().endswith(".sgf"):
                    yield os.path.join(directory, name)

    def sgfs_to_shards(self, files, out_prefix: str,
                       shard_size: int = 8192,
                       ignore_errors: bool = True) -> dict:
        """Convert SGF files to ``{out_prefix}-NNNNN.npz`` shards plus a
        ``{out_prefix}-manifest.json``. Corrupt or illegal games are
        skipped with a warning (reference ``ignore_errors`` behavior).
        """
        parent = os.path.dirname(out_prefix)
        if parent:
            os.makedirs(parent, exist_ok=True)
        buf_s, buf_a = [], []
        counts, errors = [], []
        n_shards = n_positions = n_games = 0

        def flush():
            nonlocal n_shards, n_positions
            if not buf_s:
                return
            states = np.concatenate(buf_s, axis=0)
            actions = np.concatenate(buf_a, axis=0)
            path = f"{out_prefix}-{n_shards:05d}.npz"
            np.savez_compressed(path, states=states, actions=actions)
            counts.append(len(actions))
            n_shards += 1
            n_positions += len(actions)
            buf_s.clear()
            buf_a.clear()

        for path in files:
            try:
                with open(path, "r", errors="replace") as f:
                    states, actions = self.convert_game(f.read())
            except (sgflib.SGFError, pygo.IllegalMove, OSError,
                    ValueError) as e:
                if not ignore_errors:
                    raise
                errors.append({"file": path, "error": str(e)})
                warnings.warn(f"skipping {path}: {e}")
                continue
            if len(actions) == 0:
                continue
            n_games += 1
            buf_s.append(states)
            buf_a.append(actions)
            if sum(len(a) for a in buf_a) >= shard_size:
                flush()
        flush()

        manifest = {
            "format": "rocalphago_tpu/npz-shards/v1",
            "board_size": self.board_size,
            "features": list(self.feature_list),
            "planes": self.pre.output_dim,
            "layout": "NHWC",
            "num_shards": n_shards,
            "num_positions": n_positions,
            "num_games": n_games,
            "shard_counts": counts,
            "errors": errors,
        }
        with open(f"{out_prefix}-manifest.json", "w") as f:
            json.dump(manifest, f, indent=2)
        return manifest

    def sgfs_to_hdf5(self, files, outfile: str,
                     ignore_errors: bool = True) -> int:
        """Reference-layout HDF5: growable uint8 ``states`` (n, F, s, s)
        NCHW + int32 ``actions`` (n,), feature list as a file attr."""
        import h5py
        parent = os.path.dirname(outfile)
        if parent:
            os.makedirs(parent, exist_ok=True)
        n_positions = 0
        with h5py.File(outfile, "w") as h5:
            s = self.board_size
            states = h5.create_dataset(
                "states", shape=(0, self.pre.output_dim, s, s),
                maxshape=(None, self.pre.output_dim, s, s),
                dtype=np.uint8, chunks=(64, self.pre.output_dim, s, s),
                compression="lzf")
            acts = h5.create_dataset(
                "actions", shape=(0,), maxshape=(None,), dtype=np.int32,
                chunks=(1024,))
            h5.attrs["features"] = ",".join(self.feature_list)
            h5.attrs["board_size"] = s
            for path in files:
                try:
                    with open(path, "r", errors="replace") as f:
                        st, ac = self.convert_game(f.read())
                except (sgflib.SGFError, pygo.IllegalMove, OSError,
                        ValueError) as e:
                    if not ignore_errors:
                        raise
                    warnings.warn(f"skipping {path}: {e}")
                    continue
                if len(ac) == 0:
                    continue
                k = len(ac)
                states.resize(n_positions + k, axis=0)
                acts.resize(n_positions + k, axis=0)
                states[n_positions:] = st.transpose(0, 3, 1, 2)  # → NCHW
                acts[n_positions:] = ac
                n_positions += k
        return n_positions


def game_ids(game) -> list:
    """One game as move ids: a board point is ``x·size + y`` (the
    action the plane converter emits), a pass ``size²``."""
    n = game.size * game.size
    return [n if move is None else move[0] * game.size + move[1]
            for _, move in game.moves]


def sgfs_to_sequences(files, out_prefix: str, seq_len: int,
                      board_size: int = 19, shard_size: int = 1024,
                      ignore_errors: bool = True) -> dict:
    """Convert SGF files to packed id rows for a sequence policy
    (``models/seqpolicy.py``): games end to end, a separator
    (``size² + 1``) after each, cut into rows of ``seq_len`` inputs
    whose label at each token is the next token. Same shard files and
    manifest as :meth:`GameConverter.sgfs_to_shards` — ``states`` are
    the id rows ``[n, seq_len]``, ``actions`` the next ids, and
    ``planes`` is 0 — so ``ShardedDataset`` and the SL trainer read
    them as they read plane shards. A tail shorter than a row is
    dropped."""
    parent = os.path.dirname(out_prefix)
    if parent:
        os.makedirs(parent, exist_ok=True)
    separator = board_size * board_size + 1
    stream, rows, counts, errors = [], [], [], []
    n_games = 0

    def flush(everything: bool = False):
        while rows and (everything or len(rows) >= shard_size):
            part = np.asarray(rows[:shard_size], np.int32)
            del rows[:shard_size]
            np.savez_compressed(
                f"{out_prefix}-{len(counts):05d}.npz",
                states=part[:, :-1], actions=part[:, 1:])
            counts.append(len(part))

    for path in files:
        try:
            with open(path, "r", errors="replace") as f:
                game = sgflib.parse(f.read())
            if game.size != board_size:
                raise sgflib.SGFError(
                    f"board size {game.size} != {board_size}")
        except (sgflib.SGFError, OSError, ValueError) as e:
            if not ignore_errors:
                raise
            errors.append({"file": path, "error": str(e)})
            warnings.warn(f"skipping {path}: {e}")
            continue
        n_games += 1
        stream.extend(game_ids(game) + [separator])
        # a row is seq_len + 1 ids; the next row starts at its last
        while len(stream) > seq_len:
            rows.append(stream[:seq_len + 1])
            del stream[:seq_len]
        flush()
    flush(everything=True)
    manifest = {
        "format": "rocalphago_tpu/npz-shards/v1",
        "board_size": board_size, "features": [], "planes": 0,
        "layout": "id rows", "seq_len": seq_len,
        "num_shards": len(counts), "num_positions": sum(counts),
        "num_games": n_games, "shard_counts": counts,
        "errors": errors,
    }
    with open(f"{out_prefix}-manifest.json", "w") as f:
        json.dump(manifest, f, indent=2)
    return manifest


def run_game_converter(argv=None):
    """CLI mirroring the reference's ``run_game_converter``."""
    ap = argparse.ArgumentParser(
        description="Convert SGF games to training data")
    ap.add_argument("--directory", "-d", required=True)
    ap.add_argument("--outfile", "-o", required=True,
                    help="shard prefix (npz) or .h5 path (hdf5)")
    ap.add_argument("--recurse", "-R", action="store_true")
    ap.add_argument("--features", default=",".join(DEFAULT_FEATURES))
    ap.add_argument("--size", type=int, default=19)
    ap.add_argument("--format", choices=("npz", "hdf5"), default="npz")
    ap.add_argument("--shard-size", type=int, default=8192)
    ap.add_argument("--sequence", type=int, default=None, metavar="LEN",
                    help="write packed move-id rows of LEN tokens for "
                         "a sequence policy instead of feature planes "
                         "(npz shards; --shard-size counts rows)")
    args = ap.parse_args(argv)

    if args.sequence:
        manifest = sgfs_to_sequences(
            GameConverter._iter_sgf_files(args.directory,
                                          args.recurse),
            args.outfile, args.sequence, board_size=args.size,
            shard_size=args.shard_size)
        print(json.dumps({k: manifest[k] for k in
                          ("num_shards", "num_positions", "num_games")}))
        return
    conv = GameConverter(tuple(args.features.split(",")),
                         board_size=args.size)
    files = conv._iter_sgf_files(args.directory, args.recurse)
    if args.format == "npz":
        manifest = conv.sgfs_to_shards(files, args.outfile,
                                       shard_size=args.shard_size)
        print(json.dumps({k: manifest[k] for k in
                          ("num_shards", "num_positions", "num_games")}))
    else:
        n = conv.sgfs_to_hdf5(files, args.outfile)
        print(json.dumps({"num_positions": n}))


if __name__ == "__main__":
    run_game_converter(sys.argv[1:])
