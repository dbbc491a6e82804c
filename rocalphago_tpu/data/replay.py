"""Replay-buffer service: the hand-off point between self-play actors
and the sharded learner (docs/SCALE.md).

A bounded, thread-safe ring of finished self-play batches
(:class:`ZeroGames`). Producers (``training/actor.py``) ``put``
batches — blocking when full (pacing) or evicting the oldest
(free-run) — and consumers take them out either FIFO
(:meth:`ReplayBuffer.next_batch`, the bit-exact lockstep path) or by
prioritized-recency draw (:meth:`ReplayBuffer.sample`, geometric from
the newest entry, which approximates the KataGo-style sliding window
without ever blocking the learner on a specific game).

Durability and transport:

- crash-safe spill: with ``spill_dir`` set, every accepted entry is
  persisted via :func:`rocalphago_tpu.runtime.atomic.atomic_write_json`
  (tmp + fsync + rename — a crash never leaves a torn file) and
  removed again when consumed or evicted; :meth:`ReplayBuffer.restore`
  reloads whatever survived, skipping anything unreadable.
- tolerant-JSONL ingest: :class:`JsonlIngester` tails ``*.jsonl``
  shards written by out-of-process actors (one game record per line),
  consuming only newline-terminated lines so a writer crashed
  mid-line never poisons the stream — the torn tail is simply re-read
  on the next poll once completed.

Observability (all emitted OUTSIDE the buffer lock):
``replay_fill_games`` gauge, ``replay_ingest_games_total`` counter,
``replay_ingest_per_min`` gauge, ``replay_sample_staleness_seconds``
histogram (age of each consumed/sampled entry),
``replay_evicted_games_total`` + ``replay_spilled_total`` counters.
Blocking waits are tagged :func:`rocalphago_tpu.runtime.watchdog
.waiting_on` ``("replay_fill")`` so a starving learner's stall events
are distinguishable from a hang.

This module is deliberately jax-free (numpy only): report scripts and
out-of-process ingest helpers can import it without touching a
backend.
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import time
from typing import NamedTuple

import numpy as np

from rocalphago_tpu.analysis import lockcheck
from rocalphago_tpu.obs import registry
from rocalphago_tpu.runtime import atomic, watchdog

CAPACITY_ENV = "ROCALPHAGO_REPLAY_CAPACITY"
SAMPLE_P_ENV = "ROCALPHAGO_REPLAY_SAMPLE_P"


def default_capacity() -> int:
    """Buffer capacity in entries (one entry = one self-play batch)."""
    return int(os.environ.get(CAPACITY_ENV, "8"))


def default_sample_p() -> float:
    """Geometric recency parameter for :meth:`ReplayBuffer.sample`."""
    return float(os.environ.get(SAMPLE_P_ENV, "0.5"))


#: Record-schema version written by :func:`games_to_record`. v1:
#: the five core fields, no ``schema`` key. v2: adds the OPTIONAL
#: self-play-economics fields (``full``/``ownership``/``score``,
#: present only when recorded). Readers accept any version ≤ current
#: (absent optionals synthesize as None); records from a FUTURE
#: schema raise :class:`UnknownSchemaError` so ingest can count and
#: skip them instead of mis-reading half-understood data.
RECORD_SCHEMA = 2


class UnknownSchemaError(ValueError):
    """Record written by a newer schema than this reader knows."""


class ZeroGames(NamedTuple):
    """One finished self-play batch — the unit the buffer stores.

    Raw recorder dtypes, exactly as ``training.zero``'s self-play
    returns them (the learner does its own float casts, so a
    host round-trip through the buffer stays bit-exact):

    - ``actions``: ``[T, B]`` int32 move indices per ply
    - ``live``: ``[T, B]`` bool — ply happened before the game ended
    - ``visits``: ``[T, B, A]`` visit counts (int32) or improved-
      policy targets (float32, gumbel mode; normalized pruned
      targets with forced-playout pruning)
    - ``winners``: ``[B]`` int32 (+1 black / -1 white / 0 draw)
    - ``finished``: ``[B]`` bool — game ended by two passes

    Self-play-economics fields (schema v2; ``None`` when the game was
    generated with the flags off — v1 records load with all three
    None):

    - ``full``: ``[T, B]`` bool — ply ran a FULL search (playout-cap
      randomization; only these plies carry policy targets)
    - ``ownership``: ``[B, N]`` int8 terminal ownership labels
      (black-positive; :func:`rocalphago_tpu.engine.jaxgo
      .terminal_labels`)
    - ``score``: ``[B]`` float32 terminal score margins (black −
      white, komi included)
    """

    actions: np.ndarray
    live: np.ndarray
    visits: np.ndarray
    winners: np.ndarray
    finished: np.ndarray
    full: np.ndarray | None = None
    ownership: np.ndarray | None = None
    score: np.ndarray | None = None


class ReplayEntry(NamedTuple):
    """A buffered batch plus its provenance: ``seq`` (ingest order),
    ``version`` (params snapshot that played it — staleness = learner
    version minus this) and ``t_ingest`` (monotonic, for age)."""

    seq: int
    version: int
    games: ZeroGames
    t_ingest: float


def compute_game_id(games: ZeroGames) -> str:
    """Content-hash identity of one batch: sha256 over every
    present field's name, dtype, shape and raw bytes (16 hex chars).

    The id is a pure function of the game CONTENT — transport
    metadata (``version``/``seq``) is excluded — so the same batch
    re-encoded, re-shipped after an ambiguous ack, re-read after a
    shard rotation or re-spilled under a fresh sequence number hashes
    to the same id. That property is what lets every dedup window
    (replaynet's server, :class:`JsonlIngester`) collapse
    at-least-once delivery into effectively exactly-once."""
    h = hashlib.sha256()
    for name, arr in zip(ZeroGames._fields, games):
        if arr is None:
            continue
        a = np.asarray(arr)
        h.update(name.encode("utf-8"))
        h.update(str(a.dtype).encode("utf-8"))
        h.update(str(a.shape).encode("utf-8"))
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def games_to_record(games: ZeroGames, version: int = 0,
                    seq: int = 0, game_id: str | None = None) -> dict:
    """JSON-serializable record preserving shapes and dtypes.
    Optional (None) fields are simply absent from the record — a
    flags-off game writes exactly the v1 field set plus the
    ``schema`` tag. Every record carries its content-hash
    ``game_id`` (:func:`compute_game_id`; pass it in when already
    known to skip the rehash)."""
    rec = {"version": int(version), "seq": int(seq),
           "schema": RECORD_SCHEMA,
           "game_id": game_id or compute_game_id(games)}
    for name, arr in zip(ZeroGames._fields, games):
        if arr is None:
            continue
        a = np.asarray(arr)
        rec[name] = a.tolist()
        rec[name + "_dtype"] = str(a.dtype)
    return rec


def record_game_id(rec: dict, games: ZeroGames | None = None) -> str:
    """A record's ``game_id`` — the embedded one when present, else
    recomputed from ``games`` (the parsed batch; older records wrote
    no id, and the content hash is recomputable by design)."""
    gid = rec.get("game_id")
    if gid:
        return str(gid)
    if games is None:
        games, _ = record_to_games(rec)
    return compute_game_id(games)


def record_to_games(rec: dict) -> tuple[ZeroGames, int]:
    """Inverse of :func:`games_to_record`; raises ``KeyError`` /
    ``TypeError`` / ``ValueError`` on malformed records (callers
    treat those as torn input and skip). v1 records (no ``schema``
    key) and v2 records missing optional fields synthesize those
    fields as None; a FUTURE schema raises
    :class:`UnknownSchemaError` (counted separately by
    :class:`JsonlIngester` — unknown ≠ torn)."""
    schema = int(rec.get("schema", 1))
    if schema > RECORD_SCHEMA:
        raise UnknownSchemaError(
            f"record schema {schema} is newer than this reader's "
            f"{RECORD_SCHEMA}")
    arrs = []
    for name in ZeroGames._fields:
        if name in ZeroGames._field_defaults and name not in rec:
            arrs.append(None)
            continue
        arrs.append(np.asarray(rec[name],
                               dtype=np.dtype(rec[name + "_dtype"])))
    return ZeroGames(*arrs), int(rec.get("version", 0))


class ReplayBuffer:
    """Bounded thread-safe ring of :class:`ReplayEntry`.

    ``capacity`` is in entries; ``put(block=True)`` paces producers
    (waits for a FIFO consumer to make room), ``put(block=False)``
    evicts the oldest entry instead — the right mode when the
    consumer is :meth:`sample`, which never removes entries.
    """

    def __init__(self, capacity: int | None = None, *,
                 sample_p: float | None = None,
                 spill_dir: str | None = None, seed: int = 0):
        self.capacity = (default_capacity() if capacity is None
                         else int(capacity))
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.sample_p = (default_sample_p() if sample_p is None
                         else float(sample_p))
        if not 0.0 < self.sample_p <= 1.0:
            raise ValueError(f"sample_p must be in (0, 1], "
                             f"got {self.sample_p}")
        self.spill_dir = spill_dir
        self._cond = lockcheck.make_condition("ReplayBuffer._cond")
        self._entries: list[ReplayEntry] = []  # guarded-by: self._cond
        self._seq = 0                          # guarded-by: self._cond
        self._closed = False                   # guarded-by: self._cond
        self._ingested = 0                     # guarded-by: self._cond
        self._t_first: float | None = None     # guarded-by: self._cond
        self._rng = np.random.default_rng(seed)  # guarded-by: self._cond
        # spill filenames carry an incarnation tag so THIS buffer's
        # files can never collide with (or be mistaken for) a dead
        # incarnation's leftovers: restore() ingests only foreign
        # tags, and a live put during restore can't overwrite the
        # old file restore is about to read
        self._spill_tag = (f"{os.getpid():x}."
                           f"{int(time.time() * 1e3) & 0xffffffff:08x}")
        if spill_dir:
            os.makedirs(spill_dir, exist_ok=True)

    # ------------------------------------------------------- producers

    def put(self, games: ZeroGames, version: int = 0,
            block: bool = False, timeout: float | None = None,
            evict: bool = True) -> bool:
        """Append a batch; True if accepted, False on timeout/closed.

        ``block=True`` waits for room (producer pacing — bounds
        sample staleness by construction); ``block=False`` evicts the
        oldest entry when full. ``evict=False`` turns a full
        non-blocking put into a plain refusal (return False, buffer
        untouched) — the mode a LOSSLESS ingest path needs: the
        replay service answers ``overload`` with ``retry_after_s``
        instead of silently dropping the oldest game.
        """
        games = ZeroGames(*(None if x is None else np.asarray(x)
                            for x in games))
        n_games = int(games.winners.shape[0])
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        evict_seqs: list[int] = []
        evicted_games = 0
        with self._cond:
            while (block and not self._closed
                   and len(self._entries) >= self.capacity):
                rem = (None if deadline is None
                       else deadline - time.monotonic())
                if rem is not None and rem <= 0:
                    return False
                self._cond.wait(rem)
            if self._closed:
                return False
            if not evict and len(self._entries) >= self.capacity:
                return False
            while len(self._entries) >= self.capacity:
                old = self._entries.pop(0)
                evict_seqs.append(old.seq)
                evicted_games += int(old.games.winners.shape[0])
            entry = ReplayEntry(self._seq, int(version), games,
                                time.monotonic())
            self._seq += 1
            self._entries.append(entry)
            self._ingested += n_games
            if self._t_first is None:
                self._t_first = time.monotonic()
            fill = sum(int(e.games.winners.shape[0])
                       for e in self._entries)
            total, t_first = self._ingested, self._t_first
            self._cond.notify_all()
        if self.spill_dir:
            atomic.atomic_write_json(
                self._spill_path(entry.seq),
                games_to_record(games, entry.version, entry.seq),
                indent=None)
            registry.counter("replay_spilled_total").inc()
            for seq in evict_seqs:
                self._unspill(seq)
        registry.gauge("replay_fill_games").set(fill)
        registry.counter("replay_ingest_games_total").inc(n_games)
        minutes = max(time.monotonic() - t_first, 1e-9) / 60.0
        registry.gauge("replay_ingest_per_min").set(total / minutes)
        if evicted_games:
            registry.counter("replay_evicted_games_total").inc(
                evicted_games)
        return True

    def requeue(self, entry: ReplayEntry) -> bool:
        """Put a consumed entry BACK at the head of the FIFO.

        The take-side loss guard: when the replay service pops an
        entry for ``next_batch`` and then fails to send the reply
        (peer died mid-response), the entry is requeued — same seq,
        same position — and re-spilled, so the failed delivery costs
        nothing. Capacity is deliberately allowed to overshoot by
        the requeued entry (dropping here would be the exact loss
        the guard exists to prevent). False only when closed.
        """
        with self._cond:
            if self._closed:
                return False
            self._entries.insert(0, entry)
            fill = sum(int(e.games.winners.shape[0])
                       for e in self._entries)
            self._cond.notify_all()
        if self.spill_dir:
            atomic.atomic_write_json(
                self._spill_path(entry.seq),
                games_to_record(entry.games, entry.version, entry.seq),
                indent=None)
        registry.gauge("replay_fill_games").set(fill)
        return True

    # ------------------------------------------------------- consumers

    def next_batch(self, timeout: float | None = None) \
            -> ReplayEntry | None:
        """FIFO-pop the oldest entry (the lockstep/bit-exact path).

        Blocks until an entry arrives; None on timeout or when the
        buffer is closed and drained.
        """
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with watchdog.waiting_on("replay_fill"):
            with self._cond:
                while not self._entries and not self._closed:
                    rem = (None if deadline is None
                           else deadline - time.monotonic())
                    if rem is not None and rem <= 0:
                        return None
                    self._cond.wait(rem)
                if not self._entries:
                    return None
                entry = self._entries.pop(0)
                fill = sum(int(e.games.winners.shape[0])
                           for e in self._entries)
                self._cond.notify_all()   # room for paced producers
        if self.spill_dir:
            self._unspill(entry.seq)      # consumed — don't restore it
        self._observe_out(entry, fill)
        return entry

    def sample(self, timeout: float | None = None) \
            -> ReplayEntry | None:
        """Prioritized-recency draw (geometric from the newest entry,
        parameter ``sample_p``); the entry stays in the ring. Blocks
        until non-empty; None on timeout/closed-and-empty."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with watchdog.waiting_on("replay_fill"):
            with self._cond:
                while not self._entries and not self._closed:
                    rem = (None if deadline is None
                           else deadline - time.monotonic())
                    if rem is not None and rem <= 0:
                        return None
                    self._cond.wait(rem)
                if not self._entries:
                    return None
                n = len(self._entries)
                back = min(int(self._rng.geometric(self.sample_p)) - 1,
                           n - 1)
                entry = self._entries[n - 1 - back]
                fill = sum(int(e.games.winners.shape[0])
                           for e in self._entries)
        self._observe_out(entry, fill)
        return entry

    # ------------------------------------------------------- lifecycle

    def close(self) -> None:
        """Reject further puts and unblock every waiter (consumers
        drain what's left, then get None)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    @property
    def closed(self) -> bool:
        with self._cond:
            return self._closed

    @property
    def fill(self) -> int:
        with self._cond:
            return len(self._entries)

    @property
    def ingested_games(self) -> int:
        with self._cond:
            return self._ingested

    # ----------------------------------------------------- persistence

    def restore(self) -> int:
        """Reload spilled entries after a crash; returns the count.

        Tolerant: unreadable/torn files are skipped. All on-disk
        files are consumed (removed) and the survivors re-spilled
        under fresh sequence numbers, so a second crash can't
        double-restore.

        The insert is ONE critical section: restore-while-producers-
        publish is a real path (a replay service restores its spill
        while reconnecting actors are already shipping), and
        inserting the recovered entries one ``put`` at a time would
        let live puts interleave into the middle of the restored
        stream — reordering the FIFO. Under the single section the
        restored entries land contiguously, before or after any live
        put, and both streams keep their own order."""
        if not self.spill_dir:
            return 0
        paths = sorted(
            p for p in glob.glob(
                os.path.join(self.spill_dir, "entry.*.json"))
            if f".{self._spill_tag}." not in os.path.basename(p))
        recovered = []
        for path in paths:
            try:
                with open(path, encoding="utf-8") as f:
                    rec = json.load(f)
                games, version = record_to_games(rec)
            except (OSError, ValueError, KeyError, TypeError):
                continue
            recovered.append((ZeroGames(
                *(None if x is None else np.asarray(x)
                  for x in games)), version))
        evict_seqs: list[int] = []
        evicted_games = 0
        new_entries: list[ReplayEntry] = []
        with self._cond:
            if self._closed:
                return 0
            for games, version in recovered:
                while len(self._entries) >= self.capacity:
                    old = self._entries.pop(0)
                    evict_seqs.append(old.seq)
                    evicted_games += int(old.games.winners.shape[0])
                entry = ReplayEntry(self._seq, int(version), games,
                                    time.monotonic())
                self._seq += 1
                self._entries.append(entry)
                self._ingested += int(games.winners.shape[0])
                new_entries.append(entry)
            if new_entries and self._t_first is None:
                self._t_first = time.monotonic()
            fill = sum(int(e.games.winners.shape[0])
                       for e in self._entries)
            self._cond.notify_all()
        # file I/O stays outside the lock: consume the old files
        # first, then re-spill only the entries still IN the buffer
        # (a restored entry evicted by a later restored one, or a
        # live entry evicted mid-restore, must not leave a spill
        # file behind to double-restore next time)
        for path in paths:
            try:
                os.unlink(path)
            except OSError:
                pass
        evicted = set(evict_seqs)
        for entry in new_entries:
            if entry.seq in evicted:
                continue
            atomic.atomic_write_json(
                self._spill_path(entry.seq),
                games_to_record(entry.games, entry.version,
                                entry.seq),
                indent=None)
        restored_seqs = {e.seq for e in new_entries}
        for seq in evict_seqs:
            if seq not in restored_seqs:
                self._unspill(seq)
        if new_entries:
            registry.counter("replay_spilled_total").inc(
                len(new_entries))
            registry.gauge("replay_fill_games").set(fill)
        if evicted_games:
            registry.counter("replay_evicted_games_total").inc(
                evicted_games)
        return len(new_entries)

    def discard_spill(self) -> int:
        """Delete every spilled entry WITHOUT restoring it; returns
        the count. The lockstep resume path: the lockstep actor
        replays its games bit-identically from the checkpointed rng
        chain, so restoring leftovers would double-insert them —
        free-run resumes call :meth:`restore` instead."""
        if not self.spill_dir:
            return 0
        paths = glob.glob(os.path.join(self.spill_dir, "entry.*.json"))
        n = 0
        for path in paths:
            try:
                os.unlink(path)
                n += 1
            except OSError:
                pass
        return n

    def _spill_path(self, seq: int) -> str:
        return os.path.join(
            self.spill_dir,
            f"entry.{self._spill_tag}.{seq:08d}.json")

    def _unspill(self, seq: int) -> None:
        try:
            os.unlink(self._spill_path(seq))
        except OSError:
            pass

    def _observe_out(self, entry: ReplayEntry, fill: int) -> None:
        registry.histogram("replay_sample_staleness_seconds").observe(
            time.monotonic() - entry.t_ingest)
        registry.gauge("replay_fill_games").set(fill)


class JsonlIngester:
    """Tail ``*.jsonl`` shards in a directory into a buffer — the
    transport for out-of-process actors (each actor process appends
    game records to its own shard; see docs/SCALE.md).

    Single-consumer by design (no locks): per-shard byte offsets live
    on the instance, and only newline-terminated lines are consumed —
    a torn tail (writer mid-append or crashed) is left for the next
    :meth:`poll`. Records that fail to parse or decode are counted
    and skipped, never fatal. A shard that SHRINKS under our offset
    (an actor restarted by its supervisor truncates and rewrites, or
    logrotate swapped the file) is re-read from byte 0 — counted in
    ``shard_rotated`` — instead of silently tailing past EOF forever.

    Rotation re-reads make ingest at-least-once; the bounded
    ``game_id`` window (:func:`record_game_id` content hashes, the
    newest ``dedup_window`` ids) makes it effectively exactly-once:
    a record already ingested before the rotation is counted in
    ``dedup_hits`` and skipped, never double-fed to the buffer.
    """

    def __init__(self, buffer: ReplayBuffer, path: str,
                 dedup_window: int = 4096):
        self.buffer = buffer
        self.path = path
        self.skipped = 0
        self.schema_skipped = 0
        self.shard_rotated = 0
        self.dedup_hits = 0
        self.dedup_window = int(dedup_window)
        self._offsets: dict[str, int] = {}
        self._seen: dict[str, None] = {}   # insertion-ordered id ring

    def poll(self) -> int:
        """Ingest every complete new line; returns entries added."""
        added = 0
        for shard in sorted(glob.glob(
                os.path.join(self.path, "*.jsonl"))):
            offset = self._offsets.get(shard, 0)
            try:
                with open(shard, "rb") as f:
                    if os.fstat(f.fileno()).st_size < offset:
                        # rotation/truncation: our offset points past
                        # EOF — restart from the top of the new file
                        self.shard_rotated += 1
                        offset = 0
                        self._offsets[shard] = 0
                    f.seek(offset)
                    data = f.read()
            except OSError:
                continue
            end = data.rfind(b"\n")
            if end < 0:
                continue
            for line in data[:end].splitlines():
                if not line.strip():
                    continue
                try:
                    rec = json.loads(line)
                    games, version = record_to_games(rec)
                    gid = record_game_id(rec, games)
                except UnknownSchemaError:
                    # a NEWER writer shares the stream (rolling
                    # upgrade): count separately — the operator's cue
                    # to upgrade the reader, not a data-corruption
                    # signal
                    self.schema_skipped += 1
                    continue
                except (ValueError, KeyError, TypeError):
                    self.skipped += 1
                    continue
                if gid in self._seen:
                    self.dedup_hits += 1
                    continue
                if self.buffer.put(games, version=version):
                    added += 1
                    self._seen[gid] = None
                    while len(self._seen) > self.dedup_window:
                        self._seen.pop(next(iter(self._seen)))
            self._offsets[shard] = offset + end + 1
        return added


def append_jsonl_record(path: str, games: ZeroGames,
                        version: int = 0, seq: int = 0) -> None:
    """Producer side of the JSONL transport: append one record as a
    single newline-terminated line (the ingester's torn-line rule
    makes a concurrent reader safe without locking)."""
    line = json.dumps(games_to_record(games, version, seq),
                      separators=(",", ":")) + "\n"
    with open(path, "a", encoding="utf-8") as f:
        f.write(line)
