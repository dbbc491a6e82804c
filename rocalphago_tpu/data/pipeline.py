"""Host→device input pipeline over converted shards.

Replaces the reference's ``shuffled_hdf5_batch_generator`` (h5py chunk
reads + per-sample numpy transforms on host; SURVEY.md §3.1 HOT) with:

* memory-mapped/sharded loads on host,
* index-level shuffling with a persistable permutation (the reference's
  ``shuffle.npz`` resume trick),
* double-buffered ``jax.device_put`` prefetch so the TPU never waits on
  the host,
* dihedral augmentation deferred to the *device* (see
  ``training.symmetries``), not done per-sample on host.
"""

from __future__ import annotations

import json
import os
import queue
import threading
import zipfile

import numpy as np


class ShardedDataset:
    """Random-access view over ``prefix-NNNNN.npz`` shards."""

    def __init__(self, prefix: str):
        with open(f"{prefix}-manifest.json") as f:
            self.manifest = json.load(f)
        self.prefix = prefix
        counts = self.manifest["shard_counts"]
        self._starts = np.cumsum([0] + counts)
        self.num_positions = int(self._starts[-1])
        self._cache: dict[int, tuple[np.ndarray, np.ndarray]] = {}

    def __len__(self) -> int:
        return self.num_positions

    @property
    def planes(self) -> int:
        return int(self.manifest["planes"])

    @property
    def board_size(self) -> int:
        return int(self.manifest["board_size"])

    def _shard(self, i: int):
        if i not in self._cache:
            z = np.load(f"{self.prefix}-{i:05d}.npz")
            self._cache[i] = (z["states"], z["actions"])
            # keep at most 4 shards resident
            while len(self._cache) > 4:
                self._cache.pop(next(iter(self._cache)))
        return self._cache[i]

    def gather(self, indices: np.ndarray):
        """(states [b,s,s,F] uint8, actions [b] int32) for global
        indices (any order) — or a sequence corpus's id rows and next
        ids, both ``[b, seq_len]`` int32."""
        states = actions = None
        shard_ids = np.searchsorted(self._starts, indices, "right") - 1
        for sid in np.unique(shard_ids):
            s_states, s_actions = self._shard(int(sid))
            sel = shard_ids == sid
            local = indices[sel] - self._starts[sid]
            if states is None:
                states = np.empty(
                    (len(indices),) + s_states.shape[1:], s_states.dtype)
                actions = np.empty(
                    (len(indices),) + s_actions.shape[1:], np.int32)
            states[sel] = s_states[local]
            actions[sel] = s_actions[local]
        if actions is None:         # no index: no shard was opened
            actions = np.empty(0, np.int32)
        return states, actions


def load_hdf5(path: str):
    """Reference-layout HDF5 → (states uint8 NHWC, actions int32).
    Interchange reader for corpora converted by the reference stack."""
    import h5py
    with h5py.File(path, "r") as h5:
        states = np.asarray(h5["states"], np.uint8).transpose(0, 2, 3, 1)
        actions = np.asarray(h5["actions"], np.int32)
    return states, actions


def split_indices(n: int, fractions=(0.93, 0.05, 0.02), seed: int = 0,
                  path: str | None = None, write: bool = True):
    """Shuffled train/val/test index split; persisted to ``path`` (npz)
    so interrupted runs resume with the identical split (the
    reference's ``shuffle.npz`` behavior). ``write=False``
    (non-coordinator processes) still reads an existing file but never
    creates one — the permutation is a pure function of ``seed``, so
    every process computes the identical split regardless."""
    if path is not None:
        try:
            z = np.load(path)
            tr, va, te = z["train"], z["val"], z["test"]
        except (OSError, KeyError, ValueError, zipfile.BadZipFile):
            # BadZipFile/ValueError: a torn read of a file another
            # process is mid-writing (the writer renames atomically,
            # but NFS-style filesystems can still surface partial
            # views) — fall through and recompute; the permutation is
            # a pure function of the seed, so every process agrees
            tr = None
        if tr is not None:
            total = len(tr) + len(va) + len(te)
            if total != n:
                raise ValueError(
                    f"persisted split at {path} covers {total} positions "
                    f"but the dataset has {n}; the corpus changed — "
                    "delete the split file to reshuffle (this breaks "
                    "resume reproducibility) or restore the old corpus")
            return tr, va, te
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    n_train = int(n * fractions[0])
    n_val = int(n * fractions[1])
    train = perm[:n_train]
    val = perm[n_train:n_train + n_val]
    test = perm[n_train + n_val:]
    if path is not None and write:
        # atomic write: non-coordinator processes read this file
        # concurrently in multi-host runs (.npz suffix on the temp
        # name stops np.savez appending another one)
        tmp = path + ".tmp.npz"
        np.savez(tmp, train=train, val=val, test=test)
        os.replace(tmp, path)
    return train, val, test


def batch_iterator(dataset, indices: np.ndarray, batch_size: int,
                   rng: np.random.Generator, epochs: int | None = None,
                   drop_remainder: bool = True,
                   shard_window: int | None = 4, skip: int = 0):
    """Yield host (states, actions) batches, reshuffling every epoch.

    Shuffling is two-level when the corpus spans many shards: shard
    visit order is permuted per epoch, then indices are fully permuted
    inside windows of ``shard_window`` shards — so a minibatch only
    touches shards the dataset cache holds resident (a global
    permutation would decompress nearly every shard per minibatch).
    ``shard_window=None`` restores the global permutation.

    ``skip`` drops the first ``skip`` batches of the FIRST epoch only —
    index arithmetic, no shard reads — the mid-epoch resume cursor:
    with the same ``rng`` seed the epoch's batch order is reproduced
    and the already-consumed prefix is skipped.
    """
    starts = getattr(dataset, "_starts", None)
    epoch = 0
    while epochs is None or epoch < epochs:
        if shard_window is None or starts is None or len(starts) <= 2:
            order = rng.permutation(indices)
        else:
            shard_of = np.searchsorted(starts, indices, "right") - 1
            shard_ids = rng.permutation(np.unique(shard_of))
            chunks = []
            for w in range(0, len(shard_ids), shard_window):
                window = shard_ids[w:w + shard_window]
                pool = indices[np.isin(shard_of, window)]
                chunks.append(rng.permutation(pool))
            order = np.concatenate(chunks)
        end = (len(order) // batch_size) * batch_size if drop_remainder \
            else len(order)
        start = (skip * batch_size) if epoch == 0 else 0
        for i in range(start, end, batch_size):
            yield dataset.gather(order[i:i + batch_size])
        epoch += 1


def device_prefetch(host_iter, size: int = 2):
    """Stage host batches onto the device ahead of consumption.

    A small thread keeps ``size`` batches in flight (``jax.device_put``
    is async, so staging overlaps with the current train step). Worker
    exceptions propagate to the consumer; closing the generator early
    (the normal case — ``batch_iterator`` is infinite by default)
    releases the worker and its staged batches instead of deadlocking
    on the full queue.

    Close is BOUNDED: the stop event is set, staged batches are
    drained so the worker's pending ``put`` can observe the stop
    within its 100 ms poll, and the worker is joined (5 s cap — it
    may be inside one last host batch read). Before this join the
    prefetch thread was fire-and-forget: ``close()`` returned while
    the worker could still be touching the dataset/shard cache it
    was handed (the exact loose-lifecycle shape the ``thread-no-join``
    lint rule now rejects).
    """
    import jax

    q: queue.Queue = queue.Queue(maxsize=size)
    stop = threading.Event()
    _END = object()

    def put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for item in host_iter:
                if not put(jax.device_put(item)):
                    return
            put(_END)
        except BaseException as e:  # noqa: BLE001 — relayed to consumer
            put(e)

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item
    finally:
        stop.set()
        # drain staged batches so a worker blocked on the full queue
        # reaches its stop-event poll, then wait for it to exit —
        # quiescence is part of the generator's close contract
        while not q.empty():
            q.get_nowait()
        t.join(timeout=5.0)
