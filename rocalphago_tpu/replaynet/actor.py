"""Actor process: self-play games over the wire, crash-resumable.

The out-of-process half of the wire rig: each actor process owns a
:class:`~rocalphago_tpu.replaynet.client.ReplayClient` with a local
spool WAL and ships finished games to the replay service —
degraded-mode rules apply (service down: keep playing, keep
spooling; reconnect: re-ship in order).

Two game sources:

* ``--mode synthetic`` (default) — a jax-free deterministic
  generator: game ``i`` of actor ``k`` is a pure function of
  ``(seed, k, i)``, so a SIGKILLed actor restarted with the same
  arguments regenerates byte-identical content → identical
  ``game_id``s → every replayed overlap collapses in the server's
  dedup window. That determinism is what lets the chaos soak
  (``scripts/replay_soak.py``) assert exact produced-vs-ingested
  set equality through kill storms.
* ``--mode selfplay`` — real self-play from a tiny fixed model
  (1 layer × 4 filters), for driving the wire with games a search
  made. Params stay at version 0 (parameter distribution is out of
  scope for this rig).

Resume protocol: on start the actor counts its durably produced
games (``acked ∪ spooled`` — :meth:`ReplayClient.produced_ids`) and
continues from that index; the crash window between "generated" and
"WAL-written" is the only replayed work, and it replays to the same
id. Exit status: 0 once every requested game is produced AND the
spool drained; 2 when games remain spooled at the flush deadline
(the service stayed unreachable — the WAL holds them for the next
run).
"""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from rocalphago_tpu.data.replay import ZeroGames
from rocalphago_tpu.replaynet.client import ReplayClient


def synth_games(seed: int, actor_id: int, index: int, *,
                batch: int = 2, plies: int = 4,
                board: int = 5) -> ZeroGames:
    """Deterministic synthetic batch: content (hence ``game_id``) is
    a pure function of ``(seed, actor_id, index)``."""
    rng = np.random.default_rng(
        np.random.SeedSequence((seed, actor_id, index)))
    actions = board * board + 1
    return ZeroGames(
        actions=rng.integers(0, actions, size=(plies, batch),
                             dtype=np.int32),
        live=np.ones((plies, batch), dtype=bool),
        visits=rng.integers(0, 8, size=(plies, batch, actions),
                            dtype=np.int32),
        winners=rng.choice(np.array([-1, 1], dtype=np.int32),
                           size=(batch,)),
        finished=np.ones((batch,), dtype=bool),
    )


def _drain_spool(client: ReplayClient, timeout: float) -> bool:
    """Final flush loop: True once the spool is empty."""
    deadline = time.monotonic() + timeout
    while client.spool_depth:
        client.flush(best_effort=True)
        if not client.spool_depth:
            break
        if time.monotonic() >= deadline:
            return False
        time.sleep(0.25)
    return True


def _run_synthetic(a, client: ReplayClient) -> int:
    done = len(client.produced_ids())
    while done < a.games:
        games = synth_games(a.seed, a.actor_id, done,
                            batch=a.batch, plies=a.plies,
                            board=a.board)
        client.put_games(games, version=0)
        done += 1
        if a.rate_s:
            time.sleep(a.rate_s)
    return done


def _run_selfplay(a, client: ReplayClient) -> int:
    """Real self-play on a tiny fixed model (one process, own
    mesh); ships one batch per produced game index."""
    import jax
    import optax

    from rocalphago_tpu.engine.jaxgo import GoConfig
    from rocalphago_tpu.models import CNNPolicy, CNNValue
    from rocalphago_tpu.parallel import mesh as meshlib
    from rocalphago_tpu.training.zero import make_zero_iteration

    feats = ("board", "ones")
    vfeats = feats + ("color",)
    pol = CNNPolicy(feats, board=a.board, layers=1,
                    filters_per_layer=4)
    val = CNNValue(vfeats, board=a.board, layers=1,
                   filters_per_layer=4)
    n_dev = len(jax.devices())
    while a.batch % n_dev:
        n_dev -= 1
    mesh = meshlib.make_mesh(n_dev)
    iteration = make_zero_iteration(
        GoConfig(size=a.board), feats, vfeats, pol.module.apply,
        val.module.apply, optax.sgd(0.01), optax.sgd(0.01),
        batch=a.batch, move_limit=a.move_limit, n_sim=a.sims,
        max_nodes=16, sim_chunk=a.sim_chunk, mesh=mesh)
    pp = meshlib.replicate(mesh, pol.params)
    vp = meshlib.replicate(mesh, val.params)
    key = jax.random.PRNGKey(a.seed + 1000 * (a.actor_id + 1))
    done = len(client.produced_ids())
    # selfplay content is NOT restart-deterministic (the rng chain
    # isn't checkpointed) — the count-based resume still never
    # under- or over-produces, which is all this rig needs
    for _ in range(done, a.games):
        key, game_key = jax.random.split(key)
        games = jax.device_get(
            iteration.play(pp, vp, game_key))
        client.put_games(ZeroGames(
            *(None if x is None else np.asarray(x)
              for x in games)), version=0)
        done += 1
    return done


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Replay actor process: generate self-play games "
                    "and ship them to a replay service "
                    "(docs/REPLAYNET.md)")
    ap.add_argument("--connect", required=True,
                    metavar="HOST:PORT",
                    help="replay service address")
    ap.add_argument("--spool-dir", required=True,
                    help="local WAL directory (degraded-mode spool "
                         "+ acked ledger; also the resume state)")
    ap.add_argument("--actor-id", type=int, default=0)
    ap.add_argument("--games", type=int, default=16,
                    help="total games to produce (resume-aware)")
    ap.add_argument("--mode", choices=("synthetic", "selfplay"),
                    default="synthetic")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--board", type=int, default=5)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--plies", type=int, default=4,
                    help="synthetic: plies per game batch")
    ap.add_argument("--rate-s", type=float, default=0.0,
                    help="synthetic: sleep between games (pacing)")
    ap.add_argument("--move-limit", type=int, default=16,
                    help="selfplay: move cap")
    ap.add_argument("--sims", type=int, default=4,
                    help="selfplay: search budget")
    ap.add_argument("--sim-chunk", type=int, default=2)
    ap.add_argument("--attempts", type=int, default=6,
                    help="ship attempts before degrading to spool")
    ap.add_argument("--flush-timeout", type=float, default=30.0,
                    help="final spool-drain budget (seconds)")
    a = ap.parse_args(argv)

    host, _, port = a.connect.rpartition(":")
    client = ReplayClient(host or "127.0.0.1", int(port),
                          spool_dir=a.spool_dir,
                          attempts=a.attempts,
                          base_delay=0.1, max_delay=1.0,
                          seed=a.actor_id)
    try:
        if a.mode == "synthetic":
            done = _run_synthetic(a, client)
        else:
            done = _run_selfplay(a, client)
        drained = _drain_spool(client, a.flush_timeout)
    finally:
        client.close()
    print(f"actor {a.actor_id}: produced {done}/{a.games} games, "
          f"spool_depth={client.spool_depth} "
          f"reconnects={client.reconnects} "
          f"dup_acks={client.dup_acks}", flush=True)
    return 0 if drained else 2


if __name__ == "__main__":
    sys.exit(main())
