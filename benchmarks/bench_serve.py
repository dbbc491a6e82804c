"""Serving throughput: aggregate moves/sec vs concurrent sessions.

The headline for ``rocalphago_tpu/serve`` (docs/SERVING.md): N
concurrent game sessions, each an on-device PUCT search, served two
ways —

* **batched** — sessions share ONE :class:`~rocalphago_tpu.serve.
  evaluator.BatchingEvaluator`: every simulation's leaf eval is
  coalesced with the other sessions' leaves into one device batch
  (``prepare_sim`` → shared eval → ``apply_sim``);
* **unbatched** (the A/B) — the per-session path: each session runs
  the fused single-game search (``init`` + ``run_sims``), its NN
  evals at batch 1 inside its own compiled program.

Both sides share one compiled searcher (no per-mode compile skew);
measurement starts after an explicit warmup of every program either
side runs. Per (sessions, mode) config one record goes to
``results.jsonl``: aggregate ``moves/s`` (value), p50/p99 per-genmove
latency, and — batched — the evaluator's real batch occupancy.

Defaults are CPU-shaped (the A/B's decision surface: the eval must
dominate the split path's per-row overhead, so the default net is
eval-heavy): board 9, 6×96 convs, 8 sims/move. On one CPU core the
batched curve rises with session count while unbatched stays flat at
its single-session rate — the cross-game economics the serving
subsystem exists for — and saturates once the core runs out of
FLOPs (~64 sessions here; 256 measured flat within noise, which is
why the default sweep stops at 64; on the chip: not measured).

``--cache-ab`` replaces the batched/unbatched sweep with the
transposition-cache A/B (docs/SERVING.md "Evaluation cache"): the
same fleet drive run twice — ``eval_cache=False`` vs an attached
:class:`~rocalphago_tpu.serve.evalcache.EvalCache` — over an
opening-replay workload shaped like real fleet traffic: K
deterministic opening lines shared round-robin by the sessions
(in-batch dedup inside one rep) and replayed identically across reps
(cross-rep cache hits). Both arms share one compiled searcher, both
records carry the measured hit rate, the arms' move lists are
asserted identical (cache hits are bit-identical by construction)
and ``jax_compiles_total`` is asserted flat across both measured
phases.

Usage::

    python benchmarks/bench_serve.py [--sessions 1,8,64]
        [--board 9] [--layers 6] [--filters 96] [--sims 8]
        [--moves 2] [--max-wait-us 50000] [--reps 3]
    python benchmarks/bench_serve.py --cache-ab --sessions 16
        [--opening-lines 4] [--opening-moves 6]
"""

from __future__ import annotations

import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmarks._harness import report, std_parser  # noqa: E402


def _percentile(sorted_vals, q):
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _run_threads(n, fn):
    """Run ``fn(i)`` in n threads behind one start barrier; returns
    (wall seconds, list of per-call exceptions)."""
    ready = threading.Barrier(n + 1)
    errors: list = []

    def work(i):
        try:
            ready.wait()
            fn(i)
        except Exception as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n)]
    for t in threads:
        t.start()
    ready.wait()
    t0 = time.monotonic()
    for t in threads:
        t.join()
    return time.monotonic() - t0, errors


def main():
    ap = std_parser("serving throughput vs concurrent sessions "
                    "(batched evaluator A/B)")
    ap.add_argument("--sessions", default="1,8,64",
                    help="comma list of concurrent-session counts. "
                         "The CPU default stops at 64: on one host "
                         "core the batched path saturates there "
                         "(measured flat ±2%% to 256 on CPU)")
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--filters", type=int, default=96)
    ap.add_argument("--sims", type=int, default=8,
                    help="simulations per move")
    ap.add_argument("--moves", type=int, default=2,
                    help="genmoves per session per rep")
    ap.add_argument("--max-wait-us", type=float, default=50000.0,
                    help="partial-batch flush age — keep it above "
                         "one convoy period (it only bites when "
                         "sessions stop submitting)")
    ap.add_argument("--max-nodes", type=int, default=None,
                    help="search slab size (default sims+1: the "
                         "exact per-move serving need)")
    ap.add_argument("--skip-unbatched", action="store_true")
    ap.add_argument("--skip-threaded", action="store_true",
                    help="skip the thread-per-session latency-mode "
                         "arm (the batched driver and unbatched A/B "
                         "still run)")
    ap.add_argument("--cache-ab", action="store_true",
                    help="run the transposition-cache A/B (opening-"
                         "replay fleet workload, cache off vs on) "
                         "instead of the batched/unbatched sweep")
    ap.add_argument("--opening-lines", type=int, default=4,
                    help="[cache-ab] distinct deterministic opening "
                         "lines shared round-robin by the sessions")
    ap.add_argument("--opening-moves", type=int, default=6,
                    help="[cache-ab] plies per opening line")
    ap.set_defaults(board=9)   # serving default (std_parser's 19 is
    #                            the training benches' default)
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from rocalphago_tpu.engine import jaxgo, pygo
    from rocalphago_tpu.models import CNNPolicy, CNNValue
    from rocalphago_tpu.search.device_mcts import make_device_mcts
    from rocalphago_tpu.serve.evaluator import default_batch_sizes
    from rocalphago_tpu.serve.sessions import ServePool

    session_counts = [int(s) for s in a.sessions.split(",") if s]
    pol = CNNPolicy(("board", "ones"), board=a.board,
                    layers=a.layers, filters_per_layer=a.filters)
    val = CNNValue(("board", "ones", "color"), board=a.board,
                   layers=a.layers, filters_per_layer=a.filters)
    cfg = pol.cfg
    # ONE compiled searcher for every pool and the unbatched side.
    # Serving slab sizing: a reuse-free per-move search allocates at
    # most root + n_sim nodes, so sims+1 (not the reuse-friendly
    # 2×n_sim default) — at 256 sessions the slab is the cache
    # footprint, and halving it is measurable.
    max_nodes = a.max_nodes or (a.sims + 1)
    searcher = make_device_mcts(cfg, pol.feature_list,
                                val.feature_list, pol.module.apply,
                                val.module.apply, n_sim=a.sims,
                                max_nodes=max_nodes)

    def fresh_game():
        return pygo.GameState(size=a.board, komi=7.5)

    # ---------------- transposition-cache A/B (module docstring) ----
    if a.cache_ab:
        import random

        from rocalphago_tpu.obs.registry import REGISTRY
        from rocalphago_tpu.serve.evalcache import EvalCache

        # K deterministic opening lines: each a fixed pseudo-random
        # legal sequence — sessions share them round-robin (in-batch
        # dedup) and every rep replays them (cross-rep cache hits),
        # the shape of real fleet traffic (shared openings/joseki)
        lines = []
        for k in range(a.opening_lines):
            rng = random.Random(1000 + k)
            st = fresh_game()
            line: list = []
            for _ in range(a.opening_moves):
                legal = st.get_legal_moves(include_eyes=False)
                if not legal:
                    break
                mv = legal[rng.randrange(len(legal))]
                line.append(mv)
                st.do_move(mv)
            lines.append(line)

        def games_for(n_sessions):
            games = []
            for i in range(n_sessions):
                g = fresh_game()
                for mv in lines[i % len(lines)]:
                    g.do_move(mv)
                games.append(g)
            return games

        def compiles():
            return {k: v
                    for k, v in REGISTRY.snapshot()["counters"].items()
                    if k.startswith("jax_compiles_total")}

        for n_sessions in session_counts:
            sizes = default_batch_sizes(cap=n_sessions)
            results = {}
            for arm in ("off", "on"):
                # False force-disables even under the env switch —
                # both arms share the one compiled searcher
                cache = EvalCache() if arm == "on" else False
                pool = ServePool(val, pol, n_sim=a.sims,
                                 max_sessions=n_sessions,
                                 queue_rows=4 * max(sizes),
                                 batch_sizes=sizes,
                                 max_wait_us=a.max_wait_us,
                                 searcher=searcher, eval_cache=cache)
                pool.warm()
                sessions = [pool.open_session(resilient=False)
                            for _ in range(n_sessions)]
                driver = pool.driver(sessions)
                driver.warm()
                snap0 = compiles()
                played: list = []
                t0 = time.monotonic()
                for _ in range(a.reps):
                    games = games_for(n_sessions)
                    for _ in range(a.moves):
                        mvs = driver.genmove_all(games)
                        played.append(list(mvs))
                        for game, mv in zip(games, mvs):
                            game.do_move(mv)
                wall = time.monotonic() - t0
                if compiles() != snap0:
                    raise AssertionError(
                        "jax_compiles_total moved during the measured "
                        f"cache-ab phase (arm={arm}) — warmup gap")
                ev = pool.evaluator.stats()
                if arm == "on":
                    # hit bit-identity probe: a warm cached evaluate
                    # against the direct device eval of the same row
                    import numpy as _np
                    root = jax.tree.map(lambda x: x[None],
                                        jaxgo.from_pygo(cfg, games[0]))
                    d_p, d_v = jax.device_get(
                        pool.evaluator.eval_direct(root))
                    c_p, c_v = pool.evaluator.evaluate(root, rows=1)
                    c_p, c_v = pool.evaluator.evaluate(root, rows=1)
                    if not (_np.array_equal(_np.asarray(c_p),
                                            _np.asarray(d_p))
                            and _np.array_equal(_np.asarray(c_v),
                                                _np.asarray(d_v))):
                        raise AssertionError(
                            "cached eval not bit-identical to direct")
                for s in sessions:
                    s.close()
                pool.close()
                rate = n_sessions * a.moves * a.reps / wall
                results[arm] = (rate, played, ev)
                report("serve_moves_per_s", rate, "moves/s",
                       sessions=n_sessions, mode="batched", cache=arm,
                       hit_rate=ev["cache"]["hit_rate"],
                       dedup_saved=ev["dedup_saved"],
                       occupancy=ev["batch_occupancy"],
                       batch_sizes=",".join(str(s) for s in sizes),
                       max_wait_us=a.max_wait_us, board=a.board,
                       layers=a.layers, filters=a.filters,
                       sims=a.sims, moves=a.moves, reps=a.reps,
                       opening_lines=a.opening_lines)
            if results["off"][1] != results["on"][1]:
                raise AssertionError(
                    "cache on/off move divergence — cache hits must "
                    "be bit-identical to device evals")
            report("serve_cache_speedup",
                   results["on"][0] / results["off"][0], "x",
                   sessions=n_sessions,
                   hit_rate=results["on"][2]["cache"]["hit_rate"],
                   board=a.board, layers=a.layers, filters=a.filters,
                   sims=a.sims, moves=a.moves, reps=a.reps,
                   opening_lines=a.opening_lines)
        return

    def unbatched_move(state):
        """The per-session fused path: one init + one k-sim program."""
        root = jaxgo.from_pygo(cfg, state)
        roots = jax.tree.map(lambda x: x[None], root)
        tree = searcher.init(pol.params, val.params, roots)
        tree = searcher.run_sims(pol.params, val.params, tree,
                                 k=a.sims)
        visits, _ = searcher.root_stats(tree)
        counts = np.asarray(jax.device_get(visits))[0]
        action = int(counts.argmax())
        if action >= cfg.num_points or counts[action] == 0:
            return None
        from rocalphago_tpu.utils.coords import unflatten_idx

        return unflatten_idx(action, cfg.size)

    # warm the unbatched programs once (compile excluded everywhere)
    if not a.skip_unbatched:
        unbatched_move(fresh_game())

    common = dict(board=a.board, layers=a.layers, filters=a.filters,
                  sims=a.sims, moves=a.moves)

    for n_sessions in session_counts:
        sizes = default_batch_sizes(cap=n_sessions)
        pool = ServePool(val, pol, n_sim=a.sims,
                         max_sessions=n_sessions,
                         queue_rows=4 * max(sizes),
                         batch_sizes=sizes,
                         max_wait_us=a.max_wait_us,
                         searcher=searcher)
        pool.warm()
        sessions = [pool.open_session(resilient=False)
                    for _ in range(n_sessions)]

        # ---- batched: the fleet driver — every simulation one
        # cross-game convoy through the shared evaluator
        driver = pool.driver(sessions)
        driver.warm()
        best = None
        for _ in range(a.reps):
            lats: list = []
            games = [fresh_game() for _ in range(n_sessions)]
            t_rep = time.monotonic()
            for _ in range(a.moves):
                t0 = time.monotonic()
                moves = driver.genmove_all(games)
                dt = time.monotonic() - t0
                lats.extend([dt] * n_sessions)
                for game, mv in zip(games, moves):
                    game.do_move(mv)
            wall = time.monotonic() - t_rep
            rate = n_sessions * a.moves / wall
            if best is None or rate > best[0]:
                best = (rate, sorted(lats))
        stats = pool.evaluator.stats()
        rate, lats = best
        report("serve_moves_per_s", rate, "moves/s",
               sessions=n_sessions, mode="batched",
               p50_s=round(_percentile(lats, 0.50), 4),
               p99_s=round(_percentile(lats, 0.99), 4),
               occupancy=stats["batch_occupancy"],
               batch_sizes=",".join(str(s) for s in sizes),
               max_wait_us=a.max_wait_us, **common)

        # ---- threaded: the latency-mode A/B — one thread per
        # session, per-sim leaf submits coalesced by the dispatcher
        if not a.skip_threaded:
            best = None
            for _ in range(a.reps):
                lats = []
                lat_lock = threading.Lock()
                games = [fresh_game() for _ in range(n_sessions)]

                def play(i):
                    game = games[i]
                    for _ in range(a.moves):
                        t0 = time.monotonic()
                        mv = sessions[i].get_move(game)
                        dt = time.monotonic() - t0
                        with lat_lock:
                            lats.append(dt)
                        game.do_move(mv)

                wall, errors = _run_threads(n_sessions, play)
                if errors:
                    raise errors[0]
                rate = n_sessions * a.moves / wall
                if best is None or rate > best[0]:
                    best = (rate, sorted(lats))
            rate, lats = best
            report("serve_moves_per_s", rate, "moves/s",
                   sessions=n_sessions, mode="threaded",
                   p50_s=round(_percentile(lats, 0.50), 4),
                   p99_s=round(_percentile(lats, 0.99), 4),
                   occupancy=pool.evaluator.stats()[
                       "batch_occupancy"],
                   max_wait_us=a.max_wait_us, **common)
        for s in sessions:
            s.close()
        pool.close()

        # ---- unbatched A/B: same sessions, fused per-game search
        if a.skip_unbatched:
            continue
        best = None
        for _ in range(a.reps):
            lats = []
            lat_lock = threading.Lock()
            games = [fresh_game() for _ in range(n_sessions)]

            def play_unbatched(i):
                game = games[i]
                for _ in range(a.moves):
                    t0 = time.monotonic()
                    mv = unbatched_move(game)
                    dt = time.monotonic() - t0
                    with lat_lock:
                        lats.append(dt)
                    game.do_move(mv)

            wall, errors = _run_threads(n_sessions, play_unbatched)
            if errors:
                raise errors[0]
            rate = n_sessions * a.moves / wall
            if best is None or rate > best[0]:
                best = (rate, sorted(lats))
        rate, lats = best
        report("serve_moves_per_s", rate, "moves/s",
               sessions=n_sessions, mode="unbatched",
               p50_s=round(_percentile(lats, 0.50), 4),
               p99_s=round(_percentile(lats, 0.99), 4), **common)


if __name__ == "__main__":
    main()
