"""Headline benchmark: 19×19 self-play throughput (games/min).

Runs the fully on-device batched self-play loop (encode → policy
forward → sample → rules step, all under one jit; SURVEY.md §6) with
the flagship 48-plane policy on whatever accelerator is attached and
prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

``vs_baseline`` is against the north-star target of 200 games/min on a
16-chip v5e slice, prorated to the number of attached chips
(BASELINE.md; the reference publishes no numbers of its own).

One process, no fallback: the headline is a device metric, so
``python bench.py`` on anything but a TPU is an error (nonzero exit,
no result line), and any failure while measuring is a traceback and a
nonzero exit — never an exit-0 record. The tests drive
:func:`_measure` in-process on the CPU backend at toy size.
"""

from __future__ import annotations

import json
import os
import sys
import time

METRIC = "selfplay_19x19_games_per_min"
_DEADLINE_MARK = "_GRAFT_BENCH_BUDGET_S"
# plies below which a 19×19 game is considered truncated for metric
# honesty (real games run 200–400)
FULL_GAME_PLIES = 250
# a competing process burning more than this fraction of one core
# during the sample window marks the measurement contended
_HEAVY_CPU_FRAC = 0.5


def _host_contention(sample_s: float = 0.25):
    """``(load_1m, contended, heavy_pids)`` — bench-capture isolation
    (a training run sharing the host's cores with a capture once cut
    the reading eightfold). Samples /proc twice ``sample_s``
    apart and flags any OTHER process that burned >50% of a core in
    between; also reports the 1-minute load average. Best-effort:
    returns ``(None, False, [])`` where /proc (or getloadavg) is
    unavailable — a missing reading must never fail the bench."""
    try:
        load1 = round(os.getloadavg()[0], 2)
    except (OSError, AttributeError):
        load1 = None

    def cpu_ticks():
        ticks = {}
        try:
            pids = os.listdir("/proc")
        except OSError:
            return ticks
        me = os.getpid()
        for pid in pids:
            if not pid.isdigit() or int(pid) == me:
                continue
            try:
                with open(f"/proc/{pid}/stat") as f:
                    # fields after the ")" delimiter: state is index 0,
                    # utime/stime are indices 11/12
                    parts = f.read().rsplit(") ", 1)[-1].split()
                ticks[int(pid)] = int(parts[11]) + int(parts[12])
            except (OSError, IndexError, ValueError):
                continue
        return ticks

    before = cpu_ticks()
    if not before:
        return load1, False, []
    time.sleep(sample_s)
    after = cpu_ticks()
    try:
        hz = os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, AttributeError):
        hz = 100
    heavy = sorted(
        pid for pid, t in after.items()
        if pid in before
        and (t - before[pid]) / hz / sample_s > _HEAVY_CPU_FRAC)
    return load1, bool(heavy), heavy


def _honest_metric(metric: str, value: float, target: float, *,
                   truncated: bool, includes_compile: bool,
                   contended: bool):
    """``(metric_name, vs_baseline)`` — the headline honesty rules in
    one place: a truncated-game rate, a
    compile-polluted rate or a contended-host capture reports under a
    SUFFIXED metric name, never the headline's, and no compromised
    measurement (truncated, compile-included, or contended) ever
    emits a ratio against the full-game north star. (The exact-
    program warmup makes ``includes_compile`` unreachable from the
    normal headline flow — the suffix is defense in depth for any
    future caller that still measures through a compile.)"""
    name = metric
    if truncated:
        name += "_truncated"
    if includes_compile:
        name += "_compiled"
    if contended:
        name += "_contended"
    compromised = truncated or includes_compile or contended
    return name, (None if compromised
                  else round(value / max(target, 1e-9), 3))


def _self_size_from_results():
    """(batch, chunk) from today's on-chip self-play rates, or None.

    The adaptive probe exists because per-ply cost is unknowable a
    priori — but when ``bench_selfplay.py`` has ALREADY measured it on
    the chip today (``benchmarks/results.jsonl``), the probe's extra
    programs (mid-game seeding + one per candidate batch, each a
    fresh compile) are pure cost. Pick the best-throughput measured
    batch and size the chunk to ≤20s segments, so the deadline check
    between segments stays responsive. Same-day records only: the
    engine/encoder change daily."""
    # same resolution as benchmarks/_harness.py::report — the log the
    # component sweep writes is the log this reads
    path = os.environ.get(
        "ROCALPHAGO_BENCH_LOG",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "benchmarks", "results.jsonl"))
    if not path:
        return None
    today = time.strftime("%Y-%m-%d")
    best = None     # (plies_per_s, batch)
    # tolerant reader: a run killed mid-line leaves a torn final
    # record, which must not cost the day's measurements
    from rocalphago_tpu.runtime.jsonl import iter_jsonl
    try:
        with open(path) as f:
            for r in iter_jsonl(f):
                if (r.get("metric") == "selfplay_ply_program"
                        and r.get("platform") == "tpu"
                        and str(r.get("date", "")).startswith(today)
                        and isinstance(r.get("batch"), int)
                        and r.get("board", 19) == 19  # headline board
                        and r.get("value", 0) > 0):
                    cand = (float(r["value"]), r["batch"])
                    if best is None or cand > best:
                        best = cand
    except OSError:
        return None
    if best is None:
        return None
    rate, batch = best
    sec_per_ply = batch / rate
    chunk = max(5, min(100, int(20.0 / max(sec_per_ply, 1e-3))))
    print(f"bench: self-sized from today's results.jsonl: "
          f"batch {batch}, chunk {chunk} "
          f"({rate:.0f} board-plies/s measured)", file=sys.stderr)
    return batch, chunk


def _measure() -> None:
    """Run the benchmark on the backend JAX selected and print the
    result line (:func:`main` has already refused a non-TPU backend).

    An optional deadline (``_GRAFT_BENCH_BUDGET_S`` seconds from
    start) is checked between compiled chunks and between reps: the
    in-flight device program always finishes, and a run that cannot
    complete one full rep inside it raises.
    """
    import jax

    from rocalphago_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()

    from rocalphago_tpu.engine.jaxgo import GoConfig, engine_formulation
    from rocalphago_tpu.models import CNNPolicy
    from rocalphago_tpu.search.selfplay import (
        host_winners,
        make_selfplay_chunked,
    )

    deadline = time.time() + float(
        os.environ.get(_DEADLINE_MARK, "1e18"))
    n_dev = len(jax.devices())
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    # full games by default: the chunked program's compile cost
    # doesn't scale with max_moves (one compiled segment,
    # re-dispatched), and stop_when_done exits as soon as every game
    # has really ended
    max_moves = int(os.environ.get("_GRAFT_BENCH_MAX_MOVES", "300"))

    cfg = GoConfig(size=19)
    net = CNNPolicy(board=19, layers=12, filters_per_layer=128)

    def make(batch, chunk, mm=None):
        # terminal scoring happens on host: it shaves the whole-board
        # region labeling off the compiled program, and costs
        # microseconds per game
        return make_selfplay_chunked(
            cfg, net.feature_list, net.module.apply, net.module.apply,
            batch, mm or max_moves, chunk=chunk, temperature=1.0,
            score_on_device=False)

    # operator override "batch,chunk": skip the adaptive probe
    # entirely — its extra programs (mid-game seeding + one per
    # candidate batch) each pay a fresh compile; a fixed config plays
    # full games with ONE compiled program. Only honored on TPU: a
    # TPU-sized batch on the host CPU (the tests' in-process runs)
    # would take hours.
    fixed = os.environ.get("_GRAFT_BENCH_FIXED", "") if on_tpu else ""
    try:
        fixed_cfg = tuple(int(v) for v in fixed.split(","))
        if len(fixed_cfg) != 2 or min(fixed_cfg) <= 0:
            fixed_cfg = None
    except ValueError:
        fixed_cfg = None
    if fixed and not fixed_cfg:
        # the operator asked for explicit control and got the value
        # wrong — fall through to the adaptive probe (NOT self-sizing,
        # which would silently substitute a different fixed config)
        # and say why: a silent discard is undiagnosable
        print(f"bench: ignoring malformed _GRAFT_BENCH_FIXED={fixed!r}"
              " (want 'batch,chunk' positive ints); running adaptive",
              file=sys.stderr)
    elif not fixed_cfg and on_tpu \
            and os.environ.get("_GRAFT_BENCH_NO_SELF_SIZE") != "1":
        fixed_cfg = _self_size_from_results()
    if fixed_cfg:
        batch, chunk = fixed_cfg
    elif on_tpu or os.environ.get("_GRAFT_BENCH_FORCE_ADAPTIVE") == "1":
        # ADAPTIVE sizing: per-ply cost per batch size moves with
        # every engine/encoder optimization — so probe instead of
        # hard-coding. Crucially the probe runs from MID-GAME states —
        # opening boards are near-uniform and hide the vmap'd fixpoint
        # stalls that historically made small batches win.
        # Seed DIVERSE mid-game games in short segments; each
        # candidate probe then runs the REAL two-net program (a fixed
        # 10-ply segment — no early exit, so t/10 is exact) from a
        # slice of those seeds. Slicing (not tiling) keeps the
        # slowest-board tail realistic: the vmap'd fixpoint loops
        # stall on the slowest board, and duplicated boards would
        # fake away exactly that cost.
        seed_plies = int(os.environ.get("_GRAFT_BENCH_SEED_PLIES",
                                        "80"))
        cands = tuple(int(c) for c in os.environ.get(
            "_GRAFT_BENCH_BATCHES", "256,64,16").split(","))
        seed_batch = max(cands)
        # seeding gets at most 40% of the remaining budget: a deadline
        # truncation here just means shallower mid-game seeds. Chunk
        # 5: per-ply cost at the largest candidate batch is unmeasured
        # on any given day, and short segments keep the deadline
        # check responsive
        seed = make(seed_batch, 5, mm=seed_plies)
        t_seed = time.time()
        seed_res = seed(net.params, net.params, jax.random.key(0),
                        deadline=time.time()
                        + 0.4 * max(deadline - time.time(), 0.0))
        mid = seed_res.final
        jax.device_get(mid.board)
        # observed seed rate (compile included — conservative): the
        # budget guard for the FIRST probe, before any probe has run
        seed_wall = time.time() - t_seed
        seed_sec_per_ply = seed_wall / max(seed_res.actions.shape[0], 1)
        probed, best = [], None
        for cand in sorted(cands, reverse=True):
            # each probe = compile run + timed run; skip candidates
            # that can't fit twice the expected probe time PLUS a
            # fresh-compile allowance (each batch size compiles its
            # own program). Expectation
            # comes from the last probe, or — before any probe has
            # run — from the seed run's observed rate scaled to the
            # candidate's batch share
            est_t10 = (probed[-1][2] if probed
                       else seed_sec_per_ply * 10 * cand / seed_batch)
            if time.time() + 2 * est_t10 + 45 > deadline:
                print(f"bench probe: skipping batch {cand} "
                      "(deadline)", file=sys.stderr)
                continue
            states_c = jax.tree.map(lambda x: x[:cand], mid)
            probe = make(cand, 10, mm=10)   # the real program, 1 segment
            jax.device_get(probe(
                net.params, net.params, jax.random.key(0),
                initial_states=states_c).final.board)  # compile+warm
            t0 = time.time()
            jax.device_get(probe(
                net.params, net.params, jax.random.key(1),
                initial_states=states_c).final.board)
            t10 = time.time() - t0          # one compiled 10-ply run
            rate = cand / max(t10, 1e-6)    # board-plies per second
            probed.append((cand, rate, t10))
            print(f"bench probe: batch {cand} mid-game: "
                  f"{t10:.1f}s / 10 plies", file=sys.stderr)
            # highest throughput whose estimated full measured rep
            # (per-ply × max_moves) fits a third of what's left
            fits = (t10 / 10.0) * max_moves < max(
                (deadline - time.time()) / 3.0, 30.0)
            if fits and (best is None or rate > best[1]):
                best = (cand, rate, t10)
        if best is None and probed:
            # nothing fit the remaining budget — fall back to the
            # fastest MEASURED probe (real data, never a made-up time;
            # the deadline machinery will truncate the rep if needed)
            best = min(probed, key=lambda p: p[2])
        if best is not None:
            batch, _, t10 = best
            per_ply = t10 / 10.0
            # target ≤20s per segment, with margin for late-game plies
            # costing more than the probe's
            chunk = max(5, min(100, int(20.0 / max(per_ply, 1e-3))))
        else:
            # no probe ran at all (deadline already spent): smallest
            # batch at the minimum segment size
            batch, chunk = min(cands), 5
    else:
        # off-TPU (the tests' in-process runs): a toy size
        batch, chunk = 8, 40

    run = make(batch, chunk)

    # pipelined dispatch (runtime.pipeline): the measured reps run at
    # the process-default depth (env ROCALPHAGO_PIPELINE_DEPTH / 1 —
    # one segment in flight, done-poll one segment behind); the
    # pipeline's host_gap_frac (fraction of wall time with nothing in
    # flight) lands in the result line for the pipelined-vs-sync A/B
    from rocalphago_tpu.runtime.pipeline import ChunkPipeline, default_depth
    pipe = ChunkPipeline(runner="bench_headline")

    def one(r, pipeline=pipe):
        # stop_when_done: games/min measures time to *finish* the
        # games — once every game has ended by two passes there is
        # nothing left to measure, and the early exit keeps full-game
        # (max_moves=300) runs well inside the budget
        res = run(net.params, net.params, jax.random.key(r),
                  deadline=deadline, stop_when_done=True,
                  pipeline=pipeline)
        boards = jax.device_get(res.final.board)
        done_all = bool(jax.device_get(res.final.done.all()))
        # a deadline stop mid-run leaves games unfinished AND short of
        # the move limit — that rep measured nothing usable
        valid = done_all or res.actions.shape[0] >= max_moves
        host_winners(cfg, boards)
        return valid

    # exact-program warmup (run.warmup, see make_selfplay_chunked):
    # compile-and-once-execute precisely the programs the timed rep
    # dispatches — the chunk segment, the remainder segment, the
    # done-poll and the finish — at a couple of segments' cost (a
    # full-rep warmup can eat the budget the timed reps need). The
    # per-segment reading sizes the rep-budget estimate below.
    tc0 = time.time()
    seg_s = run.warmup(net.params, net.params)
    warmup_dt = time.time() - tc0
    n_segments = max(1, -(-max_moves // chunk))
    # upper bound: stop_when_done usually exits earlier
    est_rep = seg_s * n_segments
    print(f"bench: warmup {warmup_dt:.1f}s ({seg_s:.2f}s/segment, "
          f"est {est_rep:.1f}s/rep)", file=sys.stderr)

    # bench-capture isolation: sample host contention right before the
    # measured reps; the reading lands in the result line either way
    load_1m, contended, heavy_pids = _host_contention()
    if contended:
        print(f"bench: host contended (load_1m={load_1m}, heavy "
              f"pids {heavy_pids}) — measuring anyway, reporting "
              "under the _contended metric name", file=sys.stderr)

    pipe.reset_stats()      # the compile rep pollutes gap accounting

    # adaptive reps: stop once ~2 minutes of measurement accumulate
    # (or the deadline nears) so the round-end run always completes.
    # Only VALID reps' wall time enters dt — a deadline-truncated
    # rep's partial elapsed time is discarded along with the rep
    reps, measured = 0, 0.0
    for r in range(1, 4):
        if time.time() + est_rep * 1.25 > deadline:
            break
        tr = time.time()
        if not one(r):
            break           # deadline truncated this rep: discard
        measured += time.time() - tr
        reps = r
        if measured > 120:
            break

    # sync A/B rep (budget permitting): one rep at pipeline depth 0
    # (the old per-segment host sync) so the result line carries both
    # sides of the pipelined-vs-sync gap comparison. Same compiled
    # programs — depth is host-side scheduling only.
    gap_frac_sync = None
    if reps and default_depth() > 0 \
            and time.time() + est_rep * 1.25 < deadline:
        sync_pipe = ChunkPipeline(depth=0, runner="bench_headline_sync")
        if one(reps + 1, pipeline=sync_pipe):
            gap_frac_sync = round(sync_pipe.host_gap_frac, 4)
    includes_compile = False
    if reps:
        dt = measured / reps
    else:
        # the estimator said no rep fits — the programs are warm, so
        # try one anyway and let the in-run deadline machinery decide;
        # a completed rep is a real compile-free measurement
        tr = time.time()
        if not one(0):
            raise RuntimeError(
                "bench: deadline exhausted before one full rep")
        dt, reps = time.time() - tr, 1

    games_per_min = batch / dt * 60.0
    target = 200.0 * (n_dev / 16.0)  # north star prorated per chip
    truncated = max_moves < FULL_GAME_PLIES
    # honesty rules (_honest_metric): truncated/contended runs report
    # under suffixed names, and no compromised measurement (truncated,
    # compile-included, contended) emits a north-star ratio
    name, vs_baseline = _honest_metric(
        METRIC, games_per_min, target, truncated=truncated,
        includes_compile=includes_compile, contended=contended)
    line = {
        "metric": name,
        "value": round(games_per_min, 2),
        "unit": "games/min",
        "vs_baseline": vs_baseline,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": n_dev,
        "engine": engine_formulation(),
        "batch": batch,
        "max_moves": max_moves,
        "chunk": chunk,
        "pipeline_depth": default_depth(),
        "host_gap_frac": round(pipe.host_gap_frac, 4),
        "load_1m": load_1m,
    }
    if gap_frac_sync is not None:
        line["host_gap_frac_sync"] = gap_frac_sync
    if truncated:
        line["truncated"] = True
    if contended:
        line["contended"] = True
    if includes_compile:
        line["includes_compile"] = True
    print(json.dumps(line))


def main() -> int:
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench: JAX found platform {platform!r} — the headline "
              "is a device metric and is measured on a TPU only "
              "(no CPU fallback)", file=sys.stderr)
        return 1
    _measure()
    return 0


if __name__ == "__main__":
    sys.exit(main())
