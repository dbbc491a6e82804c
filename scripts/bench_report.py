"""Render bench result tables from the machine log.

Input: ``benchmarks/results.jsonl`` — every benchmark script appends
one record per measurement (metric, value, unit, config, platform,
date); this renders tables from data instead of hand-transcription.

Usage:
    python scripts/bench_report.py [--date YYYY-MM-DD]
        [--platform tpu] [--log benchmarks/results.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def load_records(path: str, date: str, platform: str | None):
    """Latest record per (metric, batch, board, config-ish key)."""
    latest: dict = {}
    try:
        f = open(path)
    except OSError as e:
        print(f"bench_report: cannot read {path}: {e}",
              file=sys.stderr)
        return []
    with f:
        for line in f:
            try:
                r = json.loads(line)
            except ValueError:
                continue
            if not isinstance(r, dict) or "metric" not in r:
                continue
            if not str(r.get("date", "")).startswith(date):
                continue
            if platform and r.get("platform") != platform:
                continue
            key = (r["metric"], r.get("batch"), r.get("board"),
                   r.get("interpret"), r.get("lmbda"),
                   r.get("devices"), r.get("pipeline_depth"),
                   # encode A/B axes (bench_encode.py): every
                   # gating/phase1/impl side is its own row
                   r.get("gating"), r.get("phase1"),
                   r.get("chase_impl"),
                   # serving sweep axes (bench_serve.py): each
                   # session count × drive mode is its own row
                   r.get("sessions"), r.get("mode"),
                   # gateway sweep axis (bench_gateway.py): each
                   # connection count is its own row — the direct
                   # and gateway sides of the wire-tax A/B already
                   # split on mode
                   r.get("conns"),
                   # actor/learner scale axes (bench_zero_scale.py):
                   # each actor count × mesh shape is its own row
                   r.get("actors"), r.get("mesh_shape"),
                   # self-play economics axis (bench_selfplay.py
                   # --cap-ab / bench_zero_scale.py --cap-p): each
                   # cap probability is its own row — the baseline
                   # (cap_p=1.0 or absent) and capped sides of the
                   # A/B must not collapse into one
                   r.get("cap_p"),
                   # recovery A/B axis (bench_zero_scale.py
                   # --kill-actor-at): the killed-actor run and the
                   # fault-free run are separate rows
                   r.get("kill_at"),
                   # transposition-cache A/B axis (bench_serve.py
                   # --cache-ab): the cache-off and cache-on arms at
                   # one session count are separate rows
                   r.get("cache"))
            prev = latest.get(key)
            if prev is None or str(r.get("date")) >= str(prev.get("date")):
                latest[key] = r
    def order(k):
        batch = k[1] if isinstance(k[1], (int, float)) else 0
        return (k[0], batch, str(k))
    return [latest[k] for k in sorted(latest, key=order)]


_SKIP_FIELDS = {"metric", "value", "unit", "platform", "date",
                "vs_baseline", "mfu", "host_gap_frac", "us_per_pos",
                "sessions", "conns", "actors", "learner_idle_frac",
                "board", "cap_p", "fullsearch_frac", "mttr_s",
                "hit_rate"}


def render_table(records) -> str:
    """MFU gets its own column (VERDICT r3 #3): benches that know
    their program's XLA-costed flops record ``mfu`` = achieved
    flops/s ÷ the chip's bf16 peak (see benchmarks/_harness.py);
    '—' where a record has none (CPU runs, non-flops metrics).
    The host-gap column shows ``host_gap_frac`` — the fraction of
    wall time the device had nothing in flight (the pipelined-vs-sync
    dispatch A/B; ``pipeline_depth`` in config names the side). The
    µs/pos column renders ``us_per_pos`` — the encode A/B's
    per-position cost (``benchmarks/bench_encode.py``), keyed by the
    gating/phase1/impl fields that stay visible in config. The
    sessions column keys the serving sweep (``bench_serve.py``:
    moves/sec vs concurrent-session count — read the batched-mode
    rows top to bottom for the scaling curve; p50/p99/occupancy stay
    in config). The actors and learner-idle columns key the
    actor/learner scale sweep (``bench_zero_scale.py``: ingest
    games/min and learner steps/s vs actor count — actors=0 is the
    synchronous baseline, whose self-play fraction stays in config as
    ``selfplay_frac``; ``mesh_shape`` also stays in config). The same
    two columns key the wire-rig A/B (``bench_zero_scale.py --wire``:
    ``zero_wire_*`` rows put actor PROCESSES behind replaynet — read
    the learner-idle column against the in-process row at the same
    actor count for the wire tax; docs/REPLAYNET.md). The
    board column keys multi-size sweeps (``bench_multisize.py``: one
    FCN checkpoint served per board size — read same-metric rows
    across boards for the size-scaling table). The cap-p and
    full-frac columns key the self-play economics A/B
    (``bench_selfplay.py --cap-ab``: games/min vs the probability a
    ply gets the full search budget; ``fullsearch_frac`` is the frac
    the run actually drew — read the cap_p=1 row as the baseline).
    The MTTR column renders ``mttr_s`` — the recovery A/B's
    kill-to-first-post-restart-game time (``bench_zero_scale.py
    --kill-actor-at``; ``kill_at`` stays in config and keys the
    row). The conns column keys the gateway wire-tax sweep
    (``bench_gateway.py``: moves/sec vs concurrent connections, the
    direct/gateway modes A/B'd per count — p50/p99 stay in
    config). The hit-rate column renders ``hit_rate`` — the
    transposition-cache A/B's measured cache hit rate
    (``bench_serve.py --cache-ab``; the ``cache`` off/on field stays
    in config and keys the row against its other arm)."""
    lines = ["| metric | value | unit | board | MFU | host gap "
             "| µs/pos | sessions | conns | actors | learner idle "
             "| cap p | full frac | MTTR | hit rate | config |",
             "|---|---|---|---|---|---|---|---|---|---|---|---|---|"
             "---|---|---|"]
    for r in records:
        cfg = ", ".join(f"{k}={v}" for k, v in sorted(r.items())
                        if k not in _SKIP_FIELDS)
        extra = ("" if r.get("vs_baseline") in (None, "")
                 else f" (vs_baseline {r['vs_baseline']})")
        board = r.get("board")
        board = "—" if board in (None, "") else str(board)
        u = r.get("mfu")
        u = "—" if u in (None, "") else f"{100.0 * float(u):.1f}%"
        gap = r.get("host_gap_frac")
        gap = "—" if gap in (None, "") else f"{100.0 * float(gap):.2f}%"
        upp = r.get("us_per_pos")
        upp = "—" if upp in (None, "") else f"{float(upp):g}"
        sess = r.get("sessions")
        sess = "—" if sess in (None, "") else str(sess)
        conns = r.get("conns")
        conns = "—" if conns in (None, "") else str(conns)
        act = r.get("actors")
        act = "—" if act in (None, "") else str(act)
        idle = r.get("learner_idle_frac")
        idle = ("—" if idle in (None, "")
                else f"{100.0 * float(idle):.1f}%")
        capp = r.get("cap_p")
        capp = "—" if capp in (None, "") else f"{float(capp):g}"
        ff = r.get("fullsearch_frac")
        ff = "—" if ff in (None, "") else f"{100.0 * float(ff):.1f}%"
        mttr = r.get("mttr_s")
        mttr = "—" if mttr in (None, "") else f"{float(mttr):g}s"
        hr = r.get("hit_rate")
        hr = "—" if hr in (None, "") else f"{100.0 * float(hr):.1f}%"
        lines.append(f"| {r['metric']} | {r.get('value', '?')}{extra}"
                     f" | {r.get('unit', '?')} | {board} | {u} | {gap}"
                     f" | {upp} | {sess} | {conns} | {act} | {idle}"
                     f" | {capp} | {ff} | {mttr} | {hr} | {cfg} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Render bench tables from the results log")
    ap.add_argument("--date", default=time.strftime("%Y-%m-%d"))
    ap.add_argument("--platform", default=None)
    ap.add_argument("--log", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks", "results.jsonl"))
    a = ap.parse_args(argv)

    for platform in ([a.platform] if a.platform else ["tpu", "cpu"]):
        recs = load_records(a.log, a.date, platform)
        if recs:
            print(f"\n## {platform} — {a.date}\n")
            print(render_table(recs))
    return 0


if __name__ == "__main__":
    sys.exit(main())
