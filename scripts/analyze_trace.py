"""Summarize a ``jax.profiler`` capture: device time by scope, the
top device operations, and what the host was doing while the device
was idle.

A front for the chip benchmark's reducers — ``chipbench/scopes.py``
(device self time by the program's ``jax.named_scope`` names and
Flax's module scopes, idle gaps by the program's ``rocalphago.*``
spans) and ``chipbench/trace_reduce.py`` (busy/idle time, top
operations) — over the ``.xplane.pb`` the profiler writes, so an
operator's ``--profile-dir`` / ``ROCALPHAGO_JAX_PROFILE`` capture is
read by the same code, into the same tables, as the benchmark's
traced window. No GUI needed.

Usage:
    python scripts/analyze_trace.py DIR [--top 25] [--json]

``DIR`` may be the profile dir itself or any ancestor (the newest
``*.xplane.pb`` under it is picked).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def newest_trace(root: str) -> str:
    paths = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise SystemExit(f"no *.xplane.pb under {root}")
    return max(paths, key=os.path.getmtime)


def summarize(path: str, top: int = 25) -> dict:
    """The by-scope account of one trace file plus its top device
    operations by self time (``[]`` when the capture holds no device
    operation line the older reducer knows)."""
    from chipbench import scopes, trace_reduce

    acct = scopes.reduce(scopes.load(path))
    acct["by_scope"] = dict(list(acct["by_scope"].items())[:top])
    acct.pop("scope_names")
    try:
        acct["device_ops"] = trace_reduce.reduce(
            trace_reduce.load_events(path), top=top)["device_ops"]
    except ValueError:
        acct["device_ops"] = []
    return dict(acct, trace=path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("dir", help="profile dir (or any ancestor)")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    a = ap.parse_args(argv)

    s = summarize(newest_trace(a.dir), a.top)
    if a.json:
        print(json.dumps(s))
        return 0
    busy, window = s["busy_s"], s["window_s"]
    print(f"trace: {s['trace']}")
    print(f"window {window:.4f} s, device busy {busy:.4f} s "
          f"({100 * busy / window if window else 0:.2f} %), "
          f"{s['devices']} device plane(s)")
    for name, runs in sorted(s["program_runs"].items(),
                             key=lambda kv: -kv[1])[:a.top]:
        print(f"  ran x{runs:<6d} {name}")
    print("\n== device self time by scope")
    for scope, t in s["by_scope"].items():
        pct = 100.0 * t / busy if busy else 0.0
        print(f"  {t * 1e3:10.3f} ms {pct:5.1f}%  {scope}")
    print("\n== top device operations (self time)")
    for name, t in s["device_ops"]:
        print(f"  {t * 1e3:10.3f} ms  {name}")
    print("\n== device idle time by the innermost host span")
    for name, t in s["idle_by_span"][:a.top]:
        print(f"  {t * 1e3:10.3f} ms  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
