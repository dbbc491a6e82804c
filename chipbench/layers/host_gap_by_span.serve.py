"""Serve pool / sessions on the host: all of the device's idle time
by the innermost host span over each gap, on an earlier line; the
value is the percent of that idle time under one of the PROGRAM's
spans (``rocalphago.serve.*``, ``rocalphago.session.*``:
``obs/trace.py``) — the rest is under the benchmark's own
(``chipbench.client_wait``) or under none. None where the program
puts no span on the profiler's clock."""

import json

from chipbench.scopes import account


def read(ctx, raw):
    gaps = account(ctx)["idle_by_span"]
    print(json.dumps({"host_gap_by_span": gaps[:20]}), flush=True)
    total = sum(t for _, t in gaps)
    ours = sum(t for name, t in gaps if name.startswith("rocalphago."))
    if not total or not ours:
        return None
    return 100.0 * ours / total
