"""From a profiler trace to numbers: device busy and idle time, the
device operations that took most time, the longest idle gaps and
what the host was doing in them.

Two steps, so that the arithmetic can be checked without a chip:

* :func:`load_events` reads the profiler's ``.xplane.pb`` with
  ``jax.profiler.ProfileData`` and keeps what the reduction needs as
  plain lists: every event of the device planes and, of the host
  planes, the benchmark's own spans (``jax.profiler.TraceAnnotation``
  names starting with ``chipbench.``).
* :func:`reduce` is pure arithmetic on those lists;
  ``tests/data/trace_events.json`` is a recorded sample it is checked
  on.

The program has no stable scope names today (PERF.md §7), so nothing
here looks for one: operations are reported under the names XLA
prints, and the only layer reading taken from the trace is the
device's idle share.
"""

from __future__ import annotations

import bisect
import glob
import os

#: prefix of the benchmark's own host spans
SPAN_PREFIX = "chipbench."
#: the span that brackets the traced window
WINDOW_SPAN = SPAN_PREFIX + "window"
#: device-plane lines that hold operations, best first. "XLA Ops" has
#: one event per executed HLO op (a while loop's event spans its
#: body's); "XLA Modules" one per executed program.
OP_LINES = ("XLA Ops", "XLA Modules")
#: host-plane lines on which XLA:CPU executes programs, and the name
#: of the stand-in plane a CPU rehearsal's events are filed under
HOST_XLA_LINES = ("tf_XLAPjRtCpuClient", "tf_XLAEigen")
HOST_STAND_IN = "/host:CPU XLA threads (rehearsal)"
#: gaps labelled for the whole-window account, longest first
LABELLED_GAPS = 200_000
#: an operation's name in the breakdown is cut to this length
MAX_NAME = 120
#: at most this many entries in each list of the breakdown
TOP = 10


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_events(xplane_path: str) -> dict:
    """``{"device": {plane: {line: [[name, start_ns, dur_ns], …]}},
    "spans": [[name, start_ns, dur_ns], …]}`` from one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    device, spans, host_xla = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                lines[line.name] = [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events]
            device[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [[e.name, float(e.start_ns),
                           float(e.duration_ns)] for e in line.events]
                spans.extend(e for e in events
                             if e[0].startswith(SPAN_PREFIX))
                if line.name.startswith(HOST_XLA_LINES):
                    host_xla.extend(e for e in events if e[2] > 0)
    if not device and host_xla:
        # a CPU rehearsal: XLA's own threads stand in for the device,
        # so that the same reduction runs; never a chip number
        device[HOST_STAND_IN] = {OP_LINES[0]: host_xla}
    return {"device": device, "spans": spans}


def sample(events: dict, keep: int = 300) -> dict:
    """The first ``keep`` events of every device line and of the
    spans, with what each line held in all: small enough to keep,
    enough to see how a trace is laid out."""
    return {
        "device": {plane: {line: evs[:keep]
                           for line, evs in lines.items()}
                   for plane, lines in events["device"].items()},
        "spans": sorted(events["spans"], key=lambda e: e[1])[:keep],
        "counts": {plane: {line: len(evs)
                           for line, evs in lines.items()}
                   for plane, lines in events["device"].items()},
    }


def short_name(name: str) -> str:
    """An operation's name as XLA prints it, cut to what tells it
    apart: on a TPU the event's name is the whole HLO instruction
    (``%fusion.7 = s32[1024]{…} fusion(…), kind=…, calls=%f``);
    kept are the result's name and shape and what it calls."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:MAX_NAME]
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    calls = rest.rpartition("calls=")[2] if "calls=" in rest else ""
    return " ".join(x for x in (head, shape, calls) if x)[:MAX_NAME]


def _union(intervals: list) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def _clip(events: list, lo: float, hi: float) -> list:
    """Events cut to the window ``[lo, hi]``; those outside dropped."""
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append([name, s, e - s])
    return out


def self_times(events: list) -> dict:
    """Summed SELF time per operation name: an event's duration less
    the part its nested events cover (a ``while`` event contains its
    body's events on the same line, and must not count them twice)."""
    totals: dict = {}
    stack: list = []        # [name, end, start, child_time]

    def close(upto: float):
        while stack and stack[-1][1] <= upto:
            name, end, start, child = stack.pop()
            dur = end - start
            totals[name] = totals.get(name, 0.0) + max(dur - child, 0.0)
            if stack:
                stack[-1][3] += dur

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        stack.append([name, start + dur, start, 0.0])
    close(float("inf"))
    return totals


class _Spans:
    """The benchmark's spans, indexed to find those over a gap."""

    def __init__(self, spans: list):
        self.spans = sorted((s for s in spans if s[0] != WINDOW_SPAN),
                            key=lambda s: s[1])
        self.starts = [s[1] for s in self.spans]
        self.longest = max((s[2] for s in self.spans), default=0.0)

    def label(self, lo: float, hi: float) -> str:
        """The span that covers most of the gap ``[lo, hi]``; the
        shortest such span on a tie (the innermost)."""
        best, best_key = "unlabelled", (0.0, 0.0)
        i = bisect.bisect_left(self.starts, hi)
        while i > 0:
            i -= 1
            name, start, dur = self.spans[i]
            if start + self.longest <= lo:
                break           # no earlier span can reach the gap
            overlap = min(start + dur, hi) - max(start, lo)
            if overlap > 0 and (overlap, -dur) > best_key:
                best, best_key = name, (overlap, -dur)
        return best


def reduce(events: dict, top: int = TOP) -> dict:
    """Busy seconds (union of device-operation intervals, averaged
    over the device planes), the window's length, the ``top``
    operations by self time and the ``top`` longest idle gaps, each
    labelled by the benchmark span that covers it.

    The window is the ``chipbench.window`` span when the trace has
    one, else the extent of all the benchmark's spans (client waits
    on many threads have no one bracket), else that of the device
    events."""
    device = events["device"]
    spans = events["spans"]
    index = _Spans(spans)
    per_plane = []
    for plane, lines in sorted(device.items()):
        for want in OP_LINES:
            if lines.get(want):
                per_plane.append((plane, lines[want]))
                break
    if not per_plane:
        have = {p: sorted(lines) for p, lines in device.items()}
        raise ValueError(
            f"the trace has no device operation: none of the lines "
            f"{OP_LINES} in the device planes {have}")
    window = [s for s in spans if s[0] == WINDOW_SPAN] or spans
    if window:
        lo = min(s[1] for s in window)
        hi = max(s[1] + s[2] for s in window)
    else:
        lo = min(e[1] for _, evs in per_plane for e in evs)
        hi = max(e[1] + e[2] for _, evs in per_plane for e in evs)
    busy_ns, ops, gaps = 0.0, {}, []
    for _, evs in per_plane:
        evs = _clip(evs, lo, hi)
        merged = _union([[s, s + d] for _, s, d in evs])
        busy_ns += sum(e - s for s, e in merged)
        for name, t in self_times(evs).items():
            name = short_name(name)
            ops[name] = ops.get(name, 0.0) + t
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
    n = len(per_plane)
    gaps.sort(reverse=True)
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "devices": n,
        "device_ops": [[name, t / n / 1e9] for name, t in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[index.label(a, b), g / 1e9]
                      for g, a, b in gaps[:top]],
        "idle_by_span": _idle_by_span(index, gaps),
    }


def _idle_by_span(index: _Spans, gaps: list) -> list:
    """All idle time, summed by the span that labels each gap —
    the breakdown's lists are the top few, this is the whole."""
    totals: dict = {}
    for g, a, b in gaps[:LABELLED_GAPS]:
        label = index.label(a, b)
        totals[label] = totals.get(label, 0.0) + g
    rest = sum(g for g, _, _ in gaps[LABELLED_GAPS:])
    if rest:
        totals["(shorter gaps, not labelled)"] = rest
    return [[k, v / 1e9] for k, v in sorted(
        totals.items(), key=lambda kv: -kv[1])]


def idle_pct(trace: dict | None):
    """The device's idle share of the traced window, in percent."""
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
