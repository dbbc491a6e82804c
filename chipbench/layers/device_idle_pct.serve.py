"""Device: the share of the traced window (at most 5 s of steady
state) in which no operation ran on the chip — 1 − union of the
device-operation intervals over the window, from the profiler's
trace (``chipbench/trace_reduce.py``)."""

from chipbench.trace_reduce import idle_pct


def read(ctx, raw):
    return idle_pct(ctx.trace)
