"""Benchmark-side spans: one layer's program timed alone.

The program has no scopes inside its fused device programs yet
(PERF.md §7), so a layer's cost is read by running that layer's own
function jitted alone on the cell's inputs. Such a number ignores
overlap and fusion with its neighbours: it bounds the layer's share,
it does not measure it, and it is replaced when in-program scopes
exist.
"""

from __future__ import annotations

import time

#: a host-clock reading spans at least this long (the clock is good
#: to about half a millisecond)
MIN_SECONDS = 0.3
MIN_CALLS = 3


def time_alone(ctx, name: str, fn, *args) -> float:
    """Seconds per call of ``fn(*args)`` on the device: one call to
    compile and warm, then back-to-back calls for at least
    :data:`MIN_SECONDS`, blocked at the end, under the span ``name``."""
    import jax

    jax.block_until_ready(fn(*args))
    calls = 0
    with ctx.span(name):
        t0 = time.monotonic()
        while True:
            out = fn(*args)
            calls += 1
            if calls >= MIN_CALLS:
                jax.block_until_ready(out)
                dt = time.monotonic() - t0
                if dt >= MIN_SECONDS:
                    return dt / calls
