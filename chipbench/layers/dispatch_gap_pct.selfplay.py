"""Self-play driver (``search/selfplay.py``, ``runtime/pipeline.py``):
the share of the window in which the cell's ``ChunkPipeline`` had no
segment in flight (``host_gap_frac``), by the HOST's clock. It is
not the device's idle share — ``device_idle_pct.selfplay`` is, from
the trace — and stands beside it so the two can be compared."""


def read(ctx, raw):
    gap = raw.get("host_gap_frac")
    return None if gap is None else 100.0 * gap
