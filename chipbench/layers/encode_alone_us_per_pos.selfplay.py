"""Encode (``features/``): microseconds per position of
``vgroup_data`` + ``batched_encoder`` jitted ALONE on the cell's
staggered states at the cell's batch (``chipbench/probe.py``: outside
the fused ply, so overlap and fusion are ignored)."""

from chipbench.probe import time_alone


def read(ctx, raw):
    drv = ctx.driver
    if not hasattr(drv, "encode"):
        return None
    per_call = time_alone(ctx, "chipbench.encode_alone", drv.encode(),
                          drv.states)
    return 1e6 * per_call / drv.batch
