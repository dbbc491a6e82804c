"""Networks (``models/``): microseconds per position of the policy
``apply`` jitted ALONE at the cell's batch, on the encoded planes of
the cell's states (``chipbench/probe.py``)."""

from chipbench.probe import time_alone


def read(ctx, raw):
    drv = ctx.driver
    if not hasattr(drv, "encode"):
        return None
    import jax

    planes = drv.encode()(drv.states)
    fwd = jax.jit(drv.net.module.apply)
    per_call = time_alone(ctx, "chipbench.fwd_alone", fwd,
                          drv.net.params, planes)
    return 1e6 * per_call / drv.batch
