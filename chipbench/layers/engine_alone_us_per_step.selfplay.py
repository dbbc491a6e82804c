"""Rules engine (``engine/jaxgo.py``): microseconds per game-step of
the vmapped ``step`` jitted ALONE on the cell's staggered states,
each playing the move the window's last rep sampled there
(``chipbench/probe.py``: the fused ply shares one group analysis with
the encoder, this call computes its own)."""

import functools

from chipbench.probe import time_alone


def read(ctx, raw):
    drv = ctx.driver
    if not hasattr(drv, "states") or "last" not in raw:
        return None
    import jax

    from rocalphago_tpu.engine.jaxgo import step

    vstep = jax.jit(jax.vmap(functools.partial(step, drv.cfg)))
    per_call = time_alone(ctx, "chipbench.engine_alone", vstep,
                          drv.states, raw["last"].actions[0])
    return 1e6 * per_call / drv.batch
