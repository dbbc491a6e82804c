"""Networks (``models/seqpolicy.py::held_experts``): device self
time per train step in the grouped products XLA makes of
``jax.lax.ragged_dot`` — forward, recomputed forward and both
gradients — found by the operations' own name in a traced window
(``chipbench/seq_readers.py``): XLA's rewrite drops their scope, so
``experts_ms_per_step.train`` does not hold them and the by-scope
account files them under none. None where no such operation ran (off
the TPU)."""

from chipbench.seq_readers import RAGGED_DOT, ops_ms_per_step


def read(ctx, raw):
    return ops_ms_per_step(ctx, RAGGED_DOT)
