"""Kernels (``models/seqpolicy.py::kernel_attention``, JAX's Pallas
``splash_attention``): device self time per train step in the
kernel's FORWARD calls alone, found by the instruction's name in the
traced window the grouped products' readers share
(``chipbench/seq_readers.py::op_account``: no trace of its own). The
kernel's three phases are custom calls named ``splash_mha_fwd…``,
``splash_mha_dq…`` and ``splash_mha_dkv…``. A layer whose
recomputation keeps the kernel's output and row statistics runs the
forward call once a step, one that recomputes the whole layer twice:
this reads half there of what it reads here. None where no such
operation ran (the XLA form of attention, off the TPU or at toy
shapes)."""

from chipbench.seq_readers import ops_ms_per_step

#: how ``op_account`` prints the forward kernel's instructions:
#: ``splash_mha_fwd_residuals.N`` under differentiation
SPLASH_FWD = "splash_mha_fwd"


def read(ctx, raw):
    return ops_ms_per_step(ctx, SPLASH_FWD)
