"""Kernels (``models/seqpolicy.py::unit_lower_inverse``, under the
scope ``seq.attn.kda.scan.inverse`` inside ``seq.attn.kda.scan``):
device self time per train step of the delta rule's inverse ``(I +
A)^−1`` of every chunk and head — the forward, the layers' recomputed
forward and the ``custom_vjp`` backward together
(``chipbench/seq_readers.py``). ``kda_scan_ms_per_step.train`` holds
it too. None where no program that ran has the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.attn.kda.scan.inverse")
