"""Kernels (``models/seqpolicy.py::kda_chunked``, XLA's fusions and
loop under the scope ``seq.attn.kda.scan``): device self time per
train step from ``q, k, v, g, beta`` to ``o`` — the pairwise decays,
the inverse and the scan over chunks, forward, recomputed forward
and backward together (``chipbench/seq_readers.py``). None where no
program that ran has the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.attn.kda.scan")
