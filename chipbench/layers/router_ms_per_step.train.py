"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.router`` — the norm before a sparse layer, the float32 router, its softmax, top-k and weights
(``chipbench/seq_readers.py``). None where no program that ran has
the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.router")
