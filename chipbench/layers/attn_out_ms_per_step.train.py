"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.attn.out`` — a softmax layer's ``o_proj`` and the
reshape in front of it, forward, recomputed forward and backward
together (the delta layers' ``seq.attn.kda.out`` is another name and
does not hold this one). None where no program that ran has the
scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.attn.out")
