"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.mtp`` — what the multi-token-prediction module adds
outside its block: the next ids' embedding, two norms, ``eh_proj``,
its final norm and its head product (the block itself runs under the
layers' scopes) (``chipbench/seq_readers.py``). None where no program
that ran has the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.mtp")
