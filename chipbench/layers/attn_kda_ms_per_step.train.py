"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.attn.kda`` — a Kimi-delta-attention layer's norm,
projections, convolutions, gates, chunked recurrence, output norm
and output projection, forward, recomputed forward and backward
together; ``scopes.json`` has the split into ``.proj``, ``.scan``
and ``.out`` (``chipbench/seq_readers.py``). None where no program
that ran has the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.attn.kda")
