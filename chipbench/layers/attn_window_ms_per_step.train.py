"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.attn.window`` — a sliding-window layer's norm, projections, rotary, gate, attention and output projection
(``chipbench/seq_readers.py``). None where no program that ran has
the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.attn.window")
