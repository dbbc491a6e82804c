"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.attn.mla`` — a latent-attention layer's norms,
low-rank projections, rotary, attention (the kernel included) and
output projection, forward, recomputed forward and backward together
(``chipbench/seq_readers.py``). None where no program that ran has
the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.attn.mla")
