"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.attn.kda.proj.conv`` — the delta layers' three
depthwise causal convolutions (a pad, four shifted slices, the taps,
in float32) and SiLU, for q, k and v, inside ``seq.attn.kda.proj``;
forward, recomputed forward and backward together. None where no
program that ran has the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.attn.kda.proj.conv")
