"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.attn.kda.proj.norm`` — the delta layers' two L2
norms, the queries' ``1/√d_k`` and the casts of q, k, v to the
compute type, inside ``seq.attn.kda.proj``; forward, recomputed
forward and backward together. None where no program that ran has the
scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.attn.kda.proj.norm")
