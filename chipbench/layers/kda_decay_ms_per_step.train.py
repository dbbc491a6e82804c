"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.attn.kda.proj.decay`` — the delta layers' ``beta``
(its product and sigmoid) and the log-decay: ``f_proj``'s float32
product, ``dt_bias``, ``A_log``, the sigmoid — inside
``seq.attn.kda.proj``; forward, recomputed forward and backward
together. None where no program that ran has the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.attn.kda.proj.decay")
