"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.attn.gate`` — the per-head gate of ``GatedAttention``
and of a gated latent layer: ``gate_proj``'s product from the layer's
input, the sigmoid, and the product with the kernel's output —
forward, recomputed forward and backward together. None where no
program that ran has the scope (latent attention without a gate)."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.attn.gate")
