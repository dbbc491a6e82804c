"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.attn.rope`` — the float32 rotary passes over
``[B, S, H, d]``; in latent attention also the scaling of ``q_nope``,
the broadcast of ``k_pe`` to the heads and the two concatenations
that build q and k — forward, recomputed forward and backward
together. What XLA fuses into a product reads under the product's
scope and not here (``chipbench/scopes.py::resolve``). None where no
program that ran has the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.attn.rope")
