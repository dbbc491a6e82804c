"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.attn.proj`` — the MXU work in front of the attention
kernel: the q, k, v products from the layer's input and their
weights' casts; in latent attention the low-rank products and their
two norms — inside ``seq.attn.full`` / ``.window`` / ``.mla``,
forward, recomputed forward and backward together. A pass XLA fuses
into one of these products reads here too (a fusion goes to the scope
of the dot inside it: ``chipbench/scopes.py::resolve``). None where
no program that ran has the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.attn.proj")
