"""Networks (``models/seqpolicy.py``, a ``bailing_hybrid``
configuration): model FLOP/s utilization of the training window — the
step's operations from shapes and from the routed pairs that landed
on held experts (``chipbench/flops_ling.py``: 3 × forward; the delta
rule's recurrence as ``7·d_k·d_v`` a head a token, what the algorithm
needs whatever chunking implements it; the program's
``moe_tokens_held_total``) × steps per second ÷ the chip's bf16 peak
(``chipbench/peaks.json``): the cell's share of the whole step's
peak. Recomputed layers, a chunk's pairwise products and inverse,
masked halves of score blocks and a kernel's padding do not count.
None where the program has no such counter."""

from chipbench.flops_ling import train_step_flops
from chipbench.peaks import peak
from chipbench.seq_readers import held_pairs_per_step


def read(ctx, raw):
    top = peak(ctx.device)
    pairs = held_pairs_per_step(ctx, raw)
    if top is None or pairs is None:
        return None
    t = ctx.traffic
    per_step = train_step_flops(ctx.config, t["rows"], t["seq_len"],
                                pairs)
    rate = raw["steps"] / raw["elapsed_s"]
    return 100.0 * per_step * rate / (top * ctx.device["count"])
