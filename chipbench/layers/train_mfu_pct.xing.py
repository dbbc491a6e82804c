"""Networks (``models/seqpolicy.py``, an ``xing4_0`` configuration):
model FLOP/s utilization of the training window — the step's
operations from shapes and from the routed pairs that landed on held
experts (``chipbench/flops_xing.py``: 3 × forward; the program's
``moe_tokens_held_total``) × steps per second ÷ the chip's bf16 peak
(``chipbench/peaks.json``): the cell's share of the whole step's
peak. What the algorithm needs, whatever implements it: recomputed
layers, masked halves of score blocks and a kernel's padding do not
count. None where the program has no such counter."""

from chipbench.flops_xing import train_step_flops
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
