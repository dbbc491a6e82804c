"""Networks (``models/seqpolicy.py``, an ``lfm2_moe`` configuration):
model FLOP/s utilization of the training window — the step's
operations from shapes and from the routed pairs that landed on held
experts (``chipbench/flops_lfm2.py``: 3 × forward; the short
convolutions' products and taps, the attention layers' projections
and scores on the unmasked pairs, the dense MLP, the routers, the
held experts' pairs from the program's ``moe_tokens_held_total``,
the tied head) × steps per second ÷ the chip's bf16 peak
(``chipbench/peaks.json``): the cell's share of the whole step's
peak. Recomputed layers, masked halves of score blocks and a
kernel's padding do not count. None where the program has no such
counter, or off a chip whose peak is known."""

from chipbench.flops_lfm2 import train_step_flops
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
