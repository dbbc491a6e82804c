"""Networks (``models/seqpolicy.py``, the expert layer, an
``lfm2_moe`` configuration): token–expert pairs a held expert
computes per step, on average — growth of the program's
``moe_tokens_held_total`` over the window ÷ steps ÷ expert layers
held ÷ experts held. The deployment's figure is this times the chips
that would feed the expert (PERF.md §4). None where the program has
no such counter."""

from chipbench.flops_lfm2 import expert_layers
from chipbench.seq_readers import held_pairs_per_step


def read(ctx, raw):
    pairs = held_pairs_per_step(ctx, raw)
    if pairs is None:
        return None
    return (pairs / expert_layers(ctx.config)
            / ctx.config["num_experts"])
