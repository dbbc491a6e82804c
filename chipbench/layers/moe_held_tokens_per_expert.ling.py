"""Networks (``models/seqpolicy.py``, the expert layer, a
``bailing_hybrid`` configuration): token–expert pairs a held expert
computes per step, on average — growth of the program's
``moe_tokens_held_total`` over the window ÷ steps ÷ expert-bearing
blocks ÷ experts held. The deployment's figure is this times the
chips that would feed the expert (PERF.md §4). None where the program
has no such counter."""

from chipbench.flops_ling import expert_blocks
from chipbench.seq_readers import held_pairs_per_step


def read(ctx, raw):
    pairs = held_pairs_per_step(ctx, raw)
    if pairs is None:
        return None
    return (pairs / expert_blocks(ctx.config)
            / ctx.config["num_experts"])
