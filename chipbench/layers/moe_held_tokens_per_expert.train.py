"""Networks (``models/seqpolicy.py``, the expert layer): token–expert
pairs a held expert computes per step, on average — growth of the
program's ``moe_tokens_held_total`` over the window ÷ steps ÷ sparse
layers held ÷ experts held (``chipbench/seq_readers.py``). The
deployment's figure is this times the chips that would feed the
expert (PERF.md §4). None where the program has no such counter."""

from chipbench.seq_readers import mean_load


def read(ctx, raw):
    return mean_load(ctx, raw)
