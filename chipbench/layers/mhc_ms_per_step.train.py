"""Networks (``models/seqpolicy.py``): device self time per train
step under the hyper-connections' three scopes — ``seq.mhc.coeff``
(the streams' norm statistic, the ``phi`` products, the sigmoids),
``seq.mhc.sinkhorn`` (the iterations that make ``H_res`` doubly
stochastic) and ``seq.mhc.mix`` (the passes over the streams) —
matched by their common ``seq.mhc``; ``scopes.json`` has the split
(``chipbench/seq_readers.py``). None where no program that ran has
the scopes."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.mhc")
