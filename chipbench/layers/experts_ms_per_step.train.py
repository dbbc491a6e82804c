"""Networks (``models/seqpolicy.py``): device self time per train
step under ``seq.experts`` — sorting the token-expert pairs, the dispatch, the masks, the SwiGLU and the weighted combine (the grouped products' own custom calls lose their scope in XLA's rewrite and are filed unscoped: PERF.md section 5)
(``chipbench/seq_readers.py``). None where no program that ran has
the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.experts")
