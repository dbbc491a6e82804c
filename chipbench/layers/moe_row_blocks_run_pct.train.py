"""Networks (``models/seqpolicy.py``, the expert layer): the share of
the expert buffers' row blocks that held a token-expert pair and were
computed — growth of the program's ``moe_row_blocks_run_total`` over
the window ÷ growth of ``moe_row_blocks_total``, in percent (forward
pass, every chunk of every sparse layer). The dense passes over a
buffer cost by this share, not by the buffer's length; 100 when every
pair a chunk routes lands on the experts held here. None where the
program has no such counters."""

from chipbench.counters import counter_delta


def read(ctx, raw):
    run, total = (counter_delta(ctx.counters_before, ctx.counters_after,
                                name)
                  for name in ("moe_row_blocks_run_total",
                               "moe_row_blocks_total"))
    if run is None or not total:
        return None
    return 100.0 * run / total
