"""Networks (``models/seqpolicy.py``, the expert layer): imbalance —
the busiest held expert's pairs in the window's last step (the
program's gauge ``moe_expert_load_max``, the largest over the sparse
layers) over the mean pairs per held expert per step
(``chipbench/seq_readers.py``). 1 is perfect balance. None where the
program has no such gauge."""

from chipbench.seq_readers import mean_load


def read(ctx, raw):
    mean = mean_load(ctx, raw)
    top = (ctx.counters_after or {}).get("gauges", {}).get(
        "moe_expert_load_max")
    if not mean or top is None:
        return None
    return top / mean
