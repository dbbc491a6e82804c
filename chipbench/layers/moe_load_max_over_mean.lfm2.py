"""Networks (``models/seqpolicy.py``, the expert layer, an
``lfm2_moe`` configuration): imbalance — the busiest held expert's
pairs in the window's last step (the program's gauge
``moe_expert_load_max``, the largest over the expert layers) over the
mean pairs per held expert per step (``moe_held_tokens_per_expert.lfm2``'s
count). 1 is perfect balance. None where the program has no such
gauge or counter."""

from chipbench.flops_lfm2 import expert_layers
from chipbench.seq_readers import held_pairs_per_step


def read(ctx, raw):
    pairs = held_pairs_per_step(ctx, raw)
    top = (ctx.counters_after or {}).get("gauges", {}).get(
        "moe_expert_load_max")
    if not pairs or top is None:
        return None
    return top / (pairs / expert_layers(ctx.config)
                  / ctx.config["num_experts"])
