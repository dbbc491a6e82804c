"""Kernels (XLA's grouped products of ``jax.lax.ragged_dot``, the
held experts' matrices over the pairs routed to them, an ``lfm2_moe``
configuration): the least time the chip could take for the products a
step needs — the larger of operations ÷ the bf16 peak and bytes ÷ the
HBM peak, from shapes and from the pairs that landed on held experts
(``chipbench/flops_lfm2.py``, ``peaks.json``, the program's
``moe_tokens_held_total``) — over the device self time in those
operations, forward, recomputed forward and both gradients together.
None where no such operation ran or the program has no such
counter."""

from chipbench.flops_lfm2 import expert_product_bytes, expert_product_flops
from chipbench.peaks import peak
from chipbench.seq_readers import (
    RAGGED_DOT,
    held_pairs_per_step,
    ops_ms_per_step,
)


def read(ctx, raw):
    flops = peak(ctx.device)
    pairs = held_pairs_per_step(ctx, raw)
    if flops is None or pairs is None:
        return None
    taken_ms = ops_ms_per_step(ctx, RAGGED_DOT)
    if not taken_ms:
        return None
    least = max(
        expert_product_flops(ctx.config, pairs) / flops,
        expert_product_bytes(ctx.config, pairs)
        / peak(ctx.device, "hbm_bytes_per_s"))
    return 100.0 * least / (taken_ms / 1e3)
