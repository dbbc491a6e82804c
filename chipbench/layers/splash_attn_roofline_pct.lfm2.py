"""Kernels (``models/seqpolicy.py::kernel_attention``, JAX's Pallas
``splash_attention`` under the scope ``seq.attn.kernel``, at an
``lfm2_moe`` configuration's 64-wide heads, unpadded): the least time
the chip could take for the attention a step needs — the larger of
operations ÷ the bf16 peak and bytes ÷ the HBM peak, both from
shapes at the published head width (``chipbench/flops_lfm2.py``: the
six products on the unmasked pairs; q, k, v, o and their gradients
once; ``peaks.json``) — over the device self time under the scope,
forward and backward kernels together (a layer's recomputation keeps
the kernel's output). Compute-bound at these shapes. None where no
program that ran has the scope (the XLA form of attention, off the
TPU or at toy shapes)."""

from chipbench.flops_lfm2 import (
    attention_kernel_bytes,
    attention_kernel_flops,
)
from chipbench.peaks import peak
from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    flops = peak(ctx.device)
    taken_ms = scope_ms_per_step(ctx, "seq.attn.kernel")
    if flops is None or not taken_ms:
        return None
    t = ctx.traffic
    least = max(
        attention_kernel_flops(ctx.config, t["rows"], t["seq_len"])
        / flops,
        attention_kernel_bytes(ctx.config, t["rows"], t["seq_len"])
        / peak(ctx.device, "hbm_bytes_per_s"))
    return 100.0 * least / (taken_ms / 1e3)
