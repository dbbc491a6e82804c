"""Kernels (``models/seqpolicy.py::kda_chunked`` under the scope
``seq.attn.kda.scan``): the least time the chip could take for the
delta rule's recurrence that a step needs — the larger of its
operations ÷ the bf16 peak and its bytes ÷ the HBM peak, both from
shapes (``chipbench/flops_ling.py::scan_flops`` / ``scan_bytes``:
``7·d_k·d_v`` a head a token, 3 × forward; ``q, k, v, o`` in the
compute type, the log-decay in float32 and ``beta`` once forward,
they and their cotangents once backward; ``peaks.json``) — over the
device self time under the scope, forward, recomputed forward and
backward together. Bandwidth-bound at these shapes. None where no
program that ran has the scope, or off a chip whose peaks are
known."""

from chipbench.flops_ling import scan_bytes, scan_flops
from chipbench.peaks import peak
from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    flops = peak(ctx.device)
    taken_ms = scope_ms_per_step(ctx, "seq.attn.kda.scan")
    if flops is None or not taken_ms:
        return None
    t = ctx.traffic
    least = max(
        scan_flops(ctx.config, t["rows"], t["seq_len"]) / flops,
        scan_bytes(ctx.config, t["rows"], t["seq_len"])
        / peak(ctx.device, "hbm_bytes_per_s"))
    return 100.0 * least / (taken_ms / 1e3)
