"""Kernels (``models/seqpolicy.py::mix_in`` / ``mix_out``, XLA's
fusions under the scope ``seq.mhc.mix``): the least time the chip
could take for the passes over the hyper-connections' streams that a
step needs — their bytes from shapes in the compute type
(``chipbench/flops_xing.py::stream_mix_bytes``) ÷ the HBM peak
(``peaks.json``); the multiply-adds are a few per byte and bind
nothing — over the device self time under the scope, forward,
recomputed forward and backward together. None where no program that
ran has the scope, or off a chip whose peak is known."""

from chipbench.flops_xing import stream_mix_bytes
from chipbench.peaks import peak
from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    rate = peak(ctx.device, "hbm_bytes_per_s")
    taken_ms = scope_ms_per_step(ctx, "seq.mhc.mix")
    if rate is None or not taken_ms:
        return None
    t = ctx.traffic
    least = stream_mix_bytes(ctx.config, t["rows"], t["seq_len"]) / rate
    return 100.0 * least / (taken_ms / 1e3)
