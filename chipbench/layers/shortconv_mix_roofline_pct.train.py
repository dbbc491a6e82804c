"""Kernels (``models/seqpolicy.py::ShortConv``, XLA's fusions under
the scope ``seq.shortconv.mix``: ``B ⊙ x̃``, the three causal taps,
``C ⊙``): the least time the chip could take for the gated
convolution a step needs — its bytes from shapes in the compute type
(``chipbench/flops_lfm2.py::shortconv_mix_bytes``: forward reads
``B, C, x̃`` and writes ``y``, backward reads ``dy, B, C, x̃`` and
writes ``dB, dC, dx̃``, 45,056 B a token a layer at the published
width) ÷ the HBM peak (``peaks.json``); its multiply-adds are a few
per byte and bind nothing — over the device self time under the
scope, forward, recomputed forward and backward together. None where
no program that ran has the scope, or off a chip whose peak is
known."""

from chipbench.flops_lfm2 import shortconv_mix_bytes
from chipbench.peaks import peak
from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    rate = peak(ctx.device, "hbm_bytes_per_s")
    taken_ms = scope_ms_per_step(ctx, "seq.shortconv.mix")
    if rate is None or not taken_ms:
        return None
    t = ctx.traffic
    least = shortconv_mix_bytes(ctx.config, t["rows"], t["seq_len"]) / rate
    return 100.0 * least / (taken_ms / 1e3)
