"""Networks (``models/``): model FLOP/s utilization of the training
window — 3 × the forward operations per position from shapes
(``chipbench/flops.py``) × positions per second ÷ the chip's bf16
peak (``chipbench/peaks.json``). An end-to-end utilization: it says
nothing of idle time or of any one kernel."""

from chipbench.flops import forward_flops
from chipbench.peaks import peak


def read(ctx, raw):
    top = peak(ctx.device)
    if top is None or not raw.get("positions"):
        return None
    per_pos = 3 * forward_flops(ctx.config["policy"],
                                ctx.config["board"])
    rate = raw["positions"] / raw["elapsed_s"]
    return 100.0 * per_pos * rate / (top * ctx.device["count"])
