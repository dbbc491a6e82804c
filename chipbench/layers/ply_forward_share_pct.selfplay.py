"""Networks (``models/``) inside the fused self-play ply: percent of
the device's busy time under ``ply.forward`` — both half-batch
forwards and the half swap (``chipbench/scopes.py``). Replaces
``fwd_alone_us_per_pos.selfplay``."""

from chipbench.scopes import share_pct


def read(ctx, raw):
    return share_pct(ctx, "ply.forward")
