"""Rules engine (``engine/jaxgo.py``) inside the fused self-play
ply: percent of the device's busy time under ``ply.step`` and
``ply.groups`` — the vmapped step and the group analysis it shares
with the encode (``chipbench/scopes.py``). Replaces
``engine_alone_us_per_step.selfplay``."""

from chipbench.scopes import share_pct


def read(ctx, raw):
    return share_pct(ctx, "ply.step", "ply.groups")
