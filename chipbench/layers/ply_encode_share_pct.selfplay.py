"""Encode (``features/``) inside the fused self-play ply: percent of
the device's busy time under ``ply.encode`` (``chipbench/scopes.py``).
Replaces ``encode_alone_us_per_pos.selfplay``, which times the encode
jitted alone outside the fusion it really runs in."""

from chipbench.scopes import share_pct


def read(ctx, raw):
    return share_pct(ctx, "ply.encode")
