"""Networks (``models/``) inside a leaf evaluation: percent of the
device's busy time under ``eval.policy`` and ``eval.value``
(``chipbench/scopes.py``)."""

from chipbench.scopes import share_pct


def read(ctx, raw):
    return share_pct(ctx, "eval.policy", "eval.value")
