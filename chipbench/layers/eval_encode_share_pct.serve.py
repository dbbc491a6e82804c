"""Encode (``features/``) inside a leaf evaluation: percent of the
device's busy time under ``eval.encode`` and ``eval.groups``
(``search/device_mcts.py::eval_batch*``; ``chipbench/scopes.py``)."""

from chipbench.scopes import share_pct


def read(ctx, raw):
    return share_pct(ctx, "eval.encode", "eval.groups")
