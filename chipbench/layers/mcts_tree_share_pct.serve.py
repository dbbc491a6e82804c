"""Search, split path (``search/device_mcts.py`` prepare/apply):
percent of the device's busy time under ``mcts.select``,
``mcts.expand`` and ``mcts.backup`` — the tree's own work beside the
evaluations (``chipbench/scopes.py``)."""

from chipbench.scopes import share_pct


def read(ctx, raw):
    return share_pct(ctx, "mcts.select", "mcts.expand", "mcts.backup")
