"""Trainer step: the share of the device's busy time that falls
under no scope of the program's or of Flax's — the ratio that says
the by-scope account is whole (``chipbench/scopes.py``). The classes
(forward, backward, augment, loss, update, unscoped) partition the
self time, so with this one small the others add up to the step."""

from chipbench.scopes import train_split


def read(ctx, raw):
    split = train_split(ctx)
    if split is None or not split["busy"]:
        return None
    return 100.0 * split["unscoped"] / split["busy"]
