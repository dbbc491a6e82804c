"""Trainer step (``training/sl.py``, ``training/symmetries.py``):
device self time per train step under ``train.augment`` — the input
cast and the on-device dihedral augmentation
(``chipbench/scopes.py``). None where the step has no such scope."""

from chipbench.scopes import train_ms


def read(ctx, raw):
    return train_ms(ctx, "augment")
