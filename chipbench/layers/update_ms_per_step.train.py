"""Trainer step (``training/sl.py``): device self time per train
step under ``train.update`` — what is left of ``tx.update`` +
``apply_updates`` outside the weight-gradient fusions
(``chipbench/scopes.py``). None where the step has no such scope."""

from chipbench.scopes import train_ms


def read(ctx, raw):
    return train_ms(ctx, "update")
