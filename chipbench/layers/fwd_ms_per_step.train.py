"""Networks (``models/``): device self time per train step under the
network's forward scopes — ``jvp(PolicyNet)/…``, Flax's module names
under JAX's transform of them — from a traced window of its own
(``chipbench/scopes.py``), divided by that window's steps."""

from chipbench.scopes import train_ms


def read(ctx, raw):
    return train_ms(ctx, "fwd")
