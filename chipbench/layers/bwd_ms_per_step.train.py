"""Networks (``models/``): device self time per train step under the
network's backward scopes — ``transpose(jvp(PolicyNet))/…``: input
and weight gradients, and with the weight gradients the SGD update
XLA fuses into them (a fusion is put down to the convolution inside
it: ``chipbench/scopes.py``)."""

from chipbench.scopes import train_ms


def read(ctx, raw):
    return train_ms(ctx, "bwd")
