"""Networks (``models/seqpolicy.py``, the gated short convolution):
device self time per train step under ``seq.shortconv`` — the layer's
input norm and residual add, ``in_proj``, the gated convolution and
``out_proj``, forward, recomputed forward and backward together;
``scopes.json`` has the split into ``.proj``, ``.mix`` and ``.out``
(``chipbench/seq_readers.py``). None where no program that ran has
the scope."""

from chipbench.seq_readers import scope_ms_per_step


def read(ctx, raw):
    return scope_ms_per_step(ctx, "seq.shortconv")
