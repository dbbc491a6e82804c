"""Networks (``models/seqpolicy.py``): device self time per train
step DIRECTLY under a softmax layer's scope — in a scope path that
holds ``seq.attn.full`` / ``.window`` / ``.mla`` and none of the five
parts inside them (``seq.attn.kernel`` / ``.proj`` / ``.rope`` /
``.gate`` / ``.out``): the input norm, the residual add, the
transpose of the kernel's output. The figure that says the split of
those layers is whole, as ``unscoped_device_pct.train`` does for the
step: the five parts and this add up to ``attn_full_`` +
``attn_window_`` + ``attn_mla_ms_per_step.train``. From the by-scope
account's own window (``chipbench/scopes.py::account``). None where
no program that ran has the parts."""

from chipbench.scopes import account, has_scope

LAYERS = ("seq.attn.full", "seq.attn.window", "seq.attn.mla")
PARTS = ("seq.attn.kernel", "seq.attn.proj", "seq.attn.rope",
         "seq.attn.gate", "seq.attn.out")


def read(ctx, raw):
    acct = account(ctx)
    steps = acct["window"].get("steps")
    if not steps or not has_scope(acct, "seq.attn.proj"):
        return None
    return 1e3 * sum(
        t for scope, t in acct["by_scope"].items()
        if any(n in scope for n in LAYERS)
        and not any(n in scope for n in PARTS)) / steps
