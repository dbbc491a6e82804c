"""Serve pool (``serve/evaluator.py``): leaf rows per device batch —
growth of ``serve_eval_rows_total`` over growth of
``serve_eval_batches_total`` in the window. The ladder's largest
size is the ceiling; a low reading means sessions fall out of
step and the device evaluates padding."""

from chipbench.counters import counter_delta


def read(ctx, raw):
    rows = counter_delta(ctx.counters_before, ctx.counters_after,
                         "serve_eval_rows_total")
    batches = counter_delta(ctx.counters_before, ctx.counters_after,
                            "serve_eval_batches_total")
    if not rows or not batches:
        return None
    return rows / batches
