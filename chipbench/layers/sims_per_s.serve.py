"""Search, split path (``search/device_mcts.py`` prepare/apply driven
per simulation by ``serve/sessions.py``): simulations per second —
growth of ``serve_session_sims_total`` over the window's length."""

from chipbench.counters import counter_delta


def read(ctx, raw):
    sims = counter_delta(ctx.counters_before, ctx.counters_after,
                         "serve_session_sims_total")
    if not sims or not raw.get("elapsed_s"):
        return None
    return sims / raw["elapsed_s"]
