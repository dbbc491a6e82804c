"""What the move-sequence cell's layer readers share: device self
time per step under one of the program's ``seq.*`` scopes or of the
operations of one name, and the held experts' mean load from the
program's counter."""

from __future__ import annotations

import json
import os
import shutil

from chipbench import scopes, trace_reduce
from chipbench.counters import counter_delta
from chipbench.scopes import account, has_scope, seconds_under

#: how XLA names what it makes of ``ragged_dot`` on the TPU: custom
#: calls ``%ragged-dot-none.N`` (the products) and
#: ``%ragged-dot-metadata.N`` (their group offsets), ``op_name`` the
#: same — the scope is lost, so the by-scope account files them under
#: none (read off the step compiled for a v5e)
RAGGED_DOT = "ragged-dot"
#: the earlier line holds this many operations, longest first
SAID = 24


def scope_ms_per_step(ctx, scope: str):
    """Milliseconds of device self time per train step under
    ``scope`` — forward, backward and each layer's recomputed forward
    together (the recomputation runs under the backward's
    ``transpose(jvp(…))`` wrapper and keeps the scope's name) — from
    the by-scope account's own traced window; None where no program
    that ran has the scope."""
    acct = account(ctx)
    steps = acct["window"].get("steps")
    if not steps or not has_scope(acct, scope):
        return None
    return 1e3 * seconds_under(acct, scope) / steps


def op_account(ctx) -> dict:
    """``{"by_op": {instruction: device self seconds}, "steps": n}``
    over one more traced window of
    ``scopes.WINDOW_SECONDS`` — the by-scope account keeps scopes and
    not instructions, and deletes its trace. Kept on ``ctx`` for the
    next reader."""
    cached = getattr(ctx, "op_account", None)
    if cached is not None:
        return cached
    import jax

    trace_dir = os.path.join(scopes.HERE, "out", ctx.cell["name"],
                             "op_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    traced = ctx.driver.window(
        scopes.WINDOW_SECONDS,
        on_start=lambda: jax.profiler.start_trace(
            trace_dir, profiler_options=options))
    jax.profiler.stop_trace()
    events = scopes.load(trace_reduce.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    window = [s for s in events["spans"]
              if s[0] == trace_reduce.WINDOW_SPAN]
    planes = [p for _, p in sorted(events["device"].items())
              if p["ops"]]
    lo = min(s[1] for s in window) if window else float("-inf")
    hi = max(s[1] + s[2] for s in window) if window else float("inf")
    by_op = {}
    for plane in planes:
        evs = trace_reduce._clip(
            [[i, s, d] for _, i, s, d in plane["ops"]], lo, hi)
        for name, t in trace_reduce.self_times(evs).items():
            by_op[name] = by_op.get(name, 0.0) + t / len(planes) / 1e9
    ctx.op_account = {"by_op": by_op, "steps": traced.get("steps")}
    print(json.dumps({"op_account": {
        "steps": traced.get("steps"),
        "longest": sorted(by_op.items(), key=lambda kv: -kv[1])[:SAID]}}),
        flush=True)
    return ctx.op_account


def ops_ms_per_step(ctx, prefix: str):
    """Milliseconds of device self time per train step in the
    operations whose instruction's name starts with ``prefix``; None
    where no operation that ran does."""
    acct = op_account(ctx)
    found = [t for name, t in acct["by_op"].items()
             if name.startswith(prefix)]
    if not found or not acct["steps"]:
        return None
    return 1e3 * sum(found) / acct["steps"]


def held_pairs_per_step(ctx, raw):
    """Token–expert pairs that landed on held experts per step, over
    all sparse layers (growth of ``moe_tokens_held_total`` over the
    window ÷ steps); None where the program has no such counter."""
    held = counter_delta(ctx.counters_before, ctx.counters_after,
                         "moe_tokens_held_total")
    if held is None or not raw.get("steps"):
        return None
    return held / raw["steps"]


def mean_load(ctx, raw):
    """Pairs per held expert per step: the above ÷ sparse layers held
    ÷ experts held."""
    pairs = held_pairs_per_step(ctx, raw)
    if pairs is None:
        return None
    cfg = ctx.config
    layers = cfg["mlp_layer_types"][:cfg["num_hidden_layers"]].count(
        "sparse")
    return pairs / layers / cfg["num_experts"]
