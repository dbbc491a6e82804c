"""Shared micro-benchmark harness.

Parity: the reference's ``benchmarks/`` cProfile scripts (SURVEY.md §2
"Benchmarks", §5 "Tracing / profiling"). Here each script times a
jitted program with compile excluded and prints one JSON line, the
same shape as the repo-root ``bench.py``; pass ``--profile DIR`` to
any script to additionally capture a ``jax.profiler`` trace viewable
in TensorBoard/Perfetto.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax

from rocalphago_tpu.runtime.compilecache import enable_compile_cache

# bf16 peak FLOP/s per chip, keyed by JAX's ``device_kind`` string
# (Google Cloud TPU documentation, per-generation system pages); used
# for MFU = achieved flops/s ÷ peak. A device that is not in the table
# is an error, never a default.
_TPU_BF16_PEAK = {"TPU v4": 275e12, "TPU v5 lite": 197e12,
                  "TPU v5p": 459e12, "TPU v6 lite": 918e12}


def bf16_peak_flops() -> float | None:
    """Peak bf16 FLOP/s of the attached chip, or None off-TPU (an MFU
    against a host CPU "peak" would be meaningless). Raises
    ``KeyError`` for a TPU whose ``device_kind`` is not in the table:
    a utilization against a guessed peak is worse than none."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    if dev.device_kind not in _TPU_BF16_PEAK:
        raise KeyError(
            f"no bf16 peak recorded for device_kind "
            f"{dev.device_kind!r}; add it to _TPU_BF16_PEAK with its "
            "source")
    return _TPU_BF16_PEAK[dev.device_kind]


def program_flops(jitted_fn, *args, **kwargs) -> float | None:
    """FLOPs XLA's cost analysis attributes to one call of the jitted
    program (``lower().compile().cost_analysis()["flops"]``) — the
    numerator of every MFU figure. None when the
    backend doesn't report it.

    SPMD note: for a program sharded over n devices this is the
    PER-DEVICE module's flops. ``mfu(flops / dt)`` is therefore the
    per-chip utilization as-is, but per-item normalizations must use
    the per-device item count (global batch ÷ n devices)."""
    try:
        analysis = jitted_fn.lower(*args, **kwargs).compile() \
            .cost_analysis()
        flops = float(analysis.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        return None


def mfu(flops_per_sec: float | None) -> float | None:
    """Model FLOPs utilization vs the chip's bf16 peak (None off-TPU
    or when flops are unknown)."""
    peak = bf16_peak_flops()
    if peak is None or not flops_per_sec:
        return None
    return flops_per_sec / peak


def std_parser(description: str) -> argparse.ArgumentParser:
    # benchmark entry points only — NOT at import time: the test suite
    # imports this module for random_game_states
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--board", type=int, default=19)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="write a jax.profiler trace to DIR")
    return ap


def timed(fn, reps: int = 3, profile_dir: str | None = None) -> float:
    """Seconds per call of ``fn`` (first call = warmup/compile,
    excluded). ``fn`` must force completion itself (return
    ``jax.device_get`` of something)."""
    fn()
    if profile_dir:
        jax.profiler.start_trace(profile_dir)
    t0 = time.time()
    for _ in range(reps):
        fn()
    dt = (time.time() - t0) / reps
    if profile_dir:
        jax.profiler.stop_trace()
    return dt


def report(metric: str, value: float, unit: str,
           baseline: float | None = None, **extra) -> None:
    """Print the one-line JSON result AND append it (with platform +
    timestamp) to the machine-readable log ``benchmarks/results.jsonl``
    (override with ``$ROCALPHAGO_BENCH_LOG``; empty disables) so perf
    history is greppable."""
    line = {"metric": metric, "value": round(value, 2), "unit": unit}
    if baseline is not None:
        line["vs_baseline"] = round(value / max(baseline, 1e-12), 3)
    line.update(extra)
    print(json.dumps(line))

    log = os.environ.get(
        "ROCALPHAGO_BENCH_LOG",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "results.jsonl"))
    if not log:
        return
    try:
        rec = dict(line, platform=jax.devices()[0].platform,
                   date=time.strftime("%Y-%m-%dT%H:%M:%S"))
        with open(log, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except Exception:  # noqa: BLE001 — logging must never fail a bench
        pass


def random_game_states(cfg, batch: int, moves: int, rng_key):
    """Batched mid-game positions: ``moves`` uniform random legal
    plies under one jit (shared by the engine/encoder benchmarks)."""
    import functools

    import jax
    import jax.numpy as jnp

    from rocalphago_tpu.engine.jaxgo import (
        legal_mask,
        new_states,
        step,
        vgroup_data,
    )

    vstep = jax.vmap(functools.partial(step, cfg))
    vlegal = jax.vmap(functools.partial(legal_mask, cfg))
    vgd = vgroup_data(cfg, with_zxor=cfg.enforce_superko)

    @jax.jit
    def run(rng):
        states = new_states(cfg, batch)

        def ply(carry, _):
            states, rng = carry
            rng, sub = jax.random.split(rng)
            # share one group analysis between legality and step — the
            # same structure as the real self-play loop
            gd = vgd(states)
            legal = vlegal(states, gd)[:, :-1]
            logits = jnp.where(legal, 0.0, -1e30)
            action = jnp.where(
                legal.any(-1),
                jax.random.categorical(sub, logits, axis=-1),
                cfg.num_points).astype(jnp.int32)
            return (vstep(states, action, gd), rng), None

        (states, _), _ = jax.lax.scan(ply, (states, rng), length=moves)
        return states

    return run(rng_key)
