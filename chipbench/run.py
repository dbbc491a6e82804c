"""One run of one cell of the chip benchmark.

    python chipbench/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

One process that holds the chip; no fallback. The last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``);
everything else — the set-up account, sample counts, what the checks
read — goes on earlier lines and into ``chipbench/out/``.

This file holds no cell, configuration, traffic or layer-metric
name. ``BENCHMARK.json`` names them; the files are found by name:

    configs/<config>.json      the sizes, as run
    traffic/<mix>.json         ``driver`` + its parameters
    drivers/<driver>.py        one per KIND of traffic
    layers/<metric>.py         one reader per per-layer metric

See README.md for how to add each without editing a file.
"""

from __future__ import annotations

import time

_T0 = time.time()       # process start, as near as Python allows

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the traced window: at most this many seconds of steady state
TRACE_SECONDS = 5.0
#: JAX's monitoring events that mean "a program was built or loaded"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)


def load_by_name(kind: str, name: str):
    """The module ``chipbench/<kind>/<name>.py``."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"no {kind[:-1]} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Context:
    """What a driver and a layer reader may see of the run."""

    def __init__(self, manifest: dict, cell: dict, seed: int,
                 root: str):
        self.manifest = manifest
        self.cell = cell
        self.seed = seed
        by_name = {c["name"]: c for c in manifest["configs"]}
        self.config = load_json(os.path.join(
            root, by_name[cell["config"]]["file"]))
        self.traffic = load_json(os.path.join(
            HERE, "traffic", cell["traffic"] + ".json"))
        self.phases = []            # (name, seconds) of set-up
        self.device = None          # the result line's device block
        self.driver = None
        self.trace = None           # trace_reduce.reduce's output
        self.counters_before = self.counters_after = None
        self.compile_times = []     # wall times of compile events

    @contextlib.contextmanager
    def phase(self, name: str):
        """A named part of set-up, for the account on earlier lines."""
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.phases.append((name, time.monotonic() - t0))

    @contextlib.contextmanager
    def span(self, name: str):
        """A benchmark-side host span, written into the profiler's
        trace (free when no trace is being taken)."""
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield

    def compiles_between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.compile_times if t0 <= t <= t1)


def say(**record) -> None:
    """An earlier line: detail for people, ignored by the driver."""
    print(json.dumps(record, default=str), flush=True)


def device_block(chips: int, platform: str) -> dict:
    """The device as JAX reports it — or exit: a cell is measured on
    the platform it names and on no other."""
    import jax

    devices = jax.devices()
    found = devices[0].platform
    if found != platform or len(devices) < chips:
        print(f"chipbench: JAX found {len(devices)} {found!r} "
              f"device(s); the cell needs {chips} {platform!r}. "
              "No fallback, no result.", file=sys.stderr)
        raise SystemExit(3)
    return {"platform": found, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak() -> dict:
    """The memory account of the fullest chip; ``held`` is its peak
    (0 where the backend does not report it, as on the CPU).

    The TPU runtime keeps two accounts. ``peak_bytes_in_use`` is the
    high-water mark of live buffers: arguments, results, whatever the
    process keeps on the device. A loaded program's temporaries —
    here the activations a training step saves for its backward pass
    — are not in it: the runtime reserves them "at the bottom of
    memory" when it loads the program, keeps the reservation while
    the program stays loaded, and counts it under
    ``peak_bytes_reserved`` (the largest loaded program's, not their
    sum). Both occupy the chip's memory at once — a program whose
    reservation does not fit beside the live buffers fails to load
    with RESOURCE_EXHAUSTED (PERF.md §4) — so the peak is their sum.
    """
    import jax

    accounts = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        live = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        accounts.append({"held": live + reserved,
                         "peak_bytes_in_use": live,
                         "peak_bytes_reserved": reserved})
    return max(accounts, key=lambda a: a["held"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=None,
                    help="another manifest than BENCHMARK.json "
                         "(chipbench/tests' fixture cells)")
    ap.add_argument("--platform", default="tpu",
                    help="only with --manifest: the platform a "
                         "fixture cell rehearses on")
    a = ap.parse_args(argv)
    if a.manifest is None and a.platform != "tpu":
        ap.error("a cell of BENCHMARK.json runs on a TPU only")
    manifest = load_json(a.manifest
                         or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if a.workload not in cells:
        ap.error(f"no workload {a.workload!r}; have {sorted(cells)}")
    cell = cells[a.workload]

    import jax

    from rocalphago_tpu.runtime.compilecache import enable_compile_cache

    # the repo's one rule for the cache's place (a fixed path in the
    # checkout unless JAX_COMPILATION_CACHE_DIR says otherwise), and
    # every program kept, however quick to compile: a second run then
    # finds all of them
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    device = device_block(int(cell["chips"]), a.platform)

    ctx = Context(manifest, cell, a.seed, ROOT)
    ctx.device = device
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_: ctx.compile_times.append(time.time())
        if event in COMPILE_EVENTS else None)

    out_dir = os.path.join(HERE, "out", a.workload)
    os.makedirs(out_dir, exist_ok=True)
    driver = load_by_name("drivers", ctx.traffic["driver"]).Driver(ctx)
    ctx.driver = driver
    try:
        return _run(a, ctx, driver, out_dir, cache_dir)
    finally:
        driver.close()


def _run(a, ctx, driver, out_dir: str, cache_dir: str) -> int:
    import jax

    from rocalphago_tpu.obs import registry as obs_registry

    manifest, cell = ctx.manifest, ctx.cell
    driver.setup()

    def mark():
        ctx.counters_before = obs_registry.snapshot()

    raw = driver.window(a.seconds, on_start=mark)
    ctx.counters_after = obs_registry.snapshot()
    setup_s = raw["started_at"] - _T0
    compiles = ctx.compiles_between(
        raw["started_at"], raw["started_at"] + raw["elapsed_s"])

    breakdown = None
    if a.trace:
        trace_dir = os.path.join(out_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the host stays quick
        traced = driver.window(
            min(a.seconds, TRACE_SECONDS),
            on_start=lambda: jax.profiler.start_trace(
                trace_dir, profiler_options=options))
        jax.profiler.stop_trace()
        from chipbench import trace_reduce

        events = trace_reduce.load_events(
            trace_reduce.find_xplane(trace_dir))
        ctx.trace = trace_reduce.reduce(events)
        shutil.rmtree(trace_dir, ignore_errors=True)    # it is large
        breakdown = {k: ctx.trace[k]
                     for k in ("device_ops", "idle_gaps")}
        say(traced_window={k: v for k, v in traced.items()
                           if isinstance(v, (int, float))},
            idle_by_span=ctx.trace["idle_by_span"][:10])
        with open(os.path.join(out_dir, "trace_sample.json"),
                  "w") as f:
            json.dump(trace_reduce.sample(events), f)

    problems, readings = driver.verify(raw)
    if compiles:
        problems.append(f"{compiles} compile event(s) inside the "
                        "measured window")

    if a.trace:
        wanted = [m for m in manifest["per_layer"]
                  if applies(m, cell["name"])]
        values = {m["name"]: load_by_name("layers", m["name"]).read(
            ctx, raw) for m in wanted}
    else:
        wanted = [m for m in manifest["end_to_end"]
                  if applies(m, cell["name"])]
        values = dict(driver.end_to_end(raw), setup_s=setup_s)
    metrics = {m["name"]: {"value": values[m["name"]],
                           "unit": m["unit"]}
               for m in wanted if values.get(m["name"]) is not None}

    memory = memory_peak()
    device = dict(ctx.device, memory_peak_bytes=memory["held"])
    if ctx.trace:
        device.update(busy_s=ctx.trace["busy_s"],
                      window_s=ctx.trace["window_s"])
    say(workload=cell["name"], seed=a.seed, seconds=a.seconds,
        setup_s=setup_s, setup_phases=ctx.phases,
        compile_events_total=len(ctx.compile_times),
        compile_events_in_window=compiles, cache_dir=cache_dir,
        memory=memory,
        window={k: v for k, v in raw.items()
                if isinstance(v, (int, float, str))},
        checks=readings, problems=problems)
    line = {"correct": not problems,
            "attempted": int(raw["attempted"]),
            "failed": int(raw["failed"]),
            "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
