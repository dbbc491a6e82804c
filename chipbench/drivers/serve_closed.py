"""Closed-loop serving: ``GatewayServer`` over a ``ServePool``.

Bot-ladder and analysis-fleet traffic: ``clients`` callers that each
wait for a reply before asking again. Server and load generator run
in ONE process over loopback TCP — the gateway has no profiler
switch, and only the process that holds the chip can trace it — with
the jax-free client of ``chipbench/loadgen.py`` on its own threads.
Every simulation of every search goes through the host: the session
thread's prepare/apply dispatches and a hand-off to the batching
evaluator (ROADMAP S2), which is what this cell is for.

Latency is timed by the client. ``failed`` counts every reply that
is not a legal ``move`` from rung ``search``: the resilience ladder
answering from a lower rung is a hidden failure here, not a service.
"""

from __future__ import annotations

import random

import numpy as np


def percentile(sorted_values: list, q: float) -> float:
    """Nearest-rank percentile of an ascending list."""
    rank = max(1, int(np.ceil(q * len(sorted_values))))
    return sorted_values[rank - 1]


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.t = ctx.traffic

    def setup(self) -> None:
        import jax

        from chipbench.loadgen import deal_prefix
        from chipbench.nets import build_net
        from rocalphago_tpu.engine import pygo
        from rocalphago_tpu.gateway.server import GatewayServer
        from rocalphago_tpu.serve.sessions import ServePool

        ctx, t = self.ctx, self.t
        self.clients = int(t["clients"])
        with ctx.phase("weights"):
            self.policy = build_net(ctx.config, "policy", ctx.seed)
            self.value = build_net(ctx.config, "value", ctx.seed)
            jax.block_until_ready((self.policy.params,
                                   self.value.params))
        cache = t["eval_cache"]
        slo = t["slo_ms"]
        self.pool = ServePool(
            self.value, self.policy, n_sim=int(t["n_sim"]),
            max_sessions=int(t["max_sessions"]),
            batch_sizes=tuple(t["batch_sizes"]),
            slo_s=None if slo is None else slo / 1e3,
            eval_cache=None if cache else False)
        with ctx.phase("pool_warm"):
            self.pool.warm()
        with ctx.phase("convoy_warm"):
            self._warm_convoys()
        self.server = GatewayServer(
            self.pool, host="127.0.0.1", port=0,
            max_conns=2 * self.clients, slo_ms=slo).start()
        komi = float(self.pool.cfg.komi)
        size = self.pool.board
        self.new_state = lambda: pygo.GameState(size=size, komi=komi)
        with ctx.phase("prefixes"):
            # one corpus of prefixes for every run, dealt under the
            # mix's own key: the positions a search starts from (and
            # their ladder work) must not change with the seed, or
            # the seed changes the work. The run's seed deals them to
            # the sessions in another order and draws the weights.
            # Twice as many as clients, for games that end.
            lengths = np.linspace(t["prefix_min"], t["prefix_max"],
                                  2 * self.clients).round().astype(int)
            corpus = random.Random(int(t["position_seed"]))
            self.prefixes = [deal_prefix(self.new_state, int(n), corpus)
                             for n in lengths]
            random.Random(ctx.seed).shuffle(self.prefixes)

    def _warm_convoys(self) -> None:
        """Compile the evaluator's batch ASSEMBLY for every convoy
        size this traffic can form.

        ``pool.warm()`` compiles the eval program at each ladder
        size. But the dispatcher builds a device batch with eager
        array operations — a concatenation of the k single-row
        requests it coalesced, a pad of replicated rows up to the
        ladder size — and JAX compiles each once per k. Which k
        first shows up when is a matter of thread timing, so without
        this a run compiles a handful of tiny programs somewhere in
        its window. The loop below performs the same assembly
        (``serve/evaluator.py::_dispatch``) on fresh states for k =
        1..clients; PERF.md §7 lists the program-side cure."""
        import jax
        import jax.numpy as jnp

        from rocalphago_tpu.engine.jaxgo import new_states

        ev = self.pool.evaluator
        one = new_states(self.pool.cfg, 1)
        out = None
        for k in range(1, self.clients + 1):
            states = one if k == 1 else jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *[one] * k)
            size = next((s for s in ev.batch_sizes if s >= k),
                        ev.max_batch)
            if size > k:
                states = jax.tree.map(
                    lambda x: jnp.concatenate(
                        [x, jnp.broadcast_to(
                            x[:1], (size - k,) + x.shape[1:])],
                        axis=0), states)
            priors, values = ev.eval_direct(states)
            out = [(priors[i:i + 1], values[i:i + 1])
                   for i in range(k)]
        jax.block_until_ready(out)

    def _loop(self, seconds: float, on_start=None) -> dict:
        from chipbench.loadgen import closed_loop

        return closed_loop("127.0.0.1", self.server.port, self.clients,
                           self.prefixes, self.new_state, seconds,
                           span=self.ctx.span, on_start=on_start)

    def window(self, seconds: float, on_start=None) -> dict:
        # the generator's ramp (connect, prefix, one untimed genmove
        # per client at full concurrency) runs the whole served path
        # before the window's clock starts, and counts as set-up
        raw = self._loop(seconds, on_start=on_start)
        raw["moves"] = raw["attempted"] - raw["failed"]
        return raw

    def end_to_end(self, raw: dict) -> dict:
        lat = sorted(raw["latencies_s"])
        return {"serve_moves_per_s": raw["moves"] / raw["elapsed_s"],
                "genmove_p90_ms": 1e3 * percentile(lat, 0.90)}

    def verify(self, raw: dict) -> tuple:
        from chipbench.reference import check_nets

        problems, readings = check_nets(
            {"policy": self.policy, "value": self.value},
            self.ctx.seed)
        if raw["failed"]:
            problems.append(f"{raw['failed']} of {raw['attempted']} "
                            f"replies failed: {raw['why']}")
        stats = self.server.stats()
        if stats["requests"]["unhandled"]:
            problems.append(f"{stats['requests']['unhandled']} "
                            "requests escaped the gateway's handler")
        lat = sorted(raw["latencies_s"])
        readings.update(
            genmoves=len(lat),
            genmove_p50_ms=1e3 * percentile(lat, 0.5),
            genmove_max_ms=1e3 * lat[-1],
            samples_beyond_p90=len(lat) - int(np.ceil(0.90 * len(lat))))
        return problems, readings

    def close(self) -> None:
        self.server.close()
        self.pool.close()
