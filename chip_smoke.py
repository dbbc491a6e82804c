#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

Drives the main path once, through the entry points a user calls, at
the flagship's full width (what ``python -m rocalphago_tpu.models.specs
policy|value`` writes by default: 19×19, 12 conv layers × 128 filters,
all 48 / 49 planes with both ladder planes, bf16 trunk, FCN heads,
fresh weights from seed 0). Counts are cut; widths are not.

* **specs** — name the device (refuse here, before anything compiles,
  when it is not the expected platform), then write the two specs.
* **leg A, a trainer that takes a few steps** —
  ``python -m rocalphago_tpu.training.zero p.json v.json <out>/train
  --iterations 2 --save-every 1 …``: engine step, 48/49-plane encode
  with ladder chase, both forwards inside device PUCT, replay
  gradients, optimizer steps, the gate match, Orbax save, spec +
  msgpack export.
* **verify** — the exported pair loads, every parameter is finite, the
  policy moved off its seed value, and on a few seeded 19×19 positions the
  device encode agrees with the host oracle (``features.pyfeatures``:
  exact off the ladder planes, under the tests' 1% bound on them) and
  both forwards give finite, normalized outputs.
* **replay** — the device rules engine against the host's: uniform
  random legal play through the program's ``vgroup_data``,
  ``legal_mask`` and ``step`` under one ``lax.scan`` at 1,024 games
  (the batch at which the vmapped step lost stones on the TPU,
  PERF.md §7 row 2), then 16 sampled games replayed move by move on
  ``pygo``: every dealt move legal there, every board equal.
* **leg B, a server that answers a few requests** —
  ``python -m rocalphago_tpu.gateway.server --policy <out>/train/
  policy.json --value …`` serving the pair leg A exported; the jax-free
  ``gateway.client`` plays ``new_game`` + a handful of ``genmove``s,
  ``/healthz`` and ``/metrics`` are scraped, then SIGTERM → drain →
  exit 0.

Every check reads the legs' own artifacts (``metrics.jsonl``, replies,
probes). Any phase's nonzero exit, timeout, missing artifact, ``retry``
event, recompile in iteration 2, non-``search`` rung or non-``tpu``
platform fails the smoke: exit code 1 and no result line.

ONE PROCESS PER CHIP: this parent never imports jax; its children hold
the chip one at a time and every one of them is stopped before exit.

The last stdout line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": …, "count": …}}``.
``--rehearse-cpu`` runs the same flow at toy width on the CPU backend
(control flow only — it prints ``"platform": "cpu"`` and proves
nothing about the chip).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
#: the driver allows 1200 s, compilation included
BUDGET_S = 1150.0

#: spec arguments + counts. Flagship width is the specs CLI's default.
#: --replay-chunk divides --move-limit: each distinct replay segment
#: length is its own ~70 s compile on the v5e (PR 21's first run paid
#: for a 10 and a 6).
FLAGSHIP = dict(
    board=19, spec_args=[],
    train_args=["--game-batch", "8", "--sims", "8", "--move-limit", "16",
                "--replay-chunk", "8", "--gate-games", "8"],
    playouts=8, genmoves=6, verify_plies=(0, 12, 40, 90),
    replay=dict(games=1024, plies=48, sampled=16))
REHEARSAL = dict(
    board=9, spec_args=["--board", "9", "--layers", "2",
                        "--filters", "16"],
    train_args=["--game-batch", "4", "--sims", "4", "--move-limit", "8",
                "--gate-games", "4"],
    playouts=4, genmoves=4, verify_plies=(0, 6, 20),
    replay=dict(games=32, plies=24, sampled=4))


class SmokeFailure(Exception):
    """A phase failed; the message says which check and why."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ----------------------------------------------------------- children

def _child_specs(platform: str, out: str, spec_args: list) -> int:
    """Name the device first — a wrong platform exits before any
    weight init compiles — then write the seed-0 specs."""
    from rocalphago_tpu.obs import jaxobs

    device = jaxobs.device_record()
    print(json.dumps({"device": device}), flush=True)
    if device["platform"] != platform:
        print(f"chip_smoke: JAX found platform "
              f"{device['platform']!r}, wanted {platform!r}",
              file=sys.stderr)
        return 3
    from rocalphago_tpu.models import specs

    for kind, name in (("policy", "p.json"), ("value", "v.json")):
        specs.main([kind, "--out", os.path.join(out, name),
                    "--seed", "0", *spec_args])
    return 0


def _child_verify(out: str, board: int, plies: list) -> int:
    """What leg A exported is right, by the repo's own means."""
    import jax
    import numpy as np

    from rocalphago_tpu.engine import jaxgo, pygo
    from rocalphago_tpu.features import pyfeatures
    from rocalphago_tpu.models.nn_util import NeuralNetBase
    from rocalphago_tpu.obs import jaxobs
    from rocalphago_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()
    print(json.dumps({"device": jaxobs.device_record()}), flush=True)
    nets = {}
    for kind, seed_spec in (("policy", "p.json"), ("value", "v.json")):
        seed = NeuralNetBase.load_model(os.path.join(out, seed_spec))
        net = NeuralNetBase.load_model(
            os.path.join(out, "train", f"{kind}.json"))
        new = jax.tree.leaves(jax.device_get(net.params))
        old = jax.tree.leaves(jax.device_get(seed.params))
        _check([a.shape for a in new] == [a.shape for a in old],
               f"{kind}: exported parameter shapes differ from seed")
        _check(all(np.isfinite(a).all() for a in new),
               f"{kind}: non-finite exported parameter")
        # the value loss is masked to FINISHED games and the smoke's
        # move limit ends none, so only the policy is required to move
        _check(kind == "value"
               or any((a != b).any() for a, b in zip(new, old)),
               "policy: exported parameters equal the seed's — no step")
        nets[kind] = net

    # seeded positions along one random host game
    rng = np.random.default_rng(0)
    st = pygo.GameState(size=board, komi=7.5)
    states = []
    for ply in range(max(plies) + 1):
        if ply in plies:
            states.append(st.copy())
        legal = st.get_legal_moves(include_eyes=False)
        if not legal or st.is_end_of_game:
            break
        st.do_move(legal[rng.integers(len(legal))])
    _check(len(states) == len(plies), "seeded game ended early")

    for kind, net in nets.items():
        batched = jax.tree.map(
            lambda *xs: np.stack(xs),
            *[jaxgo.from_pygo(net.cfg, s) for s in states])
        dev = np.asarray(net.preprocess.states_to_tensor(batched),
                         np.float32)
        ora = np.stack([pyfeatures.state_to_planes(s, net.feature_list)
                        for s in states])
        _check(dev.shape == ora.shape,
               f"{kind}: planes {dev.shape} != oracle {ora.shape}")
        # the repo's own contract (tests/test_features.py): every
        # plane exact but the two ladder planes, whose 2-ply device
        # read may differ from the oracle's full-branching read on
        # under 1% of cells
        ladder = np.zeros(dev.shape[-1], bool)
        off = 0
        for f in net.feature_list:
            k = pyfeatures.FEATURE_PLANES[f]
            ladder[off:off + k] = f in pyfeatures.LADDER_FEATURES
            off += k
        diff = dev != ora
        bad = np.argwhere(diff[..., ~ladder])
        _check(not len(bad), f"{kind}: device encode != host oracle at "
                             f"[pos, x, y, plane] {bad[:5].tolist()}")
        rate = diff[..., ladder].mean() if ladder.any() else 0.0
        _check(rate < 0.01, f"{kind}: ladder planes disagree with the "
                            f"oracle on {rate:.2%} of cells (bound 1%)")
    for s, dist in zip(states, nets["policy"].batch_eval_state(states)):
        probs = np.asarray([p for _, p in dist], np.float64)
        _check(len(probs) == len(s.get_legal_moves()),
               "policy support is not the legal moves")
        _check(bool(np.isfinite(probs).all())
               and abs(probs.sum() - 1) < 1e-3,
               f"policy distribution not finite/normalized: {probs.sum()}")
    values = np.asarray(nets["value"].batch_eval_state(states))
    _check(values.shape == (len(states),)
           and bool(np.isfinite(values).all())
           and bool((abs(values) <= 1).all()),
           f"value outputs {values}")
    print(json.dumps({"verified_positions": len(states)}), flush=True)
    return 0


def _child_replay(board: int, games: int, plies: int,
                  sampled: int) -> int:
    """The device engine's own stepping against the host's rules, at
    a batch the self-play programs really run."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from rocalphago_tpu.engine import jaxgo, pygo
    from rocalphago_tpu.obs import jaxobs
    from rocalphago_tpu.runtime.compilecache import enable_compile_cache
    from rocalphago_tpu.utils.coords import unflatten_idx

    enable_compile_cache()
    print(json.dumps({"device": jaxobs.device_record()}), flush=True)
    cfg = jaxgo.GoConfig(size=board, komi=7.5)
    n = cfg.num_points
    vgd = jaxgo.vgroup_data(cfg, with_zxor=cfg.enforce_superko)
    vlegal = jax.vmap(functools.partial(jaxgo.legal_mask, cfg))
    vstep = jax.vmap(functools.partial(jaxgo.step, cfg))

    @jax.jit
    def deal(key):
        def ply(carry, _):
            states, key = carry
            key, sub = jax.random.split(key)
            gd = vgd(states)
            legal = vlegal(states, gd)[:, :-1]
            action = jnp.where(
                legal.any(-1),
                jax.random.categorical(
                    sub, jnp.where(legal, 0.0, -1e30), axis=-1),
                n).astype(jnp.int32)
            states = vstep(states, action, gd)
            return (states, key), (action, states.board)

        _, (actions, boards) = jax.lax.scan(
            ply, (jaxgo.new_states(cfg, games), key), None,
            length=plies)
        return actions, boards

    actions, boards = jax.device_get(deal(jax.random.key(0)))
    picks = np.random.default_rng(0).choice(games, sampled,
                                            replace=False)
    for g in sorted(int(x) for x in picks):
        st = pygo.GameState(size=board, komi=7.5)
        for t in range(plies):
            a = int(actions[t, g])
            move = None if a >= n else unflatten_idx(a, board)
            _check(move is None or st.is_legal(move),
                   f"replay: game {g} ply {t}: the device dealt "
                   f"{move}, which the host engine calls illegal")
            st.do_move(move)
            host = np.asarray(st.board, np.int8).reshape(-1)
            bad = np.flatnonzero(host != boards[t, g])
            _check(not len(bad),
                   f"replay: game {g} ply {t} (action {a}): device "
                   f"board differs from the host's at cells "
                   f"{bad[:8].tolist()}")
    print(json.dumps({"replayed_games": int(sampled), "plies": plies,
                      "batch": games}), flush=True)
    return 0


# ------------------------------------------------------------- checks
# Pure functions of the legs' artifacts, so the rules themselves are
# unit-tested without a chip (tests/test_chip_smoke.py).

def read_events(path: str) -> list:
    _check(os.path.exists(path), f"missing artifact {path}")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def device_of(events: list, leg: str, platform: str) -> dict:
    """The leg's one ``device`` event, on the expected platform."""
    devs = [e for e in events if e.get("event") == "device"]
    _check(len(devs) == 1, f"{leg}: {len(devs)} device events")
    _check(devs[0]["platform"] == platform,
           f"{leg}: ran on platform {devs[0]['platform']!r}, not "
           f"{platform!r}")
    return devs[0]


def compile_seconds(events: list) -> float | None:
    """Sum of the run's tracked-compile histogram (the ``registry``
    event every entry point writes at exit)."""
    snaps = [e["snapshot"] for e in events
             if e.get("event") == "registry"]
    if not snaps:
        return None
    return round(sum(
        h["sum"] for k, h in snaps[-1]["histograms"].items()
        if k.startswith("jax_compile_seconds")), 1)


def check_train(events: list, platform: str) -> dict:
    """Leg A's ``metrics.jsonl``; returns its device event."""
    device = device_of(events, "leg A", platform)
    names = [e.get("event") for e in events]
    _check("retry" not in names,
           "leg A: retry event(s) — a device error was retried: "
           f"{[e for e in events if e.get('event') == 'retry']}")
    its = [e for e in events if e.get("event") == "iteration"]
    _check(len(its) == 2, f"leg A: {len(its)} iteration events")
    for e in its:
        for k in ("policy_loss", "value_loss"):
            v = e.get(k)
            _check(isinstance(v, float) and math.isfinite(v),
                   f"leg A: iteration {e['iteration']} {k}={v!r}")
    # iteration 1's gate match is the last first-time program;
    # anything compiled after it is a recompile in iteration 2
    _check("gate" in names, "leg A: no gate event")
    late = [e for e in events[names.index("gate"):]
            if e.get("event") == "compile"]
    _check(not late, f"leg A: iteration 2 compiled again: {late}")
    return device


def check_serve(replies: list, health: dict, prom: str, events: list,
                platform: str) -> dict:
    """Leg B's genmove replies, ``/healthz``, ``/metrics`` and
    ``metrics.jsonl``; returns its device event. The resilience
    ladder answers SOMETHING whatever breaks, so a dead search shows
    only here: in the rung each move came from."""
    n = len(replies)
    for i, reply in enumerate(replies):
        _check(reply.get("type") == "move"
               and isinstance(reply.get("move"), str),
               f"leg B: genmove reply {reply}")
        _check(reply.get("rung") == "search",
               f"leg B: genmove {i} answered from rung "
               f"{reply.get('rung')!r}, not the search: {reply}")
    serve, gw = health["serve"], health["gateway"]
    _check(serve["warmed"] is True, "leg B: pool not warmed")
    _check(serve["evaluator"]["failures"] == 0,
           f"leg B: evaluator failures {serve['evaluator']}")
    _check(gw["requests"]["unhandled"] == 0
           and gw["requests"]["errors"] == 0,
           f"leg B: gateway requests {gw['requests']}")
    _check(gw["requests"]["genmoves"] == n,
           f"leg B: {gw['requests']['genmoves']} genmoves != {n}")
    # the ladder's registry counters: every rung failure (illegal
    # moves included) and every move served below the search
    rungs = {line.split()[0]: float(line.split()[1])
             for line in prom.splitlines()
             if line.startswith(("serve_rung_total",
                                 "serve_degradation_total"))}
    _check(rungs == {'serve_rung_total{rung="search"}': float(n)},
           f"leg B: degradation ladder counters {rungs}")
    bad = [e for e in events if e.get("event") == "degradation"]
    _check(not bad, f"leg B: degradation events {bad}")
    return device_of(events, "leg B", platform)


# ------------------------------------------------------------- parent

class Smoke:
    def __init__(self, out: str, rehearse: bool):
        self.out = out
        self.platform = "cpu" if rehearse else "tpu"
        self.size = REHEARSAL if rehearse else FLAGSHIP
        self.deadline = time.monotonic() + BUDGET_S
        self.procs: list = []
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = HERE + os.pathsep + \
            self.env.get("PYTHONPATH", "")
        if rehearse:
            self.env["JAX_PLATFORMS"] = "cpu"
        self.summary: dict = {"phases": {}}

    # ------------------------------------------------------ processes

    def _spawn(self, name: str, argv: list, env=None):
        with open(os.path.join(self.out, f"{name}.log"), "w") as log:
            proc = subprocess.Popen(
                argv, cwd=HERE, env=env or self.env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)
        self.procs.append(proc)
        return proc

    def _tail(self, name: str, n: int = 30) -> str:
        try:
            with open(os.path.join(self.out, f"{name}.log"),
                      errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def _remaining(self) -> float:
        left = self.deadline - time.monotonic()
        _check(left > 0, f"out of time ({BUDGET_S:.0f}s budget)")
        return left

    def _run(self, name: str, argv: list) -> None:
        """One child to completion; nonzero exit or timeout fails."""
        t0 = time.monotonic()
        proc = self._spawn(name, argv)
        try:
            rc = proc.wait(timeout=self._remaining())
        except subprocess.TimeoutExpired:
            raise SmokeFailure(f"{name}: timed out\n{self._tail(name)}")
        self.summary["phases"][name] = {
            "wall_s": round(time.monotonic() - t0, 1)}
        _check(rc == 0, f"{name}: exit code {rc}\n{self._tail(name)}")

    def stop_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()

    def _note_device(self, d: dict, leg: str) -> None:
        triple = {"platform": d["platform"], "kind": d["device_kind"],
                  "count": d["count"]}
        first = self.summary.setdefault("device", triple)
        _check(first == triple, f"{leg}: device {triple} != {first}")
        self.summary.setdefault("engine", d["engine"])

    # ----------------------------------------------------------- legs

    def specs(self) -> None:
        self._run("specs", [
            sys.executable, os.path.abspath(__file__), "--child",
            "specs", self.platform, self.out,
            json.dumps(self.size["spec_args"])])

    def leg_a(self) -> None:
        train = os.path.join(self.out, "train")
        self._run("train", [
            sys.executable, "-m", "rocalphago_tpu.training.zero",
            os.path.join(self.out, "p.json"),
            os.path.join(self.out, "v.json"), train,
            "--iterations", "2", "--save-every", "1", "--seed", "0",
            *self.size["train_args"]])
        events = read_events(os.path.join(train, "metrics.jsonl"))
        self._note_device(check_train(events, self.platform), "leg A")
        ckpts = os.path.join(train, "checkpoints")
        _check(os.path.isdir(ckpts) and bool(os.listdir(ckpts)),
               "leg A: no checkpoint")
        for name in ("policy.json", "value.json", "metadata.json"):
            _check(os.path.exists(os.path.join(train, name)),
                   f"leg A: missing {name}")
        self.summary["phases"]["train"]["compile_s"] = \
            compile_seconds(events)

    def verify(self) -> None:
        self._run("verify", [
            sys.executable, os.path.abspath(__file__), "--child",
            "verify", self.out, str(self.size["board"]),
            json.dumps(list(self.size["verify_plies"]))])

    def replay(self) -> None:
        r = self.size["replay"]
        self._run("replay", [
            sys.executable, os.path.abspath(__file__), "--child",
            "replay", str(self.size["board"]), str(r["games"]),
            str(r["plies"]), str(r["sampled"])])

    def leg_b(self) -> None:
        from rocalphago_tpu.gateway.client import (
            GatewayClient,
            GatewayClosed,
            GatewayError,
        )

        train = os.path.join(self.out, "train")
        metrics = os.path.join(self.out, "serve", "metrics.jsonl")
        ports = []
        for _ in range(2):
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                ports.append(s.getsockname()[1])
        port, http_port = ports
        # the compiled eval ladder is deployment sizing: one session
        # here, so warm compiles batch 1 and 8 — not the 5-rung default
        env = dict(self.env, ROCALPHAGO_SERVE_BATCH_SIZES="1,8",
                   ROCALPHAGO_SERVE_MAX_SESSIONS="8")
        t0 = time.monotonic()
        proc = self._spawn("serve", [
            sys.executable, "-m", "rocalphago_tpu.gateway.server",
            "--policy", os.path.join(train, "policy.json"),
            "--value", os.path.join(train, "value.json"),
            "--port", str(port), "--http-port", str(http_port),
            "--playouts", str(self.size["playouts"]),
            "--metrics", metrics], env=env)
        base = f"http://127.0.0.1:{http_port}"

        def get(path):
            with urllib.request.urlopen(base + path, timeout=10) as r:
                return r.read().decode()

        while True:     # the probe port opens once the pool is warm
            _check(proc.poll() is None,
                   f"leg B: server exited {proc.returncode} before "
                   f"serving\n{self._tail('serve')}")
            self._remaining()
            try:
                get("/healthz")
                break
            except OSError:
                time.sleep(0.5)
        ready_s = round(time.monotonic() - t0, 1)

        try:
            client = GatewayClient("127.0.0.1", port,
                                   timeout=self._remaining())
            try:
                client.new_game(board=self.size["board"])
                replies = [client.genmove("bw"[i % 2])
                           for i in range(self.size["genmoves"])]
            finally:
                client.close()
        except (GatewayError, GatewayClosed, OSError) as e:
            raise SmokeFailure(f"leg B: conversation failed: {e!r}\n"
                               f"{self._tail('serve')}")
        health = json.loads(get("/healthz"))
        prom = get("/metrics")

        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=min(120.0, self._remaining()))
        except subprocess.TimeoutExpired:
            raise SmokeFailure("leg B: no exit within 120s of SIGTERM")
        _check(rc == 0, f"leg B: exit code {rc} after SIGTERM\n"
                        f"{self._tail('serve')}")
        events = read_events(metrics)
        self._note_device(
            check_serve(replies, health, prom, events, self.platform),
            "leg B")
        self.summary["phases"]["serve"] = {
            "ready_s": ready_s,
            "wall_s": round(time.monotonic() - t0, 1),
            "compile_s": compile_seconds(events)}

    # ------------------------------------------------------------ run

    def run(self) -> dict:
        from rocalphago_tpu.runtime.compilecache import cache_dir

        cache = cache_dir()

        def entries():
            return len(os.listdir(cache)) if os.path.isdir(cache) else 0

        t0 = time.monotonic()
        self.summary["cache"] = {"dir": cache, "before": entries()}
        for phase in (self.specs, self.leg_a, self.verify, self.replay,
                      self.leg_b):
            print(f"chip_smoke: {phase.__name__} ...", flush=True)
            phase()
            print(f"chip_smoke: {phase.__name__} ok", flush=True)
        self.summary["cache"]["after"] = entries()
        self.summary["wall_s"] = round(time.monotonic() - t0, 1)
        return self.summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(HERE, "chip_smoke_out"),
                    help="output directory (wiped first)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy-width rehearsal on the CPU backend")
    ap.add_argument("--child", nargs="+", help=argparse.SUPPRESS)
    a = ap.parse_args(argv)
    if a.child:
        sys.path.insert(0, HERE)
        if a.child[0] == "specs":
            _, platform, out, spec_args = a.child
            return _child_specs(platform, out, json.loads(spec_args))
        if a.child[0] == "replay":
            return _child_replay(*(int(x) for x in a.child[1:]))
        _, out, board, plies = a.child
        return _child_verify(out, int(board), json.loads(plies))

    import shutil

    sys.path.insert(0, HERE)
    out = os.path.abspath(a.out)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    smoke = Smoke(out, a.rehearse_cpu)
    try:
        summary = smoke.run()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        return 1
    finally:
        smoke.stop_all()
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    result = {"ok": True, "device": summary["device"]}
    if a.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
