"""Actor/learner scaling for the zero loop (docs/SCALE.md).

Measures, per actor count, on one mesh: games-ingested/min into the
replay buffer, learner steps/s, and the learner-idle fraction — vs
the synchronous loop's baseline, whose self-play phase fraction IS
its learner idleness (the update waits out every self-play phase).
The actor/learner split exists to push that idle fraction down: the
sweep runs the decoupled configuration (free-running actors,
prioritized-recency sampling), where the learner's cadence is no
longer gated on fresh games — it waits only for the initial fill.
Device sections share a ``DispatchGang`` (``training/actor.py``):
on one mesh, concurrent play/learn programs with collectives must
not interleave.

CPU: run with a virtual 8-device mesh (the default here — the
``--no-force-host-devices`` flag disables the XLA override for real
accelerators, where the platform's own devices form the mesh).
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, ".")

# the virtual-device override must land before jax imports (no
# conftest here); harmless but pointless on TPU, hence the flag
if ("--no-force-host-devices" not in sys.argv
        and "xla_force_host_platform_device_count"
        not in os.environ.get("XLA_FLAGS", "")):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

from benchmarks._harness import report, std_parser  # noqa: E402


def main() -> None:
    import time

    import jax
    import optax

    from rocalphago_tpu.data.replay import ReplayBuffer
    from rocalphago_tpu.engine.jaxgo import GoConfig
    from rocalphago_tpu.io.checkpoint import pack_rng, unpack_rng
    from rocalphago_tpu.models import CNNPolicy, CNNValue
    from rocalphago_tpu.parallel import mesh as meshlib
    from rocalphago_tpu.training.actor import (
        DispatchGang,
        ParamsPublisher,
        SelfplayActor,
    )
    from rocalphago_tpu.training.learner import ZeroLearner
    from rocalphago_tpu.training.zero import (
        init_zero_state,
        make_zero_iteration,
    )

    ap = std_parser(__doc__)
    ap.add_argument("--actors", default="1,2,4",
                    help="comma-separated actor counts to sweep")
    ap.add_argument("--steps", type=int, default=8,
                    help="learner steps measured per actor count")
    ap.add_argument("--move-limit", type=int, default=16)
    ap.add_argument("--sims", type=int, default=4)
    ap.add_argument("--sim-chunk", type=int, default=2)
    ap.add_argument("--replay-chunk", type=int, default=8)
    ap.add_argument("--no-force-host-devices", action="store_true",
                    help="keep the platform's real devices (TPU)")
    ap.add_argument("--kill-actor-at", type=int, default=None,
                    help="recovery A/B: run the sweep under the fleet "
                    "supervisor and inject a kill into actor 0 at "
                    "this learner step; the report row gains kill_at "
                    "+ mttr_s (death detection to first post-restart "
                    "game — docs/RESILIENCE.md 'Fleet supervision')")
    ap.add_argument("--cap-p", type=float, default=0.0,
                    help="playout-cap randomization: probability a "
                    "ply gets the full --sims budget (0 = off; the "
                    "'econ row' runs this at 0.25 — see "
                    "docs/PERFORMANCE.md 'Self-play economics')")
    ap.add_argument("--cap-cheap", type=int, default=None,
                    help="cheap budget for capped plies "
                    "(default sims/4)")
    ap.add_argument("--wire", action="store_true",
                    help="wire rig: actors run as PROCESSES shipping "
                    "games to an in-process replay service "
                    "(docs/REPLAYNET.md) and the learner samples the "
                    "service's buffer — the wire-tax A/B against the "
                    "in-process sweep (rows: zero_wire_ingest_"
                    "games_per_min + learner_idle_frac)")
    ap.add_argument("--wire-measure-s", type=float, default=20.0,
                    help="wire: minimum timed-window length — the "
                    "learner keeps stepping past --steps until this "
                    "much wall clock has elapsed, so the ingest rate "
                    "is measured over a meaningful window")
    ap.add_argument("--wire-warmup-s", type=float, default=600.0,
                    help="wire: wait budget for every actor process "
                    "to compile and ship its first game before the "
                    "timed window opens (matches the in-process "
                    "sweep, whose actors start compile-hot)")
    ap.set_defaults(board=5, batch=8)
    args = ap.parse_args()
    if args.wire and jax.default_backend() != "cpu":
        # one process per chip: this process holds the accelerator
        # and every replaynet.actor child would ask for it too — on
        # one chip that hangs or fails. The wire rig is a CPU rig
        # until there is a launcher that hands each actor a device.
        raise SystemExit(
            f"bench_zero_scale --wire spawns actor PROCESSES that "
            f"each claim the default backend; on "
            f"{jax.default_backend()!r} they would fight this "
            f"process for the chip. Run it with JAX_PLATFORMS=cpu.")
    econ = {}
    if args.cap_p:
        econ = {"cap_p": args.cap_p,
                "cap_cheap": args.cap_cheap or max(1, args.sims // 4)}

    feats = ("board", "ones")
    vfeats = feats + ("color",)
    pol = CNNPolicy(feats, board=args.board, layers=1,
                    filters_per_layer=4)
    val = CNNValue(vfeats, board=args.board, layers=1,
                   filters_per_layer=4)
    cfg = GoConfig(size=args.board)
    tx_p, tx_v = optax.sgd(0.01), optax.sgd(0.01)
    n_dev = len(jax.devices())
    while args.batch % n_dev:
        n_dev -= 1
    mesh = meshlib.make_mesh(n_dev)
    mesh_shape = (f"{mesh.shape[meshlib.DATA_AXIS]}"
                  f"x{mesh.shape[meshlib.MODEL_AXIS]}")
    iteration = make_zero_iteration(
        cfg, feats, vfeats, pol.module.apply, val.module.apply,
        tx_p, tx_v, batch=args.batch, move_limit=args.move_limit,
        n_sim=args.sims, max_nodes=16, sim_chunk=args.sim_chunk,
        replay_chunk=args.replay_chunk, mesh=mesh, **econ)
    state0 = meshlib.replicate(mesh, init_zero_state(
        pol.params, val.params, tx_p, tx_v, seed=0))

    # ---------------- synchronous baseline: selfplay-phase fraction
    def sync_iter(state):
        _, game_key = jax.random.split(unpack_rng(state.rng))
        t0 = time.monotonic()
        games = jax.device_get(iteration.play(
            state.policy_params, state.value_params, game_key))
        t1 = time.monotonic()
        state, m = iteration.learn(state, games)
        float(jax.device_get(m["policy_loss"]))    # sync
        return state, t1 - t0, time.monotonic() - t1

    state, _, _ = sync_iter(state0)                # compile
    t_play = t_learn = 0.0
    reps = max(args.reps, 2)
    t0 = time.monotonic()
    for _ in range(reps):
        state, dp, dl = sync_iter(state)
        t_play += dp
        t_learn += dl
    sync_dt = time.monotonic() - t0
    selfplay_frac = t_play / max(t_play + t_learn, 1e-9)
    report("zero_sync_games_per_min",
           reps * args.batch * 60.0 / sync_dt, "games/min",
           batch=args.batch, board=args.board, actors=0,
           mesh_shape=mesh_shape,
           selfplay_frac=round(selfplay_frac, 4), **econ)

    # ---------------- wire sweep: actor processes over replaynet
    if args.wire:
        import shutil
        import subprocess
        import tempfile

        from rocalphago_tpu.replaynet.server import ReplayService

        for n_actors in [int(x) for x in str(args.actors).split(",")]:
            buf = ReplayBuffer(capacity=max(2 * n_actors, 4))
            # evict mode: the sampling learner never pops, so the
            # buffer is a sliding window (same semantics as the
            # in-process free-run sweep)
            svc = ReplayService(buf, evict=True).start()
            tmp = tempfile.mkdtemp(prefix="zero_wire_")
            procs = [subprocess.Popen(
                [sys.executable, "-m",
                 "rocalphago_tpu.replaynet.actor",
                 "--connect", f"127.0.0.1:{svc.port}",
                 "--spool-dir", os.path.join(tmp, f"a{i}"),
                 "--actor-id", str(i), "--mode", "selfplay",
                 "--games", "1000000", "--seed", "0",
                 "--board", str(args.board),
                 "--batch", str(args.batch),
                 "--move-limit", str(args.move_limit),
                 "--sims", str(args.sims),
                 "--sim-chunk", str(args.sim_chunk)])
                for i in range(n_actors)]
            try:
                # warmup: every actor pays its play compile cold (the
                # in-process sweep's actors start hot off the sync
                # baseline) — open the timed window once each has
                # shipped at least one game
                t_warm = time.monotonic()
                while (buf.ingested_games < n_actors * args.batch
                       and time.monotonic() - t_warm
                       < args.wire_warmup_s):
                    if any(p.poll() is not None for p in procs):
                        raise RuntimeError(
                            "wire actor process died during warmup")
                    time.sleep(0.5)
                base_ingested = buf.ingested_games
                learner = ZeroLearner(iteration.learn, buf,
                                      sample=True)
                state = state0
                t0 = time.monotonic()
                steps_done = 0
                while (steps_done < args.steps
                       or time.monotonic() - t0
                       < args.wire_measure_s):
                    out = learner.step(state, timeout=300.0)
                    if out is None:
                        raise RuntimeError(
                            "wire learner starved at step "
                            f"{steps_done}")
                    state, m, _ = out
                    steps_done += 1
                dt = time.monotonic() - t0
                ingested = buf.ingested_games - base_ingested
            finally:
                for p in procs:
                    p.terminate()
                for p in procs:
                    try:
                        p.wait(timeout=30)
                    except subprocess.TimeoutExpired:
                        p.kill()
                        p.wait()
                svc.drain("bench")
                buf.close()
                shutil.rmtree(tmp, ignore_errors=True)
            idle = round(learner.idle_frac, 4)
            report("zero_wire_ingest_games_per_min",
                   ingested * 60.0 / dt, "games/min",
                   batch=args.batch, board=args.board,
                   actors=n_actors, mesh_shape=mesh_shape,
                   learner_idle_frac=idle,
                   sync_selfplay_frac=round(selfplay_frac, 4),
                   **econ)
            report("zero_wire_learner_steps_per_s",
                   steps_done / dt, "steps/s", batch=args.batch,
                   board=args.board, actors=n_actors,
                   mesh_shape=mesh_shape, learner_idle_frac=idle,
                   **econ)
        return

    # ---------------- actor/learner sweep
    for n_actors in [int(x) for x in str(args.actors).split(",")]:
        buf = ReplayBuffer(capacity=max(2 * n_actors, 4))
        pub = ParamsPublisher()
        gang = DispatchGang()

        def make_actor(i, attempt=0, beat=None):
            key = jax.random.fold_in(unpack_rng(state0.rng), i + 1)
            if attempt:
                key = jax.random.fold_in(key, attempt)
            return SelfplayActor(
                iteration.play, pub, buf, pack_rng(key),
                name=f"a{i}", lockstep=False, pace=False,
                poll_s=0.1, gang=gang, on_progress=beat)

        sup = None
        handles = actors = []
        if args.kill_actor_at is not None:
            # the recovery A/B rides the supervised rig: the injected
            # kill, the restart and the MTTR stamp are the production
            # machinery, not bench scaffolding
            from rocalphago_tpu.runtime.supervisor import (
                RestartPolicy,
                Supervisor,
            )

            sup = Supervisor(policy=RestartPolicy(base_delay=0.05,
                                                  max_delay=0.5),
                             poll_s=0.05)
            handles = [
                sup.add((lambda i: lambda attempt, beat:
                         make_actor(i, attempt, beat))(i),
                        name=f"a{i}")
                for i in range(n_actors)]
        else:
            actors = [make_actor(i) for i in range(n_actors)]
        learner = ZeroLearner(iteration.learn, buf, sample=True,
                              gang=gang)
        pub.publish(state0.policy_params, state0.value_params,
                    version=0)
        if sup is not None:
            sup.start()
        else:
            for ac in actors:
                ac.start()
        state = state0
        t0 = time.monotonic()
        for step in range(args.steps):
            if sup is not None and step == args.kill_actor_at:
                handles[0].worker.inject_fault()
            out = learner.step(state, timeout=300.0)
            if out is None:
                err = next((ac.error for ac in actors if ac.error),
                           None)
                raise RuntimeError(
                    f"learner starved at step {step} "
                    f"(actor error: {err})")
            state, m, _ = out
            pub.publish(state.policy_params, state.value_params,
                        version=step + 1)
        dt = time.monotonic() - t0
        ingested = buf.ingested_games
        buf.close()
        if sup is not None:
            sup.stop()
        else:
            for ac in actors:
                ac.stop()
        idle = round(learner.idle_frac, 4)
        recovery = {}
        if sup is not None:
            mttr = handles[0].last_mttr_s
            recovery = {"kill_at": args.kill_actor_at,
                        "mttr_s": (round(mttr, 3)
                                   if mttr is not None else None),
                        "restarts": sum(h.restarts
                                        for h in sup.handles())}
        report("zero_ingest_games_per_min",
               ingested * 60.0 / dt, "games/min",
               batch=args.batch, board=args.board, actors=n_actors,
               mesh_shape=mesh_shape, learner_idle_frac=idle,
               sync_selfplay_frac=round(selfplay_frac, 4),
               **recovery, **econ)
        report("zero_learner_steps_per_s", args.steps / dt,
               "steps/s", batch=args.batch, board=args.board,
               actors=n_actors, mesh_shape=mesh_shape,
               learner_idle_frac=idle, **econ)


if __name__ == "__main__":
    main()
