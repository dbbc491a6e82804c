"""Full REINFORCE iteration throughput (games/min).

Parity: the reference's ``reinforcement_policy_trainer_benchmark.py``
— its RL game loop was the slowest path in the repo (SURVEY.md §2
"Benchmarks", §3.2). Measures the whole jitted iteration: self-play
game scan + replay gradient + SGD update.
"""

from __future__ import annotations

import sys

sys.path.insert(0, ".")
from benchmarks._harness import report, std_parser, timed  # noqa: E402


def main() -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from rocalphago_tpu.io.checkpoint import pack_rng
    from rocalphago_tpu.models import CNNPolicy
    from rocalphago_tpu.parallel import mesh as meshlib
    from rocalphago_tpu.training.rl import RLState, make_rl_iteration

    ap = std_parser(__doc__)
    ap.add_argument("--moves", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=None,
                    help="plies per compiled segment (0 = monolithic "
                         "program; default 10 on TPU, where the "
                         "trainers run chunked)")
    args = ap.parse_args()
    on_tpu = jax.devices()[0].platform == "tpu"
    batch = args.batch or (64 if on_tpu else 8)
    moves = args.moves or (400 if on_tpu else 40)
    chunk = args.chunk if args.chunk is not None else (
        10 if on_tpu else 0)

    net = CNNPolicy(board=args.board, layers=12, filters_per_layer=128)
    mesh = meshlib.make_mesh()
    tx = optax.sgd(0.001)
    if chunk:
        from rocalphago_tpu.training.rl import make_rl_iteration_chunked

        iteration = make_rl_iteration_chunked(
            net.cfg, net.feature_list, net.module.apply, tx, batch,
            moves, temperature=0.67, chunk=chunk, mesh=mesh)
    else:
        iteration = jax.jit(make_rl_iteration(
            net.cfg, net.feature_list, net.module.apply, tx, batch,
            moves, temperature=0.67, mesh=mesh))
    state = meshlib.replicate(mesh, RLState(
        params=net.params, opt_state=tx.init(net.params),
        iteration=jnp.int32(0), rng=pack_rng(jax.random.key(0))))
    opp = meshlib.replicate(mesh, net.params)
    holder = [state]

    def once():
        holder[0], m = iteration(holder[0], opp)
        return jax.device_get(m["win_rate"])

    dt = timed(once, reps=args.reps, profile_dir=args.profile)
    report("rl_iteration", batch / dt * 60.0, "games/min",
           batch=batch, moves=moves, board=args.board, chunk=chunk,
           devices=mesh.shape[meshlib.DATA_AXIS])


if __name__ == "__main__":
    main()
