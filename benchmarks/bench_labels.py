"""Flood-fill labeling: XLA while_loop vs the Pallas VMEM kernel.

The labeling is the engine's hottest primitive (one per ply per game
in self-play, one per ladder rung). This compares the default XLA
formulation (`jaxgo.compute_labels`, convergence loop + pointer
jumping) against `ops.pallas_labels` (whole fixpoint in VMEM, static
sweep bound). The kernel row is TPU-only: only that comparison
decides whether the engine should switch.
"""

from __future__ import annotations

import sys

sys.path.insert(0, ".")
from benchmarks._harness import report, std_parser, timed  # noqa: E402


def main() -> None:
    import jax
    import numpy as np

    from rocalphago_tpu.engine.jaxgo import GoConfig, compute_labels
    from rocalphago_tpu.ops import pallas_labels

    args = std_parser(__doc__).parse_args()
    batch = args.batch or 256
    cfg = GoConfig(size=args.board)
    n = cfg.num_points

    rng = np.random.default_rng(0)
    boards = rng.choice(np.asarray([0, 1, -1], np.int8), (batch, n),
                        p=[0.4, 0.3, 0.3])
    boards = jax.device_put(boards)

    xla = jax.jit(jax.vmap(lambda b: compute_labels(cfg, b)))
    dt = timed(lambda: jax.device_get(xla(boards)), reps=args.reps,
               profile_dir=args.profile)
    report("labels_xla", batch / dt, "boards/s", batch=batch,
           board=args.board)

    if jax.devices()[0].platform != "tpu":
        # interpret mode is a correctness tool (tests/test_ops.py),
        # never a timing: no kernel row off the chip
        print("bench_labels: labels_pallas skipped off-TPU",
              file=sys.stderr)
        return
    dt = timed(lambda: jax.device_get(
        pallas_labels(boards, args.board)),
        reps=args.reps, profile_dir=args.profile)
    report("labels_pallas", batch / dt, "boards/s", batch=batch,
           board=args.board)

if __name__ == "__main__":
    main()
