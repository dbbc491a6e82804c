"""On-chip check of the Pallas labeling kernel: compile with
``interpret=False`` at 19×19, compare with its XLA twin on a seeded
batch (``tests/test_ops.py``'s check at real shape), and record the
compiler's words either way. (PR 21 ran the same check over the
ladder-chase kernel; Mosaic refused it and it was deleted — CHANGES.md.)

    chiprun -- python scripts/chip_kernels.py

Writes ``chiprun_out/kernels.json``; exits nonzero if the kernel failed
to compile or disagreed. TPU only — on CPU the kernel runs in interpret
mode through the test suite.
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SIZE, BOARDS = 19, 256


def check_labels() -> None:
    import jax
    import numpy as np

    from benchmarks._harness import random_game_states
    from rocalphago_tpu.engine.jaxgo import GoConfig, compute_labels
    from rocalphago_tpu.ops.labels import pallas_labels

    cfg = GoConfig(size=SIZE)
    boards = random_game_states(cfg, BOARDS, 120, jax.random.key(0)).board
    want = np.asarray(jax.jit(jax.vmap(
        lambda b: compute_labels(cfg, b)))(boards))
    got = np.asarray(pallas_labels(boards, SIZE, interpret=False))
    np.testing.assert_array_equal(got, want)


def main() -> int:
    from rocalphago_tpu.obs import jaxobs

    device = jaxobs.device_record()
    if device["platform"] != "tpu":
        print(f"chip_kernels: platform {device['platform']!r} is not a "
              "TPU", file=sys.stderr)
        return 1
    t0 = time.time()
    try:
        check_labels()
        rec = {"ok": True, "boards": BOARDS}
    except Exception as e:  # noqa: BLE001 — the record IS the point
        rec = {"ok": False, "error_type": type(e).__name__,
               "error": str(e)[:6000],
               "traceback": traceback.format_exc()[-3000:]}
    rec["wall_s"] = round(time.time() - t0, 1)
    print(json.dumps({"labels": rec})[:3000], flush=True)
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "kernels.json"), "w") as f:
        json.dump({"device": device, "labels": rec}, f, indent=1)
    return 0 if rec["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
