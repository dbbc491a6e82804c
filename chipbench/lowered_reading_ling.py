"""The control a tolerance of ``reference_ling.py`` is set against:
the reference with its float32 parts lowered (``dtype=bfloat16``:
router, softmax, norms, loss, the delta rule's log-decay, its
exponentials and the carried state in the compute type) put in the
program's place and held to the reference by the driver's own
``verify``, at a cell's sizes, on the cell's first augmented batch —
``lowered_reading.py``'s method for the ``train_seq_ling`` driver.

    python3 chipbench/lowered_reading_ling.py --workload <cell> --seed <n>

``verify`` has to refuse what it is given: the exit code is 0 when it
does and 1 when the lowered outputs came out ``correct`` — the limits
are then too wide. Prints one JSON object: what ``verify`` refused it
by and every reading. Not part of a run of the benchmark; PERF.md §6
(PR 32) quotes what it printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def control(d) -> tuple:
    """``verify``'s (problems, readings) for a prepared driver ``d``
    with the lowered reference's outputs in the program's place."""
    import jax.numpy as jnp
    import numpy as np

    from chipbench import reference_ling as reference
    from chipbench.drivers.train_seq import cut
    from rocalphago_tpu.training import sl

    d.ref = d.reference()
    low = d.reference(dtype=jnp.bfloat16)
    d.lr = lr = sl.SLConfig().learning_rate
    d.logits = low["logits"]
    d.before = {k: np.asarray(cut(k, v, d.expert), np.float32)
                for k, v in reference.pick(d.net.params,
                                           d.paths).items()}
    d.after = {k: old - np.float32(lr) * low["grads"][k]
               for k, old in d.before.items()}
    k = d.kw["num_experts_per_tok"]
    d.chosen = [np.nonzero(mask)[1].reshape(-1, k)
                for mask in low["choices"]]
    d.first = {"loss": low["loss"], "second_loss": low["loss"],
               "moe_dropped": 0}
    return d.verify({"dropped": 0, "failed": 0,
                     "losses": [low["loss"]]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    a = ap.parse_args(argv)

    import jax

    from chipbench import run
    from chipbench.drivers import train_seq_ling
    from rocalphago_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()
    manifest = run.load_json(a.manifest)
    cell = {w["name"]: w for w in manifest["workloads"]}[a.workload]
    d = train_seq_ling.Driver(run.Context(manifest, cell, a.seed, ROOT))
    d.prepare()
    problems, readings = control(d)
    print(json.dumps({
        "workload": a.workload, "seed": a.seed,
        "device": jax.devices()[0].device_kind,
        "correct": not problems, "refused_by": problems,
        "checks": readings}))
    return 0 if problems else 1


if __name__ == "__main__":
    sys.exit(main())
