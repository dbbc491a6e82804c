"""The control a limit of ``reference_lfm2.py`` is set against, put
in the program's place and held to the reference by the
``train_seq_lfm2`` driver's own ``verify`` at a cell's sizes, on the
cell's first augmented batch (after the set-up's balancing of the
selection biases) — ``lowered_reading_olmo.py``'s method: the
reference with its float32 parts lowered (``dtype=bfloat16``: router,
softmax, norms, loss, the short convolution and its gating in the
compute type), the gated output's check from the program's own
product ``[B | C | x̃]``, as the driver's.

    python3 chipbench/lowered_reading_lfm2.py --workload <cell> --seed <n> [<n> ...]

``verify`` has to refuse the control at every seed — the reference in
the nearest precision below the configuration's: the exit code is 0
when it does and 1 when it came out ``correct`` at a seed (the limits
are then too wide). Prints one JSON object a seed: what ``verify``
refused the control by, every reading, and the program's own gated
output error at the seed. Not part of a run of the benchmark; PERF.md
§6 quotes what it printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def control(d, low: dict, mix_err: float) -> tuple:
    """``verify``'s (problems, readings) for a prepared driver ``d``
    whose ``ref`` is set, with a control's outputs ``low`` and its
    gated output's error ``mix_err`` in the program's place."""
    import numpy as np

    from chipbench import reference_lfm2 as reference
    from chipbench.drivers.train_seq import cut
    from rocalphago_tpu.training import sl

    d.lr = lr = sl.SLConfig().learning_rate
    d.mix_err = mix_err
    d.logits = low["logits"]
    k = d.kw["num_experts_per_tok"]
    # a choice mask's k experts, as the program's chosen indices
    d.chosen = [np.argsort(~mask, axis=-1, kind="stable")[:, :k]
                for mask in low["choices"]]
    d.before = {key: np.asarray(cut(key, v, d.expert), np.float32)
                for key, v in reference.pick(d.net.params,
                                             d.paths).items()}
    d.after = {key: old - np.float32(lr) * low["grads"][key]
               for key, old in d.before.items()}
    d.first = {"loss": low["loss"], "second_loss": low["loss"],
               "moe_dropped": 0}
    return d.verify({"failed": 0, "losses": [low["loss"]],
                     "dropped": 0})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from chipbench import reference_lfm2 as reference
    from chipbench import run
    from chipbench.drivers import train_seq_lfm2
    from rocalphago_tpu.runtime.compilecache import enable_compile_cache

    enable_compile_cache()
    manifest = run.load_json(a.manifest)
    cell = {w["name"]: w for w in manifest["workloads"]}[a.workload]
    refused = True
    for seed in a.seed:
        d = train_seq_lfm2.Driver(run.Context(manifest, cell, seed, ROOT))
        d.prepare()
        d.ref = d.reference()
        bcx, mixed = d.mix(d.net.params, d.first_batch[0])
        want = d.reference_mix(bcx)
        program = reference.relative_error(mixed, want)
        problems, readings = control(
            d, d.reference(dtype=jnp.bfloat16),
            reference.relative_error(
                d.reference_mix(bcx, jnp.bfloat16), want))
        del bcx, mixed, want
        refused = refused and bool(problems)
        print(json.dumps({
            "workload": a.workload, "seed": seed,
            "device": jax.devices()[0].device_kind,
            "program_mix_rel_err": program,
            "control": {"correct": not problems, "refused_by": problems,
                        "checks": readings}}), flush=True)
    return 0 if refused else 1


if __name__ == "__main__":
    sys.exit(main())
