"""CLI to create model JSON specs (+ fresh weights).

The reference keeps model architecture in a JSON spec created ad hoc in
user code before training (SURVEY.md §2 "NN base / registry"); this
small CLI makes that a one-liner:

    python -m rocalphago_tpu.models.specs policy --out models/policy.json
    python -m rocalphago_tpu.models.specs value --out models/value.json
    python -m rocalphago_tpu.models.specs rollout --out models/rollout.json
    python -m rocalphago_tpu.models.specs seq --config cfg.json --out seq.json

``seq`` is the move-sequence policy (``models/seqpolicy.py``). Its
``--config`` is a JSON object of the published decoder config's keys
under their own names (``hidden_size``, ``layer_types``,
``num_attention_heads_per_layer``, ``rope_parameters``,
``num_experts``, …) plus the share of the model held here:
``layers_held``, ``vocab_held``, ``experts_held``, ``expert_offset``.
"""

from __future__ import annotations

import argparse
import json
import sys

from rocalphago_tpu.features import (
    DEFAULT_FEATURES,
    VALUE_FEATURES,
    default_features,
    value_features,
)
from rocalphago_tpu.models.policy import CNNPolicy
from rocalphago_tpu.models.rollout import ROLLOUT_FEATURES, CNNRollout
from rocalphago_tpu.models.seqpolicy import SeqPolicy
from rocalphago_tpu.models.value import CNNValue


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Write a model JSON spec with fresh weights")
    ap.add_argument("kind", choices=("policy", "value", "rollout", "seq"))
    ap.add_argument("--config", default=None,
                    help="seq only: JSON file of the decoder's "
                         "published keys plus the held share")
    ap.add_argument("--out", required=True, help="spec path (.json)")
    ap.add_argument("--board", type=int, default=19)
    ap.add_argument("--layers", type=int, default=12,
                    help="conv trunk depth (policy/value only; the "
                         "rollout net is fixed at one conv layer)")
    ap.add_argument("--filters", type=int, default=None,
                    help="filters per conv layer (default 128; "
                         "rollout default 32)")
    ap.add_argument("--features", nargs="*", default=None,
                    help=f"feature names (policy default: the AlphaGo "
                         f"48-plane set {', '.join(DEFAULT_FEATURES)}; "
                         f"value default adds the 'color' plane (49); "
                         f"rollout default: {', '.join(ROLLOUT_FEATURES)}. "
                         f"ROCALPHAGO_LADDER_PLANES=off drops the two "
                         f"ladder planes from the policy/value defaults "
                         f"— the ladder-free configuration)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--head", default=None,
                    help="head variant: 'fcn' (size-generic params — "
                         "the default; one checkpoint applies at any "
                         "board, see docs/MULTISIZE.md) or the legacy "
                         "size-locked head ('dense' for value, 'bias' "
                         "for policy/rollout). The value default also "
                         "honors ROCALPHAGO_VALUE_HEAD")
    ap.add_argument("--trunk-pool", type=int, default=0,
                    help="number of KataGo-style global-pooling bias "
                         "blocks interleaved in the conv trunk "
                         "(policy/value only; default 0 = the plain "
                         "AlphaGo trunk). Pair with "
                         "ROCALPHAGO_LADDER_PLANES=off so the net can "
                         "see whole-board ladder state without the "
                         "handcrafted planes")
    a = ap.parse_args(argv)

    if a.kind == "seq":
        if not a.config:
            ap.error("seq needs --config")
        with open(a.config) as f:
            net = SeqPolicy(board=a.board, seed=a.seed, **json.load(f))
        net.save_model(a.out)
        print(f"wrote {a.out} (SeqPolicy, board={a.board}, "
              f"{net.spec_kwargs['layers_held']} layers, "
              f"{net.num_outputs} ids)")
        return net
    if a.kind == "policy":
        features = tuple(a.features) if a.features else default_features()
        net = CNNPolicy(features, board=a.board, layers=a.layers,
                        filters_per_layer=a.filters or 128, seed=a.seed,
                        **({"head": a.head} if a.head else {}),
                        **({"trunk_pool": a.trunk_pool}
                           if a.trunk_pool else {}))
    elif a.kind == "value":
        features = tuple(a.features) if a.features else value_features()
        net = CNNValue(features, board=a.board, layers=a.layers,
                       filters_per_layer=a.filters or 128, seed=a.seed,
                       **({"head": a.head} if a.head else {}),
                       **({"trunk_pool": a.trunk_pool}
                          if a.trunk_pool else {}))
    else:
        features = tuple(a.features) if a.features else ROLLOUT_FEATURES
        net = CNNRollout(features, board=a.board,
                         filters=a.filters or 32, seed=a.seed,
                         **({"head": a.head} if a.head else {}))
    net.save_model(a.out)
    print(f"wrote {a.out} ({type(net).__name__}, board={a.board}, "
          f"head={net.module.head}, "
          f"{net.preprocess.output_dim} planes)")
    return net


if __name__ == "__main__":
    main(sys.argv[1:])
