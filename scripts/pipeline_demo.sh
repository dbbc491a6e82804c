#!/usr/bin/env bash
# End-to-end pipeline demo on CPU — the complete AlphaGo recipe as
# installed CLIs: SGF corpus → training shards → SL policy training
# (data-parallel over 8 virtual devices) → held-out top-1 eval →
# mesh-sharded batched self-play → REINFORCE improvement → value
# corpus + value training → MCTS-vs-greedy tournament → GTP →
# AlphaZero-style loop over the on-device search (training.zero).
#
# The reference's workflow (SURVEY.md §3.1–§3.5: game_converter →
# supervised/reinforcement/value trainers → ai/mcts/gtp_wrapper),
# exercised as a product: every stage is the installed CLI, artifacts
# land in $OUT.
#
#   bash scripts/pipeline_demo.sh [OUT_DIR]
#
# Runs ~5-10 minutes on one CPU host (tiny nets, bundled SGFs).
set -euo pipefail
cd "$(dirname "$0")/.."

OUT="${1:-/tmp/rocalphago_demo}"
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8"
PY="python"
rm -rf "$OUT"      # fresh demo dir — stale shards/splits would trip
mkdir -p "$OUT"    # the trainer's corpus-changed resume guard

echo "== 1/9 convert: bundled SGFs → npz shards"
$PY -m rocalphago_tpu.data.convert \
    --directory tests/test_data --outfile "$OUT/corpus" --size 9

echo "== 2/9 spec + SL training (2 epochs, 8-device data parallel)"
$PY -m rocalphago_tpu.models.specs policy --out "$OUT/policy.json" \
    --board 9 --layers 2 --filters 16
$PY -m rocalphago_tpu.training.sl "$OUT/policy.json" "$OUT/corpus" \
    "$OUT/sl" --epochs 2 --minibatch 16
echo "   metadata:"; tail -c 400 "$OUT/sl/metadata.json"; echo

echo "== 3/9 held-out eval (top-1 / loss on the test split)"
$PY -m rocalphago_tpu.training.evaluate "$OUT/sl/model.json" \
    "$OUT/corpus" --split test --shuffle-npz "$OUT/sl/shuffle.npz"

echo "== 4/9 batched self-play with the trained policy (sharded)"
$PY -m rocalphago_tpu.interface.selfplay_cli \
    --policy "$OUT/sl/model.json" --games 16 --max-moves 30 \
    --chunk 15 --shard --out "$OUT/selfplay"

echo "== 5/9 REINFORCE self-play improvement (2 tiny iterations)"
$PY -m rocalphago_tpu.training.rl "$OUT/sl/model.json" "$OUT/rl" \
    --game-batch 4 --iterations 2 --move-limit 25 --save-every 1
echo

echo "== 6/9 value corpus (one de-correlated position/game) + training"
$PY -m rocalphago_tpu.training.selfplay_data "$OUT/sl/model.json" \
    "$OUT/rl/model.json" "$OUT/value_data" --n-positions 48 \
    --batch 8 --max-moves 30
$PY -m rocalphago_tpu.models.specs value --out "$OUT/value.json" \
    --board 9 --layers 2 --filters 16
$PY -m rocalphago_tpu.training.value "$OUT/value.json" \
    "$OUT/value_data" "$OUT/value" --epochs 1 --minibatch 8 \
    --train-val-test 0.8 0.1 0.1

echo "== 7/9 head-to-head: MCTS(RL policy + value net) vs greedy SL"
$PY -m rocalphago_tpu.interface.tournament \
    "mcts:$OUT/rl/model.json:$OUT/value/model.json" \
    "greedy:$OUT/sl/model.json" --games 2 --board 9 \
    --move-limit 40 --playouts 8

echo "== 8/9 GTP smoke: genmove with the trained policy"
printf 'boardsize 9\nclear_board\ngenmove b\nquit\n' | \
    $PY -m rocalphago_tpu.interface.gtp --policy "$OUT/sl/model.json"

echo "== 9/9 AlphaZero-style loop over the on-device search (1 tiny iteration)"
$PY -m rocalphago_tpu.training.zero "$OUT/rl/model.json" \
    "$OUT/value/model.json" "$OUT/zero" --game-batch 2 \
    --iterations 1 --move-limit 20 --sims 4 --sim-chunk 2

echo "PIPELINE DEMO OK — artifacts in $OUT"
