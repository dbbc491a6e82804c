#!/bin/bash
# Round-robin + Elo demo: three agent kinds over the same tiny 9x9
# nets (greedy / probabilistic / device-mcts), tournament logs fed to
# the Bradley-Terry Elo fitter. The point is the evaluation PIPELINE
# (tournament --log -> interface.elo) on real games; with random-init
# nets the ordering itself is weak evidence.
#
# Usage: bash scripts/elo_demo.sh [outdir] [games-per-pair]
set -eu
cd "$(dirname "$0")/.."
OUT=${1:-results/elo_demo}
GAMES=${2:-6}
SPECS=results/elo_demo/specs
mkdir -p "$OUT"

run_pair() {
    a=$1; b=$2; tag=$3
    JAX_PLATFORMS=cpu python -m \
        rocalphago_tpu.interface.tournament "$a" "$b" \
        --games "$GAMES" --board 9 --move-limit 120 --playouts 16 \
        --log "$OUT/$tag.jsonl" 2>>"$OUT/games.log" \
        | tee -a "$OUT/games.log"
}

# names in the logs come from the tournament's A/B labels — rewrite
# with jq-free sed to the agent kinds so the Elo table reads naturally
name_fix() {
    sed -i "s/\"A\"/\"$1\"/g; s/\"B\"/\"$2\"/g" "$OUT/$3.jsonl"
}

run_pair "device-mcts:$SPECS/p9.json:$SPECS/v9.json" \
         "greedy:$SPECS/p9.json" mcts_vs_greedy
name_fix mcts greedy mcts_vs_greedy
run_pair "device-mcts:$SPECS/p9.json:$SPECS/v9.json" \
         "probabilistic:$SPECS/p9.json" mcts_vs_prob
name_fix mcts prob mcts_vs_prob
run_pair "probabilistic:$SPECS/p9.json" \
         "greedy:$SPECS/p9.json" prob_vs_greedy
name_fix prob greedy prob_vs_greedy

JAX_PLATFORMS=cpu python -m \
    rocalphago_tpu.interface.elo "$OUT"/*.jsonl --anchor greedy \
    --anchor-elo 1000 | tee "$OUT/elo.json"
