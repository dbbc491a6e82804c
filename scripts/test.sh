#!/usr/bin/env bash
# Run the test suite on CPU with the 8-fake-device mesh.
#
# JAX_PLATFORMS=cpu keeps the suite off any attached accelerator;
# conftest.py adds the fake-device XLA flag (and pins the CPU platform
# again for harnesses that invoke pytest directly). The compile cache
# follows runtime/compilecache.py: JAX_COMPILATION_CACHE_DIR if set,
# else <checkout>/.jax_cache.
set -euo pipefail
cd "$(dirname "$0")/.."
# Default tier excludes @pytest.mark.slow (multi-minute trainer/e2e
# tests) to keep the edit-test loop under 5 minutes; `--all`, an
# explicit -m, or an exact ::node-id selection runs without the tier
# filter (so naming one slow test runs it). CI should run --all
# nightly.
#
# Fault tolerance: the default tier includes the chaos SMOKE
# (tests/test_chaos.py::test_chaos_smoke_single_kill_resume — one
# injected kill + exact resume of the 5x5 zero loop, ~1 min) and the
# SERVING-chaos smoke (tests/test_serving_chaos.py fast tier —
# injected faults at every genmove barrier/ladder rung, one fully
# degraded 5x5 game, and the hard-deadline anytime proof, ~15 s);
# the full every-barrier chaos sweeps (training kill/resume AND the
# serving barrier×rung×kind sweep over the real device search) are
# @slow and run with --all. See docs/RESILIENCE.md.
#
# Observability: tests/test_obs.py is tier-1 — span/registry/compile
# -tracking units, a zero-trainer smoke asserting the per-phase span
# records land in metrics.jsonl, and `scripts/obs_report.py
# --selftest` (the fixture render), so the report path cannot rot
# silently. See docs/OBSERVABILITY.md.
#
# Encode parity (tests/test_features.py, tier-1): the gated shared
# chase (`ladders.ladder_planes`) is pinned bit-identical to the
# legacy split formulation at capacity (TestSharedGating), sound on
# the adversarial edge/corner-ladder family, dense-19×19-bounded
# (≤1%) vs the pyfeatures oracle, and a warm second encode is
# asserted compile-free via the obs counters
# (test_warm_encode_compiles_nothing). The overflow/truncation and
# two-phase-equivalence sweeps are @slow. See docs/PERFORMANCE.md
# "Encode path".
#
# Incremental encode (tests/test_incremental.py, tier-1): the delta
# path (features/incremental.py) is trajectory-fuzzed bit-identical
# to the from-scratch encoder at every ply of randomized games
# (captures, ko, a curated 9×9 ladder opening, passes, game end,
# cross-game jumps), the chunked self-play cache carry is pinned
# move-identical, Preprocess.advance matches state_to_tensor, and
# warm advances are compile-free via the obs counters. The longer
# 9×9 fuzz, the monolithic-scan identity and the direct
# batched-encoder match are @slow. See docs/PERFORMANCE.md
# "Incremental encode".
#
# Self-play economics (tests/test_econ.py, tier-1): budget-masked
# slab identity (budget == n_sim bit-matches the plain run; mixed
# budgets stop each row exactly at its cap), forced-playout target
# pruning units, the flags-OFF bit-identity pins for selfplay and a
# tiny zero iteration, terminal ownership/score label parity against
# the engine's area scoring, and the aux-head graft keeping the
# value output bit-identical. The everything-ON zero end-to-end
# (cap + forced-k + aux learn) is @slow. Replay schema-v2 round-trip
# /spill/skip semantics live in tests/test_replay.py (tier-1). See
# docs/PERFORMANCE.md "Self-play economics".
#
# Pipelined dispatch: tests/test_pipeline.py is tier-1 —
# bit-identical pipelined-vs-sync sweeps for PUCT/gumbel search,
# chunked self-play (lagged done-poll) and a zero iteration, the
# sync-gap-strictly-higher A/B, the donation/retry refusal, and the
# step-on-done no-op lemma; the deadline-overshoot-at-depth tests
# live in tests/test_serving_chaos.py. See docs/PERFORMANCE.md.
# Serving (tests/test_serve.py, tier-1): the cross-game batching
# subsystem (rocalphago_tpu/serve; docs/SERVING.md) — evaluator
# coalescing/max-wait/padding semantics (padded rows pinned
# bit-ignored), bounded-queue sheds stepping the resilience ladder
# down (reason `overload`), session admission caps, the probes'
# serve block, and the multi-session SOAK under an installed fault
# plan (one failed eval batch + one watchdog-abandoned hang; every
# other session keeps being served). The split search path
# (prepare_sim/advance_sim/apply_sim) is pinned bit-identical to the
# fused search in tests/test_device_mcts.py, and the concurrent-emit
# test in tests/test_obs.py pins the MetricsLogger/registry
# thread-safety the many-session emit pattern relies on.
# Static analysis (jaxlint, docs/STATIC_ANALYSIS.md): the JAX-aware
# lint — donation reuse, retry-wrapping-donators, host syncs and
# Python branches on tracers in jit bodies, PRNG key reuse,
# float/unhashable static args, mutable-global capture, the
# metric/span/barrier/serve-probe/ROCALPHAGO_* knob inventories
# diffed against docs/{OBSERVABILITY,RESILIENCE,SERVING,KNOBS}.md,
# and the CONCURRENCY family (docs/CONCURRENCY.md): guarded-by
# annotated shared state, a cycle-free whole-project lock-
# acquisition graph, no blocking calls or user callbacks inside
# critical sections, every thread joinable — runs first (stdlib-
# only, all 6 families a few seconds, budgeted <30 s) and fails the
# tier on any unbaselined finding. tests/test_jaxlint.py re-runs it
# in-process (self-lint) plus per-rule fixture coverage, so
# `pytest tests/` alone still enforces it.
#
# Actor/learner split (docs/SCALE.md): tests/test_replay.py is
# tier-1 — replay-buffer semantics (FIFO/eviction/pacing/recency
# sampling/close), spill-restore with torn files, dtype-preserving
# record round-trip, tolerant JSONL ingest, publisher versioning,
# lockstep actor key-chain walk, actor error parking, learner idle
# accounting, and the watchdog waiting_on=replay_fill stall tag
# (~3 s total). The full lockstep-vs-sync bit-exactness A/Bs
# (in-process AND through the run_training CLI) and the 2-process
# gloo sharded-learner-step consistency test are @slow
# (tests/test_zero.py, tests/test_multihost.py) and run with --all.
#
# Multi-size (docs/MULTISIZE.md): tests/test_multisize.py is
# tier-1 — FCN-vs-bias-head A/B at the native size (bit-equal), the
# one-checkpoint-applies-at-every-size facade proof (params shared
# by reference, saved weights bit-equal across at_board sizes),
# value-symmetry invariance across 5/9/13, per-session komi
# (eval_batch_komi bit-compat at default, terminal-sign flip,
# ServePool komi plumbing), MultiSizePool routing/probe/refusal,
# the GTP boardsize re-route carrying komi, and the curriculum
# driver's stage handoff (fast, run_training monkeypatched —
# per-stage seed/iterations argv, bit-equal checkpoint carry,
# curriculum.stage spans in metrics.jsonl). The real 2-stage
# curriculum run (two trainer invocations + transfer gate) is @slow.
#
# Fleet supervision (runtime/supervisor.py; docs/RESILIENCE.md
# "Fleet supervision"): tests/test_fleet_chaos.py is tier-1 — the
# probabilistic fault grammar (kill/:p=/:seed=/random, deterministic
# schedules), supervisor units (restart+MTTR, crash-loop park,
# lockstep restart REFUSAL, drain semantics, stale-heartbeat tags
# reaching the watchdog's waiting_on), dispatcher resurrection and
# park-fails-pending, the lockstep-kill-parks-loudly subprocess
# proof, the SIGTERM drain → exact-resume bit-identity pin, and the
# chaos-soak SMOKE (scripts/chaos_soak.py --steps 3 --min-kills 2:
# randomized kills across actor/learner/serve barriers with the
# green-gate check, ~40 s). The full soak (12 learner steps, ≥6
# kills, defaults) is @slow and runs with --all.
#
# Network gateway (rocalphago_tpu/gateway; docs/GATEWAY.md):
# tests/test_gateway.py is tier-1 — NDJSON framing units (torn/
# oversized/undecodable frames), the full wire conversation over a
# real socket, every typed refusal (bad_proto, unknown_type,
# no_game, illegal_move, bad_board, overload at BOTH the connection
# cap and the pool's admission cap), abrupt-disconnect slot
# reclamation, the gateway.conn fault wall (transient fails one
# request, kill aborts one connection, zero unhandled), graceful
# drain (goodbye + 503 health + phase events), /healthz + /metrics,
# multi-size board routing, the GTP --connect bridge, and the
# gateway-soak SMOKE (scripts/gateway_soak.py in a subprocess:
# kills under load, sheds reconciled against /metrics, clean
# SIGTERM drain, exit 0). The multi-minute default soak is @slow.
#
# Concurrency proofing (runtime half): tests/test_lockcheck.py
# units the ROCALPHAGO_LOCKCHECK=1 instrumented locks (observed
# lock-order graph, cycle raise, held-sets, blocking-while-held,
# contention metrics); the serve SOAK and the concurrent-emit test
# each run once more under the harness, with the soak reconciling
# every OBSERVED lock edge against the STATIC acquisition graph
# (tests/test_serve.py::test_soak_under_lockcheck_...).
python scripts/lint.py --check

ARGS=()
TIER=(-m "not slow")
for a in "$@"; do
    case "$a" in
        --all)  TIER=() ;;
        -m)     TIER=(); ARGS+=("$a") ;;
        *::*)   TIER=(); ARGS+=("$a") ;;
        *)      ARGS+=("$a") ;;
    esac
done
exec env JAX_PLATFORMS=cpu \
    python -m pytest tests/ ${TIER[@]+"${TIER[@]}"} ${ARGS[@]+"${ARGS[@]}"}
