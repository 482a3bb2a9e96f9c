#!/usr/bin/env bash
# One-command test runner (reference: ``tests/L0/run_test.py`` — the
# upstream entry point CI and contributors invoke). Tiers:
#
#   ./run_tests.sh          # L0: unit/integration suite (CPU, 8 virtual
#                           #     devices via tests/conftest.py)
#   ./run_tests.sh L1       # L1: loss-curve parity sweeps (slower)
#   ./run_tests.sh all      # both
#   ./run_tests.sh quick    # fast high-signal subset (-m quick) for the
#                           #     inner loop; full tier stays in CI
#   ./run_tests.sh chaos    # deterministic fault-injection tier for the
#                           #     serving engine (-m chaos): pinned and
#                           #     randomized fault schedules, typed
#                           #     outcomes, pool invariants audited
#                           #     after every tick, bit-identity of
#                           #     unaffected streams. Runs fully traced
#                           #     and dumps the Perfetto JSONL trace to
#                           #     $APEX_CHAOS_TRACE_OUT (defaulted below;
#                           #     CI uploads it as an artifact)
#   ./run_tests.sh gate     # L1 loss-curve gate: amp levels AND the
#                           #     reduced-precision optimizer-state modes
#                           #     (bf16 m, fused cast-out) must track the
#                           #     fp32 golden curve, and the quantized
#                           #     serving tiers (w8 / kv8 / w8+kv8) must
#                           #     track the trained fp32 eval-NLL curve
#                           #     — run on every PR
#   ./run_tests.sh lint     # apxlint, all six tiers: AST contract
#                           #     checks (kernel aliasing, collectives,
#                           #     AMP lists, hygiene), the VMEM budget
#                           #     pass, the jaxpr trace tier (APX5xx)
#                           #     over the entry registry, the cost
#                           #     tier (APX6xx byte budgets), the
#                           #     sharding tier (APX7xx partition-rule
#                           #     contracts), the determinism tier
#                           #     (APX8xx serving-stack race/ordering +
#                           #     fault-contract coverage), and the
#                           #     scaling tier (APX9xx mesh-sweep
#                           #     scale-invariance, per-shape trace
#                           #     time reported on stderr) — blocking
#                           #     in CI, with a combined wall-time
#                           #     budget enforced so the gate stays
#                           #     fast enough to run on every push
#
# The suite always runs on the CPU: conftest.py forces the 8-device CPU
# world whatever JAX_PLATFORMS says, and Pallas kernels run in interpret
# mode there. The on-chip check is `python chip_smoke.py`.
set -euo pipefail
cd "$(dirname "$0")"
tier="${1:-L0}"
shift || true
case "$tier" in
  L0)    exec python -m pytest tests/L0 -q "$@" ;;
  L1)    exec python -m pytest tests/L1 -q "$@" ;;
  all)   exec python -m pytest tests -q "$@" ;;
  quick) exec python -m pytest tests -q -m quick "$@" ;;
  chaos) # per-seed trace dumps land next to this path (a tag + seed
         # suffix is spliced in before the extension); set it empty to
         # disable the dump
         : "${APEX_CHAOS_TRACE_OUT=$(mktemp -d)/apex_chaos_trace.jsonl}"
         export APEX_CHAOS_TRACE_OUT
         echo "chaos traces: ${APEX_CHAOS_TRACE_OUT:-disabled}" >&2
         exec python -m pytest tests -q -m chaos "$@" ;;
  gate)  exec python -m pytest tests/L1/test_loss_curve_parity.py \
             tests/L1/test_quant_eval_parity.py -q "$@" ;;
  lint)  # combined AST + VMEM + trace + cost + sharding + determinism
         # + scaling tiers, under a wall-time budget: a slow lint gate
         # stops being run, so exceeding the budget is itself a failure
         # (trim the entry registry or sweep grid — the per-shape
         # scaling timings on stderr say where the time goes)
         budget=90
         start=$SECONDS
         python -m apex_tpu.lint apex_tpu tests --trace --cost \
             --sharding --determinism --scaling "$@"
         elapsed=$(( SECONDS - start ))
         if (( elapsed > budget )); then
           echo "apxlint: combined run took ${elapsed}s," \
                "budget is ${budget}s" >&2
           exit 1
         fi ;;
  *)     echo "usage: $0 [L0|L1|all|quick|chaos|gate|lint] [pytest args...]" >&2
         exit 2 ;;
esac
