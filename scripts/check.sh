#!/usr/bin/env bash
# CI entry point: tier-1 test suite (default marks: slow excluded, ~3 min)
# plus the static analysis gate and the obs/engine/fleet smokes. The obs/
# acceptance property (a clean fleet is silent, an injected noisy neighbor
# is flagged) is pinned by the fleet tests of the tier-1 suite.
#
#   scripts/check.sh          # default suite + smokes
#   scripts/check.sh --full   # include slow-marked tests (full matrix)
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

MARK=(-m "not slow")
if [[ "${1:-}" == "--full" ]]; then
    MARK=(-m "")
fi

echo "== tier-1 tests =="
python -m pytest -x -q "${MARK[@]}"

echo "== static analysis gate (jaxpr passes + repo lint vs committed baseline) =="
python -m repro.analysis --gate

echo "== obs exporter smoke (Chrome trace + Prometheus exposition) =="
python -m benchmarks.obs_export --smoke

echo "== scale smoke (T=16, L=16k, 50 ticks) =="
python -m benchmarks.scale_sweep --smoke

echo "== churn smoke (dynamic ownership, >=50 lifecycle events) =="
python -m benchmarks.churn_sweep --smoke

echo "== fleet smoke (128 mixed static+churn hosts, 10k-tick chunked rollout) =="
python -m benchmarks.fleet_sweep --smoke

echo "== attribution smoke (conservation, counterfactuals, sketch, jaxpr gate) =="
python -m benchmarks.attribution --smoke

echo "== hotness smoke (sketch agreement >= 0.95, hotness-path speedup >= 2x) =="
python -m benchmarks.hotness --smoke

echo "== roofline smoke (kernel select speedup >= 1.2x, tick vs hotness baseline, interpret equivalence) =="
python -m benchmarks.roofline --smoke
