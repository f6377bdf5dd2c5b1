#!/usr/bin/env bash
# Tier-1 verify with a baseline gate, then smoke-sized benchmark +
# artifact runs so every verify appends rows to results/BENCH_geo.json
# (the bench trajectory accumulates with the test history):
#
#   1. full pytest run (no -x: the baseline gate needs complete counts);
#   2. scripts/check_tier1.py prints the pass/fail delta vs the recorded
#      seed baseline (scripts/tier1_baseline.json) and fails the verify
#      on any regression — pytest's raw exit status is informational
#      (the baseline's known model-stack failures are expected);
#   2b. scripts/check_static.py — the GeoLint static-analysis ratchet
#      (lock discipline, wallclock, compat boundary, trace purity,
#      dead code; DESIGN.md §17) vs scripts/static_baseline.json —
#      then a REPRO_LOCKCHECK=1 rerun of the frontend + analytics
#      concurrency batteries under the runtime lock-order detector;
#   3. benchmarks/geo_perf --smoke, benchmarks/serve_perf --smoke, and
#      benchmarks/load_perf --smoke (sustained-QPS-at-SLO through the
#      concurrent AsyncGeoServer front-end — the serve_slo row) — run
#      even on test failure: known-failing model-stack tests must not
#      starve the bench record.  load_perf runs with --trace at 100%
#      sampling and scripts/check_trace.py validates the exported
#      Chrome trace (per-request timeline reconstruction, §15);
#      benchmarks/trace_overhead --smoke enforces the tracing overhead
#      budget (tracer-off and 1%-sampled within 3% of untraced);
#   4. scripts/check_bench.py (soft perf ratchet: warns, never fails,
#      on a >30% regression vs the trailing median — points/sec and
#      qps_at_slo alike).  benchmarks/roofline --geo is not run here:
#      roofline shares need a chip with published peaks;
#   5. benchmarks/analytics_perf --smoke — fused vs unfused per-block
#      aggregation (bit-identity asserted in-bench) + windowed
#      streaming throughput rows appended to the same trajectory
#      (DESIGN.md §16);
#   6. scripts/artifact_smoke.py — GeoIndexSet save/load round trip
#      (the serving cold-start path) checked bit-identical — and
#      scripts/analytics_smoke.py — windowed-analytics snapshot schema,
#      event conservation, k-anonymity suppression, and window-state
#      merge associativity under a deterministic injected clock.
#
# Exit status: the baseline gate's verdict wins; bench/smoke failures
# surface only when the gate passed.
# Usage: scripts/verify.sh [extra pytest args]
set -uo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

pytest_log=$(mktemp)
trap 'rm -f "$pytest_log"' EXIT
python -m pytest -q "$@" 2>&1 | tee "$pytest_log"
python scripts/check_tier1.py "$pytest_log"
status=$?

# GeoLint static-analysis ratchet (DESIGN.md §17): per-rule finding
# counts gated against scripts/static_baseline.json — regressions AND
# stale baselines both fail.
python scripts/check_static.py
static=$?
[ "$status" -eq 0 ] && status=$static

# Runtime lock-order / guarded-write detector over the concurrency
# batteries: instruments the §14 locks and fails on any acquisition
# cycle or annotated-field write without its lock held.
REPRO_LOCKCHECK=1 python -m pytest -q tests/test_frontend.py \
    tests/test_analytics.py
lockcheck=$?
[ "$status" -eq 0 ] && status=$lockcheck

python -m benchmarks.geo_perf --smoke
bench=$?
python -m benchmarks.serve_perf --smoke
serve_bench=$?
# --trace at 100% sampling: the smoke's Chrome trace must reconstruct
# valid per-request timelines (scripts/check_trace.py, DESIGN.md §15).
python -m benchmarks.load_perf --smoke --trace --trace-sample 1.0 \
    --trace-out results/trace_load
load_bench=$?
python scripts/check_trace.py results/trace_load.chrome.json
trace_check=$?
python -m benchmarks.trace_overhead --smoke
overhead=$?
python -m benchmarks.analytics_perf --smoke
analytics_bench=$?
python scripts/check_bench.py   # soft ratchet: informational exit only
python scripts/artifact_smoke.py
smoke=$?
python scripts/analytics_smoke.py
analytics_smoke=$?
[ "$bench" -eq 0 ] && bench=$serve_bench
[ "$bench" -eq 0 ] && bench=$load_bench
[ "$bench" -eq 0 ] && bench=$trace_check
[ "$bench" -eq 0 ] && bench=$overhead
[ "$bench" -eq 0 ] && bench=$analytics_bench
[ "$bench" -eq 0 ] && bench=$smoke
[ "$bench" -eq 0 ] && bench=$analytics_smoke
[ "$status" -eq 0 ] && status=$bench
exit $status
