#!/usr/bin/env bash
# CI gate: tier-1 test suite + backend-comparison propagation smoke.
#
#   make check            # or: scripts/check.sh
#
# Runs the ROADMAP tier-1 command (full pytest; ZERO failures required),
# a 2-size bench_propagation smoke
# comparing all registered propagation backends, a model-zoo solver smoke
# (every zoo model through the EPS engine, DESIGN.md §10, with per-model
# typed-propagator-table sizes, §12), a session-API smoke (cold+warm
# compile amortization + solve_many batched throughput on 4 knapsack
# instances, DESIGN.md §11), a resident-megakernel smoke (one
# pallas_resident solve in interpret mode on CPU, DESIGN.md §13 — its
# K-launch bit-parity suite tests/test_resident.py already runs inside
# tier-1), the superstep-orchestration bench (ms_per_superstep +
# dispatches_per_solve per backend), the distributed-EPS bench (mesh
# 1→8 on faked host devices: speedup vs mesh=1, steal events,
# bound-all-reduce counts, DESIGN.md §14), the solver-serving bench
# (fixed-seed open-loop Poisson load through the continuous-batching
# scheduler, DESIGN.md §15), the scale-tier bench (sparse-vs-dense peak
# bank-tile bytes, forced dense/sparse objective parity, large-tier
# props/s + nodes/s probes, DESIGN.md §16), the Compact-Table bench
# (bitset-carried props/s + currtable word statics on the extensional
# zoo models, every backend proven + ground-checked, native vs
# decompose=True oracle — hard-fails on any status/objective mismatch,
# DESIGN.md §17) and the docs check, writing
# BENCH_propagation_smoke.json (propagation rows + `solver` + `api` +
# `superstep` + `distributed` + `serving` + `scale` + `compact_table`
# sections) at the repo root so the perf trajectory populates per PR.  The zoo smoke
# sweeps EVERY registered backend, pallas_resident included, and
# hard-fails on any proven-optimum mismatch between backends; the dist
# bench hard-fails on any mesh losing status/objective parity with
# mesh=1; the serving bench hard-fails on parity vs sequential
# Solver.solve, on no request ever batching, or on any bucket
# recompiling after its cold compile; the scale bench hard-fails unless
# the sparse AllDifferent tile is strictly smaller than the dense O(N³)
# tile at N ≥ 128 and on any dense/sparse status/objective mismatch.
#
# Exit code: nonzero on ANY test failure, collection error or bench
# failure.
set -uo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

echo "== tier-1 tests (zero-failures gate) =="
pytest_log=$(mktemp)
python -m pytest -q --durations=15 --continue-on-collection-errors 2>&1 | tee "$pytest_log"
rc=${PIPESTATUS[0]}
if [ "$rc" -ne 0 ]; then
    echo "FAIL: tier-1 suite not green (pytest exit $rc)" >&2
    exit 1
fi
summary=$(grep -E "[0-9]+ (passed|failed|skipped|error)" "$pytest_log" | tail -1)
if [ -z "$summary" ]; then
    echo "FAIL: no pytest summary line found" >&2
    exit 1
fi
if grep -qiE "failed|error" <<<"$summary"; then
    echo "FAIL: failures/collection errors present ($summary)" >&2
    exit 1
fi

echo
echo "== propagation backend smoke (2 sizes, all backends) =="
python -m benchmarks.bench_propagation \
    --sizes 6 8 --lanes 8 --json BENCH_propagation_smoke.json || exit 1

echo
echo "== resident megakernel smoke (pallas_resident, interpret on CPU) =="
python -m repro.launch.solve --n 8 --lanes 8 --subs 16 \
    --backend pallas_resident --supersteps-per-launch 16 || exit 1

echo
echo "== model-zoo solver smoke (all zoo models, EPS engine, ALL backends) =="
python -m benchmarks.bench_solver \
    --zoo-smoke --json BENCH_propagation_smoke.json || exit 1

echo
echo "== superstep bench (dispatch amortization, all backends, §13) =="
python -m benchmarks.bench_solver \
    --superstep-bench --json BENCH_propagation_smoke.json || exit 1

echo
echo "== session-API smoke (cold+warm solve, solve_many x4, all backends) =="
python -m benchmarks.bench_solver \
    --throughput --json BENCH_propagation_smoke.json || exit 1

echo
echo "== distributed-EPS bench (mesh 1..8 on faked host devices, §14) =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m benchmarks.bench_solver \
    --dist-bench --json BENCH_propagation_smoke.json || exit 1

echo
echo "== solver-serving bench (open-loop load, continuous batching, §15) =="
python -m benchmarks.bench_solver \
    --serve-bench --json BENCH_propagation_smoke.json || exit 1

echo
echo "== scale bench (sparse banks: bytes, parity, large-tier probes, §16) =="
python -m benchmarks.bench_solver \
    --scale-smoke --json BENCH_propagation_smoke.json || exit 1

echo
echo "== compact-table bench (bitset CT: props/s, parity, oracle, §17) =="
python -m benchmarks.bench_solver \
    --ct-smoke --json BENCH_propagation_smoke.json || exit 1

echo
echo "== docs check (README/DESIGN references + quickstart dry-run) =="
python scripts/docs_check.py || exit 1

# stamp the test summary into the bench JSON so one file carries the
# whole check result
python - "$summary" <<'PYEOF'
import json, sys
path = "BENCH_propagation_smoke.json"
doc = json.load(open(path))
doc["tier1_summary"] = sys.argv[1]
json.dump(doc, open(path, "w"), indent=2)
PYEOF

echo
echo "check OK — wrote BENCH_propagation_smoke.json ($summary)"
