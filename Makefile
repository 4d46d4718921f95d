PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export PYTHONPATH

.PHONY: check test bench docs-check

# tier-1 suite + propagation smoke + model-zoo solver smoke + session-API
# smoke (cold/warm + solve_many) + solver-serving bench (open-loop
# continuous batching, §15) + scale bench (sparse banks, §16) + docs
# check (writes the git-ignored BENCH_propagation_smoke.json; see
# scripts/check.sh)
check:
	scripts/check.sh

test:
	python -m pytest -x -q

bench:
	python -m benchmarks.run --fast

# README/DESIGN path references resolve + quickstart commands dry-run
docs-check:
	python scripts/docs_check.py
