PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export PYTHONPATH

.PHONY: test test-fast lint bench serve-demo serve-prefix-demo

# tier-1 verify (ROADMAP): full suite, stop on first failure
test:
	python -m pytest -x -q

# skip the slow multi-device subprocess dry-runs
test-fast:
	python -m pytest -x -q -m "not slow" --ignore=tests/test_dist_subprocess.py

# static analysis (DESIGN.md §15): jaxpr lint + Pallas kernel contracts
# + repo conventions, gated against analysis/baseline.json
lint:
	python -m repro.analysis --gate

bench:
	python -m benchmarks.run

serve-demo:
	python -m repro.launch.serve --smoke --paged --requests 8 --slots 4 --new-tokens 8

# shared system prompt across all requests: prefix index dedups + skips
# the shared prefill (DESIGN.md §9)
serve-prefix-demo:
	python -m repro.launch.serve --smoke --paged --prefix --requests 8 --slots 4 \
		--new-tokens 8 --shared-prefix-len 32
