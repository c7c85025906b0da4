PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-chaos bench-smoke bench-peel bench-stream bench-api bench-obs bench-kernels bench-serve lint lint-analysis

# Tier-1 verify (see ROADMAP.md).
test:
	$(PYTHON) -m pytest -x -q

# Chaos gate: fault storms (dispatch/oom/compile/poison/clock-skew) over
# 3 fixed seeds; writes the storm's metrics snapshot (retries, fallbacks,
# quarantines, faults injected) to CHAOS_metrics.json for CI to archive.
test-chaos:
	CHAOS_METRICS_OUT=CHAOS_metrics.json \
		$(PYTHON) -m pytest tests/test_chaos.py tests/test_resilience.py -q

# Tiny serving benchmark: 6 small graphs, batch widths 1 and 2.
bench-smoke:
	$(PYTHON) -m benchmarks.service_bench --smoke

# On-device peel benchmark -> BENCH_peel.json (decompose graphs/s at batch
# widths {1, 8}, sharded over 8 simulated host devices vs unsharded).
bench-peel:
	XLA_FLAGS="--xla_force_host_platform_device_count=8 $$XLA_FLAGS" \
		$(PYTHON) -m benchmarks.peel_bench --out BENCH_peel.json

# Streaming-update benchmark -> BENCH_stream.json (updates/s + frontier
# ratio at batch widths {1, 16, 256}; smoke asserts the frontier bound and
# the one-full-triangle-enumeration-per-session cache claim).
bench-stream:
	$(PYTHON) -m benchmarks.stream_bench --smoke --out BENCH_stream.json

# Declarative API benchmark -> BENCH_api.json (planner overhead µs/query +
# which backend the auto rule chose per shape bucket; smoke asserts the
# one-dispatch contract and that both formulations are exercised).
bench-api:
	$(PYTHON) -m benchmarks.api_bench --smoke --out BENCH_api.json

# Observability benchmark -> BENCH_obs.json (tracing overhead on/off +
# per-(bucket, backend) observed imbalance) and a sample Chrome trace
# (BENCH_trace_sample.json); smoke asserts the overhead bound and that
# every query-path stage shows up as a span.
bench-obs:
	$(PYTHON) -m benchmarks.obs_bench --smoke --out BENCH_obs.json \
		--trace-out BENCH_trace_sample.json

# Kernel benchmark -> BENCH_kernels.json (structural tile models of the
# Pallas support kernel + its interpret-mode timing per schedule; CPU
# emulation, not TPU wall-clock).
bench-kernels:
	$(PYTHON) -m benchmarks.kernels_bench --out BENCH_kernels.json

# Fleet benchmark -> BENCH_serve.json (queries/s + p50/p99 latency at
# 1 vs 3 replica processes under mixed-bucket traffic, plus the router's
# affinity hit rate; smoke asserts bit-identical-to-solve() and an
# affinity hit rate above 0.8 on the 3-replica fleet).
bench-serve:
	$(PYTHON) -m benchmarks.serve_bench --smoke --out BENCH_serve.json

# Byte-compile gate (no extra tooling required) + ruff when available
# (CI installs it via requirements-dev.txt; bare containers skip it).
lint:
	$(PYTHON) -m compileall -q src tests benchmarks examples
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check src tests benchmarks examples; \
	else \
		echo "ruff not installed; skipped (pip install -r requirements-dev.txt)"; \
	fi

# Repo-native static analysis (rules R1-R6, see src/repro/analysis/).
# Pure-stdlib AST pass: fails on any finding not in analysis/baseline.json
# (which ships empty — the dispatch-path and serve layers are lint-clean)
# and writes ANALYSIS_report.json for CI to archive.
lint-analysis:
	$(PYTHON) -m repro.analysis --report ANALYSIS_report.json
