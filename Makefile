# phys-MCP reproduction — reproducible verify + benchmark entry points.
#
#   make test              tier-1 verify (the ROADMAP.md command)
#   make test-fast         everything not marked slow (control plane,
#                          chaos, health; ~20s, no kernel/model suites)
#   make chaos-smoke       ~30s concurrent mini-campaign: recovery bench
#                          (1 quick trial) + full chaos scenario matrix
#   make test-twin         executable-twin suites: fidelity/parity,
#                          executor (shadow/fallback/speculate), properties
#   make twin-smoke        quick twin-fallback goodput trial + validity audit
#   make test-gateway      wire-layer suites: protocol round-trips (both
#                          codecs), gateway endpoint/error-taxonomy e2e,
#                          federated planes, streaming telemetry,
#                          multi-hop topology, coalesced wire path
#   make gateway-smoke     ~20s wire round-trip (discover→invoke→telemetry
#                          on the mixed testbed) + 1 overhead trial per
#                          codec, asserting the p50 wire-excess budget
#   make bench-gateway-smoke  alias for gateway-smoke (budget-asserting
#                          quick trial, for CI)
#   make hierarchy-smoke   ~60s 3-tier drill: 4-plane chain per-hop cost,
#                          stream-vs-poll fan-in, kill-the-middle-plane
#                          breaker + twin-fallback verification
#   make bench-gateway     local vs wire control-path overhead per codec
#                          (asserts median wire excess p50 <= 1 ms) + the
#                          connection-churn capacity sweep (async gateway
#                          must sustain >= 10x the threaded baseline)
#   make bench-hierarchy   multi-hop chain + streaming fan-in benchmark
#                          (per-hop added latency <= single-hop margin,
#                          >= 2x fewer requests than cursor polling)
#   make serving-smoke     LM serving drill: engine/adapter + paged-KV
#                          suites (allocator properties, paged/contiguous
#                          parity), quick paged trials and a 16-session
#                          gateway flood
#                          (structured DEADLINE/QUEUE_SATURATED refusals,
#                          zero mid-decode expiries, zero page leaks)
#   make bench-serving     full LM serving benchmark: paged-KV
#                          parity/capacity/prefix gates on a mixed-length
#                          trace (>= 1x goodput, 2x capacity, >= 30% TTFT
#                          cut) + 128 concurrent gateway sessions (bounded
#                          p99 TTFT, admission refusals)
#   make test-sim          virtual-time suites: clock semantics, scheduler
#                          timebase regressions, simulator invariants
#   make sim-smoke         CI-sized scenario matrix: >=100 planes on pure
#                          virtual time, all invariant audits, <60s
#   make bench-scenarios   full planet-scale scenario harness: 6 scenarios
#                          x 1000 planes x 10k substrates, 1 simulated
#                          hour each, zero violations + determinism check
#   make bench-throughput  headline serial-vs-pooled scheduler benchmark
#   make bench-recovery    resilience benchmark: goodput under faults with
#                          vs without the HealthManager
#   make bench-twin        twin-fallback vs reject-only goodput benchmark
#   make bench             full benchmark harness (all paper tables)
#   make lint-plane        planelint --strict (five control-plane invariant
#                          checkers + pinned goldens), then ruff when
#                          installed
#   make dev-deps          install dev/test dependencies

PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-fast chaos-smoke test-twin twin-smoke test-gateway \
        gateway-smoke bench-gateway-smoke hierarchy-smoke serving-smoke \
        test-sim sim-smoke bench-scenarios \
        bench bench-throughput bench-recovery bench-twin bench-gateway \
        bench-hierarchy bench-serving lint-plane dev-deps

test:
	$(PYTHON) -m pytest -x -q

test-fast:
	$(PYTHON) -m pytest -q -m "not slow"

chaos-smoke:
	$(PYTHON) -m benchmarks.bench_recovery --smoke

test-twin:
	$(PYTHON) -m pytest -q tests/test_twin_fidelity.py \
	    tests/test_twin_executor.py tests/test_twin_property.py

twin-smoke:
	$(PYTHON) -m benchmarks.bench_twin --smoke

test-gateway:
	$(PYTHON) -m pytest -q tests/test_protocol.py tests/test_codec.py \
	    tests/test_gateway.py tests/test_federation.py tests/test_stream.py \
	    tests/test_topology.py tests/test_wirepath.py

gateway-smoke:
	$(PYTHON) -m benchmarks.bench_gateway --smoke

bench-gateway-smoke: gateway-smoke

hierarchy-smoke:
	$(PYTHON) -m benchmarks.bench_hierarchy --smoke

serving-smoke:
	$(PYTHON) -m pytest -q tests/test_serving.py tests/test_kv_pages.py \
		tests/test_serving_paged.py -m "not slow"
	$(PYTHON) -m benchmarks.bench_serving --smoke

bench-serving:
	$(PYTHON) -m benchmarks.bench_serving

test-sim:
	$(PYTHON) -m pytest -q -m sim

sim-smoke:
	$(PYTHON) -m pytest -q -m sim
	$(PYTHON) -m benchmarks.bench_scenarios --smoke

bench-scenarios:
	$(PYTHON) -m benchmarks.bench_scenarios

bench-gateway:
	$(PYTHON) -m benchmarks.bench_gateway

bench-hierarchy:
	$(PYTHON) -m benchmarks.bench_hierarchy

bench-throughput:
	$(PYTHON) -m benchmarks.bench_throughput

bench-recovery:
	$(PYTHON) -m benchmarks.bench_recovery

bench-twin:
	$(PYTHON) -m benchmarks.bench_twin

bench:
	$(PYTHON) -m benchmarks.run

lint-plane:
	$(PYTHON) -m repro.analysis --strict
	@if command -v ruff >/dev/null 2>&1; then \
	    ruff check .; \
	else \
	    echo "ruff not installed; skipping (make dev-deps to get it)"; \
	fi

dev-deps:
	$(PYTHON) -m pip install -r requirements-dev.txt
