# Developer entry points.  PYTHONPATH=src is the only environment the repo
# needs.  Tests and these targets run on the CPU (JAX_PLATFORMS=cpu, Pallas
# kernels in interpret mode); their timings are CPU wall clocks.  The chip
# run is `python chip_smoke.py` on a TPU host.

PY ?= python
export PYTHONPATH := src

.PHONY: test test-diff test-chaos bench-smoke bench bench-json perf-gate \
	trace-demo clean-cache

# tier-1 verify: the gate every PR must keep green (collects the
# differential suite too — test-diff is the focused entry point)
test:
	$(PY) -m pytest -x -q

# differential/property harness: seeded random workloads replayed through
# scalar-vs-batched fault paths and untiered/2-tier/4-tier managers.  The
# generating seed is part of each test id (shown on failure); add seeds
# with DIFF_SEEDS=7,8 make test-diff
test-diff:
	$(PY) -m pytest -q -m differential tests/test_differential.py

# resilience/chaos lane: seeded failure schedules through the containment
# machinery — injector determinism, quarantine/backoff, supervisor detach,
# degraded engine modes, and the chaos differential (identical failure
# schedule across scalar/batched routes => bit-identical KV + end state)
test-chaos:
	$(PY) -m pytest -q -m chaos

# tier-1 tests + the tiered-memory capacity sweep in smoke mode
bench-smoke: test
	$(PY) -m benchmarks.capacity_sweep --smoke

# full benchmark harness (fig2 policy sweep, capacity sweep, hot path, VM,
# kernels)
bench:
	$(PY) -m benchmarks.run

# hot-path perf artifact: BENCH_hotpath.json (steps/s, faults/s,
# policy-invocations/step, mgmt_ns, step_wall_s; scalar vs batched per
# policy and batch size) — the perf trajectory tracked from PR 2 onward
bench-json:
	$(PY) -m benchmarks.hotpath_bench --json BENCH_hotpath.json
	$(PY) -m benchmarks.prefix_bench --json BENCH_prefix.json
	$(PY) -m benchmarks.profile_bench --json BENCH_profile.json

# CI perf gates: zero-cost claims (telemetry off / resilience disarmed
# within 2% of baseline) + the one-dispatch hot path (batched ebpf@b16
# steps/s within 2% of the committed BENCH_hotpath.json, fused executor
# still issuing <= 1 dispatch/step, steady-state table crossings zero)
perf-gate:
	$(PY) -m benchmarks.telemetry_gate
	$(PY) -m benchmarks.hotpath_gate
	$(PY) -m benchmarks.prefix_gate
	$(PY) -m benchmarks.profile_gate

# telemetry demo: serve a tiered smoke workload with ONLINE profiling and
# tracing on; writes out/trace_demo.json (load in ui.perfetto.dev — the
# "mm profiler" track carries per-process WSS counters and profile-reload
# instants), a Prometheus-style metrics snapshot, and the profiler's
# WSS-curve dump — the artifacts CI uploads per run
trace-demo:
	mkdir -p out
	$(PY) examples/serve_paged.py --requests 4 --hbm-blocks 64 \
		--host-blocks 128 --profile auto \
		--trace out/trace_demo.json \
		--metrics out/metrics_demo.txt \
		--wss-curve out/wss_demo.json

# drop the cross-session compiler-artifact cache (pickled lowering/unroll
# artifacts + persisted XLA executables under .cache/); everything rebuilds
# cold on the next run — use after suspicious cache behavior or to measure
# cold-start costs.  REPRO_CACHE_DIR overrides the location; =off disables.
clean-cache:
	rm -rf .cache
