# Test entry points. JAX_PLATFORMS=cpu matches tests/conftest.py's virtual
# 8-device CPU setup. chip-smoke is the one target that wants the chip.

PY ?= python

.PHONY: test chip-smoke smoke serve-smoke serve-restart-smoke observatory-smoke \
	scenarios-smoke fleet-smoke perf-diff bench-byzantine bench-churn \
	bench-robust-scale bench-sweep bench-compute bench-telemetry \
	bench-serving bench-serving-load bench-fleet \
	bench-federated \
	bench-async bench-async-faults bench-observatory bench-mesh \
	bench-mesh-scale bench-scenarios bench-monitors

# Full fast suite (tier-1 shape, minus --continue-on-collection-errors:
# local runs should fail loudly on broken collection).
test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

# The main path once on the TPU (headline GLM to eps, full-width softmax,
# reference check, four-chip segment where four are visible): exits
# non-zero without a chip. No platform pin — it takes the machine's TPU.
chip-smoke:
	$(PY) chip_smoke.py

# Fast robustness smoke: fault-injection + churn + Byzantine + gather-
# aggregation + replica-batched-parity + telemetry + serving +
# observatory suites, first failure stops, strict collection (no marker
# typos, no swallowed import errors); then the end-to-end observatory
# smoke (daemon up -> run -> scrape /metrics -> stream progress ->
# observatory compare + perf-diff self-check) over real HTTP.
smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest -q -m 'not slow' -x \
		tests/test_faults.py tests/test_churn.py tests/test_byzantine.py \
		tests/test_robust_gather.py \
		tests/test_compressed_gossip.py tests/test_batch.py \
		tests/test_telemetry.py tests/test_serving.py \
		tests/test_federated.py tests/test_async.py \
		tests/test_async_faults.py \
		tests/test_matrix_free_faults.py tests/test_observatory.py \
		tests/test_monitors.py tests/test_worker_mesh.py \
		tests/test_mesh_scale.py \
		tests/test_scenarios.py tests/test_scenario_chaos.py \
		tests/test_fleet.py
	$(MAKE) observatory-smoke
	$(MAKE) scenarios-smoke
	$(MAKE) serve-restart-smoke
	$(MAKE) fleet-smoke

# End-to-end scenario-engine smoke (docs/SCENARIOS.md): a seeded sample
# over a mixed axis bank (validity agreement + per-cell invariants +
# warm-replay identity through the real serving layer), then one
# operational chaos kill/restart cycle served warm from the surviving
# executable cache.
scenarios-smoke:
	JAX_PLATFORMS=cpu $(PY) examples/scenarios_smoke.py

# End-to-end live-observatory smoke over real HTTP (docs/OBSERVABILITY.md):
# boot the daemon, stream /v1/progress while a run executes, scrape
# /metrics mid-run (consistent-histogram check), then drive the
# observatory CLI (list/compare) over the served manifests and self-check
# make perf-diff against the committed docs/perf tree.
observatory-smoke:
	JAX_PLATFORMS=cpu $(PY) examples/observatory_smoke.py

# Perf-regression checker (ISSUE-10): re-check bench JSON in FRESH
# against the committed docs/perf within per-artifact tolerances
# (observability/observatory.py PERF_TOLERANCES; exit 1 on regression).
# Default FRESH=docs/perf is the self-check; point FRESH at a regen
# output directory to guard a new measurement session:
#   bash examples/regen_perf_artifacts.sh && make perf-diff FRESH=docs/perf
FRESH ?= docs/perf
perf-diff:
	$(PY) -m distributed_optimization_tpu.observatory perf-diff \
		--fresh $(FRESH) --committed docs/perf

# End-to-end serving smoke over real HTTP (docs/SERVING.md): boot the
# daemon, submit 3 requests (2 structurally identical -> ONE compile via
# one coalesced cohort, 1 outlier), assert cache/cohort facts + served
# responses match a direct run, shut down cleanly over the wire.
serve-smoke:
	JAX_PLATFORMS=cpu $(PY) examples/serve_smoke.py

# Full-process restart over the persistent executable store (ISSUE-15
# restart-warm gate): daemon A serves cold + writes through, SIGKILL,
# daemon B over the same store replays with 0 compile seconds and a
# bitwise-identical final gap.
serve-restart-smoke:
	JAX_PLATFORMS=cpu $(PY) examples/serve_restart_smoke.py

# Self-healing fleet chaos gate (docs/SCENARIOS.md, docs/SERVING.md
# "Self-healing"): each remediation policy and the autoscaler proven
# by its dedicated chaos mode — divergence halt + quarantine, store
# corruption quarantine + cold recompile, SIGKILL storm, burst/idle
# autoscale cycle — plus real worker-pool scale_up/scale_down.
fleet-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest -q -x -m slow tests/test_fleet.py

# Regenerate the Byzantine breakdown evidence (docs/perf/byzantine.json).
bench-byzantine:
	JAX_PLATFORMS=cpu $(PY) examples/bench_byzantine.py

# Regenerate the correlated-failure evidence (docs/perf/churn.json).
bench-churn:
	JAX_PLATFORMS=cpu $(PY) examples/bench_churn.py

# Regenerate the degree-bounded robust-aggregation scaling evidence
# (docs/perf/robust_scale.json: gather-vs-dense e2e, asserted >= 5x floor
# at N=256 ring + crossover cells behind the robust_impl auto gate).
bench-robust-scale:
	JAX_PLATFORMS=cpu $(PY) examples/bench_robust_scale.py

# Regenerate the replica-batched sweep-throughput evidence
# (docs/perf/sweep.json: run_batch aggregate vs sequential baseline per
# R, asserted regime-dependent floor — 8x at R=32 on accelerators, 2.5x
# steady on CPU hosts).
bench-sweep:
	JAX_PLATFORMS=cpu $(PY) examples/bench_sweep.py

# Regenerate the compute-bound tier evidence with its published MFU-floor
# gate (docs/perf/compute_bound.json; exits without a TPU).
bench-compute:
	$(PY) examples/bench_compute_bound.py

# Regenerate the flight-recorder overhead evidence
# (docs/perf/telemetry.json: telemetry off vs on, asserted <=10%
# steady-state ceiling + bitwise off/on trajectory gate).
bench-telemetry:
	JAX_PLATFORMS=cpu $(PY) examples/bench_telemetry.py

# Regenerate the federated-regime evidence (docs/perf/federated.json:
# local-steps floats-to-eps reduction >= 2x floor, participation-rate
# convergence curves + q^2 cost model, matrix-free throughput/memory
# cells with the N=10k completion asserted).
bench-federated:
	JAX_PLATFORMS=cpu $(PY) examples/bench_federated.py

# Regenerate the asynchronous-gossip evidence (docs/perf/async.json:
# sync vs async iters/wall-clock-to-eps on a shared simulated latency
# realization — heavy-tail speedup floors, the constant-latency
# degenerate gate asserted == sync one-peer <= 1e-12, oracle parity).
bench-async:
	JAX_PLATFORMS=cpu $(PY) examples/bench_async.py

# Regenerate the event-clock fault evidence (docs/perf/async_faults.json:
# crash-free all-up injection asserted BITWISE vs the PR 9 async scan,
# gradient-tracking telescoping residual <= 1e-9 at any staleness with
# the staleness-vs-final-gap degradation curve, churn-vs-thinning
# no-free-lunch envelope at matched availability, and the >= 2x
# wall-clock-to-eps barrier floor surviving the fault composition).
bench-async-faults:
	JAX_PLATFORMS=cpu $(PY) examples/bench_async_faults.py

# Regenerate the serving-layer evidence (docs/perf/serving.json:
# executable-cache warm-vs-cold submit->start latency >= 10x floor,
# coalesced-cohort throughput >= 2.5x one-at-a-time on this CPU
# container, mixed-workload replay stats, f64 parity re-check).
bench-serving:
	JAX_PLATFORMS=cpu $(PY) examples/bench_serving.py

# Regenerate the sustained-load serving evidence
# (docs/perf/serving_load.json: scenario-sampled mixed traffic through
# the multi-worker daemon + persistent store — warm p50/p99 latency,
# saturation >= the PR-7 coalesced baseline, shed + fairness cells,
# restart-warm ratio, worker-plane f64 parity).
bench-serving-load:
	JAX_PLATFORMS=cpu $(PY) examples/bench_serving_load.py

# Self-healing fleet soak (docs/SERVING.md "Self-healing"): mixed
# traffic with chaos injections (planted divergence, worker SIGKILL,
# store corruption, burst/idle autoscale cycle) through the fleet
# reflex layer; every injection must come back remediated.
bench-fleet:
	JAX_PLATFORMS=cpu $(PY) examples/bench_fleet.py

# Regenerate the live-observatory evidence (docs/perf/observatory.json:
# heartbeat-on vs off steady-state overhead <= 3% ceiling + off/on
# bitwise gate, async-path cell, /metrics scrape p95 under load).
bench-observatory:
	JAX_PLATFORMS=cpu $(PY) examples/bench_observatory.py

# Regenerate the anomaly-sentinel evidence (docs/perf/monitors.json:
# ≤5% monitor overhead on the sequential + async paths, monitors-on
# bitwise, planted f>b divergence onset within 2 eval windows, early
# halt with attacker-naming incident — all gated).
bench-monitors:
	JAX_PLATFORMS=cpu $(PY) examples/bench_monitors.py

# Regenerate the scenario-matrix golden corpus (docs/perf/scenarios.json:
# validity-table agreement over a seeded 700-cell sample, the
# 34-composition golden matrix with per-cell invariants + warm replay,
# bitwise checkpoint-resume cells, and the operational chaos gates;
# forces 4 host devices itself for the worker-mesh cells).
bench-scenarios:
	$(PY) examples/bench_scenarios.py

# Regenerate the sharded worker-mesh evidence (docs/perf/worker_mesh.json:
# sharded-vs-unsharded bitwise parity, the N=100k completion over 4
# forced host devices, flat per-device memory at matched rows/device,
# N-independent ring ICI bytes — the script forces the 4-device host
# platform itself).
bench-mesh:
	$(PY) examples/bench_worker_mesh.py

# Regenerate the million-worker mesh evidence (docs/perf/mesh_scale.json:
# N=1M ring/torus sharded completions over 16 forced host devices, flat
# per-device memory at matched rows/device, the O(N·k_max) sparse ER
# build at 1M and the <=50% compressed-halo wire cut inside the 2.5x gap
# envelope — the script forces the 16-device host platform itself).
bench-mesh-scale:
	$(PY) examples/bench_mesh_scale.py
