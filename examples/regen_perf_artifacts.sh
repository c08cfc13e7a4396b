#!/usr/bin/env bash
# Regenerate every committed performance artifact on the real chip.
#
# Each script is independent and idempotent; together they rebuild all of
# docs/perf/*.json, docs/figures/scaling.png, and the numbers quoted in
# docs/PERF.md. The committed artifacts date from 2026-07 under an earlier
# runtime; how long a full regeneration takes on the current machine is
# not measured. Every script interleaves its variants so within-artifact
# comparisons do not depend on which stretch of the session a variant got.
# NEVER run two of these concurrently: a chip belongs to one process.
set -euo pipefail
cd "$(dirname "$0")/.."

python examples/bench_breakdown.py         # -> docs/perf/breakdown.json
python examples/bench_scaling.py           # -> docs/perf/scaling.json + figure
python examples/bench_presets.py           # -> docs/perf/presets.json
python examples/bench_faults.py            # -> docs/perf/faults.json
python examples/bench_churn.py             # -> docs/perf/churn.json
python examples/bench_byzantine.py         # -> docs/perf/byzantine.json
python examples/bench_robust_scale.py      # -> docs/perf/robust_scale.json
python examples/bench_compute_bound.py     # -> docs/perf/compute_bound.json (MFU-floor gated)
python examples/bench_sweep.py             # -> docs/perf/sweep.json (replica-batch floor gated)
python examples/bench_telemetry.py         # -> docs/perf/telemetry.json (overhead-ceiling gated)
python examples/bench_serving.py           # -> docs/perf/serving.json (latency/throughput floors gated)
python examples/bench_serving_load.py      # -> docs/perf/serving_load.json (sustained-load warm-p99/saturation/fairness floors + restart-warm + shed gates; multi-worker daemon + persistent store)
python examples/bench_fleet.py            # -> docs/perf/fleet.json (self-healing soak: every injected incident remediated + zero stuck + autoscale cycle gated; fleet reflex layer over the multi-worker daemon)
python examples/bench_observatory.py       # -> docs/perf/observatory.json (heartbeat-overhead ceiling incl. async segment-fused cell + /metrics scrape gated)
python examples/bench_monitors.py          # -> docs/perf/monitors.json (anomaly-sentinel overhead/onset/halt gated)
python examples/bench_federated.py         # -> docs/perf/federated.json (floats-to-eps floor + N=10k completion gated)
python examples/bench_async.py             # -> docs/perf/async.json (wall-clock-to-eps floors + degenerate sync gate)
python examples/bench_async_faults.py      # -> docs/perf/async_faults.json (crash-free bitwise gate + tracking-invariant bound + matched-availability envelope + under-faults barrier floor)
python examples/bench_worker_mesh.py       # -> docs/perf/worker_mesh.json (sharded parity bitwise + N=100k completion incl. sparse-sampled ER + flat per-device memory gated; forces 4 host devices itself)
python examples/bench_mesh_scale.py        # -> docs/perf/mesh_scale.json (N=1M ring/torus sharded completions + flat per-device memory + sparse-ER 1M build + compressed-halo wire cut gated; forces 16 host devices itself)
python examples/bench_scenarios.py         # -> docs/perf/scenarios.json (validity-agreement + per-cell invariant + warm-replay + chaos gates; forces 4 host devices itself)
python examples/reproduce_report.py --json docs/perf/report_reproduction.json
python examples/northstar_consensus.py --ring-full  # -> docs/perf/northstar_consensus.json
python bench.py                            # headline JSON line (stdout)
# docs/perf/anomaly_rootcause.json is a one-off investigation record
# (round-3 nested-scan root cause), not regenerated here.
