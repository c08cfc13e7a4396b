"""Replica-batched sweep throughput (the PR-4 tentpole evidence).

Every seed replicate, suite row, and bench variant used to execute as its
own sequential compiled scan (``bench_byzantine.py``, ``bench_churn.py``,
``simulator.run_suite``): R replicates cost R compiles + R program
dispatches + R runs. ``jax_backend.run_batch`` vmaps the whole run over a
leading [R] replica axis — one compile, one program, [R, N, d] state —
so a sweep's aggregate iters/sec is bounded by how much idle capacity the
single run leaves, not by R.

Two cells, measured end to end through real backend runs:

1. **flagship_n25** — the reference study's flagship decentralized config
   (logistic, N=25, ring): per-R table for R ∈ {1, 2, 4, 8, 16, 32},
   batched aggregate vs the sequential single-run baseline, both as
   steady-state (compile excluded) and end-to-end (compile included —
   what a sequential sweep actually pays, since each ``run()`` call
   re-traces and re-compiles; see bench.py's protocol notes).
2. **northstar_n256** — the BASELINE.json north-star shape (N=256 ring):
   the heavier per-replica cell, where batching's gain is SMALLER on a
   compute-bound host (less idle capacity to fill) — the honest
   crossover direction, flagged per row via ``batching_loses``.

Plus an eta0-sweep demo row (the hyperparameter axis riding the same
batched program).

Asserted floors (same convention as bench.py's published-range gate,
BENCH_NO_RANGE_CHECK escape hatch included):

- **accelerator platforms** (the canonical latency/dispatch-bound regime
  this tentpole targets — the 2026-07 headline capture measured the
  [256, 81] hot loop at ~103k iters/sec with the vector lanes mostly
  idle): aggregate at R=32
  must be ≥ 8× the sequential single-run baseline.
- **CPU hosts** (this container: single core, every config compute-bound
  — SIMD lane-filling is the only headroom, measured ~3.5–4.6×):
  aggregate at R=32 must be ≥ 2.5× steady-state. The 8× claim is an
  accelerator-regime claim; asserting it on a 1-core host would gate on
  hardware this machine does not have, and writing 8× into the artifact
  without measuring it would be exactly the silent-docs-drift failure
  bench.py exists to kill. The artifact records which floor applied.

Writes ``docs/perf/sweep.json``.

Usage:  python examples/bench_sweep.py [--out PATH] [--seq-cycles 3]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

R_TABLE = (1, 2, 4, 8, 16, 32)
FLOOR_ACCELERATOR = 8.0   # aggregate/single at R=32, e2e or steady
FLOOR_CPU_STEADY = 2.5    # measured-here SIMD-fill floor at R=32


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-cycles", type=int, default=3,
                    help="sequential-baseline repetitions (median)")
    ap.add_argument("--out", default="docs/perf/sweep.json")
    args = ap.parse_args()

    import jax

    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )

    dev = jax.devices()[0]
    platform = dev.platform
    print(f"[sweep] device={dev} platform={platform}", file=sys.stderr)

    cells_cfg = {
        # The reference study's flagship decentralized row (main.py
        # defaults: N=25 ring logistic d=80 b=16), shortened to a
        # bench-scale horizon.
        "flagship_n25": (
            ExperimentConfig(
                problem_type="logistic", algorithm="dsgd", topology="ring",
                n_iterations=2000, eval_every=500,
            ),
            R_TABLE,
        ),
        # The north-star scale shape ([256, 81] model stack) — the
        # heavier per-replica cell; two R points bound its scaling.
        "northstar_n256": (
            ExperimentConfig(
                problem_type="logistic", algorithm="dsgd", topology="ring",
                n_workers=256, n_iterations=400, eval_every=100,
            ),
            (8, 32),
        ),
    }

    cells = {}
    for name, (cfg, r_points) in cells_cfg.items():
        ds = generate_synthetic_dataset(cfg)
        T = cfg.n_iterations
        # Sequential baseline: median over fresh run() calls, each paying
        # its own trace + compile — what a sweep WITHOUT the serving
        # layer's executable cache pays per replicate. The process cache
        # (docs/SERVING.md) would now skip that re-compile for repeat
        # programs, so this baseline opts out explicitly to keep the
        # protocol's meaning; the cached regime is measured in
        # docs/perf/serving.json.
        seq_e2e, seq_steady = [], []
        for c in range(args.seq_cycles):
            t0 = time.perf_counter()
            r = jax_backend.run(
                cfg.replace(seed=cfg.seed + c), ds, 0.0,
                executable_cache=False,
            )
            seq_e2e.append(time.perf_counter() - t0)
            seq_steady.append(float(r.history.iters_per_second))
        single = {
            "steady_ips": round(statistics.median(seq_steady), 1),
            "e2e_ips": round(T / statistics.median(seq_e2e), 1),
            "e2e_wall_s": round(statistics.median(seq_e2e), 2),
        }
        rows = {}
        for R in r_points:
            t0 = time.perf_counter()
            batch = jax_backend.run_batch(
                cfg, ds, 0.0, seeds=[cfg.seed + i for i in range(R)]
            )
            wall = time.perf_counter() - t0
            assert np.all(np.isfinite(batch.objective)), (
                f"{name} R={R}: non-finite batched metrics"
            )
            agg_steady = batch.aggregate_iters_per_second
            agg_e2e = R * T / wall
            rows[str(R)] = {
                "aggregate_steady_ips": round(agg_steady, 1),
                "aggregate_e2e_ips": round(agg_e2e, 1),
                "compile_s": round(batch.compile_seconds, 2),
                "run_s": round(batch.run_seconds, 2),
                "speedup_steady": round(
                    agg_steady / single["steady_ips"], 2
                ),
                "speedup_e2e": round(agg_e2e / single["e2e_ips"], 2),
                # Honest crossover flag: a row where the batch delivers
                # LESS aggregate throughput than sequential runs would.
                "batching_loses": agg_steady < single["steady_ips"],
            }
            print(
                f"[sweep] {name} R={R}: agg {agg_steady:.0f} steady / "
                f"{agg_e2e:.0f} e2e ips "
                f"({rows[str(R)]['speedup_steady']}x / "
                f"{rows[str(R)]['speedup_e2e']}x)",
                file=sys.stderr,
            )
        cells[name] = {"single_run": single, "batched": rows}

    # --- hyperparameter axis demo: eta0 sweep through the same program --
    demo_cfg, _ = cells_cfg["flagship_n25"]
    demo_cfg = demo_cfg.replace(n_iterations=1000, eval_every=250)
    etas = [0.01, 0.02, 0.05, 0.08, 0.1, 0.15, 0.2, 0.3]
    demo_ds = generate_synthetic_dataset(demo_cfg)
    demo = jax_backend.run_batch(
        demo_cfg, demo_ds, 0.0, seeds=[demo_cfg.seed] * len(etas),
        sweep={"learning_rate_eta0": etas},
    )
    assert np.all(np.isfinite(demo.objective)), "eta-sweep NaNs"
    eta_demo = {
        "learning_rate_eta0": etas,
        "aggregate_steady_ips": round(demo.aggregate_iters_per_second, 1),
        "final_objective_per_replica": [
            round(float(v), 5) for v in demo.objective[:, -1]
        ],
    }
    print(
        f"[sweep] eta0 sweep x{len(etas)}: "
        f"{eta_demo['aggregate_steady_ips']:.0f} aggregate ips",
        file=sys.stderr,
    )

    # --- asserted floor (bench.py convention, incl. the escape hatch) ---
    head = cells["flagship_n25"]["batched"]["32"]
    best_32 = max(head["speedup_steady"], head["speedup_e2e"])
    on_accelerator = platform != "cpu"
    floor = FLOOR_ACCELERATOR if on_accelerator else FLOOR_CPU_STEADY
    skip = os.environ.get("BENCH_NO_RANGE_CHECK", "").lower() not in (
        "", "0", "false"
    )
    if skip:
        print(
            "[sweep] BENCH_NO_RANGE_CHECK set: skipping the speedup-floor "
            "gate (non-canonical hardware mode)",
            file=sys.stderr,
        )
    else:
        assert best_32 >= floor, (
            f"flagship R=32 aggregate speedup {best_32}x is below the "
            f"{'accelerator' if on_accelerator else 'cpu'} floor "
            f"({floor}x) — the replica axis is not paying for itself; "
            "investigate before publishing (docs/PERF.md sweep section)"
        )

    payload = {
        "device": str(dev),
        "platform": platform,
        "protocol": (
            "aggregate sweep throughput of run_batch (one vmapped "
            "compiled program, [R, N, d] state) vs the sequential "
            "single-run baseline, per R; steady = compile excluded, "
            f"e2e = compile included (each sequential run() re-traces "
            f"and re-compiles — bench.py's documented behavior); "
            f"sequential baseline = median of {args.seq_cycles} runs; "
            "metrics on (gap + consensus per eval cadence)"
        ),
        "note": (
            "The asserted floor is regime-dependent and recorded in "
            "'floors': >= 8x at R=32 on accelerator platforms (the "
            "latency/dispatch-bound regime the tentpole targets — the "
            "chip idles its vector lanes at the [256, 81] hot-loop "
            "shape), >= 2.5x steady on CPU hosts, where this "
            "container's single core makes every config compute-bound "
            "and SIMD lane-filling is the only headroom (measured "
            "3.5-4.6x at R=32; the northstar_n256 cell shows the "
            "heavier-compute direction at ~1.9-3.8x). batching_loses "
            "flags any row where the batch underperforms sequential."
        ),
        "floors": {
            "accelerator_speedup_at_r32": FLOOR_ACCELERATOR,
            "cpu_steady_speedup_at_r32": FLOOR_CPU_STEADY,
            "applied": None if skip else floor,
            "measured_best_speedup_at_r32": best_32,
        },
        "cells": cells,
        "eta_sweep_demo": eta_demo,
    }
    path = Path(args.out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload, indent=2) + "\n")
    from distributed_optimization_tpu.telemetry import write_bench_manifest

    write_bench_manifest(path)

    print(json.dumps({
        "metric": "replica_batch_speedup_flagship_r32",
        "value": best_32,
    }))


if __name__ == "__main__":
    main()
