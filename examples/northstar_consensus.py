"""North-star demonstration: a 256-worker decentralized run that actually
reaches 1e-4 consensus, with MEASURED wall-clock (VERDICT r1 item 2).

``BASELINE.json`` defines the metric as "iters/sec to 1e-4 consensus;
wall-clock to target loss" (consensus definition: reference
``trainer.py:184-186``, (1/N) Σ_i ||x_i - x̄||²). Round 1 benchmarked
throughput at T=10k on the N=256 ring, where the spectral gap (2.0e-4)
makes 1e-4 consensus unreachable on any affordable horizon — under the
η₀/√(t+1) schedule consensus decays ~1/t once gossip equilibrates, putting
the ring's crossing at ~3e7 iterations (measured + extrapolated in the
artifact). This script demonstrates the metric literally on the N=256
**16x16 toroidal grid** (spectral gap 0.030, same worker count, same
objective/data/schedule), which crosses 1e-4 within a few thousand
iterations, and records the ring's measured trajectory plus its 1/t
extrapolation for honesty.

Runs use ``measure_timestamps=True`` — every eval boundary carries a real
``perf_counter`` sample (one host sync per ``eval_every`` iterations), so
"seconds to consensus 1e-4" and "seconds to gap<=0.08" are measured, not
interpolated.

Artifact: ``docs/perf/northstar_consensus.json`` (+ summary in
``docs/PERF.md``). Run on the real TPU chip: ``python
examples/northstar_consensus.py``.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

CONSENSUS_TARGET = 1e-4
GAP_TARGET = 0.08  # the reference study's suboptimality threshold (PDF §III-A)


def first_crossing(values: np.ndarray, threshold: float) -> int:
    """First index with values[i] <= threshold, or -1."""
    hit = np.nonzero(values <= threshold)[0]
    return int(hit[0]) if hit.size else -1


def run_one(topology: str, n_iterations: int, eval_every: int) -> dict:
    cfg = ExperimentConfig(
        problem_type="logistic",
        algorithm="dsgd",
        topology=topology,
        n_workers=256,
        n_iterations=n_iterations,
        eval_every=eval_every,
    )
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    res = jax_backend.run(cfg, ds, f_opt, measure_timestamps=True)
    h = res.history
    assert h.time_measured, "demonstration requires measured timestamps"
    cons = h.consensus_error
    gaps = h.objective
    iters = h.eval_iterations

    entry = {
        "topology": topology,
        "n_workers": 256,
        "n_iterations": n_iterations,
        "eval_every": eval_every,
        "spectral_gap": h.spectral_gap,
        "iters_per_second": round(float(h.iters_per_second), 1),
        "compile_seconds": round(float(h.compile_seconds), 2),
        "time_measured": True,
        "final_gap": float(gaps[-1]),
        "final_consensus": float(cons[-1]),
    }
    ci = first_crossing(cons, CONSENSUS_TARGET)
    gi = first_crossing(gaps, GAP_TARGET)
    entry["consensus_1e4"] = (
        {
            "iteration": int(iters[ci]),
            "seconds_measured": round(float(h.time[ci]), 3),
        }
        if ci >= 0
        else None
    )
    entry["gap_008"] = (
        {
            "iteration": int(iters[gi]),
            "seconds_measured": round(float(h.time[gi]), 3),
        }
        if gi >= 0
        else None
    )
    if ci < 0:
        # Consensus under the sqrt-decay schedule behaves ~ C/t once mixing
        # equilibrates; extrapolate the crossing from the last sample.
        t_last, c_last = float(iters[-1]), float(cons[-1])
        entry["consensus_1e4_extrapolated_iteration"] = int(
            t_last * c_last / CONSENSUS_TARGET
        )
    return entry


def main() -> None:
    ring_full = "--ring-full" in sys.argv
    t0 = time.perf_counter()
    results = {
        "metric": "iters/sec to 1e-4 consensus; wall-clock to target loss",
        "consensus_definition": "(1/N) sum_i ||x_i - xbar||^2  (reference trainer.py:184-186)",
        "device": str(jax_backend.jax.devices()[0]),
        "runs": [],
    }

    # The demonstration: N=256 grid crosses 1e-4 consensus AND the 0.08
    # suboptimality threshold inside T=100k. The measured-timestamps path
    # pays one host round-trip per eval chunk, so the cadence is 500 (200
    # chunks): crossing resolution of 500
    # iterations with a real timestamp at each eval.
    grid = run_one("grid", n_iterations=100_000, eval_every=500)
    results["runs"].append(grid)
    print(f"[northstar] grid: {json.dumps(grid)}", file=sys.stderr, flush=True)

    # The headline ring at a 1M horizon: shows the measured trajectory and
    # the 1/t extrapolation to the 1e-4 crossing (~3e7 iterations).
    ring = run_one("ring", n_iterations=1_000_000, eval_every=5000)
    results["runs"].append(ring)
    print(f"[northstar] ring: {json.dumps(ring)}", file=sys.stderr, flush=True)

    if ring_full:
        # --ring-full: run the ring all the way THROUGH the 1e-4 crossing
        # (~3e7 iterations — affordable since the dense-sampling path landed;
        # ~5-10 min on the real chip depending on co-tenant load). Removes
        # the extrapolation caveat on the headline topology itself.
        ring_x = run_one("ring", n_iterations=40_000_000, eval_every=100_000)
        results["runs"].append(ring_x)
        print(f"[northstar] ring-full: {json.dumps(ring_x)}",
              file=sys.stderr, flush=True)

    results["total_wall_seconds"] = round(time.perf_counter() - t0, 1)

    out = pathlib.Path(__file__).resolve().parents[1] / "docs" / "perf"
    out.mkdir(parents=True, exist_ok=True)
    path = out / "northstar_consensus.json"
    path.write_text(json.dumps(results, indent=2) + "\n")
    print(f"[northstar] wrote {path}", file=sys.stderr)

    ok = grid["consensus_1e4"] is not None and grid["gap_008"] is not None
    print(
        json.dumps(
            {
                "demonstrated": ok,
                "grid_consensus_1e4": grid["consensus_1e4"],
                "grid_gap_008": grid["gap_008"],
            }
        )
    )
    if not ok:
        raise SystemExit("grid run failed to demonstrate the north-star metric")


if __name__ == "__main__":
    main()
