"""Worker-count scaling study on the real chip (VERDICT r1 item 8).

How does the framework scale in N — the honest scaling axis for this problem
family (SURVEY.md §5.7: the worker graph is the structural analog of sequence
parallelism)? Sweeps N ∈ {25, 64, 256, 1024, 4096} on the headline config (D-SGD,
ring, logistic, T=10k, parity eval cadence k=1) and records

- **iters/sec** (fused scan, best-of-2 per N, interleaved so no N owns one
  stretch of the session),
- **consensus decay** over the horizon (first→last consensus error and the
  topology's spectral gap, which sets the rate), and
- the CPU reference-semantics simulator's iters/sec at the same N (the
  baseline the ≥50x north star is measured against), for N ≤ 256 (the numpy
  loop at N ≥ 1024 would take minutes for no additional insight; it scales
  ~1/N).

Artifacts: ``docs/perf/scaling.json`` + ``docs/figures/scaling.png`` + a
table in ``docs/PERF.md``. Usage: ``python examples/bench_scaling.py``.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_optimization_tpu.backends import jax_backend, numpy_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

NS = (25, 64, 256, 1024, 4096)
T = 10_000
CYCLES = 2


def main() -> None:
    root = pathlib.Path(__file__).resolve().parents[1]
    setups = {}
    for n in NS:
        cfg = ExperimentConfig(
            problem_type="logistic", algorithm="dsgd", topology="ring",
            n_workers=n, n_iterations=T,
        )
        ds = generate_synthetic_dataset(cfg)
        _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
        setups[n] = (cfg, ds, f_opt)

    rows = {n: {"iters_per_sec": 0.0} for n in NS}
    # Interleave cycles so chip-load swings hit every N comparably.
    for _ in range(CYCLES):
        for n, (cfg, ds, f_opt) in setups.items():
            res = jax_backend.run(cfg, ds, f_opt)
            h = res.history
            r = rows[n]
            r["iters_per_sec"] = max(
                r["iters_per_sec"], float(h.iters_per_second)
            )
            r["spectral_gap"] = h.spectral_gap
            r["final_gap"] = float(h.objective[-1])
            r["consensus_first"] = float(h.consensus_error[0])
            r["consensus_last"] = float(h.consensus_error[-1])

    # CPU reference-semantics baseline (200 iters is enough for steady rate).
    for n in NS:
        if n <= 256:
            cfg, ds, f_opt = setups[n]
            base = numpy_backend.run(
                cfg.replace(n_iterations=200), ds, f_opt
            )
            rows[n]["numpy_iters_per_sec"] = round(
                float(base.history.iters_per_second), 1
            )
            rows[n]["speedup_vs_numpy"] = round(
                rows[n]["iters_per_sec"] / base.history.iters_per_second, 1
            )

    for n in NS:
        rows[n]["iters_per_sec"] = round(rows[n]["iters_per_sec"], 1)
        print(f"[scaling] N={n}: {rows[n]}", file=sys.stderr, flush=True)

    out = {
        "config": f"dsgd ring logistic T={T} eval_every=1 (parity cadence)",
        "device": str(jax_backend.jax.devices()[0]),
        "rows": {str(n): rows[n] for n in NS},
    }
    perf_dir = root / "docs" / "perf"
    perf_dir.mkdir(parents=True, exist_ok=True)
    (perf_dir / "scaling.json").write_text(json.dumps(out, indent=2) + "\n")
    from distributed_optimization_tpu.telemetry import write_bench_manifest

    write_bench_manifest(perf_dir / "scaling.json")


    # Figure: iters/sec vs N and consensus decay vs N, same visual language
    # as the repo's report figures (log-scale, matplotlib defaults).
    import matplotlib

    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    fig, (ax1, ax2) = plt.subplots(1, 2, figsize=(11, 4))
    ns = list(NS)
    ax1.plot(ns, [rows[n]["iters_per_sec"] for n in ns], "o-",
             label="TPU jax backend")
    cpu_ns = [n for n in ns if "numpy_iters_per_sec" in rows[n]]
    ax1.plot(cpu_ns, [rows[n]["numpy_iters_per_sec"] for n in cpu_ns], "s--",
             label="CPU reference-semantics")
    ax1.set_xscale("log", base=2)
    ax1.set_yscale("log")
    ax1.set_xlabel("workers N")
    ax1.set_ylabel("iterations / second")
    ax1.set_title("Throughput vs worker count (T=10k, ring)")
    ax1.grid(True, which="both", alpha=0.3)
    ax1.legend()

    ax2.plot(ns, [rows[n]["consensus_last"] for n in ns], "o-",
             label="consensus error @ T=10k")
    ax2.plot(ns, [rows[n]["spectral_gap"] for n in ns], "s--",
             label="ring spectral gap 1−ρ")
    ax2.set_xscale("log", base=2)
    ax2.set_yscale("log")
    ax2.set_xlabel("workers N")
    ax2.set_title("Consensus vs worker count")
    ax2.grid(True, which="both", alpha=0.3)
    ax2.legend()
    fig.tight_layout()
    fig_path = root / "docs" / "figures" / "scaling.png"
    fig_path.parent.mkdir(parents=True, exist_ok=True)
    fig.savefig(fig_path, dpi=130)
    print(json.dumps({"wrote": ["docs/perf/scaling.json",
                                "docs/figures/scaling.png"]}))


if __name__ == "__main__":
    main()
