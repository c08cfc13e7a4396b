"""Headline benchmark: the BASELINE.json north-star configuration.

Runs on a TPU or not at all: without one it exits non-zero before doing any
work, so a CPU rate is never published under the headline's metric name. The
JSON line names the device and the mesh size the run used. There is no
published-range gate: ``PERF_LEDGER.jsonl`` is the record of what each
commit measured, and a faster chip is not an error. The convergence gates
below stay.

Protocol: five cycles, each pairing one numpy-simulator segment with one full
jax run at T=300,000; the reported value is the MEDIAN of the five jax
measurements over the MEDIAN of the five numpy measurements. T=300k amortizes
the fixed per-run cost (dispatch, host sync, result fetch) that dominates a
T=30k run. The eval cadence stays ``eval_every=1`` — the SAME per-iteration
full-dataset objective eval the reference performs (reference
``trainer.py:189``) and the numpy baseline pays, so the comparison stays
apples-to-apples. Pairing keeps each ratio's two samples adjacent in time.

Two measurements, one JSON line:

1. **Parity check** (stderr): the reference study's flagship decentralized
   config — logistic, N=25, ring, T=10,000, full-dataset suboptimality every
   iteration (reference ``main.py:6-21`` / PDF §III-A) — must converge to
   ε ≤ 0.08 in an iteration count consistent with the published Table I
   (9,927). Guards against benchmarking a broken optimizer.

2. **Headline** (stdout JSON): the north-star scale config named in
   BASELINE.json — 256-worker decentralized logistic regression on a ring —
   at T=300,000, a horizon the run crosses the study's ε ≤ 0.08 threshold
   well within (crossing ≈ iteration 22.5k). Gates: finite metrics, the
   ε-crossing itself, and bounded consensus.

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": ..., "unit": "iters/sec", "vs_baseline": ...,
   "device": {"platform": ..., "kind": ..., "count": ...}, "mesh_devices": ...}
"""

from __future__ import annotations

import json
import statistics
import sys
import time


def main() -> None:
    from distributed_optimization_tpu.runtime import (
        configure_compile_cache,
        require_tpu,
    )

    # Each run() call re-traces and re-compiles (the jit cache is keyed on
    # the per-call closures); the persistent cache lets every measured
    # cycle deserialize the warmup's executable instead of inserting a
    # multi-second compile between its paired numpy and jax samples.
    configure_compile_cache()
    device = require_tpu("bench.py")

    import numpy as np

    from distributed_optimization_tpu.backends import jax_backend, numpy_backend
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.metrics import iterations_to_threshold
    from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
    from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

    parity_cfg = ExperimentConfig(
        problem_type="logistic", algorithm="dsgd", topology="ring"
    )  # reference defaults: N=25, T=10000, b=16, eta0=0.05, lambda=1e-4
    cfg = parity_cfg.replace(n_workers=256, n_iterations=300_000)

    # --- 1. reference-parity convergence check (N=25, published config) ---
    t0 = time.perf_counter()
    parity_ds = generate_synthetic_dataset(parity_cfg)
    _, parity_f_opt = compute_reference_optimum(parity_ds, parity_cfg.reg_param)
    parity = jax_backend.run(parity_cfg, parity_ds, parity_f_opt)
    reached = iterations_to_threshold(
        parity.history.objective,
        parity_cfg.suboptimality_threshold,
        parity.history.eval_iterations,
    )
    print(
        f"[bench] parity N=25 ring logistic: {parity.history.iters_per_second:.0f} "
        f"iters/sec, iters-to-0.08 = {reached} (reference Table I: 9927), "
        f"final gap {parity.history.objective[-1]:.4f} "
        f"[{time.perf_counter() - t0:.0f}s]",
        file=sys.stderr,
    )
    if not (0 < reached <= parity_cfg.n_iterations):
        raise SystemExit(
            "parity config failed to reach the reference's suboptimality "
            "threshold — refusing to report throughput for a broken optimizer"
        )

    # --- 2. north-star scale config: N=256 decentralized logistic ---
    # T=300k amortizes fixed per-run overhead to <10% of wall-clock; the run
    # crosses the study's ε ≤ 0.08 within the horizon (≈ iter 22.5k).
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)

    # Interleaved median-of-5: numpy segment, then jax run, x5. The numpy
    # simulator is steady-state (same per-iteration work every iteration),
    # so a 400-iteration segment per cycle samples its rate honestly; the
    # jax run is the full T=300k workload. Throughput numbers exclude
    # compile. The warmup's metrics drive the convergence gates below.
    CYCLES = 5
    BASE_SEGMENT_ITERS = 400
    warm = jax_backend.run(cfg, ds, f_opt)
    hist = warm.history

    base_cfg = cfg.replace(n_iterations=BASE_SEGMENT_ITERS)
    numpy_ips: list[float] = []
    jax_ips: list[float] = []
    for cycle in range(CYCLES):
        b = numpy_backend.run(base_cfg, ds, f_opt)
        numpy_ips.append(float(b.history.iters_per_second))
        r = jax_backend.run(cfg, ds, f_opt, measure_compile=False)
        jax_ips.append(float(r.history.iters_per_second))
        print(
            f"[bench] cycle {cycle + 1}/{CYCLES}: numpy "
            f"{numpy_ips[-1]:.1f}, jax {jax_ips[-1]:.0f} iters/sec",
            file=sys.stderr,
        )

    jax_median = statistics.median(jax_ips)
    numpy_median = statistics.median(numpy_ips)
    print(
        f"[bench] N=256 T=300k jax: median {jax_median:.0f} iters/sec "
        f"(spread {min(jax_ips):.0f}-{max(jax_ips):.0f}); numpy "
        f"reference-semantics: median {numpy_median:.1f} "
        f"(spread {min(numpy_ips):.1f}-{max(numpy_ips):.1f}); compile "
        f"{hist.compile_seconds:.1f}s, final gap {hist.objective[-1]:.4f}, "
        f"consensus {hist.consensus_error[-1]:.2e}",
        file=sys.stderr,
    )

    if not np.all(np.isfinite(hist.objective)):
        raise SystemExit("north-star run produced non-finite metrics")
    # The run must cross the study's own suboptimality threshold within its
    # horizon — the headline is the throughput of a run that actually
    # converges to ε, not of a truncated transient.
    crossed = iterations_to_threshold(
        hist.objective, cfg.suboptimality_threshold, hist.eval_iterations
    )
    if not (0 < crossed <= cfg.n_iterations):
        raise SystemExit(
            f"north-star run never reached ε ≤ {cfg.suboptimality_threshold} "
            f"within T={cfg.n_iterations} (final gap {hist.objective[-1]:.4f})"
            " — refusing to report throughput"
        )
    print(
        f"[bench] north-star ε-crossing at iteration {crossed} "
        f"(threshold {cfg.suboptimality_threshold})",
        file=sys.stderr,
    )
    # Consensus must stay bounded (gossip contraction active). The N=256
    # ring's consensus is still in its slow ~1/t phase at this horizon
    # (spectral gap 2e-4); boundedness, not a small absolute value, is the
    # honest gate here (see docs/PERF.md §2 for the full consensus story).
    cons = hist.consensus_error
    if not (np.all(np.isfinite(cons)) and cons[-1] < 1.0):
        raise SystemExit(
            "north-star consensus error is unbounded — refusing to report "
            f"throughput (consensus {cons[0]:.3e} -> {cons[-1]:.3e})"
        )

    print(
        json.dumps(
            {
                "metric": _metric_name(cfg),
                "value": round(jax_median, 2),
                "unit": "iters/sec",
                "vs_baseline": round(jax_median / numpy_median, 2),
                "device": device,
                "mesh_devices": hist.mesh_devices,
            }
        )
    )


def _metric_name(cfg) -> str:
    # The Nk shorthand silently mislabels horizons that are not multiples of
    # 1000 (T=1500 would print as "T1k"); assert rather than round so a
    # protocol change to an off-k horizon forces an explicit rename here.
    if cfg.n_iterations % 1000 != 0:
        raise ValueError(
            f"metric name uses the T{{N}}k shorthand; horizon "
            f"{cfg.n_iterations} is not a multiple of 1000 — "
            "update _metric_name explicitly"
        )
    return (
        f"dsgd_ring_logistic_N{cfg.n_workers}_T{cfg.n_iterations // 1000}k"
        "_iters_per_sec_median5"
    )


if __name__ == "__main__":
    main()
