"""Compute-bound regime demonstration (round 5, VERDICT r4 item 1).

Every number the repo measured through round 4 lives in the latency-bound
d<=1024 scalar-GLM regime — ~1-7 MFLOP per iteration against a chip that
does ~10^5x that per millisecond, MFU <= 0.5%, MXU idle (docs/PERF.md §3).
This bench runs the tier TPUs are built for: the SOFTMAX family
(models/softmax.py), whose per-worker gradient is two real matmuls
(forward [b,d]x[d,K], backward [d,b]x[b,K]) — 4·N·b·d·K FLOPs per
iteration through the same D-SGD ring pipeline as the headline.

Reported per cell: steady-state iters/sec (fused scan, metrics off, AOT
compile excluded), achieved TFLOP/s from the analytic FLOP count, MFU
against the chip's bf16 peak, and the minimum HBM traffic (X re-read + 3x
weight traffic per iteration) as achieved GB/s. Cells interleave across
cycles so no cell owns one stretch of the session; the aggregate is the
MEDIAN of the cycles, with the raw readings recorded. dtype/precision cells
re-judge the round-3 "bf16 no win" verdict — a latency-bound statement —
where FLOPs dominate.

FLOP accounting is the dominant matmul pair only (4NbdK); softmax/one-hot/
mixing/sampling are O(N·b·K + N·d·K) lower-order terms left out of the
numerator, so MFU is slightly UNDERstated — the conservative direction.

Peaks come from ``runtime.DEVICE_PEAKS``, keyed by the ``device_kind`` JAX
reports (an unknown device is an error, and without a TPU the script exits
before measuring); f32 'highest' runs 6 bf16 passes per matmul (its
effective ceiling is peak/6 — reported MFU stays relative to the bf16 peak
so cells share one denominator).

Data is ``utils.data.random_softmax_dataset`` (random standardized X,
uniform labels) rather than sklearn's. Correctness/convergence of the
family is pinned at small shapes in tests/test_softmax.py.

Writes ``docs/perf/compute_bound.json``.

Usage:  python examples/bench_compute_bound.py [--out PATH] [--cycles 3]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cycles", type=int, default=3)
    ap.add_argument("--iters", type=int, default=2000)
    ap.add_argument("--out", default="docs/perf/compute_bound.json")
    args = ap.parse_args()

    import jax

    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.runtime import device_peaks, require_tpu
    from distributed_optimization_tpu.utils.data import random_softmax_dataset

    device = require_tpu("bench_compute_bound.py")
    peaks = device_peaks(device["kind"])
    PEAK_TFLOPS, PEAK_GBPS = peaks["bf16_tflops"], peaks["hbm_gbps"]
    dev = jax.devices()[0]
    print(f"[compute_bound] device={dev} ({device['kind']}) "
          f"peak={PEAK_TFLOPS}TF/s {PEAK_GBPS}GB/s", file=sys.stderr)

    # Published-floor pre-flight (round 6 — VERDICT r5 item 2: the 33-36%
    # MFU number had no protecting assert). The floor lives in the
    # COMMITTED artifact this bench regenerates, exactly like bench.py's
    # published_range_ips: read it before any chip work, enforce it after
    # measuring, and write it back into the new payload so the gate
    # survives regeneration. Loosening it is a committed, deliberate act.
    prev_path = Path(args.out)
    skip_gate = os.environ.get("BENCH_NO_RANGE_CHECK", "").lower() not in (
        "", "0", "false"
    )
    mfu_floor = None
    if prev_path.exists():
        prev = json.loads(prev_path.read_text())
        mfu_floor = prev.get("published_mfu_floor")
        if mfu_floor is None and not skip_gate:
            raise SystemExit(
                f"{prev_path} exists but carries no published_mfu_floor — "
                "the compute-bound tier must stay gated; add the floor "
                "(best bf16 cell's median MFU with honest margin) before "
                "regenerating, or set BENCH_NO_RANGE_CHECK=1 on "
                "non-canonical hardware"
            )
    if mfu_floor is None:
        # Bootstrap (fresh --out path or escape hatch): the regenerated
        # artifact will carry published_mfu_floor: null, i.e. an UNGATED
        # tier — say so loudly rather than disarming the gate silently.
        print(
            "[compute_bound] WARNING: no published_mfu_floor available — "
            "this run is ungated and the written artifact will carry "
            "published_mfu_floor: null; set a floor in the committed "
            "artifact to restore the regression gate",
            file=sys.stderr,
        )

    N, K, b = 8, 512, 2048
    T = args.iters
    # (label, d_feat, dtype, matmul_precision). 'highest' is the framework
    # default (parity-sensitive math: 6-pass bf16 ~ f32 accuracy); 'default'
    # is the 1-pass bf16-data-path XLA uses when precision is not forced.
    cells = [
        ("d4096_f32_highest", 4096, "float32", "highest"),
        ("d4096_f32_default", 4096, "float32", "default"),
        ("d4096_bf16", 4096, "bfloat16", "default"),
        ("d8192_f32_default", 8192, "float32", "default"),
        ("d8192_bf16", 8192, "bfloat16", "default"),
    ]

    runs: dict[str, list] = {label: [] for label, *_ in cells}
    setups = {}
    for label, d_feat, dtype, prec in cells:
        cfg = ExperimentConfig(
            problem_type="softmax", n_classes=K, algorithm="dsgd",
            topology="ring", n_workers=N, local_batch_size=b,
            n_samples=N * b, n_features=d_feat,
            n_informative_features=64, n_iterations=T, eval_every=T,
            dtype=dtype, matmul_precision=prec, record_consensus=False,
            # Pin the stencil (what auto resolves to on a ring): the cells
            # measure the gradient matmuls, and pinning keeps the mixing
            # term identical across cells by construction.
            mixing_impl="stencil",
            # At ~1 ms/iter the unroll's dispatch savings are irrelevant and
            # unrolled bodies multiply live [N, b, d] buffers; keep the scan
            # rolled so peak memory stays ~2 batches.
            scan_unroll=1,
        )
        ds = random_softmax_dataset(N, b, d_feat, K)
        setups[label] = (cfg, ds, d_feat)

    for c in range(args.cycles):
        for label, (cfg, ds, d_feat) in setups.items():
            r = jax_backend.run(cfg, ds, 0.0, collect_metrics=False,
                                measure_compile=(c == 0))
            ips = float(r.history.iters_per_second)
            runs[label].append(ips)
            print(f"[compute_bound] cycle {c + 1}/{args.cycles} {label:20s} "
                  f"{ips:8.1f} iters/sec "
                  f"(compile {r.history.compile_seconds:.1f}s)",
                  file=sys.stderr)

    import statistics

    results = {}
    for label, (cfg, ds, d_feat) in setups.items():
        d = d_feat + 1  # bias column
        flops_per_iter = 4.0 * N * b * d * K
        ips = statistics.median(runs[label])
        bytes_el = 2 if cfg.dtype == "bfloat16" else 4
        # Minimum HBM traffic: X re-read twice (fwd+bwd) + W read twice /
        # written once per worker per iteration. Logits/softmax intermediates
        # assumed fused (XLA does); this is a LOWER bound on real traffic.
        # Checked against the compiled scan, which carries the models as
        # [N, d, K] (PERF.md §5, PR 25, v5e): the logits matmul reads X and
        # W, the weight-gradient matmul reads X again and writes the models
        # once with the update fused in, and the ring stencil is an op of its
        # own that reads W and writes two shifted partial sums the gradient
        # fusion reads back. The real count is 2 reads of X, about 4 reads
        # and 3 writes of W: the bound holds.
        bytes_per_iter = (2 * N * b * d + 3 * N * d * K) * bytes_el
        achieved_tf = flops_per_iter * ips / 1e12
        results[label] = {
            "d_model": d * K,
            "dtype": cfg.dtype,
            "matmul_precision": cfg.matmul_precision,
            "iters_per_sec_median": round(ips, 1),
            "iters_per_sec_cycles_raw": [round(x, 1) for x in runs[label]],
            "gflops_per_iter": round(flops_per_iter / 1e9, 2),
            "achieved_tflops": round(achieved_tf, 1),
            "mfu_vs_bf16_peak": round(achieved_tf / PEAK_TFLOPS, 3),
            "min_hbm_gbps": round(bytes_per_iter * ips / 1e9, 1),
            "hbm_util_lower_bound": round(
                bytes_per_iter * ips / 1e9 / PEAK_GBPS, 3
            ),
        }
        row = results[label]
        print(f"[compute_bound] {label:20s} {row['achieved_tflops']:6.1f} "
              f"TF/s  MFU {row['mfu_vs_bf16_peak'] * 100:5.1f}%  HBM>= "
              f"{row['min_hbm_gbps']:5.0f} GB/s "
              f"({row['hbm_util_lower_bound'] * 100:.0f}%)", file=sys.stderr)

    # --- published-floor gate (the compute tier's bench-regression gate;
    # BENCH_NO_RANGE_CHECK = bench.py's non-canonical-hardware escape:
    # on another chip generation or a CPU container an out-of-floor MFU
    # means "different machine", not a regression) ---
    best_mfu = max(r["mfu_vs_bf16_peak"] for r in results.values())
    if skip_gate:
        print(
            "[compute_bound] BENCH_NO_RANGE_CHECK set: skipping the "
            "published MFU-floor gate (non-canonical hardware mode)",
            file=sys.stderr,
        )
    elif mfu_floor is not None and best_mfu < mfu_floor:
        raise SystemExit(
            f"best-cell MFU {best_mfu:.3f} is below the published floor "
            f"{mfu_floor} ({prev_path.name}) — the compute-bound tier "
            "regressed (or this is non-canonical hardware: set "
            "BENCH_NO_RANGE_CHECK=1). Re-derive the floor in a commit if "
            "the regression is real and explained."
        )
    elif mfu_floor is not None:
        print(
            f"[compute_bound] MFU gate OK: best cell {best_mfu:.3f} >= "
            f"published floor {mfu_floor}",
            file=sys.stderr,
        )

    payload = {
        "device": str(dev),
        "published_mfu_floor": mfu_floor,
        "peak_tflops_bf16": PEAK_TFLOPS,
        "peak_hbm_gbps": PEAK_GBPS,
        "workload": (
            f"softmax D-SGD ring N={N}, K={K}, b={b} (full local batch), "
            f"T={T}, fused scan, metrics off; FLOPs/iter = 4NbdK (dominant "
            "matmuls only, lower-order terms excluded => MFU conservative); "
            f"median of {args.cycles} interleaved cycles (raw cycles "
            "recorded)"
        ),
        "cells": results,
    }
    path = Path(args.out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload, indent=2) + "\n")
    from distributed_optimization_tpu.telemetry import write_bench_manifest

    write_bench_manifest(path)

    print(json.dumps({
        "metric": "compute_bound_median_mfu_best_cell",
        "value": max(r["mfu_vs_bf16_peak"] for r in results.values()),
    }))


if __name__ == "__main__":
    main()
