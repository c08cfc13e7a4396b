"""Fused robust gather path + compressed gossip evidence (ISSUE-6).

Two measurements, one artifact (``docs/perf/fused_robust.json``):

1. **fused vs gather** — e2e throughput of the single-kernel pallas form
   (``robust_impl='fused'``: gather + screen + mix + SGD in one
   VMEM-resident pass, the [N, k_max, d] neighbor stack never
   materialized in HBM) against the multi-op gather path, per rule, on
   the N=256 ring headline shape of robust_scale.json. The fusion claim
   is a COMPILED-path claim, and its "compiled-path floor" (fused ≥ 1.1×
   gather for the count rules on accelerators) HAS NEVER BEEN EVALUATED:
   Mosaic refuses the kernel (``_gather_lowering_rule``: "Shape mismatch
   in input, indices and output" — on the v5e, PR 21, and in
   tests/test_tpu_lowering.py), so on a TPU this script stops at the
   compiler's error before it measures anything, and ``robust_impl``
   'auto' no longer selects the kernel. On CPU hosts pallas runs in
   INTERPRETER mode — not the claimed artifact — so cells carry honest
   per-cell ``fused_loses`` flags instead of a gate (same convention as robust_scale.json's
   crossover cells and sweep.json's CPU floor; as it happens the fused
   form measured a ~2.4× WIN here even interpreted — see the committed
   note). ``BENCH_NO_RANGE_CHECK`` escapes the accelerator gate for
   non-canonical hardware.

2. **bytes-vs-gap** — the compressed-gossip production currency:
   {none, top_k, qsgd} × {dsgd, gradient_tracking} error-feedback runs
   at MATCHED round counts, reporting floats moved per round next to the
   suboptimality-gap curve. ASSERTED: every compressed cell moves < 45%
   of the uncompressed bytes AND lands within the convergence envelope
   (final gap ≤ 3× the uncompressed final gap) — compression that met
   bandwidth targets by not converging would be a silent lie.

Protocol: variants interleave per cycle (shared-machine convention),
median across cycles, compile excluded.

Usage:  python examples/bench_fused_robust.py [--out PATH] [--cycles 2]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FLOOR_COMPILED = 1.1  # fused ≥ this × gather on accelerators (count rules)
BYTES_CEILING = 0.45  # compressed cells must move < this × full bytes
GAP_ENVELOPE = 3.0    # ... while landing ≤ this × the uncompressed gap


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cycles", type=int, default=2)
    ap.add_argument("--out", default="docs/perf/fused_robust.json")
    args = ap.parse_args()

    import jax

    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.telemetry import comms_summary
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )
    from distributed_optimization_tpu.utils.oracle import (
        compute_reference_optimum,
    )

    dev = jax.devices()[0]
    platform = dev.platform
    on_accelerator = platform != "cpu"
    print(f"[fused_robust] device={dev}", file=sys.stderr)

    # --- 1. fused vs gather: N=256 ring, the robust_scale headline ------
    D_FEAT = 40
    N, T = 256, 150

    def robust_cfg(rule, impl):
        return ExperimentConfig(
            problem_type="logistic", algorithm="dsgd", topology="ring",
            n_workers=N, n_samples=N * 50, n_features=D_FEAT,
            n_informative_features=20, n_iterations=T, local_batch_size=16,
            eval_every=T // 2, partition="shuffled", aggregation=rule,
            robust_b=1, robust_impl=impl,
        )

    ds_robust = generate_synthetic_dataset(robust_cfg("trimmed_mean", "auto"))

    def ips(cfg, ds):
        r = jax_backend.run(cfg, ds, 0.0, measure_compile=False,
                            use_mesh=False)
        return float(r.history.iters_per_second)

    rules = ("trimmed_mean", "median", "clipped_gossip")
    fused_vs_gather = {
        rule: {"fused_ips": [], "gather_ips": []} for rule in rules
    }
    for c in range(args.cycles):
        for rule, row in fused_vs_gather.items():
            for impl in ("fused", "gather"):
                row[f"{impl}_ips"].append(
                    ips(robust_cfg(rule, impl), ds_robust)
                )
            print(
                f"[fused_robust] cycle {c + 1} {rule}: fused "
                f"{row['fused_ips'][-1]:.0f} gather "
                f"{row['gather_ips'][-1]:.0f}",
                file=sys.stderr,
            )
    for rule, row in fused_vs_gather.items():
        for impl in ("fused", "gather"):
            raw = row[f"{impl}_ips"]
            row[f"{impl}_ips_raw"] = [round(v, 1) for v in raw]
            row[f"{impl}_ips"] = round(statistics.median(raw), 1)
        row["fused_over_gather"] = round(
            row["fused_ips"] / row["gather_ips"], 2
        )
        row["fused_loses"] = row["fused_over_gather"] < 1.0
        row["pallas_mode"] = "mosaic" if on_accelerator else "interpret"

    # --- 2. bytes vs gap at matched rounds ------------------------------
    T2 = 600
    base = ExperimentConfig(
        problem_type="quadratic", algorithm="dsgd", topology="ring",
        n_workers=16, n_samples=1600, n_features=40,
        n_informative_features=25, n_iterations=T2, local_batch_size=16,
        eval_every=T2 // 6, partition="shuffled",
    )
    ds2 = generate_synthetic_dataset(base)
    _, f_opt = compute_reference_optimum(ds2, base.reg_param)
    d_model = ds2.n_features

    def comp_cfg(algo, comp):
        kw = {}
        if comp == "top_k":
            # keep d/5 coordinates: 2k = 16 floats/edge vs d_model = 41.
            kw = dict(compression="top_k", compression_k=8,
                      choco_gamma=0.15)
        elif comp == "qsgd":
            # 4-bit stochastic quantization: d·5/32 + 1 floats/edge.
            kw = dict(compression="qsgd", compression_k=4,
                      choco_gamma=0.3)
        return base.replace(algorithm=algo, **kw)

    bytes_vs_gap: dict = {}
    for c in range(args.cycles):
        for algo in ("dsgd", "gradient_tracking"):
            for comp in ("none", "top_k", "qsgd"):
                cfg = comp_cfg(algo, comp)
                r = jax_backend.run(cfg, ds2, f_opt, measure_compile=False,
                                    use_mesh=False)
                cell = bytes_vs_gap.setdefault(f"{algo}/{comp}", {
                    "gap_curve": [round(float(v), 6)
                                  for v in r.history.objective],
                    "eval_iterations": [int(v)
                                        for v in r.history.eval_iterations],
                    "floats_total": float(
                        r.history.total_floats_transmitted
                    ),
                    "floats_per_iteration": comms_summary(cfg, r.history)[
                        "floats_per_iteration_mean"
                    ],
                    "ips": [],
                })
                cell["ips"].append(float(r.history.iters_per_second))
                print(
                    f"[fused_robust] cycle {c + 1} {algo}/{comp}: gap "
                    f"{cell['gap_curve'][-1]:.4f} floats/iter "
                    f"{cell['floats_per_iteration']:.0f}",
                    file=sys.stderr,
                )
    for key, cell in bytes_vs_gap.items():
        cell["ips_raw"] = [round(v, 1) for v in cell["ips"]]
        cell["ips"] = round(statistics.median(cell["ips"]), 1)
    for algo in ("dsgd", "gradient_tracking"):
        full = bytes_vs_gap[f"{algo}/none"]
        for comp in ("top_k", "qsgd"):
            cell = bytes_vs_gap[f"{algo}/{comp}"]
            cell["bytes_fraction_of_full"] = round(
                cell["floats_total"] / full["floats_total"], 4
            )
            cell["gap_over_uncompressed"] = round(
                cell["gap_curve"][-1] / full["gap_curve"][-1], 3
            )

    # --- gates -----------------------------------------------------------
    skip = os.environ.get("BENCH_NO_RANGE_CHECK", "").lower() not in (
        "", "0", "false"
    )
    gates = {
        "compiled_floor": FLOOR_COMPILED,
        "bytes_ceiling": BYTES_CEILING,
        "gap_envelope": GAP_ENVELOPE,
        "floor_applied": bool(on_accelerator and not skip),
    }
    if on_accelerator and not skip:
        for rule in ("trimmed_mean", "median"):
            ratio = fused_vs_gather[rule]["fused_over_gather"]
            assert ratio >= FLOOR_COMPILED, (
                f"{rule}: fused must be >= {FLOOR_COMPILED}x gather on the "
                f"compiled (Mosaic) path, got {ratio}x — the fusion is not "
                "paying for its kernel"
            )
    elif not on_accelerator:
        print(
            "[fused_robust] CPU host: pallas runs interpreted — recording "
            "honest fused_loses flags, compiled-path floor not applicable",
            file=sys.stderr,
        )
    # Bytes-vs-gap gates apply on every platform: convergence math does
    # not depend on the chip.
    for algo in ("dsgd", "gradient_tracking"):
        for comp in ("top_k", "qsgd"):
            cell = bytes_vs_gap[f"{algo}/{comp}"]
            assert cell["bytes_fraction_of_full"] < BYTES_CEILING, (
                f"{algo}/{comp} moved {cell['bytes_fraction_of_full']:.0%} "
                f"of the uncompressed bytes (ceiling {BYTES_CEILING:.0%})"
            )
            assert cell["gap_over_uncompressed"] <= GAP_ENVELOPE, (
                f"{algo}/{comp} final gap is "
                f"{cell['gap_over_uncompressed']}x the uncompressed gap at "
                f"matched rounds (envelope {GAP_ENVELOPE}x) — bandwidth "
                "bought with non-convergence"
            )

    payload = {
        "device": str(dev),
        "platform": platform,
        "protocol": (
            f"Part 1: e2e jax-backend throughput, pure-defense robust runs "
            f"(robust_b=1, no adversary), N={N} ring, logistic d={D_FEAT}, "
            f"T={T}, fused (single pallas kernel) vs gather (multi-op), "
            f"median of {args.cycles} interleaved cycles, compile "
            f"excluded. Part 2: error-feedback compressed gossip, "
            f"quadratic N=16 ring d={d_model}, T={T2}, matched rounds per "
            "cell; floats accounting from the run's own realized totals."
        ),
        "note": (
            "fused_over_gather is the ISSUE-6 kernel criterion; the "
            "asserted floor is a COMPILED-path (Mosaic/TPU) claim and is "
            "gated to accelerator platforms. On CPU hosts pallas runs in "
            "interpreter mode — each cell records pallas_mode=interpret "
            "and an honest per-cell fused_loses flag instead of a gate "
            "(robust_scale.json crossover convention). Measured on this "
            "CPU container the fused form WINS anyway (~2.4x for the "
            "count rules): the interpret path still executes as one XLA "
            "region, and the width-(k_max+1) transposition sort network + "
            "one-hot rank selection beat the general jnp.sort + "
            "take_along_axis sequence at ring degree — but that is a CPU "
            "observation, not the artifact's claim. bytes_vs_gap is "
            "platform-independent: bytes_fraction_of_full < "
            f"{BYTES_CEILING} and gap_over_uncompressed <= {GAP_ENVELOPE} "
            "are asserted everywhere."
        ),
        "gates": gates,
        "fused_vs_gather": fused_vs_gather,
        "bytes_vs_gap": bytes_vs_gap,
    }
    path = Path(args.out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload, indent=2) + "\n")
    from distributed_optimization_tpu.telemetry import write_bench_manifest

    write_bench_manifest(path, config=base)

    print(json.dumps({
        "metric": "compressed_dsgd_topk_bytes_fraction",
        "value": bytes_vs_gap["dsgd/top_k"]["bytes_fraction_of_full"],
    }))


if __name__ == "__main__":
    main()
