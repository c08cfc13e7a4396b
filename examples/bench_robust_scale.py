"""Degree-bounded robust aggregation at scale (the PR-3 tentpole evidence).

The robust rules' dense form materializes the [N, N, d] closed-
neighborhood tensor and sorts it over the full node axis every iteration
— O(N²·d·log N) work on a ring whose closed degree is 3. The gather form
(``robust_impl='gather'``) precomputes the static [N, k_max] neighbor
table and screens over the k_max axis — O(N·k_max·d·log k_max), an
~N/k_max-fold work reduction. This script measures the end-to-end
throughput of BOTH forms through real backend runs:

1. **headline**: N=256 ring (k_max=2), all three rules, pure-defense
   configuration (the screened aggregate is the hot path; no adversary
   needed for throughput) — ASSERTED: gather ≥ 5× dense for trimmed_mean
   and median (the ISSUE-3 acceptance floor; the measured ratios are
   ~50-80×);
2. **crossover**: N=64 at k_max ∈ {2 (ring), 4 (grid), ~40 (ER p=0.5),
   63 (fully connected)} — locates where gather stops paying, which is
   what ``resolved_robust_impl``'s 'auto' rule is derived from. Honest
   reporting: if gather loses (ratio < 1) anywhere, the cell says so and
   the auto gate must route around it — ASSERTED: for every measured
   cell, 'auto' does not pick a form that measured ≥ 25% slower than the
   alternative.

Protocol: variants interleave per cycle (shared-machine convention),
median across cycles, compile excluded. Writes
``docs/perf/robust_scale.json``.

Usage:  python examples/bench_robust_scale.py [--out PATH] [--cycles 2]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cycles", type=int, default=2)
    ap.add_argument("--out", default="docs/perf/robust_scale.json")
    args = ap.parse_args()

    import jax

    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.parallel import build_topology
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )

    dev = jax.devices()[0]
    print(f"[robust_scale] device={dev}", file=sys.stderr)
    D_FEAT = 40  # model dimension (acceptance asks d >= 20)

    def cfg_for(topology, n, T, p=0.4, aggregation="trimmed_mean", **kw):
        return ExperimentConfig(
            problem_type="logistic", algorithm="dsgd", topology=topology,
            n_workers=n, n_samples=n * 50, n_features=D_FEAT,
            n_informative_features=20, n_iterations=T, local_batch_size=16,
            eval_every=T // 2, partition="shuffled", erdos_renyi_p=p,
            aggregation=aggregation, robust_b=1, **kw,
        )

    def ips(cfg, ds):
        r = jax_backend.run(cfg, ds, 0.0, measure_compile=False)
        return float(r.history.iters_per_second)

    # --- 1. headline: N=256 ring, all three rules, dense vs gather -------
    N, T = 256, 150
    base = cfg_for("ring", N, T)
    ds = generate_synthetic_dataset(base)
    headline = {
        rule: {"dense_ips": [], "gather_ips": []}
        for rule in ("trimmed_mean", "median", "clipped_gossip")
    }
    for c in range(args.cycles):
        for rule, row in headline.items():
            for impl in ("gather", "dense"):
                row[f"{impl}_ips"].append(
                    ips(base.replace(aggregation=rule, robust_impl=impl), ds)
                )
            print(
                f"[robust_scale] cycle {c + 1} {rule}: gather "
                f"{row['gather_ips'][-1]:.0f} dense {row['dense_ips'][-1]:.1f}",
                file=sys.stderr,
            )
    for rule, row in headline.items():
        for impl in ("dense", "gather"):
            raw = row[f"{impl}_ips"]
            row[f"{impl}_ips_raw"] = [round(v, 1) for v in raw]
            row[f"{impl}_ips"] = round(statistics.median(raw), 1)
        row["gather_over_dense"] = round(
            row["gather_ips"] / row["dense_ips"], 2
        )

    # --- 2. crossover: N=64 across k_max, trimmed mean ------------------
    N2, T2 = 64, 200
    cross = {}
    cells = [("ring", 0.4), ("grid", 0.4), ("erdos_renyi", 0.5),
             ("fully_connected", 0.4)]
    setups = {}
    for topo_name, p in cells:
        cfg = cfg_for(topo_name, N2, T2, p=p, aggregation="trimmed_mean")
        topo = build_topology(
            topo_name, N2, erdos_renyi_p=p, seed=cfg.seed
        )
        k_max = int(topo.degrees.max())
        setups[topo_name] = (cfg, generate_synthetic_dataset(cfg), k_max)
        cross[topo_name] = {
            "k_max": k_max,
            "auto_resolves_to": cfg.resolved_robust_impl(k_max),
            "dense_ips": [], "gather_ips": [],
        }
    for c in range(args.cycles):
        for topo_name, (cfg, ds2, _) in setups.items():
            row = cross[topo_name]
            for impl in ("gather", "dense"):
                row[f"{impl}_ips"].append(
                    ips(cfg.replace(robust_impl=impl), ds2)
                )
            print(
                f"[robust_scale] cycle {c + 1} {topo_name} "
                f"(k_max={row['k_max']}): gather {row['gather_ips'][-1]:.0f} "
                f"dense {row['dense_ips'][-1]:.0f}",
                file=sys.stderr,
            )
    for topo_name, row in cross.items():
        for impl in ("dense", "gather"):
            raw = row[f"{impl}_ips"]
            row[f"{impl}_ips_raw"] = [round(v, 1) for v in raw]
            row[f"{impl}_ips"] = round(statistics.median(raw), 1)
        row["gather_over_dense"] = round(
            row["gather_ips"] / row["dense_ips"], 2
        )
        row["gather_loses"] = row["gather_over_dense"] < 1.0

    # --- acceptance gates ------------------------------------------------
    # The ISSUE-3 floor: gather >= 5x dense for trimmed_mean and median at
    # N=256 ring (d = 40 >= 20).
    for rule in ("trimmed_mean", "median"):
        ratio = headline[rule]["gather_over_dense"]
        assert ratio >= 5.0, (
            f"{rule}: gather must be >= 5x dense at N=256 ring, got {ratio}x"
        )
    # Routing honesty: wherever a form measured >= 25% slower, 'auto' must
    # not have picked it (a tie within 25% may route either way).
    for topo_name, row in cross.items():
        ratio = row["gather_over_dense"]
        if ratio >= 1.25:
            assert row["auto_resolves_to"] == "gather", (
                f"{topo_name}: gather wins {ratio}x but auto routes dense"
            )
        elif ratio <= 0.8:
            assert row["auto_resolves_to"] == "dense", (
                f"{topo_name}: gather loses ({ratio}x) but auto routes to it"
            )

    payload = {
        "device": str(dev),
        "protocol": (
            f"e2e jax-backend throughput, pure-defense robust runs "
            f"(aggregation rule active, robust_b=1, no adversary), "
            f"logistic d={D_FEAT}, b=16; median of {args.cycles} "
            "interleaved cycles, compile excluded. Headline: N=256 ring "
            f"T={T}. Crossover: N=64 T={T2} across k_max, trimmed mean."
        ),
        "note": (
            "gather_over_dense is the tentpole criterion: the gather form "
            "replaces the dense [N,N,d] closed-neighborhood sort "
            "(O(N^2 d log N)) with a static-neighbor-table screen "
            "(O(N k_max d log k_max)). Asserted floor: >= 5x for "
            "trimmed_mean and median at N=256 ring. Honest crossover "
            "reporting: gather_loses flags any cell where dense measured "
            "faster; the only non-winning cell is fully_connected "
            "(k_max = N-1), a tie within noise — resolved_robust_impl's "
            "auto rule (gather iff k_max+1 < N) routes dense there and "
            "gather everywhere it measured a win."
        ),
        "headline_n256_ring": headline,
        "crossover_n64": cross,
    }
    path = Path(args.out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload, indent=2) + "\n")
    from distributed_optimization_tpu.telemetry import write_bench_manifest

    write_bench_manifest(path)

    print(json.dumps({
        "metric": "robust_gather_speedup_n256_ring_trimmed_mean",
        "value": headline["trimmed_mean"]["gather_over_dense"],
    }))


if __name__ == "__main__":
    main()
