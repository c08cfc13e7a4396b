"""Mixing-implementation microbenchmark on real hardware (VERDICT r1 item 3).

Measures, for the north-star N=256 ring-logistic configuration (reference
``main.py:6-21`` scaled to 256 workers per BASELINE.json):

1. **Op-level**: K back-to-back applications of each compiled mixing operator
   (x -> W x on the ``[N, d]`` model stack) under one ``lax.scan`` — isolates
   the gossip primitive itself (reference ``trainer.py:173``'s ``W @ models``).
2. **End-to-end**: full ``jax_backend.run`` throughput (iters/sec) for each
   ``mixing_impl``, identical workload, best of ``--repeats`` runs.

Implementations compared: ``stencil`` (jnp.roll stencil, XLA-fused),
``dense`` ([N,N] matmul — the reference's own formulation, on the MXU),
``shard_map`` (explicit ppermute collectives; degenerate on a single chip —
included for completeness, flagged in the output).

Writes a JSON artifact (default ``docs/perf/mixing_bench.json``) consumed by
docs/PERF.md; the measured winner is what ``mixing_impl='auto'`` encodes.

Usage:  python examples/bench_mixing.py [--iters 3000] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _time_op(fn, x, k: int = 2000, repeats: int = 3) -> float:
    """Best-of-``repeats`` seconds for ``k`` chained applications of ``fn``."""

    @jax.jit
    def chained(x0):
        return jax.lax.scan(lambda c, _: (fn(c), None), x0, None, length=k)[0]

    chained(x).block_until_ready()  # compile
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        chained(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best


def main() -> None:
    ap = argparse.ArgumentParser()
    # Default matches the committed artifact (docs/perf/mixing_bench.json was
    # produced at T=10k) so regen_perf_artifacts.sh reproduces it.
    ap.add_argument("--iters", type=int, default=10000)
    ap.add_argument("--n-workers", type=int, default=256)
    ap.add_argument("--op-chain", type=int, default=2000)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--out", default="docs/perf/mixing_bench.json")
    args = ap.parse_args()

    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.ops.mixing import make_mixing_op
    from distributed_optimization_tpu.parallel.collectives import (
        make_shard_map_mixing_op,
    )
    from distributed_optimization_tpu.parallel.mesh import make_worker_mesh
    from distributed_optimization_tpu.parallel.topology import build_topology
    from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
    from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

    dev = jax.devices()[0]
    n = args.n_workers
    platform = dev.platform
    print(f"[bench_mixing] device={dev} platform={platform} N={n}", file=sys.stderr)

    topo = build_topology("ring", n)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((n, 81)),
                    dtype=jnp.float32)

    # --- 1. op-level: K chained W-applications -----------------------------
    op_results = {}
    mesh = make_worker_mesh(n)
    impls = {
        "stencil": make_mixing_op(topo, impl="stencil").apply,
        "dense": make_mixing_op(topo, impl="dense").apply,
        "shard_map": make_shard_map_mixing_op(topo, mesh).apply,
    }
    for name, fn in impls.items():
        try:
            sec = _time_op(fn, x, k=args.op_chain, repeats=args.repeats)
            per_apply_us = sec / args.op_chain * 1e6
            op_results[name] = round(per_apply_us, 3)
            print(f"[bench_mixing] op {name:10s}: {per_apply_us:8.2f} us/apply",
                  file=sys.stderr)
        except Exception as e:  # pragma: no cover - informational
            op_results[name] = f"FAIL: {type(e).__name__}: {e}"[:200]
            print(f"[bench_mixing] op {name}: FAILED {e}", file=sys.stderr)

    # --- 2. end-to-end: full backend runs ---------------------------------
    cfg0 = ExperimentConfig(
        problem_type="logistic", algorithm="dsgd", topology="ring",
        n_workers=n, n_iterations=args.iters,
    )
    ds = generate_synthetic_dataset(cfg0)
    _, f_opt = compute_reference_optimum(ds, cfg0.reg_param)

    # Variants are INTERLEAVED round-robin across repeat cycles so co-tenant
    # load swings on the shared chip hit every impl comparably — sequential
    # per-impl repeats let a single busy window sink one impl's numbers.
    e2e = {}
    best: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for _ in range(args.repeats):
        for impl in ("stencil", "dense", "shard_map"):
            if impl in e2e:  # already failed; don't retry every cycle
                continue
            cfg = cfg0.replace(mixing_impl=impl)
            kwargs = {"mesh": mesh} if impl == "shard_map" else {}
            try:
                r = jax_backend.run(cfg, ds, f_opt, **kwargs)
                best[impl] = max(best.get(impl, 0.0),
                                 float(r.history.iters_per_second))
                gaps[impl] = float(r.history.objective[-1])
            except Exception as e:  # pragma: no cover - informational
                e2e[impl] = {"error": f"{type(e).__name__}: {e}"[:200]}
                print(f"[bench_mixing] e2e {impl}: FAILED {e}", file=sys.stderr)
    for impl, ips in best.items():
        if impl in e2e:  # failed in a later cycle: the error record stands
            continue
        e2e[impl] = {"iters_per_sec": round(ips, 1),
                     "final_gap": round(gaps[impl], 6)}
        print(f"[bench_mixing] e2e {impl:10s}: {ips:9.0f} iters/sec "
              f"(gap {gaps[impl]:.4f})", file=sys.stderr)

    # shard_map on one chip is a degenerate lower bound (its ppermutes never
    # cross a device boundary) and can't be what 'auto' picks single-chip, so
    # it is excluded from the winner the artifact reports.
    ok = {k: v["iters_per_sec"] for k, v in e2e.items()
          if "iters_per_sec" in v and k != "shard_map"}
    winner = max(ok, key=ok.get) if ok else None
    out = {
        "device": str(dev), "platform": platform, "n_workers": n,
        "d": 81, "iters": args.iters, "op_chain": args.op_chain,
        "op_us_per_apply": op_results, "end_to_end": e2e, "winner": winner,
        "note": ("shard_map on a single chip has no cross-device collectives; "
                 "its number is a degenerate lower bound on collective cost"),
    }
    path = Path(args.out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(out, indent=2) + "\n")
    from distributed_optimization_tpu.telemetry import write_bench_manifest

    write_bench_manifest(path)

    print(f"[bench_mixing] winner={winner} -> {path}", file=sys.stderr)
    print(json.dumps({"metric": "mixing_bench_winner", "value": winner}))


if __name__ == "__main__":
    main()
