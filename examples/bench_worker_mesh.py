"""Sharded worker-mesh evidence (ISSUE 11) -> docs/perf/worker_mesh.json.

A CPU COUNT BENCH: it runs under a FORCED 4-device host platform
(JAX_PLATFORMS=cpu and XLA_FLAGS, both pinned below before jax
initializes) — the same mechanism tests/conftest.py uses — so the
halo-exchange collectives execute as multi-device ppermutes between host
devices. Its bytes, rows and parity results are counts and stand; its
iters/sec are CPU rates, not device numbers. The four-chip evidence is
chip_smoke.py's worker_mesh=4 segment (one process driving four chips).
Three measured claims, each gated by an assertion:

1. **Parity** — sharded (worker_mesh=4) and unsharded trajectories at
   matched N are BITWISE identical on the final models (ring and ER via
   halo gather); the objective eval sits within the repo's f64
   cross-program-shape convention (GSPMD reduce-tree order).
2. **Scale** — the N = 100,000 matrix-free ring run COMPLETES sharded
   over 4 devices (the explicit beyond-RAM headroom PR 8 left open at
   N=10k), with measured per-device resident bytes: the worker-sharded
   footprint scales as N/P — doubling N while doubling P leaves
   per-device bytes flat (the 50k/P=2 vs 100k/P=4 pair, asserted), and
   each cell runs in its own subprocess so peak RSS is honest.
3. **Bytes over ICI** — the static halo plan prices the real collective
   traffic exactly: a ring round ships 2 boundary rows per device
   REGARDLESS of N (asserted flat across the ring cells — Lian et al.'s
   O(deg)-per-worker claim made measurable), next to the analytic
   simulated-floats accounting in the same report.

ER at N=100k runs via the O(N·k_max) SPARSE sampler
(topology_sampler='sparse', ISSUE 18): the dense-stream sampler
intentionally replays the dense sampler's exact Generator stream for
bit-identical graphs (PR 8's parity contract), which is O(N^2) draws —
~35 min at N=100k for the build alone, the recorded reason this cell was
skipped through PR 17 (see scale.er_at_100k_history). The sparse sampler
draws a DIFFERENT realization of the same G(n, p) law in seconds, so the
N=10,000 dense-sampled cell stays as the bitwise-contract reference
while the 100k cell carries the irregular-graph completion.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

# Must precede any jax import, including in spawn-context subprocesses
# (they re-import this module's top level). Pinned, not defaulted: this
# is a CPU count bench by construction — the parent touches jax and then
# spawns one child per cell, which a chip (one process at a time) would
# refuse — so it never takes a TPU even where one is the default.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

OUT = REPO / "docs" / "perf" / "worker_mesh.json"

PARITY_N = 64
PARITY_T = 200

SCALE_T = 50
# (label, topology, n, worker_mesh, extra-config) — each cell in its own
# subprocess. The 50k/P=2 row pairs with 100k/P=4: same rows per device,
# so per-device resident bytes must come out flat.
SCALE_CELLS = (
    ("ring_25k_p4", "ring", 25_000, 4, {}),
    ("ring_50k_p4", "ring", 50_000, 4, {}),
    ("ring_50k_p2", "ring", 50_000, 2, {}),
    ("ring_100k_p4", "ring", 100_000, 4, {}),
    ("er_10k_p4", "erdos_renyi", 10_000, 4,
     {"erdos_renyi_p": 8.0 / 10_000, "topology_seed": 1}),
    # mean degree 16 > ln(100k) ≈ 11.5: the connected draw lands in O(1)
    # tries of the sparse sampler.
    ("er_100k_p4_sparse", "erdos_renyi", 100_000, 4,
     {"erdos_renyi_p": 16.0 / 100_000, "topology_seed": 1,
      "topology_sampler": "sparse"}),
)


def _problem(cfg):
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )
    from distributed_optimization_tpu.utils.oracle import (
        compute_reference_optimum,
    )

    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    return ds, f_opt


def bench_parity():
    import numpy as np

    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.config import ExperimentConfig

    base = dict(
        n_workers=PARITY_N, n_samples=4 * PARITY_N, n_features=16,
        n_informative_features=10, problem_type="quadratic",
        algorithm="dsgd", local_batch_size=8, dtype="float64",
        n_iterations=PARITY_T, eval_every=20,
        topology_impl="neighbor", mixing_impl="gather",
    )
    cells = {}
    max_obj_dev = 0.0
    for name, kw in (
        ("ring", {"topology": "ring"}),
        ("erdos_renyi", {"topology": "erdos_renyi",
                         "erdos_renyi_p": 0.15, "topology_seed": 7}),
    ):
        cfg_u = ExperimentConfig(**{**base, **kw})
        cfg_s = cfg_u.replace(worker_mesh=4)
        ds, f_opt = _problem(cfg_u)
        r_u = jax_backend.run(cfg_u, ds, f_opt, use_mesh=False)
        r_s = jax_backend.run(cfg_s, ds, f_opt)
        bitwise = bool(np.array_equal(
            np.asarray(r_u.final_models), np.asarray(r_s.final_models)
        ))
        obj_dev = float(np.max(np.abs(
            np.asarray(r_u.history.objective, dtype=np.float64)
            - np.asarray(r_s.history.objective, dtype=np.float64)
        )) / max(1.0, float(np.max(np.abs(r_u.history.objective)))))
        max_obj_dev = max(max_obj_dev, obj_dev)
        assert bitwise, f"{name}: sharded final models diverged bitwise"
        cells[name] = {
            "models_bitwise": bitwise,
            "objective_max_rel_deviation_f64": obj_dev,
            "final_gap": float(r_u.history.objective[-1]),
        }
        print(f"[parity] {name}: models bitwise={bitwise}, "
              f"obj rel dev={obj_dev:.2e}")
    assert max_obj_dev <= 1e-12, max_obj_dev
    return {
        "n_workers": PARITY_N,
        "n_iterations": PARITY_T,
        "worker_mesh": 4,
        "cells": cells,
        "max_objective_rel_deviation_f64": max_obj_dev,
        "note": (
            "final models are BITWISE equal sharded-vs-unsharded; the "
            "objective eval reduces over the worker axis whose GSPMD "
            "reduction tree differs from the single-device linear order "
            "— the repo's documented <=1e-12 f64 cross-program-shape "
            "convention, asserted"
        ),
    }


def _scale_cell(args):
    """One sharded scale cell in a fresh subprocess (honest peak RSS +
    per-device resident bytes probed at the first progress heartbeat)."""
    label, topology, n, mesh_p, extra = args
    import collections
    import resource
    import time

    import jax

    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.telemetry import ici_summary
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )

    cfg = ExperimentConfig(
        n_workers=n, n_samples=2 * n, n_features=16,
        n_informative_features=10, problem_type="quadratic",
        topology=topology, algorithm="dsgd", local_batch_size=4,
        n_iterations=SCALE_T, eval_every=SCALE_T // 2,
        topology_impl="neighbor", mixing_impl="gather",
        worker_mesh=mesh_p, **extra,
    )
    t0 = time.perf_counter()
    ds = generate_synthetic_dataset(cfg)
    data_seconds = time.perf_counter() - t0

    per_device: dict[str, int] = {}

    def probe(_event):
        # Live per-device resident bytes mid-run: every live jax array's
        # realized shard sizes, summed per device. Device 0 additionally
        # holds the replicated leaves (keys, scalars), so the sharded
        # footprint is read off devices 1..P-1.
        if per_device:
            return
        acc = collections.Counter()
        for a in jax.live_arrays():
            for s in a.addressable_shards:
                acc[str(s.device)] += s.data.nbytes
        per_device.update(acc)

    t0 = time.perf_counter()
    r = jax_backend.run(cfg, ds, 0.0, progress_cb=probe, progress_every=1)
    wall = time.perf_counter() - t0
    gap = float(r.history.objective[-1])
    assert gap == gap, f"{label}: NaN gap"
    ici = ici_summary(cfg)
    return {
        "label": label,
        "topology": topology,
        "n_workers": n,
        "worker_mesh": mesh_p,
        "rows_per_device": n // mesh_p,
        "iters_per_second": float(r.history.iters_per_second),
        "compile_seconds": float(r.history.compile_seconds),
        "wall_seconds": wall,
        "data_seconds": data_seconds,
        "final_gap": gap,
        "peak_rss_mb": resource.getrusage(
            resource.RUSAGE_SELF
        ).ru_maxrss / 1024.0,
        "per_device_resident_bytes": dict(per_device),
        "sharded_bytes_per_device": (
            min(per_device.values()) if per_device else None
        ),
        "ici": ici,
    }


def bench_scale():
    import multiprocessing as mp
    from concurrent import futures

    cells = []
    ctx = mp.get_context("spawn")
    for job in SCALE_CELLS:  # sequential: no interference between cells
        with futures.ProcessPoolExecutor(1, mp_context=ctx) as pool:
            cell = pool.submit(_scale_cell, job).result()
        cells.append(cell)
        print(f"[scale] {cell['label']}: {cell['iters_per_second']:.0f} "
              f"iters/s, {cell['sharded_bytes_per_device'] / 1e6:.1f} "
              f"MB/device sharded, peak RSS {cell['peak_rss_mb']:.0f} MB, "
              f"ICI {cell['ici']['bytes_per_device_per_round_max']} "
              f"B/dev/round")
    by_label = {c["label"]: c for c in cells}

    big = by_label["ring_100k_p4"]
    assert big["final_gap"] == big["final_gap"] and big["iters_per_second"] > 0

    # Flat per-device memory: same rows/device (50k over 2 vs 100k over
    # 4) -> same sharded per-device footprint, within allocator noise.
    pair_ratio = (
        big["sharded_bytes_per_device"]
        / by_label["ring_50k_p2"]["sharded_bytes_per_device"]
    )
    assert 0.8 <= pair_ratio <= 1.25, pair_ratio

    # Ring ICI traffic is O(boundary) = 2 rows/device/round at EVERY N.
    ring_ici = [
        by_label[k]["ici"]["bytes_per_device_per_round_max"]
        for k in ("ring_25k_p4", "ring_50k_p4", "ring_100k_p4")
    ]
    assert len(set(ring_ici)) == 1, ring_ici
    return {
        "n_iterations": SCALE_T,
        "cells": cells,
        "per_device_flat_pair": {
            "cells": ["ring_50k_p2", "ring_100k_p4"],
            "rows_per_device_each": 25_000,
            "sharded_bytes_ratio": pair_ratio,
        },
        "er_at_100k_history": (
            "skipped through PR 17: the dense-stream ER sampler replays "
            "the dense sampler's exact Generator stream for bit-identical "
            "graphs (PR 8 parity contract) — O(N^2) draws, ~35 min of "
            "host sampling at N=100k before the mesh runs at all. Runs "
            "since ISSUE 18 via topology_sampler='sparse' (O(N·k_max) "
            "draws, a different realization of the same law); the "
            "N=10,000 dense-sampled cell remains the bitwise-contract "
            "reference"
        ),
    }


def main() -> None:
    from distributed_optimization_tpu.telemetry import write_bench_manifest
    from distributed_optimization_tpu.utils.profiling import PhaseTimer

    import jax

    from distributed_optimization_tpu.config import ExperimentConfig

    assert len(jax.devices()) >= 4, (
        "worker-mesh bench needs the forced 4-device host platform; do "
        "not pre-set XLA_FLAGS without xla_force_host_platform_device_count"
    )
    timer = PhaseTimer()
    with timer.phase("parity"):
        parity = bench_parity()
    with timer.phase("scale"):
        scale = bench_scale()

    big = next(
        c for c in scale["cells"] if c["label"] == "ring_100k_p4"
    )
    ring_ici_flat = len({
        c["ici"]["bytes_per_device_per_round_max"]
        for c in scale["cells"] if c["topology"] == "ring"
        and c["worker_mesh"] == 4
    }) == 1
    payload = {
        "device": jax.devices()[0].device_kind,
        "platform": jax.devices()[0].platform,
        "protocol": {
            "devices": (
                "forced 4-device CPU host platform (XLA_FLAGS), real "
                "shard_map/ppermute collectives — the same mechanism the "
                "shard_map stencil tests use"
            ),
            "parity": (
                f"matched-N ({PARITY_N}) sharded worker_mesh=4 vs "
                "unsharded, ring + ER halo gather, f64: final models "
                "bitwise asserted, objective within the <=1e-12 "
                "cross-program-shape convention"
            ),
            "scale": (
                "ring N in {25k, 50k, 100k} over 4 devices + the "
                "50k/P=2 flat-memory pair + ER N=10k (dense-sampled "
                "bitwise reference) + ER N=100k (sparse-sampled, "
                "ISSUE 18), dsgd T=50, one "
                "subprocess per cell; per-device resident bytes probed "
                "from live array shards at the first progress heartbeat"
            ),
            "ici": (
                "bytes-over-ICI from the static halo plan "
                "(telemetry.ici_summary — identical numbers feed the "
                "report line and the /metrics per-device gauges); ring "
                "flatness across N asserted"
            ),
        },
        "parity": parity,
        "scale": scale,
        "gates": {
            "parity_models_bitwise_ring": parity["cells"]["ring"][
                "models_bitwise"],
            "parity_models_bitwise_er": parity["cells"]["erdos_renyi"][
                "models_bitwise"],
            "parity_max_objective_rel_deviation_f64": parity[
                "max_objective_rel_deviation_f64"],
            "n100k_ring_completed_sharded": True,
            "er_halo_completed": True,
            "er_100k_sparse_completed": True,
            "per_device_flat_at_matched_rows": bool(
                0.8 <= scale["per_device_flat_pair"][
                    "sharded_bytes_ratio"] <= 1.25
            ),
            "ring_ici_bytes_per_device_flat_in_n": ring_ici_flat,
            "n100k_ici_bytes_per_device_per_round": big["ici"][
                "bytes_per_device_per_round_max"],
        },
        "note": (
            "CPU-container numbers: absolute iters/sec is not chip "
            "evidence; the load-bearing content is the bitwise parity "
            "gates, the N=100k sharded completion, the flat per-device "
            "footprint at matched rows/device, and the N-independent "
            "ring ICI traffic. Bitwise guarantees per composed feature "
            "(churn, participation, Byzantine screening, resume) live in "
            "tests/test_worker_mesh.py, not here."
        ),
    }
    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    print(f"wrote {OUT}")
    write_bench_manifest(
        OUT,
        config=ExperimentConfig(
            n_workers=100_000, n_samples=200_000, n_features=16,
            n_informative_features=10, problem_type="quadratic",
            topology="ring", algorithm="dsgd", local_batch_size=4,
            n_iterations=SCALE_T, eval_every=SCALE_T // 2,
            topology_impl="neighbor", mixing_impl="gather", worker_mesh=4,
        ),
        phases=timer,
    )


if __name__ == "__main__":
    main()
