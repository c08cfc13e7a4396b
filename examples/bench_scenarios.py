"""Scenario-matrix golden corpus (ISSUE 12) -> docs/perf/scenarios.json.

Runs under a FORCED 4-device host platform (set before jax initializes,
the tests/conftest.py mechanism) so the worker-mesh cells execute real
multi-device halo collectives on this CPU container. Four gated claims:

1. **Agreement** — the validity table and ``ExperimentConfig``
   construction agree verdict-for-verdict on a seeded >= 500-cell sample
   spanning all 10 composition axes (zero divergences, asserted).
2. **Matrix** — the committed golden spec's >= 30 valid cells (all 10
   axes: algorithm, topology/impl, faults, Byzantine, compression, local
   steps, participation, execution, replicas, worker_mesh) run through
   the serving layer and EVERY applicable per-cell invariant passes: GT
   tracking, robust-envelope containment, B̂/degradation, the
   burst/churn/zero-budget bitwise reductions, explicit-default
   identity, replica-cohort coalescing.
3. **Checkpoint** — a dedicated 3-cell spec (plain, GT, faulty) passes
   bitwise interrupt+resume (split out of the main matrix because the
   invariant costs three segmented compiles per cell).
4. **Chaos** — the operational suite degrades gracefully: poisoned
   cohort isolated, daemon kill/restart served warm from the surviving
   executable cache, truncated checkpoint chunk survived bitwise, broken
   progress callback contained.

The committed JSON is guarded by the perf-diff checker
(``observability/observatory.py`` PERF_TOLERANCES): every gate boolean
and the cell/axis counts must reproduce exactly on regen.
"""

from __future__ import annotations

import json
import os
import sys
from pathlib import Path

# Must precede any jax import.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=4"
    ).strip()

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

OUT = REPO / "docs" / "perf" / "scenarios.json"

BASE = {
    "n_workers": 8, "n_samples": 400, "n_features": 10,
    "n_informative_features": 6, "problem_type": "quadratic",
    "n_iterations": 120, "eval_every": 20, "local_batch_size": 8,
    "dtype": "float64",
}

# The golden matrix: 41 curated compositions × 2 learning rates = 82
# cells, every one VALID by construction (the spec is committed evidence
# that these compositions run, not a sampler exercise — the sampler's
# valid/invalid frontier is gated by the agreement block instead).
SCENARIOS = [
    {},
    {"algorithm": "centralized"},
    {"algorithm": "gradient_tracking"},
    {"algorithm": "extra"},
    {"algorithm": "admm"},
    {"algorithm": "choco"},
    {"algorithm": "push_sum", "topology": "directed_ring"},
    {"topology": "grid", "n_workers": 16},
    {"topology": "erdos_renyi", "topology_seed": 7},
    {"topology": "chain", "gossip_schedule": "round_robin"},
    {"topology_impl": "neighbor"},
    {"gossip_schedule": "one_peer"},
    {"dtype": "float32"},
    {"backend": "numpy"},
    {"edge_drop_prob": 0.2},
    {"edge_drop_prob": 0.2, "burst_len": 4.0},
    {"straggler_prob": 0.15},
    {"mttf": 40.0, "mttr": 15.0},
    {"mttf": 40.0, "mttr": 15.0, "rejoin": "neighbor_restart"},
    {"attack": "sign_flip", "n_byzantine": 1,
     "aggregation": "trimmed_mean", "robust_b": 1,
     "partition": "shuffled"},
    {"attack": "alie", "n_byzantine": 1, "aggregation": "median",
     "robust_b": 1, "partition": "shuffled"},
    {"aggregation": "clipped_gossip", "robust_b": 1, "clip_tau": 0.5},
    {"compression": "top_k", "compression_k": 4},
    {"algorithm": "gradient_tracking", "compression": "qsgd",
     "compression_k": 4},
    {"local_steps": 4},
    {"algorithm": "gradient_tracking", "local_steps": 2},
    # Degenerate knobs spelled explicitly at their off points: must name
    # the exact experiment of the bare baseline cell (coalescing
    # identity, reduction_explicit_defaults).
    {"local_steps": 1, "participation_rate": 1.0, "burst_len": 0.0},
    {"participation_rate": 0.5},
    {"local_steps": 2, "participation_rate": 0.5, "mttf": 40.0,
     "mttr": 15.0},
    {"execution": "async", "latency_model": "exponential"},
    {"execution": "async", "latency_model": "pareto",
     "latency_tail": 1.5},
    # Async-faulty cells (ISSUE-17): faults realized on the EVENT axis —
    # compositions the validity table rejected before the event-clock
    # fault substrate landed. Each exercises a deleted rejection rule:
    # churn, participation thinning, gradient tracking's per-event
    # telescoping, τ fused per event, straggler-churn collapse, rejoin.
    {"execution": "async", "latency_model": "lognormal",
     "latency_tail": 0.5, "mttf": 40.0, "mttr": 15.0},
    {"execution": "async", "latency_model": "exponential",
     "participation_rate": 0.5},
    {"execution": "async", "latency_model": "lognormal",
     "latency_tail": 0.5, "algorithm": "gradient_tracking"},
    {"execution": "async", "latency_model": "exponential",
     "local_steps": 2},
    {"execution": "async", "latency_model": "exponential",
     "straggler_prob": 0.15},
    {"execution": "async", "latency_model": "exponential",
     "mttf": 40.0, "mttr": 15.0, "rejoin": "neighbor_restart"},
    {"replicas": 3},
    {"worker_mesh": 2},
    {"worker_mesh": 2, "straggler_prob": 0.15},
    {"worker_mesh": 2, "attack": "sign_flip", "n_byzantine": 1,
     "aggregation": "trimmed_mean", "robust_b": 1,
     "partition": "shuffled"},
]

# The agreement sample's axis bank (weighted toward each axis's 'off'
# setting so the sample hits the valid region too — unweighted, the
# product of ~10 mostly-incompatible axes is < 1% valid).
def agreement_axes():
    return {
        "algorithm": (
            [{}] * 2
            + [{"algorithm": a} for a in
               ("centralized", "dsgd", "gradient_tracking", "extra",
                "admm", "choco", "push_sum")]
        ),
        "topology": (
            [{"topology": "ring"}] * 4 + [
                {"topology": "grid", "n_workers": 16},
                {"topology": "fully_connected"},
                {"topology": "erdos_renyi"}, {"topology": "chain"},
                {"topology": "star"}, {"topology": "directed_ring"},
                {"topology": "ring", "topology_impl": "neighbor"},
                {"topology": "ring", "gossip_schedule": "one_peer"},
                {"topology": "chain", "gossip_schedule": "round_robin"},
            ]
        ),
        "faults": (
            [{}] * 6 + [
                {"edge_drop_prob": 0.2},
                {"edge_drop_prob": 0.2, "burst_len": 4.0},
                {"straggler_prob": 0.15}, {"mttf": 40.0, "mttr": 15.0},
                {"mttf": 40.0, "mttr": 15.0,
                 "rejoin": "neighbor_restart"},
                {"burst_len": 3.0}, {"mttf": 40.0},
            ]
        ),
        "byzantine": (
            [{}] * 8 + [
                {"attack": "sign_flip", "n_byzantine": 1},
                {"attack": "sign_flip", "n_byzantine": 1,
                 "aggregation": "trimmed_mean", "robust_b": 1},
                {"aggregation": "median", "robust_b": 1},
                {"aggregation": "clipped_gossip", "robust_b": 1,
                 "clip_tau": 0.5},
                {"attack": "alie", "n_byzantine": 2,
                 "aggregation": "median", "robust_b": 2},
                {"robust_impl": "dense"},
                {"aggregation": "trimmed_mean"}, {"n_byzantine": 3},
            ]
        ),
        "compression": (
            [{}] * 3 + [
                {"compression": "top_k", "compression_k": 4},
                {"compression": "qsgd", "compression_k": 4},
                {"compression": "top_k"},
            ]
        ),
        "local_steps": [{}, {}, {"local_steps": 2}, {"local_steps": 4}],
        "participation": [
            {}, {}, {"participation_rate": 0.5},
            {"participation_rate": 1.0},
        ],
        "execution": (
            [{}] * 6 + [
                {"execution": "async", "latency_model": "exponential"},
                {"execution": "async", "latency_model": "lognormal",
                 "latency_tail": 0.5},
                {"execution": "async", "latency_model": "pareto",
                 "latency_tail": 1.5},
                {"execution": "async"}, {"latency_model": "exponential"},
                {"execution": "async", "latency_model": "exponential",
                 "backend": "numpy"},
            ]
        ),
        "replicas": [{}, {}, {"replicas": 4}],
        "worker_mesh": (
            [{}] * 3 + [
                {"worker_mesh": 2}, {"worker_mesh": 3},
                {"tp_degree": 2, "problem_type": "softmax"},
            ]
        ),
    }


def axes_coverage(report) -> dict:
    """Which of the 10 orthogonal axes the VALID cells exercise
    non-trivially (beyond the default setting)."""
    cells = [r for r in report["cells"] if r.get("valid")]

    def has(pred):
        return any(pred(r["overrides"]) for r in cells)

    return {
        "algorithm": len(
            {r["overrides"].get("algorithm", "dsgd") for r in cells}
        ) >= 5,
        "topology": has(lambda o: o.get("topology") not in (None, "ring"))
        and has(lambda o: o.get("topology_impl") == "neighbor"),
        "faults": has(lambda o: o.get("edge_drop_prob", 0) > 0)
        and has(lambda o: o.get("burst_len", 0) > 1)
        and has(lambda o: o.get("straggler_prob", 0) > 0)
        and has(lambda o: o.get("mttf", 0) > 0),
        "byzantine": has(lambda o: o.get("attack", "none") != "none"),
        "compression": has(
            lambda o: o.get("compression", "none") != "none"
        ),
        "local_steps": has(lambda o: o.get("local_steps", 1) > 1),
        "participation": has(
            lambda o: o.get("participation_rate", 1.0) < 1.0
        ),
        "execution": has(lambda o: o.get("execution") == "async"),
        # ISSUE-17: the event clock carries a fault process — churn or
        # thinning composed WITH execution='async' in one valid cell.
        "async_faults": has(
            lambda o: o.get("execution") == "async" and (
                o.get("mttf", 0) > 0
                or o.get("participation_rate", 1.0) < 1.0
                or o.get("straggler_prob", 0) > 0
            )
        ),
        "replicas": has(lambda o: o.get("replicas", 1) > 1),
        "worker_mesh": has(lambda o: o.get("worker_mesh", 0) >= 2),
    }


def main() -> int:
    from distributed_optimization_tpu.scenarios import validity
    from distributed_optimization_tpu.scenarios.chaos import run_chaos_suite
    from distributed_optimization_tpu.scenarios.engine import run_scenarios
    from distributed_optimization_tpu.scenarios.generator import generate
    from distributed_optimization_tpu.scenarios.spec import parse_spec
    from distributed_optimization_tpu.telemetry import (
        provenance,
        write_bench_manifest,
    )
    from distributed_optimization_tpu.utils.profiling import PhaseTimer

    timer = PhaseTimer()

    # ---- 1. agreement: validity table vs construction -----------------
    with timer.phase("agreement"):
        sample = generate(parse_spec({
            "name": "agreement", "seed": 11, "mode": "sample",
            "sample": 700, "base": dict(BASE), "axes": agreement_axes(),
        }))
        divergences = [
            msg for cell in sample.cells
            if (msg := validity.cross_check(cell.fields)) is not None
        ]
        agreement = {
            "cells": len(sample.cells),
            "counts": sample.counts(),
            "divergences": divergences,
        }
    assert len(sample.cells) >= 500, "agreement sample too small"
    assert not divergences, divergences[:5]
    assert agreement["counts"]["valid"] >= 20
    print(
        f"[scenarios-bench] agreement: {agreement['cells']} cells, "
        f"{agreement['counts']['valid']} valid, 0 divergences"
    )

    # ---- 2. the golden matrix -----------------------------------------
    with timer.phase("matrix"):
        report = run_scenarios(parse_spec({
            "name": "golden-matrix", "seed": 12, "mode": "enumerate",
            "base": dict(BASE),
            "axes": {
                "learning_rate_eta0": [0.05, 0.08],
                "scenario": SCENARIOS,
            },
            # checkpoint_resume runs in its own small spec below: it
            # costs three segmented compiles per eligible cell, which at
            # 60+ cells would triple this bench's wall time for a claim
            # three representative cells already pin.
            "invariants": [
                "finite_gap", "gt_tracking", "robust_envelope",
                "bhat_degradation", "reduction_burst", "reduction_churn",
                "reduction_zero_budget", "reduction_explicit_defaults",
                "replica_cohort",
            ],
        }))
    coverage = axes_coverage(report)
    n_valid = report["counts"]["valid"]
    print(
        f"[scenarios-bench] matrix: {n_valid} valid cells, "
        f"{report['invariants']['checks']} checks, "
        f"{report['invariants']['failures']} failures, "
        f"{report['wall_seconds']:.1f}s"
    )
    assert n_valid >= 30, f"golden corpus needs >= 30 valid cells, {n_valid}"
    assert report["counts"]["rejected"] == 0, (
        "the golden spec is curated: every cell must be valid"
    )
    assert all(coverage.values()), f"axis coverage incomplete: {coverage}"
    assert report["gates"]["all_cells_completed"], report["cells"]
    assert report["gates"]["all_invariants_passed"], report["invariants"]
    assert report["gates"]["warm_replay_ok"], report["warm_replay"]
    assert report["serving"]["any_coalesced_cohort"]

    # ---- 3. checkpoint-resume cells ------------------------------------
    with timer.phase("checkpoint"):
        ck_report = run_scenarios(parse_spec({
            "name": "golden-checkpoint", "seed": 12, "mode": "enumerate",
            "base": dict(BASE),
            "axes": {"scenario": [
                {}, {"algorithm": "gradient_tracking"},
                {"edge_drop_prob": 0.2, "burst_len": 4.0},
            ]},
            "invariants": ["checkpoint_resume"],
        }))
    assert ck_report["gates"]["all_invariants_passed"], (
        ck_report["invariants"]
    )
    print("[scenarios-bench] checkpoint: 3 cells bitwise resume OK")

    # ---- 4. operational chaos ------------------------------------------
    with timer.phase("chaos"):
        chaos = run_chaos_suite()
    assert all(chaos["gates"].values()), chaos
    print(f"[scenarios-bench] chaos: {chaos['gates']}")

    # ---- artifact -------------------------------------------------------
    def compact(rows):
        out = []
        for r in rows:
            if not r.get("valid"):
                continue
            out.append({
                "overrides": r["overrides"],
                "structural_hash": r["structural_hash"],
                "cohort_size": (r.get("serving") or {}).get("cohort_size"),
                "invariants": {
                    i["name"]: i["passed"] for i in r.get("invariants", [])
                },
            })
        return out

    prov = provenance()
    payload = {
        "device": prov.get("device_kind"),
        "platform": "cpu",
        "protocol": (
            "agreement: seeded 700-cell sample over the weighted 10-axis "
            "bank, validity-table verdict vs ExperimentConfig "
            "construction, zero divergences required. matrix: the "
            "committed 35-composition × 2-eta golden spec served through "
            "SimulationService (coalescing + executable cache live), all "
            "applicable invariants asserted per cell, plus a warm replay "
            "of one structural class (bitwise + zero-compile required). "
            "checkpoint: 3 cells, interrupt+resume bitwise vs the "
            "equally-segmented uninterrupted run. chaos: poisoned "
            "cohort / daemon kill+restart / truncated checkpoint chunk / "
            "broken progress callback, graceful degradation asserted."
        ),
        "spec": {
            "base": BASE,
            "n_scenarios": len(SCENARIOS),
            "etas": [0.05, 0.08],
        },
        "agreement": {
            "cells": agreement["cells"],
            "valid": agreement["counts"]["valid"],
            "rejected": agreement["counts"]["rejected"],
            "rejected_by_rule": agreement["counts"]["rejected_by_rule"],
            "divergences": agreement["divergences"],
        },
        "matrix": {
            "counts": report["counts"],
            "invariants": report["invariants"],
            "serving": report["serving"],
            "warm_replay": report["warm_replay"],
            "cells": compact(report["cells"]),
        },
        "checkpoint": {
            "invariants": ck_report["invariants"],
        },
        "chaos": chaos,
        "gates": {
            "agreement_zero_divergences": not divergences,
            "agreement_cells": agreement["cells"],
            # The composition-closure number (ISSUE-17): the FIXED seeded
            # sample's valid fraction. Every deleted async rejection rule
            # moves cells from rejected to valid, so this committed
            # fraction must strictly increase whenever closure grows —
            # and must reproduce exactly on regen (perf-diff guarded).
            "agreement_valid_cells": agreement["counts"]["valid"],
            "agreement_valid_fraction": round(
                agreement["counts"]["valid"] / agreement["cells"], 4
            ),
            "matrix_n_valid_cells": n_valid,
            "matrix_axes_covered": all(coverage.values()),
            "matrix_all_cells_completed": report["gates"][
                "all_cells_completed"],
            "matrix_all_invariants_passed": report["gates"][
                "all_invariants_passed"],
            "matrix_warm_replay_ok": report["gates"]["warm_replay_ok"],
            "matrix_any_coalesced_cohort": report["serving"][
                "any_coalesced_cohort"],
            "checkpoint_bitwise_resume": ck_report["gates"][
                "all_invariants_passed"],
            **chaos["gates"],
        },
        "note": (
            "CPU-container corpus: the load-bearing content is the "
            "boolean gates (validity agreement, per-cell invariants, "
            "warm replay, chaos degradation) and the exact cell/axis "
            "counts — per-cell gap values are platform-deterministic "
            "but not cross-platform evidence. The worker-mesh cells run "
            "over 4 forced host devices (real ppermute halo exchange)."
        ),
    }
    OUT.parent.mkdir(parents=True, exist_ok=True)
    OUT.write_text(json.dumps(payload, indent=1, sort_keys=True) + "\n")
    print(f"wrote {OUT}")
    from distributed_optimization_tpu.config import ExperimentConfig

    write_bench_manifest(
        OUT, config=ExperimentConfig(**{**BASE}), phases=timer,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
