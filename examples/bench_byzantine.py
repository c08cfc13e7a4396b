"""Breakdown-point demonstration for the Byzantine subsystem
(docs/BYZANTINE.md; acceptance rows for the robust-aggregation rules).

One config — logistic, **N=64 ring**, IID ('shuffled') partition, T=4k —
swept over the attack/defense matrix. (The bench ran at N=16 fully
connected until PR 3: the dense robust path was O(N²·d·log N), so a
ring-at-scale sweep was unaffordable. The degree-bounded gather path —
``robust_impl='auto'`` routes to it on the ring, k_max=2 ≪ N — makes the
degree-bounded regime the headline, which is also where the screening
budget semantics are per-NEIGHBORHOOD, not global: b=1 per closed ring
neighborhood of 3.)

- ATTACK-FREE: plain gossip, each robust rule at budget b=1 (defense
  cost), and a zero-budget robust run ASSERTED bitwise-equal to plain
  (robust_b=0 degrades to the plain path by construction);
- SIGN-FLIP at a tolerated placement (f=6 of 64, scale 5 — for this
  seed every honest ring neighborhood holds ≤ 1 = b attackers): plain
  gossip must diverge (NaN) or stall ≥10× above the attack-free gap;
  trimmed mean, median, and clipped gossip must land within 2× of it —
  both asserted;
- ALIE and LARGE-NOISE rows at the same placement (table rows, no hard
  gate — ALIE is designed to slip through screens, so its damage is
  bounded but nonzero on BOTH the plain and the screened path);
- BREAKDOWN SWEEP: trimmed mean at fixed budget b=1 against f ∈ {3, 10}
  attackers. Breakdown on a sparse graph is about PLACEMENT, not the
  global fraction: f=10 (seed 203) puts BOTH ring neighbors of two
  honest nodes in the Byzantine set, so their trimmed windows are
  attacker-bracketed — past the per-neighborhood budget even though
  10/64 < 5/16.

The IID partition is load-bearing, not cosmetic: screened aggregation
pays a bias ∝ attack fraction × gradient heterogeneity (He-Karimireddy-
Jaggi 2022), so under the study's sorted non-IID split the same rules
stall far above the attack-free gap — the sweep records that row too so
the limitation is measured, not hidden.

Writes ``docs/perf/byzantine.json``.

Usage:  python examples/bench_byzantine.py [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="docs/perf/byzantine.json")
    args = ap.parse_args()

    import jax
    import numpy as np

    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.metrics import iterations_to_threshold
    from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
    from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

    base = ExperimentConfig(
        problem_type="logistic", algorithm="dsgd", topology="ring",
        n_workers=64, n_samples=6400, n_features=10,
        n_informative_features=6, n_iterations=4000, local_batch_size=100,
        eval_every=500, partition="shuffled",
    )
    # This artifact documents the GATHER robust path's breakdown table
    # (PR 3); pin it explicitly so the config string says what a regen
    # measures whatever 'auto' resolves to.
    ROBUST_IMPL = "gather"
    # Attackers, per-neighborhood budget (ring min degree 2 => b <= 1),
    # sign-flip scale. f=6 under seed 203 places <= 1 attacker in every
    # honest closed ring neighborhood — within the b=1 budget everywhere;
    # f=10 sandwiches two honest nodes (both neighbors Byzantine), the
    # past-breakdown placement the sweep demonstrates.
    F, B, S = 6, 1, 5.0

    def attacked(attack, scale=S, f=F, **kw):
        if kw.get("robust_b", 0) > 0:
            kw.setdefault("robust_impl", ROBUST_IMPL)
        return base.replace(
            attack=attack, n_byzantine=f, attack_scale=scale, **kw
        )

    def defended(**kw):
        return base.replace(robust_impl=ROBUST_IMPL, **kw)

    variants = {
        "attack_free": base,
        "tm_b1_no_attack": defended(aggregation="trimmed_mean", robust_b=B),
        "median_b1_no_attack": defended(aggregation="median", robust_b=B),
        "clip_b1_no_attack": defended(
            aggregation="clipped_gossip", robust_b=B
        ),
        "tm_b0_no_attack": base.replace(aggregation="trimmed_mean", robust_b=0),
        "signflip_plain": attacked("sign_flip"),
        "signflip_tm": attacked(
            "sign_flip", aggregation="trimmed_mean", robust_b=B
        ),
        "signflip_median": attacked("sign_flip", aggregation="median", robust_b=B),
        "signflip_clip": attacked(
            "sign_flip", aggregation="clipped_gossip", robust_b=B
        ),
        "alie_plain": attacked("alie", scale=1.0),
        "alie_tm": attacked(
            "alie", scale=1.0, aggregation="trimmed_mean", robust_b=B
        ),
        "noise_plain": attacked("large_noise", scale=10.0),
        "noise_tm": attacked(
            "large_noise", scale=10.0, aggregation="trimmed_mean", robust_b=B
        ),
        # Breakdown sweep: fixed budget, placement past the neighborhood
        # budget (see module docstring — f=10 sandwiches honest nodes).
        "breakdown_tm_f3": attacked(
            "sign_flip", f=3, aggregation="trimmed_mean", robust_b=B
        ),
        "breakdown_tm_f10": attacked(
            "sign_flip", f=10, aggregation="trimmed_mean", robust_b=B
        ),
        "breakdown_plain_f3": attacked("sign_flip", f=3),
        # The measured non-IID limitation row (sorted partition).
        "signflip_tm_sorted": attacked(
            "sign_flip", aggregation="trimmed_mean", robust_b=B,
            partition="sorted",
        ),
    }

    # One dataset per partition flavor; f_opt from the same oracle path the
    # simulator uses.
    data = {}
    for part in ("shuffled", "sorted"):
        ds = generate_synthetic_dataset(base.replace(partition=part))
        _, f_opt = compute_reference_optimum(ds, base.reg_param)
        data[part] = (ds, f_opt)

    results: dict[str, dict] = {}
    trajectories: dict[str, list] = {}
    for name, cfg in variants.items():
        ds, f_opt = data[cfg.partition]
        r = jax_backend.run(cfg, ds, f_opt)
        h = r.history
        gap = float(h.objective[-1])
        results[name] = {
            "final_gap": None if np.isnan(gap) else round(gap, 6),
            "diverged": bool(np.isnan(gap)),
            "iterations_to_eps": int(iterations_to_threshold(
                h.objective, cfg.suboptimality_threshold, h.eval_iterations
            )),
            "final_honest_consensus": (
                None if np.isnan(h.consensus_error[-1])
                else round(float(h.consensus_error[-1]), 8)
            ),
        }
        trajectories[name] = [
            None if np.isnan(v) else round(float(v), 6)
            for v in h.objective
        ]
        print(f"[byzantine] {name:22s} gap {results[name]['final_gap']}",
              file=sys.stderr)

    clean = results["attack_free"]["final_gap"]
    for name, row in results.items():
        row["gap_vs_attack_free"] = (
            None if row["diverged"] or row["final_gap"] is None
            else round(row["final_gap"] / clean, 3)
        )

    # --- acceptance gates (the breakdown-point demonstration) ---
    # Zero-budget robust == plain gossip to accumulation roundoff (the
    # backend short-circuit makes it bitwise; assert the documented bound).
    zb = np.asarray(trajectories["tm_b0_no_attack"], dtype=np.float64)
    pl = np.asarray(trajectories["attack_free"], dtype=np.float64)
    assert np.max(np.abs(zb - pl)) <= 1e-12, (
        "zero-budget robust run must match plain gossip trajectories"
    )
    # Plain gossip under the in-budget sign-flip: divergent or >= 10x.
    sp = results["signflip_plain"]
    assert sp["diverged"] or sp["final_gap"] >= 10.0 * clean, (
        "plain gossip must diverge or stall >= 10x above attack-free"
    )
    # Robust rules under the same attack: within 2x of attack-free.
    for name in ("signflip_tm", "signflip_median", "signflip_clip"):
        row = results[name]
        assert not row["diverged"] and row["final_gap"] <= 2.0 * clean, (
            f"{name} must converge within 2x of the attack-free run"
        )
    # Past the breakdown point (a sandwiched neighborhood, f=10 placement)
    # the defense visibly degrades.
    assert (
        results["breakdown_tm_f10"]["diverged"]
        or results["breakdown_tm_f10"]["final_gap"]
        > 3.0 * results["breakdown_tm_f3"]["final_gap"]
    ), "past-budget placement should sit far above the tolerated rows"

    payload = {
        "device": str(jax.devices()[0]),
        "config": (
            "logistic N=64 ring T=4k shuffled partition (gather robust "
            f"path, robust_impl={ROBUST_IMPL!r} pinned); f={F} Byzantine of "
            f"64, per-neighborhood budget b={B}, sign-flip scale {S}"
        ),
        "note": (
            "final honest-suboptimality gap f(x_bar_honest) - f* per "
            "variant; gap_vs_attack_free is the breakdown criterion "
            "(plain diverges under the tolerated-placement sign-flip "
            "while trimmed mean/median/clipped gossip land within 2x of "
            "attack-free; trimmed mean under the f=10 placement — two "
            "honest nodes with BOTH ring neighbors Byzantine — sits past "
            "the per-neighborhood breakdown point). signflip_tm_sorted "
            "records the measured non-IID cost: screening bias scales "
            "with gradient heterogeneity, so the sorted partition lands "
            "above the IID row."
        ),
        "runs": results,
        "trajectories": trajectories,
    }
    path = Path(args.out)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(payload, indent=2) + "\n")
    from distributed_optimization_tpu.telemetry import write_bench_manifest

    write_bench_manifest(path)

    print(json.dumps({"metric": "byzantine_variants_measured",
                      "value": len(results)}))


if __name__ == "__main__":
    main()
