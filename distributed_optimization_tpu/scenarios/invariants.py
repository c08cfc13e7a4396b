"""Per-cell invariants: what a valid cell of the matrix must DO.

Each invariant is a named, self-describing check over one executed cell
(and, where the property is relational, its derived twin runs). The
catalog executes through the engine — twins are served through the same
serving layer as the cells, so the invariant suite doubles as mixed
traffic — and every result is a JSON-safe record the golden corpus
commits (docs/perf/scenarios.json, guarded by the perf-diff checker).

The catalog (auto-selected per cell by ``applies``; a spec may restrict
with its ``invariants`` list):

- ``finite_gap``        every cell: the objective history is finite.
- ``gt_tracking``       gradient tracking: mean(y) == mean(g_prev) at the
                        final state (the DIGing invariant — Nedić,
                        Olshevsky, Shi '17), tolerance by dtype.
- ``robust_envelope``   attacked robust cells: honest final gap within an
                        envelope factor of the attack-free twin
                        (Karimireddy-style containment).
- ``bhat_degradation``  fault cells: the realized windowed-connectivity
                        B̂ exists (the union graph stays connected), grows
                        with burst length at matched marginal (Koloskova
                        '20's B-connectivity), and the gap-vs-fault-free
                        ratio sits inside a no-free-lunch envelope.
- ``reduction_burst``   iid edge-fault cells: burst_len=1 twin is BITWISE
                        the burst_len=0 (memoryless) program.
- ``reduction_churn``   straggler cells: the mttf=1/q, mttr=1/(1-q) churn
                        twin is BITWISE the iid straggler program.
- ``reduction_zero_budget`` robust-rule cells without attack: robust_b=0
                        twin is BITWISE plain gossip.
- ``reduction_explicit_defaults`` cells that spell out degenerate knobs
                        (τ=1, q=1.0, burst 0): the stripped twin names
                        the SAME experiment — equal config and structural
                        hash, hence one serving cohort. Definitional for
                        a frozen config; its content is guarding the
                        off-point table against default drift. The
                        empirical τ/q/burst bitwise claims live in the
                        reduction_* run comparisons above.
- ``checkpoint_resume`` sync jax cells: interrupt + resume is BITWISE the
                        uninterrupted (equally-segmented) run.
- ``replica_cohort``    replicas>1 cells: the R seed-expanded requests
                        coalesce into one cohort of size R and every
                        replica finishes finite.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Any, Callable, Optional

import numpy as np

from distributed_optimization_tpu.config import ExperimentConfig


@dataclasses.dataclass
class InvariantResult:
    name: str
    passed: bool
    detail: dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict[str, Any]:
        return {"name": self.name, "passed": self.passed,
                "detail": self.detail}


@dataclasses.dataclass(frozen=True)
class Invariant:
    name: str
    applies: Callable[[ExperimentConfig], bool]
    check: Callable[["CellContext"], InvariantResult]
    doc: str = ""


class CellContext:
    """What one invariant check may touch: the executed cell and the
    engine's run services (serving-routed twins, direct backend runs for
    state/checkpoint access, datasets, envelopes)."""

    def __init__(self, cell, config, results, requests, engine, envelopes):
        self.cell = cell
        self.config: ExperimentConfig = config
        self.results = results  # list[BackendRunResult], one per replica
        self.requests = requests  # serving Request records (same order)
        self.engine = engine
        self.envelopes = dict(envelopes)

    @property
    def result(self):
        return self.results[0]

    def envelope(self, name: str, default: float) -> float:
        return float(self.envelopes.get(name, default))

    def run_served(self, config: ExperimentConfig):
        return self.engine.run_served(config)

    def run_direct(self, config: ExperimentConfig, **kwargs):
        return self.engine.run_direct(config, **kwargs)


def _gap(result) -> float:
    return float(result.history.objective[-1])


def _bitwise(a, b) -> dict[str, Any]:
    """Exact-equality comparison of two runs' trajectories."""
    obj_equal = bool(np.array_equal(
        a.history.objective, b.history.objective
    ))
    models_equal = bool(np.array_equal(a.final_models, b.final_models))
    out = {
        "objective_bitwise": obj_equal,
        "final_models_bitwise": models_equal,
    }
    if not (obj_equal and models_equal):
        out["max_abs_objective_dev"] = float(np.max(np.abs(
            np.asarray(a.history.objective)
            - np.asarray(b.history.objective)
        ))) if len(a.history.objective) == len(b.history.objective) else None
    return out


def _fault_free_fields(fields: dict) -> dict:
    out = dict(fields)
    for key in ("edge_drop_prob", "straggler_prob", "burst_len", "mttf",
                "mttr", "rejoin", "participation_rate"):
        out.pop(key, None)
    return out


def _has_fault_process(cfg: ExperimentConfig) -> bool:
    return (
        cfg.edge_drop_prob > 0.0 or cfg.straggler_prob > 0.0
        or cfg.mttf > 0.0 or cfg.participation_rate < 1.0
    )


def _robust_rule_on(cfg: ExperimentConfig) -> bool:
    return cfg.aggregation != "gossip" and cfg.robust_b > 0


# --------------------------------------------------------------- checks


def _check_finite(ctx: CellContext) -> InvariantResult:
    details = []
    ok = True
    for result in ctx.results:
        obj = np.asarray(result.history.objective)
        finite = bool(np.all(np.isfinite(obj)))
        ok = ok and finite and obj.size > 0
        details.append({
            "final_gap": float(obj[-1]) if obj.size else None,
            "all_finite": finite,
        })
    return InvariantResult("finite_gap", ok, {"replicas": details})


def _check_gt_tracking(ctx: CellContext) -> InvariantResult:
    cfg = ctx.config
    res = ctx.run_direct(cfg, return_state=True)
    state = res.final_state or {}
    if "y" not in state or "g_prev" not in state:
        return InvariantResult(
            "gt_tracking", False,
            {"error": "final state carries no y/g_prev leaves"},
        )
    resid = float(np.max(np.abs(
        np.asarray(state["y"]).mean(axis=0)
        - np.asarray(state["g_prev"]).mean(axis=0)
    )))
    tol = ctx.envelope(
        "gt_tracking_tol", 1e-8 if cfg.dtype == "float64" else 5e-3
    )
    return InvariantResult(
        "gt_tracking", resid <= tol, {"residual": resid, "tol": tol},
    )


def _check_robust_envelope(ctx: CellContext) -> InvariantResult:
    cfg = ctx.config
    twin = cfg.replace(
        attack="none", n_byzantine=0, attack_scale=1.0,
        byzantine_placement="uniform",
    )
    clean = ctx.run_served(twin)
    envelope = ctx.envelope("robust_envelope", 5.0)
    gap, gap_clean = _gap(ctx.result), _gap(clean)
    ratio = gap / max(gap_clean, 1e-12)
    passed = math.isfinite(gap) and ratio <= envelope
    return InvariantResult(
        "robust_envelope", passed,
        {"gap_attacked": gap, "gap_attack_free": gap_clean,
         "ratio": ratio, "envelope": envelope},
    )


def _check_bhat_degradation(ctx: CellContext) -> InvariantResult:
    from distributed_optimization_tpu import telemetry

    cfg = ctx.config
    detail: dict[str, Any] = {}
    ok = True
    bhat = telemetry.realized_bhat(cfg)
    detail["bhat"] = None if bhat is None else bhat.get("bhat")
    # (a) connectivity survives: a finite B̂ exists over the horizon.
    if bhat is None or bhat.get("bhat") is None:
        ok = False
        detail["bhat_exists"] = False
    else:
        detail["bhat_exists"] = True
        # (b) burstiness monotonicity at matched marginal (deterministic:
        # same seed, same marginal, longer bursts).
        if cfg.burst_len > 1.0:
            iid = telemetry.realized_bhat(cfg.replace(burst_len=1.0))
            detail["bhat_iid"] = None if iid is None else iid.get("bhat")
            if iid is not None and iid.get("bhat") is not None:
                ok = ok and bhat["bhat"] >= iid["bhat"]
                detail["bhat_monotone_in_burst"] = (
                    bhat["bhat"] >= iid["bhat"]
                )
    # (c) convergence no-free-lunch vs the fault-free twin.
    clean_cfg = ExperimentConfig(**_fault_free_fields(cfg.to_dict()))
    clean = ctx.run_served(clean_cfg)
    gap, gap_clean = _gap(ctx.result), _gap(clean)
    ratio = gap / max(gap_clean, 1e-12)
    lo = ctx.envelope("no_free_lunch_floor", 0.5)
    hi = ctx.envelope("degradation_cap", 200.0)
    in_envelope = math.isfinite(ratio) and lo <= ratio <= hi
    ok = ok and in_envelope
    detail.update({
        "gap_faulty": gap, "gap_fault_free": gap_clean,
        "degradation_ratio": ratio, "envelope": [lo, hi],
        "in_envelope": in_envelope,
    })
    return InvariantResult("bhat_degradation", ok, detail)


# The bitwise reductions compare DIRECT sequential runs on both sides:
# the established bitwise contracts (burst_len=1 == iid, churn at
# mttf=1/q == stragglers, robust_b=0 == gossip) are stated on
# ``jax_backend.run``'s sequential program, and serving-routed twins
# would land in different cohort SHAPES (R=2 vs R=1 vmap programs),
# where XLA's per-shape fusion only guarantees the repo's ≤1e-12 f64
# cross-shape convention — not bit equality (measured ~9e-13 when the
# engine first tried it served).


def _check_reduction_burst(ctx: CellContext) -> InvariantResult:
    a = ctx.run_direct(ctx.config)
    b = ctx.run_direct(ctx.config.replace(burst_len=1.0))
    detail = _bitwise(a, b)
    return InvariantResult(
        "reduction_burst",
        detail["objective_bitwise"] and detail["final_models_bitwise"],
        detail,
    )


def _check_reduction_churn(ctx: CellContext) -> InvariantResult:
    q = ctx.config.straggler_prob
    twin_cfg = ctx.config.replace(
        straggler_prob=0.0, mttf=1.0 / q, mttr=1.0 / (1.0 - q),
    )
    a = ctx.run_direct(ctx.config)
    b = ctx.run_direct(twin_cfg)
    detail = _bitwise(a, b)
    detail["mttf"] = twin_cfg.mttf
    detail["mttr"] = twin_cfg.mttr
    return InvariantResult(
        "reduction_churn",
        detail["objective_bitwise"] and detail["final_models_bitwise"],
        detail,
    )


def _check_reduction_zero_budget(ctx: CellContext) -> InvariantResult:
    base = ctx.config.replace(
        robust_b=0, clip_tau=0.0, robust_impl="auto",
        byzantine_placement="uniform",
    )
    robust_off = ctx.run_direct(base)
    gossip = ctx.run_direct(base.replace(aggregation="gossip"))
    detail = _bitwise(robust_off, gossip)
    detail["aggregation"] = ctx.config.aggregation
    return InvariantResult(
        "reduction_zero_budget",
        detail["objective_bitwise"] and detail["final_models_bitwise"],
        detail,
    )


# The degenerate knobs whose explicit spelling must not change the
# program: value == the knob's "off" point.
_EXPLICIT_DEFAULTS = {
    "local_steps": 1, "participation_rate": 1.0, "burst_len": 0.0,
    "replicas": 1, "worker_mesh": 0,
}


def _explicit_default_keys(fields: dict) -> list[str]:
    return [
        k for k, off in _EXPLICIT_DEFAULTS.items()
        if k in fields and fields[k] == off
    ]


def _check_reduction_explicit_defaults(ctx: CellContext) -> InvariantResult:
    """Spelling out a degenerate knob (τ=1, q=1.0, burst 0, replicas 1,
    mesh 0) must name the SAME experiment as omitting it: the stripped
    twin builds an equal config with an equal structural hash, so the
    serving layer coalesces the two spellings into one cohort/executable.

    Scope, honestly: for a frozen config dataclass this is definitional
    — so the check's real content is guarding the off-point table above
    against drift (a future default change, or a validation rule that
    starts rejecting an explicitly-spelled off value, breaks it loudly).
    No twin RUN is compared: the memoized served result would be the
    cell's own object, and the empirical bitwise reductions live in
    reduction_burst/churn/zero_budget instead.
    """
    keys = _explicit_default_keys(ctx.cell.fields)
    stripped = {
        k: v for k, v in ctx.cell.fields.items() if k not in keys
    }
    try:
        twin_cfg = ExperimentConfig(**_full(stripped))
    except (TypeError, ValueError) as e:
        return InvariantResult(
            "reduction_explicit_defaults", False,
            {"stripped_fields": keys, "twin_rejected": str(e)},
        )
    detail = {
        "stripped_fields": keys,
        "config_equal": twin_cfg == ctx.config,
        "structural_hash_equal": (
            twin_cfg.structural_hash() == ctx.config.structural_hash()
        ),
    }
    return InvariantResult(
        "reduction_explicit_defaults",
        detail["config_equal"] and detail["structural_hash_equal"],
        detail,
    )


def _full(overrides: dict) -> dict:
    from distributed_optimization_tpu.scenarios.validity import full_fields

    return full_fields(overrides)


def _check_checkpoint_resume(ctx: CellContext) -> InvariantResult:
    from distributed_optimization_tpu.utils.checkpoint import (
        CheckpointOptions,
    )

    cfg = ctx.config
    n_evals = cfg.n_iterations // cfg.eval_every
    every = max(1, n_evals // 4)
    half_evals = max(every, (n_evals // 2 // every) * every)
    workdir = ctx.engine.workdir(
        f"ckpt-{ctx.cell.index}-{cfg.structural_hash()}"
    )
    ref = ctx.run_direct(cfg, checkpoint=CheckpointOptions(
        os.path.join(workdir, "ref"), every_evals=every, resume=False,
    ))
    resume_dir = os.path.join(workdir, "resume")
    if cfg.execution == "async":
        # The event schedule is horizon-global, so a shorter-horizon run
        # is a DIFFERENT event sequence — n_iterations is not resumable
        # on the event clock (the RunCheckpointer sidecar pins it).
        # Interrupt instead by dropping every chunk past the midpoint of
        # a full run; resume replays the suffix from the surviving
        # mid-schedule chunk (the PR 3 truncated-chunk fallback path).
        import shutil

        from distributed_optimization_tpu.utils.checkpoint import (
            RunCheckpointer,
        )

        opts = CheckpointOptions(
            resume_dir, every_evals=every, resume=False,
        )
        ctx.run_direct(cfg, checkpoint=opts)
        ck = RunCheckpointer(opts)
        chunks = ck.completed_chunks()
        # Retention (max_to_keep) already dropped the earliest saves;
        # keep only the earliest SURVIVING chunk so the resume genuinely
        # replays a mid-schedule suffix.
        for chunk in chunks[1:]:
            shutil.rmtree(ck._step_dir(chunk), ignore_errors=True)
        half_evals = chunks[0] if chunks else half_evals
    else:
        # The "interrupted" run: half the horizon, then resume to the
        # full horizon from its last saved chunk.
        half_cfg = cfg.replace(n_iterations=half_evals * cfg.eval_every)
        ctx.run_direct(half_cfg, checkpoint=CheckpointOptions(
            resume_dir, every_evals=every, resume=False,
        ))
    resumed = ctx.run_direct(cfg, checkpoint=CheckpointOptions(
        resume_dir, every_evals=every, resume=True,
    ))
    detail = _bitwise(ref, resumed)
    detail["every_evals"] = every
    detail["interrupted_at_iteration"] = half_evals * cfg.eval_every
    return InvariantResult(
        "checkpoint_resume",
        detail["objective_bitwise"] and detail["final_models_bitwise"],
        detail,
    )


def _check_replica_cohort(ctx: CellContext) -> InvariantResult:
    R = ctx.config.replicas
    sizes = [req.cohort_size for req in ctx.requests]
    coalesced = [bool(req.coalesced) for req in ctx.requests]
    gaps = [_gap(r) for r in ctx.results]
    # The R expanded requests must land in ONE coalesced cohort — of at
    # least R (other same-class traffic in the wave legitimately rides
    # the same cohort, so == R would be wrong by design).
    ok = (
        len(ctx.results) == R
        and all(s == sizes[0] and s >= R for s in sizes)
        and all(coalesced)
        and all(math.isfinite(g) for g in gaps)
    )
    return InvariantResult(
        "replica_cohort", ok,
        {"replicas": R, "cohort_sizes": sizes, "coalesced": coalesced,
         "gaps": gaps},
    )


# --------------------------------------------------------------- catalog


def _sync_jax(cfg: ExperimentConfig) -> bool:
    return cfg.backend == "jax" and cfg.execution == "sync"


CATALOG: dict[str, Invariant] = {
    inv.name: inv for inv in (
        Invariant(
            "finite_gap", lambda cfg: True, _check_finite,
            doc="objective history is finite end to end",
        ),
        Invariant(
            "gt_tracking",
            # The DIGing identity mean(y) == mean(g_prev) is preserved by
            # average-preserving mixing ONLY: it survives faults/churn
            # (frozen rejoin) because realized-MH stays doubly stochastic,
            # but Byzantine payloads corrupt the exchanged y rows and
            # screening rules (trimmed mean/median/clipping) are not
            # average-preserving — measured residuals under attack are
            # O(payload), so the invariant's own applicability boundary
            # is plain gossip (the engine smoke that found this is why
            # the catalog encodes it).
            # Applies on BOTH clocks (ISSUE-17): the async event update's
            # per-event telescoping (y_i' picks up g_new − g_prev_i, the
            # pair averages preserve both means) keeps the identity exact
            # at ANY staleness, under event-realized crash/participation
            # faults included — a no-op event changes nothing and a
            # degraded self-exchange averages a row with itself.
            lambda cfg: (
                cfg.algorithm == "gradient_tracking"
                and cfg.backend == "jax"
                and cfg.attack == "none" and cfg.aggregation == "gossip"
                and cfg.rejoin == "frozen"
                and cfg.worker_mesh == 0 and cfg.replicas == 1
                and cfg.tp_degree == 1
            ),
            _check_gt_tracking,
            doc="mean(y) tracks mean(g_prev) at the final state",
        ),
        Invariant(
            "robust_envelope",
            lambda cfg: (
                cfg.attack != "none" and _robust_rule_on(cfg)
                and cfg.replicas == 1
            ),
            _check_robust_envelope,
            doc="honest gap within an envelope of the attack-free twin",
        ),
        Invariant(
            "bhat_degradation",
            lambda cfg: (
                _has_fault_process(cfg) and _sync_jax(cfg)
                and cfg.gossip_schedule == "synchronous"
                and cfg.worker_mesh == 0 and cfg.replicas == 1
                and cfg.resolved_topology_impl() == "dense"
            ),
            _check_bhat_degradation,
            doc="realized B-hat exists, grows with burstiness, and the "
                "fault degradation stays inside the envelope",
        ),
        Invariant(
            "reduction_burst",
            lambda cfg: (
                cfg.edge_drop_prob > 0.0 and cfg.burst_len == 0.0
                and _sync_jax(cfg)
                and cfg.gossip_schedule == "synchronous"
                and cfg.worker_mesh == 0 and cfg.replicas == 1
            ),
            _check_reduction_burst,
            doc="burst_len=1 is bitwise the memoryless iid sampler",
        ),
        Invariant(
            "reduction_churn",
            # Holds on the event clock too (ISSUE-17): the event
            # realization reads the same (seed, horizon)-pure chains at
            # (local_step, worker), and iid stragglers collapse to churn
            # at mttf=1/q bitwise at the CHAIN level, so the realized
            # fire/partner arrays — and hence the scanned program — are
            # identical.
            lambda cfg: (
                cfg.straggler_prob > 0.0 and cfg.mttf == 0.0
                and cfg.backend == "jax"
                and cfg.gossip_schedule == "synchronous"
                and cfg.worker_mesh == 0 and cfg.replicas == 1
            ),
            _check_reduction_churn,
            doc="mttf=1/q, mttr=1/(1-q) churn is bitwise iid stragglers",
        ),
        Invariant(
            "reduction_zero_budget",
            lambda cfg: (
                cfg.aggregation != "gossip" and cfg.attack == "none"
                and _sync_jax(cfg) and cfg.worker_mesh == 0
                and cfg.replicas == 1
            ),
            _check_reduction_zero_budget,
            doc="robust_b=0 degrades bitwise to plain gossip",
        ),
        Invariant(
            "reduction_explicit_defaults",
            lambda cfg: cfg.replicas == 1,
            _check_reduction_explicit_defaults,
            doc="spelling out τ=1/q=1-style off points names the same "
                "experiment (equal config + structural hash — the "
                "coalescing identity; guards the off-point table against "
                "default drift)",
        ),
        Invariant(
            "checkpoint_resume",
            # Async runs checkpoint on the same RunCheckpointer chunk
            # grammar (ISSUE-17): an eval row is a chunk, the event
            # cursor is chunk·eval_every·N, and restore replays the
            # suffix bitwise (prefix-stable schedules + counter-based
            # batch draws).
            lambda cfg: (
                cfg.backend == "jax" and cfg.replicas == 1
                and cfg.worker_mesh == 0 and cfg.tp_degree == 1
                and not cfg.telemetry
                and cfg.n_iterations // cfg.eval_every >= 4
            ),
            _check_checkpoint_resume,
            doc="interrupt + resume is bitwise the uninterrupted "
                "equally-segmented run",
        ),
        Invariant(
            "replica_cohort",
            lambda cfg: cfg.replicas > 1,
            _check_replica_cohort,
            doc="seed-expanded replica requests coalesce into one cohort",
        ),
    )
}


def applicable_invariants(
    cfg: ExperimentConfig, cell_fields: Optional[dict] = None,
    restrict: Optional[tuple[str, ...]] = None,
) -> list[Invariant]:
    """The invariants this cell must satisfy. ``restrict`` (a spec's
    ``invariants`` list) intersects the auto-selection — it never forces
    an inapplicable check onto a cell."""
    out = []
    for inv in CATALOG.values():
        if restrict is not None and inv.name not in restrict:
            continue
        if not inv.applies(cfg):
            continue
        if (
            inv.name == "reduction_explicit_defaults"
            and not _explicit_default_keys(cell_fields or {})
        ):
            continue
        out.append(inv)
    return out
