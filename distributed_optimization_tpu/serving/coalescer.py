"""Request coalescing: structurally identical configs → one run_batch cohort.

The batched program (``jax_backend.run_batch``) executes R configs as ONE
vmapped XLA program when they differ only in per-replica data: seeds and
the ``SWEEPABLE_FIELDS`` scalars (eta0, clip_tau, edge_drop_prob). This
module decides which pending requests may share such a cohort and builds
the ``run_batch`` call for them:

- **grouping**: requests coalesce iff their ``structural_hash`` matches
  AND they name the same dataset (``resolved_data_seed`` — the dataset is
  a traced input, but one cohort shares one data pytree, so requests that
  generate different data cannot ride the same call; pin ``data_seed`` to
  let seed variants share a problem instance, docs/SERVING.md). Requests
  that differ only in a non-sweepable field hash apart and never coalesce.
- **sweep axes**: eta0 is ALWAYS swept (it is pure data), edge_drop_prob
  is swept iff the structural class runs the fault path (> 0 — the zero
  boundary is structural), clip_tau iff the class runs fixed-radius
  clipping. Always sweeping keeps the traced input pytree — and therefore
  the cached executable — identical across cohorts of the same class and
  size, whether or not this particular cohort's values differ.
- **fallback**: configs ``jax_backend.batch_unsupported_reason`` rejects
  (choco, compressed gossip, tensor parallelism,
  non-jax backends) become singleton sequential plans
  executed via ``run_algorithm`` — same rejection logic, no duplicated
  condition list.

Per-request results are the cohort's per-replica ``BackendRunResult``
slices; ``run_batch``'s replica-equivalence contract (replica r ==
``run(cfg_r)`` at ≤ 1e-12 in f64, tests/test_batch.py) is what makes the
served result the standalone result — tests/test_serving.py extends that
assertion to this path end to end.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from distributed_optimization_tpu.config import ExperimentConfig


def structural_group_key(config: ExperimentConfig) -> tuple:
    """The coalescing identity: (structural hash, dataset identity).

    Two requests with equal keys compile to the same program AND consume
    the same generated dataset, so they may share one ``run_batch`` call.
    """
    return (config.structural_hash(), config.resolved_data_seed())


def sweep_fields_for(config: ExperimentConfig) -> tuple[str, ...]:
    """Which sweepable fields ride the replica axis for this structural
    class (see module docstring — the zero boundaries are structural, so
    membership is a class property, not a cohort property)."""
    fields = ["learning_rate_eta0"]
    if config.edge_drop_prob > 0.0:
        fields.append("edge_drop_prob")
    if (
        config.aggregation == "clipped_gossip"
        and config.robust_b > 0
        and config.clip_tau > 0.0
    ):
        fields.append("clip_tau")
    return tuple(fields)


# Shared by ``SimulationService.submit`` (which rejects it up front) and
# ``unbatchable_reason`` (direct plan_cohorts callers) — one wording, no
# drift.
REPLICAS_UNSUPPORTED_REASON = (
    "serving requests carry one trajectory each (replicas == 1); "
    "submit one request per seed and let the coalescer batch them"
)


def unbatchable_reason(config: ExperimentConfig) -> Optional[str]:
    """Why this config must run sequentially, or None when it can batch.

    Delegates to ``jax_backend.batch_unsupported_reason`` — the coalescer
    must agree with the executor about what the executor would reject.
    """
    from distributed_optimization_tpu.backends.jax_backend import (
        batch_unsupported_reason,
    )

    if config.replicas > 1:
        return REPLICAS_UNSUPPORTED_REASON
    return batch_unsupported_reason(config)


@dataclasses.dataclass
class CohortPlan:
    """One planned execution: either a coalesced ``run_batch`` cohort or a
    sequential singleton (``sequential_reason`` set)."""

    requests: list  # objects exposing a .config: ExperimentConfig
    base: ExperimentConfig  # the cohort's program config (first request's)
    seeds: list[int]
    sweep: dict[str, list]
    sequential_reason: Optional[str] = None

    @property
    def size(self) -> int:
        return len(self.requests)

    @property
    def coalesced(self) -> bool:
        return self.sequential_reason is None and self.size > 1


def plan_cohorts(requests, max_cohort: int) -> list[CohortPlan]:
    """Group pending requests into execution plans, submission order
    preserved within each group; groups are chunked at ``max_cohort``.

    ``requests`` are any objects with a ``.config`` attribute (the
    service's Request records, or configs wrapped in a shim for tests).
    """
    if max_cohort < 1:
        raise ValueError(f"max_cohort must be >= 1, got {max_cohort}")
    plans: list[CohortPlan] = []
    groups: dict[tuple, list] = {}
    order: list[tuple] = []
    for req in requests:
        reason = unbatchable_reason(req.config)
        if reason is not None:
            plans.append(CohortPlan(
                requests=[req], base=req.config,
                seeds=[req.config.seed], sweep={},
                sequential_reason=reason,
            ))
            continue
        key = structural_group_key(req.config)
        if key not in groups:
            groups[key] = []
            order.append(key)
        groups[key].append(req)
    for key in order:
        members = groups[key]
        for lo in range(0, len(members), max_cohort):
            chunk = members[lo:lo + max_cohort]
            base = chunk[0].config
            sweep = {
                f: [getattr(r.config, f) for r in chunk]
                for f in sweep_fields_for(base)
            }
            plans.append(CohortPlan(
                requests=chunk, base=base,
                seeds=[r.config.seed for r in chunk], sweep=sweep,
            ))
    return plans


def execute_plan(
    plan: CohortPlan, dataset, f_opt: float, *, executable_cache=None,
    collect_metrics: bool = True, progress_factory=None,
    cohort_progress_cb=None, progress_every: int = 1,
):
    """Run one plan; returns the per-request ``BackendRunResult`` list
    (plan order). Coalesced plans go through ``run_batch`` and slice per
    replica; sequential plans through ``run_algorithm`` one at a time.

    Progress streaming (ISSUE-10): ``progress_factory(request)`` builds a
    per-request heartbeat callback for sequential plans (jax, tp=1 only —
    the other entry points have no chunked form); ``cohort_progress_cb``
    receives the batched cohort's heartbeats (per-replica gaps attached —
    the service fans them out to each request's stream).
    """
    if plan.sequential_reason is not None:
        from distributed_optimization_tpu.backends.base import run_algorithm

        out = []
        for req in plan.requests:
            kwargs = {}
            if req.config.backend == "jax" and req.config.tp_degree == 1:
                # The sequential jax path still reuses identical-program
                # compiles; numpy/cpp/TP entry points take no cache.
                kwargs["executable_cache"] = executable_cache
                if progress_factory is not None:
                    cb = progress_factory(req)
                    if cb is not None:
                        kwargs["progress_cb"] = cb
                        kwargs["progress_every"] = progress_every
            out.append(run_algorithm(req.config, dataset, f_opt, **kwargs))
        return out
    from distributed_optimization_tpu.backends import jax_backend

    batch = jax_backend.run_batch(
        plan.base, dataset, f_opt,
        seeds=plan.seeds, sweep=plan.sweep,
        collect_metrics=collect_metrics,
        executable_cache=executable_cache,
        progress_cb=cohort_progress_cb,
        progress_every=progress_every,
    )
    return list(batch.results)
