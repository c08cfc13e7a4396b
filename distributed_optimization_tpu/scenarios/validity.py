"""The queryable validity table of the composition matrix.

The repo's ~10 orthogonal axes (algorithm × topology/impl × faults ×
Byzantine × compression × local steps × participation × execution ×
replicas × worker_mesh) compose under pairwise rules that historically
lived ONLY inside ``ExperimentConfig.__post_init__`` — correct, but
opaque: the only way to ask "is this cell valid, and if not, why?" was to
construct a config and parse the exception. This module is the same rule
set as DATA: every composition rule is a named ``Rule`` with the axes it
couples, a predicate, and the rejection reason, so the scenario engine
can

- pre-filter sampled cells without paying construction on invalid ones,
- count rejections BY RULE (which compositions dominate the invalid
  region), and
- answer ``explain(fields)`` with a structured verdict instead of a
  stringly exception.

Drift discipline (docs/SCENARIOS.md): the table deliberately DUPLICATES
``__post_init__`` — a table that called the constructor would be
unqueryable, and a constructor that read the table would put jax-free
config behind an import of this package. The contract that keeps the two
honest is ``ExperimentConfig.construction_error``: tests (and the golden
corpus bench) sample hundreds of seeded cells across every axis and
assert verdict-for-verdict agreement, so a rule added to one side without
the other fails loudly instead of silently mis-classifying cells.
"""

from __future__ import annotations

import dataclasses
import difflib
import math
from typing import Any, Callable, Mapping, Optional

from distributed_optimization_tpu.config import (
    AGGREGATIONS,
    ALGORITHMS,
    ATTACKS,
    BACKENDS,
    BYZANTINE_PLACEMENTS,
    COMPRESSED_ALGORITHMS,
    COMPRESSIONS,
    DIRECTED_TOPOLOGIES,
    EXECUTIONS,
    LATENCY_MODELS,
    LOCAL_STEP_ALGORITHMS,
    NEIGHBOR_TOPOLOGIES,
    PROBLEM_TYPES,
    REJOINS,
    TOPOLOGIES,
    ExperimentConfig,
)

CONFIG_FIELDS: tuple[str, ...] = tuple(
    f.name for f in dataclasses.fields(ExperimentConfig)
)
DEFAULT_FIELDS: dict[str, Any] = {
    f.name: f.default for f in dataclasses.fields(ExperimentConfig)
}

# The ten orthogonal axes of the composition matrix (ISSUE-12), named for
# reporting: each validity rule tags the axes it couples so rejection
# counters and docs group by composition, not by field soup.
AXES: tuple[str, ...] = (
    "algorithm", "topology", "faults", "byzantine", "compression",
    "local_steps", "participation", "execution", "replicas", "worker_mesh",
)


class UnknownFieldError(ValueError):
    """A field name outside the ExperimentConfig schema, with the nearest
    valid field attached — the structured form of a typo."""

    def __init__(self, field: str, *, context: str = "field"):
        self.field = field
        matches = difflib.get_close_matches(field, CONFIG_FIELDS, n=1)
        self.suggestion = matches[0] if matches else None
        hint = (
            f"; did you mean {self.suggestion!r}?" if self.suggestion
            else "; valid fields are the ExperimentConfig schema"
        )
        super().__init__(f"unknown {context} {field!r}{hint}")


@dataclasses.dataclass(frozen=True)
class Rule:
    """One composition rule: ``when(fields)`` is True where the rule
    REJECTS the cell, ``reason(fields)`` the exact rejection message."""

    name: str
    axes: tuple[str, ...]
    when: Callable[[dict], bool]
    reason: Callable[[dict], str]
    doc: str = ""


@dataclasses.dataclass(frozen=True)
class Verdict:
    """``explain``'s answer: valid, or the first rejecting rule."""

    valid: bool
    rule: Optional[str] = None
    axes: tuple[str, ...] = ()
    reason: str = "valid"

    def __bool__(self) -> bool:
        return self.valid


VALID = Verdict(valid=True)


def _robust_rule_on(f: dict) -> bool:
    return f["aggregation"] != "gossip" and f["robust_b"] > 0


def _is_perfect_square(n: int) -> bool:
    s = int(math.isqrt(int(n)))
    return s * s == n


def _r(name, axes, when, reason, doc=""):
    return Rule(name=name, axes=tuple(axes), when=when, reason=reason,
                doc=doc)


def _domain(field: str, axis: str, values) -> Rule:
    vals = tuple(values)
    return _r(
        f"domain:{field}", (axis,),
        lambda f, _field=field, _vals=vals: f[_field] not in _vals,
        lambda f, _field=field, _vals=vals: (
            f"unknown {_field} {f[_field]!r} (valid: {list(_vals)})"
        ),
        doc=f"{field} must be one of {list(vals)}",
    )


# Ordered like ``ExperimentConfig.__post_init__`` so the first rejecting
# rule names the same violation construction would raise first.
RULES: tuple[Rule, ...] = (
    # ---------------------------------------------------------- domains
    _domain("problem_type", "algorithm", PROBLEM_TYPES),
    _domain("algorithm", "algorithm", ALGORITHMS),
    _domain("topology", "topology", TOPOLOGIES),
    _domain("backend", "execution", BACKENDS),
    _domain("mixing_impl", "topology",
            ("auto", "dense", "stencil", "gather")),
    _domain("sampling_impl", "execution", ("auto", "gather", "dense")),
    _domain("lr_schedule", "algorithm", ("auto", "sqrt_decay", "constant")),
    _domain("compression", "compression", COMPRESSIONS),
    # ------------------------------------------------------ compression
    _r("compression×algorithm", ("compression", "algorithm"),
       lambda f: f["compression"] != "none"
       and f["algorithm"] not in COMPRESSED_ALGORITHMS,
       lambda f: (
           f"compression={f['compression']!r} only takes effect with the "
           f"error-feedback gossip algorithms {COMPRESSED_ALGORITHMS}"
       ),
       doc="error-feedback compression needs a gossip recursion that "
           "carries the shared estimate"),
    _r("compression:k", ("compression",),
       lambda f: f["compression"] != "none" and f["compression_k"] <= 0,
       lambda f: "compression_k must be positive with compression on"),
    _r("compression×faults", ("compression", "faults"),
       lambda f: f["compression"] != "none" and (
           f["edge_drop_prob"] > 0.0 or f["straggler_prob"] > 0.0
           or f["mttf"] > 0.0 or f["gossip_schedule"] != "synchronous"),
       lambda f: (
           "compressed gossip does not compose with time-varying graphs: "
           "a dropped exchange leaves the neighbor's error-feedback "
           "estimate stale"
       )),
    _r("compression×byzantine", ("compression", "byzantine"),
       lambda f: f["compression"] != "none" and (
           f["attack"] != "none" or f["aggregation"] != "gossip"),
       lambda f: (
           "compressed gossip does not compose with Byzantine injection / "
           "robust aggregation: screening operates on models, "
           "error-feedback exchanges compressed differences"
       )),
    # ----------------------------------------------------- scalar sanity
    _r("domain:huber_delta", ("algorithm",),
       lambda f: f["huber_delta"] <= 0.0,
       lambda f: f"huber_delta must be positive, got {f['huber_delta']}"),
    _r("domain:n_classes", ("algorithm",),
       lambda f: f["n_classes"] < 2,
       lambda f: f"n_classes must be >= 2, got {f['n_classes']}"),
    _r("domain:choco_gamma", ("compression", "algorithm"),
       lambda f: (f["algorithm"] == "choco" or f["compression"] != "none")
       and not 0.0 < f["choco_gamma"] <= 1.0,
       lambda f: f"choco_gamma must be in (0, 1], got {f['choco_gamma']}"),
    _domain("partition", "algorithm", ("sorted", "shuffled")),
    _domain("attack", "byzantine", ATTACKS),
    _domain("aggregation", "byzantine", AGGREGATIONS),
    # -------------------------------------------------------- byzantine
    _r("domain:n_byzantine", ("byzantine",),
       lambda f: f["n_byzantine"] < 0,
       lambda f: f"n_byzantine must be >= 0, got {f['n_byzantine']}"),
    _r("byzantine:attack↔count", ("byzantine",),
       lambda f: (f["attack"] == "none") != (f["n_byzantine"] == 0),
       lambda f: (
           f"attack={f['attack']!r} and n_byzantine={f['n_byzantine']} "
           "must be set together"
       ),
       doc="an attack needs attackers, and Byzantine workers need a "
           "payload to send"),
    _r("byzantine:honest_majority_floor", ("byzantine",),
       lambda f: f["attack"] != "none"
       and f["n_byzantine"] >= f["n_workers"],
       lambda f: (
           f"n_byzantine ({f['n_byzantine']}) must leave at least one "
           f"honest worker out of {f['n_workers']}"
       )),
    _r("byzantine:scale_positive", ("byzantine",),
       lambda f: f["attack"] != "none" and f["attack_scale"] <= 0.0,
       lambda f: f"attack_scale must be positive, got {f['attack_scale']}"),
    _r("byzantine:scale_without_attack", ("byzantine",),
       lambda f: f["attack"] == "none" and f["attack_scale"] != 1.0,
       lambda f: (
           f"attack_scale={f['attack_scale']} only takes effect with an "
           "attack"
       )),
    _r("domain:robust_b", ("byzantine",),
       lambda f: f["robust_b"] < 0,
       lambda f: f"robust_b must be >= 0, got {f['robust_b']}"),
    _r("byzantine:budget_without_rule", ("byzantine",),
       lambda f: f["robust_b"] > 0 and f["aggregation"] == "gossip",
       lambda f: (
           f"robust_b={f['robust_b']} only takes effect with a robust "
           "aggregation rule"
       )),
    _domain("byzantine_placement", "byzantine", BYZANTINE_PLACEMENTS),
    _r("byzantine:placement_without_budget", ("byzantine",),
       lambda f: f["byzantine_placement"] == "within_budget"
       and not (f["attack"] != "none" and _robust_rule_on(f)),
       lambda f: (
           "byzantine_placement='within_budget' needs attackers to place "
           "(an attack) and a budget to place them within (a robust "
           "aggregation rule with robust_b >= 1)"
       )),
    _domain("robust_impl", "byzantine", ("auto", "dense", "gather")),
    _r("byzantine:impl_without_rule", ("byzantine",),
       lambda f: f["robust_impl"] != "auto" and not _robust_rule_on(f),
       lambda f: (
           f"robust_impl={f['robust_impl']!r} selects the execution form "
           "of a robust aggregation rule; without one it would be "
           "silently ignored"
       )),
    _r("domain:clip_tau", ("byzantine",),
       lambda f: f["clip_tau"] < 0.0,
       lambda f: f"clip_tau must be >= 0, got {f['clip_tau']}"),
    _r("byzantine:clip_tau_without_clipping", ("byzantine",),
       lambda f: f["clip_tau"] > 0.0
       and f["aggregation"] != "clipped_gossip",
       lambda f: (
           "clip_tau only applies to aggregation='clipped_gossip'"
       )),
    _r("byzantine×schedule", ("byzantine", "topology"),
       lambda f: f["aggregation"] != "gossip"
       and f["gossip_schedule"] != "synchronous",
       lambda f: (
           f"aggregation={f['aggregation']!r} screens multiple received "
           "messages per round; matching schedules deliver at most one"
       )),
    # ------------------------------------------------------------ faults
    _r("domain:edge_drop_prob", ("faults",),
       lambda f: not 0.0 <= f["edge_drop_prob"] < 1.0,
       lambda f: (
           f"edge_drop_prob must be in [0, 1), got {f['edge_drop_prob']}"
       )),
    _r("domain:straggler_prob", ("faults",),
       lambda f: not 0.0 <= f["straggler_prob"] < 1.0,
       lambda f: (
           f"straggler_prob must be in [0, 1), got {f['straggler_prob']}"
       )),
    _r("domain:burst_len", ("faults",),
       lambda f: f["burst_len"] != 0.0 and f["burst_len"] < 1.0,
       lambda f: (
           f"burst_len must be 0 (iid edge drops) or >= 1, got "
           f"{f['burst_len']}"
       )),
    _r("faults:burst_without_drops", ("faults",),
       lambda f: f["burst_len"] != 0.0 and f["edge_drop_prob"] == 0.0,
       lambda f: (
           f"burst_len={f['burst_len']} shapes the edge-failure process "
           "and needs edge_drop_prob > 0"
       )),
    _r("faults:mttf↔mttr", ("faults",),
       lambda f: (f["mttf"] > 0.0) != (f["mttr"] > 0.0),
       lambda f: (
           f"mttf ({f['mttf']}) and mttr ({f['mttr']}) must be set "
           "together"
       )),
    _r("domain:mttf_mttr_sign", ("faults",),
       lambda f: f["mttf"] < 0.0 or f["mttr"] < 0.0,
       lambda f: (
           f"mttf/mttr must be >= 0, got ({f['mttf']}, {f['mttr']})"
       )),
    _r("faults:churn_holding_times", ("faults",),
       lambda f: f["mttf"] > 0.0 and (f["mttf"] < 1.0 or f["mttr"] < 1.0),
       lambda f: (
           "mttf/mttr are mean holding times in rounds and must be >= 1"
       )),
    _r("faults:churn×stragglers", ("faults",),
       lambda f: f["mttf"] >= 1.0 and f["mttr"] >= 1.0
       and f["straggler_prob"] > 0.0,
       lambda f: (
           "crash-recovery churn (mttf/mttr) replaces iid stragglers; "
           "set straggler_prob=0"
       )),
    _r("faults:churn×schedule", ("faults", "topology"),
       lambda f: f["mttf"] >= 1.0 and f["mttr"] >= 1.0
       and f["straggler_prob"] == 0.0
       and f["gossip_schedule"] != "synchronous",
       lambda f: (
           "crash-recovery churn requires gossip_schedule='synchronous'"
       )),
    _domain("rejoin", "faults", REJOINS),
    _r("faults:restart×byzantine", ("faults", "byzantine"),
       lambda f: f["rejoin"] == "neighbor_restart"
       and (f["attack"] != "none" or _robust_rule_on(f)),
       lambda f: (
           "rejoin='neighbor_restart' does not compose with Byzantine "
           "injection / robust aggregation: the warm restart averages "
           "raw neighbor rows, bypassing attacks and screening"
       )),
    _r("faults:rejoin_without_churn", ("faults",),
       lambda f: f["rejoin"] != "frozen" and f["mttf"] == 0.0,
       lambda f: (
           f"rejoin={f['rejoin']!r} only takes effect with crash-recovery "
           "churn (mttf/mttr)"
       )),
    # ------------------------------------------------------- local steps
    _r("domain:local_steps", ("local_steps",),
       lambda f: f["local_steps"] < 1,
       lambda f: f"local_steps must be >= 1, got {f['local_steps']}"),
    _r("local_steps×algorithm", ("local_steps", "algorithm"),
       lambda f: f["local_steps"] > 1
       and f["algorithm"] not in LOCAL_STEP_ALGORITHMS,
       lambda f: (
           f"local_steps={f['local_steps']} is unsupported for "
           f"{f['algorithm']!r}: only {LOCAL_STEP_ALGORITHMS} survive τ "
           "local descents between exchanges"
       )),
    _r("local_steps×compression", ("local_steps", "compression"),
       lambda f: f["local_steps"] > 1
       and f["algorithm"] in LOCAL_STEP_ALGORITHMS
       and f["compression"] != "none",
       lambda f: (
           "local_steps > 1 does not compose with compressed gossip"
       )),
    _r("local_steps×cpp", ("local_steps", "execution"),
       lambda f: f["local_steps"] > 1 and f["backend"] == "cpp",
       lambda f: "local_steps > 1 is unsupported on the cpp backend"),
    _r("local_steps×tp", ("local_steps",),
       lambda f: f["local_steps"] > 1 and f["tp_degree"] > 1,
       lambda f: (
           "local_steps > 1 does not compose with tp_degree > 1"
       )),
    # ----------------------------------------------------- participation
    _r("domain:participation_rate", ("participation",),
       lambda f: not 0.0 < f["participation_rate"] <= 1.0,
       lambda f: (
           f"participation_rate must be in (0, 1], got "
           f"{f['participation_rate']}"
       )),
    _r("participation×centralized", ("participation", "algorithm"),
       lambda f: f["participation_rate"] < 1.0
       and f["algorithm"] == "centralized",
       lambda f: (
           "participation_rate models client sampling of peer exchanges; "
           "the centralized pattern has no peer edges"
       )),
    _r("participation×schedule", ("participation", "topology"),
       lambda f: f["participation_rate"] < 1.0
       and f["algorithm"] != "centralized"
       and f["gossip_schedule"] != "synchronous",
       lambda f: (
           "participation_rate < 1 requires gossip_schedule='synchronous'"
       )),
    _r("participation×compression", ("participation", "compression"),
       lambda f: f["participation_rate"] < 1.0
       and f["compression"] != "none",
       lambda f: (
           "participation_rate < 1 does not compose with compressed "
           "gossip"
       )),
    _r("participation×cpp", ("participation", "execution"),
       lambda f: f["participation_rate"] < 1.0 and f["backend"] == "cpp",
       lambda f: (
           "participation_rate < 1 is unsupported on the cpp backend"
       )),
    _r("participation×tp", ("participation",),
       lambda f: f["participation_rate"] < 1.0 and f["tp_degree"] > 1,
       lambda f: (
           "participation_rate < 1 does not compose with tp_degree > 1"
       )),
    # ----------------------------------------------------- topology impl
    _domain("topology_impl", "topology", ("auto", "dense", "neighbor")),
    _r("neighbor×fully_connected", ("topology",),
       lambda f: f["topology_impl"] == "neighbor"
       and f["topology"] == "fully_connected",
       lambda f: (
           "topology_impl='neighbor' with 'fully_connected' would "
           "allocate the quadratic [N, N-1] table the matrix-free path "
           "exists to avoid"
       )),
    _r("neighbor×topology", ("topology",),
       lambda f: f["topology_impl"] == "neighbor"
       and f["topology"] != "fully_connected"
       and f["topology"] not in NEIGHBOR_TOPOLOGIES,
       lambda f: (
           f"topology_impl='neighbor' supports {NEIGHBOR_TOPOLOGIES}; "
           f"{f['topology']!r} has no matrix-free constructor"
       )),
    _r("neighbor×backend", ("topology", "execution"),
       lambda f: f["topology_impl"] == "neighbor"
       and f["topology"] in NEIGHBOR_TOPOLOGIES and f["backend"] != "jax",
       lambda f: (
           "topology_impl='neighbor' is a jax-backend capability"
       )),
    _r("neighbor×mixing_impl", ("topology",),
       lambda f: f["topology_impl"] == "neighbor"
       and f["topology"] in NEIGHBOR_TOPOLOGIES and f["backend"] == "jax"
       and f["mixing_impl"] not in ("auto", "gather", "stencil"),
       lambda f: (
           "topology_impl='neighbor' never materializes the [N, N] "
           f"matrices mixing_impl={f['mixing_impl']!r} consumes"
       )),
    _r("neighbor×robust_impl", ("topology", "byzantine"),
       lambda f: f["topology_impl"] == "neighbor"
       and f["topology"] in NEIGHBOR_TOPOLOGIES and f["backend"] == "jax"
       and (f["attack"] != "none" or _robust_rule_on(f))
       and f["robust_impl"] not in ("auto", "gather"),
       lambda f: (
           "topology_impl='neighbor' runs robust aggregation in gather "
           f"form; robust_impl={f['robust_impl']!r} materializes "
           "[N, N] objects the matrix-free path never builds"
       )),
    _r("neighbor×schedule", ("topology",),
       lambda f: f["topology_impl"] == "neighbor"
       and f["topology"] in NEIGHBOR_TOPOLOGIES and f["backend"] == "jax"
       and f["gossip_schedule"] != "synchronous",
       lambda f: (
           "topology_impl='neighbor' requires "
           "gossip_schedule='synchronous'"
       )),
    _r("neighbor×tp", ("topology",),
       lambda f: f["topology_impl"] == "neighbor"
       and f["topology"] in NEIGHBOR_TOPOLOGIES and f["backend"] == "jax"
       and f["tp_degree"] > 1,
       lambda f: (
           "topology_impl='neighbor' does not compose with tp_degree > 1"
       )),
    # ------------------------------------------------------- worker mesh
    _r("domain:worker_mesh", ("worker_mesh",),
       lambda f: f["worker_mesh"] < 0 or f["worker_mesh"] == 1,
       lambda f: (
           f"worker_mesh must be 0 (unsharded) or >= 2 devices, got "
           f"{f['worker_mesh']}"
       )),
    _r("mesh×backend", ("worker_mesh", "execution"),
       lambda f: f["worker_mesh"] >= 2 and f["backend"] != "jax",
       lambda f: (
           "worker_mesh shards the worker axis over a jax device mesh"
       )),
    _r("mesh×centralized", ("worker_mesh", "algorithm"),
       lambda f: f["worker_mesh"] >= 2 and f["backend"] == "jax"
       and f["algorithm"] == "centralized",
       lambda f: (
           "worker_mesh shards the gossip neighbor tables; the "
           "centralized pattern has no peer graph to shard"
       )),
    _r("mesh:divisibility", ("worker_mesh",),
       lambda f: f["worker_mesh"] >= 2 and f["backend"] == "jax"
       and f["algorithm"] != "centralized"
       and f["n_workers"] % f["worker_mesh"] != 0,
       lambda f: (
           f"worker_mesh={f['worker_mesh']} must divide n_workers "
           f"({f['n_workers']})"
       )),
    _r("mesh×topology", ("worker_mesh", "topology"),
       lambda f: f["worker_mesh"] >= 2 and f["backend"] == "jax"
       and f["algorithm"] != "centralized"
       and f["n_workers"] % f["worker_mesh"] == 0
       and f["topology"] not in NEIGHBOR_TOPOLOGIES,
       lambda f: (
           f"worker_mesh runs the neighbor-table halo-exchange path; "
           f"topology {f['topology']!r} has no matrix-free constructor"
       )),
    _r("mesh×dense_impl", ("worker_mesh", "topology"),
       lambda f: _mesh_base_ok(f) and f["topology_impl"] == "dense",
       lambda f: (
           "worker_mesh shards the [N, k_max] neighbor tables; "
           "topology_impl='dense' materializes the [N, N] matrices"
       )),
    _r("mesh×mixing_impl", ("worker_mesh", "topology"),
       lambda f: _mesh_base_ok(f)
       and f["mixing_impl"] not in ("auto", "gather"),
       lambda f: (
           f"worker_mesh lowers gather mixing to a ppermute halo "
           f"exchange; mixing_impl={f['mixing_impl']!r} has no sharded "
           "form"
       )),
    _r("mesh×async", ("worker_mesh", "execution"),
       lambda f: _mesh_base_ok(f) and f["execution"] == "async",
       lambda f: (
           "worker_mesh does not compose with execution='async'"
       )),
    _r("mesh×schedule", ("worker_mesh", "topology"),
       lambda f: _mesh_base_ok(f)
       and f["gossip_schedule"] != "synchronous",
       lambda f: (
           "worker_mesh requires gossip_schedule='synchronous'"
       )),
    _r("mesh×edge_faults", ("worker_mesh", "faults"),
       lambda f: _mesh_base_ok(f) and f["edge_drop_prob"] > 0.0,
       lambda f: (
           "worker_mesh does not yet compose with per-edge fault "
           "processes (edge_drop_prob/burst_len)"
       )),
    _r("mesh×alie", ("worker_mesh", "byzantine"),
       lambda f: _mesh_base_ok(f) and f["attack"] == "alie",
       lambda f: (
           "worker_mesh does not compose with attack='alie' (the "
           "colluders' global moment reduction breaks sharded bitwise "
           "parity)"
       )),
    _r("mesh×neighbor_restart", ("worker_mesh", "faults"),
       lambda f: _mesh_base_ok(f) and f["rejoin"] == "neighbor_restart",
       lambda f: (
           "worker_mesh does not yet compose with "
           "rejoin='neighbor_restart'"
       )),
    _r("mesh×robust_impl", ("worker_mesh", "byzantine"),
       lambda f: _mesh_base_ok(f)
       and f["robust_impl"] not in ("auto", "gather"),
       lambda f: (
           f"worker_mesh screens in halo-gather form; robust_impl="
           f"{f['robust_impl']!r} materializes [N, N] objects"
       )),
    _r("mesh×robust_telemetry", ("worker_mesh", "byzantine"),
       lambda f: _mesh_base_ok(f) and f["telemetry"]
       and _robust_rule_on(f),
       lambda f: (
           "worker_mesh does not yet compose with the telemetry "
           "robust-activity probe"
       )),
    # mesh×compression and mesh×replicas deleted (ISSUE-18): compressed
    # gossip runs the halo-compressed exchange (only boundary rows of the
    # error-feedback increment cross the wire — collectives.
    # make_halo_compressed_mixing_op), and a worker_mesh run with
    # replicas=R dispatches R sequential mesh runs through run_batch's
    # sequential-mesh path. The mesh+replicas+compression triple stays
    # rejected via the surviving replicas×compression/replicas×choco
    # rules below.
    _r("mesh×tp", ("worker_mesh",),
       lambda f: _mesh_base_ok(f) and f["tp_degree"] > 1,
       lambda f: (
           "worker_mesh and tp_degree > 1 are mutually exclusive"
       )),
    # --------------------------------------------------------- execution
    _domain("execution", "execution", EXECUTIONS),
    _domain("latency_model", "execution", LATENCY_MODELS),
    _r("sync:latency_knobs", ("execution",),
       lambda f: f["execution"] == "sync" and (
           f["latency_model"] != "constant" or f["latency_mean"] != 1.0
           or f["latency_tail"] != 0.0),
       lambda f: (
           "latency_model/latency_mean/latency_tail shape the "
           "asynchronous event schedule; execution='sync' would silently "
           "ignore them"
       )),
    _r("async:latency_mean", ("execution",),
       lambda f: f["execution"] == "async" and f["latency_mean"] <= 0.0,
       lambda f: f"latency_mean must be positive, got {f['latency_mean']}"),
    _r("async:lognormal_tail", ("execution",),
       lambda f: f["execution"] == "async"
       and f["latency_model"] == "lognormal" and f["latency_tail"] <= 0.0,
       lambda f: "latency_model='lognormal' needs latency_tail > 0"),
    _r("async:pareto_tail", ("execution",),
       lambda f: f["execution"] == "async"
       and f["latency_model"] == "pareto" and f["latency_tail"] <= 1.0,
       lambda f: "latency_model='pareto' needs latency_tail > 1"),
    _r("async:tail_without_shape", ("execution",),
       lambda f: f["execution"] == "async"
       and f["latency_model"] in ("constant", "exponential")
       and f["latency_tail"] != 0.0,
       lambda f: (
           f"latency_tail only shapes the lognormal/pareto tails; "
           f"latency_model={f['latency_model']!r} would silently ignore it"
       )),
    _r("async×cpp", ("execution",),
       lambda f: f["execution"] == "async" and f["backend"] == "cpp",
       lambda f: "execution='async' is unsupported on the cpp backend"),
    _r("async×algorithm", ("execution", "algorithm"),
       lambda f: f["execution"] == "async" and f["backend"] != "cpp"
       and f["algorithm"] not in ("dsgd", "gradient_tracking"),
       lambda f: (
           f"execution='async' is unsupported for {f['algorithm']!r}: an "
           "event applies ONE worker's update at its realized staleness — "
           "only dsgd and gradient tracking's per-event tracker "
           "telescoping have an event form; use algorithm='dsgd' or "
           "'gradient_tracking'"
       )),
    _r("async×directed", ("execution", "topology"),
       lambda f: f["execution"] == "async"
       and f["topology"] in DIRECTED_TOPOLOGIES,
       lambda f: (
           "execution='async' realizes mutual pairwise exchanges; "
           f"directed topology {f['topology']!r} has one-way links"
       )),
    # ISSUE-17 deleted the async×schedule and async×faults rejections:
    # gossip_schedule now has an event-axis meaning ('synchronous'/
    # 'one_peer' name the sampled mutual matchings, 'round_robin' the
    # deterministic phase rotation) and the round-indexed fault knobs
    # (edge_drop/straggler/mttf/participation) are realized on the event
    # axis by parallel.events.realize_event_faults.  The surviving
    # churn×schedule / participation×schedule rules below still apply.
    _r("async×byzantine", ("execution", "byzantine"),
       lambda f: f["execution"] == "async"
       and (f["attack"] != "none" or _robust_rule_on(f)),
       lambda f: (
           "execution='async' does not compose with Byzantine injection "
           "/ robust aggregation: an event delivers exactly one pairwise "
           "exchange"
       )),
    _r("async×compression", ("execution", "compression"),
       lambda f: f["execution"] == "async" and f["compression"] != "none",
       lambda f: (
           "execution='async' does not compose with compressed gossip"
       )),
    # ISSUE-17 deleted async×local_steps: τ local descents fuse into one
    # event (the firing worker chains τ stale-read minibatch steps before
    # its pairwise exchange), so the round-based lever composes.
    _r("async×tp_replicas", ("execution", "replicas"),
       lambda f: f["execution"] == "async"
       and (f["tp_degree"] > 1 or f["replicas"] > 1),
       lambda f: (
           "execution='async' is a sequential scan over a totally "
           "ordered event schedule — run tp_degree=1, replicas=1"
       )),
    _r("async×neighbor", ("execution", "topology"),
       lambda f: f["execution"] == "async"
       and f["topology_impl"] == "neighbor",
       lambda f: (
           "execution='async' scans events over the dense topology "
           "representation"
       )),
    # ISSUE-17 deleted async×telemetry: trace rows now ride the event
    # scan's per-eval outputs (grad/param norms, per-worker event-fire
    # fractions, live-edge rates), so telemetry=True composes.
    # ---------------------------------------------------------- schedule
    _domain("gossip_schedule", "topology",
            ("synchronous", "one_peer", "round_robin")),
    _r("round_robin×faults", ("topology", "faults"),
       lambda f: f["gossip_schedule"] == "round_robin"
       and (f["edge_drop_prob"] > 0.0 or f["straggler_prob"] > 0.0),
       lambda f: (
           "round_robin is a deterministic schedule; combine failure "
           "injection with 'synchronous' or 'one_peer'"
       )),
    _domain("dtype", "execution", ("float32", "float64", "bfloat16")),
    _domain("matmul_precision", "execution", ("default", "high", "highest")),
    # ------------------------------------------------------ shape sanity
    _r("domain:n_workers", ("topology",),
       lambda f: f["n_workers"] <= 0,
       lambda f: "n_workers must be positive"),
    _r("domain:informative_features", ("algorithm",),
       lambda f: f["n_informative_features"] > f["n_features"],
       lambda f: (
           f"n_informative_features ({f['n_informative_features']}) "
           f"cannot exceed n_features ({f['n_features']})"
       )),
    _r("domain:eval_every", ("execution",),
       lambda f: f["eval_every"] <= 0,
       lambda f: "eval_every must be positive"),
    _r("domain:scan_unroll", ("execution",),
       lambda f: f["scan_unroll"] < 0,
       lambda f: "scan_unroll must be >= 0 (0 = auto)"),
    _r("cadence:divisibility", ("execution",),
       lambda f: f["eval_every"] > 0
       and f["n_iterations"] % f["eval_every"] != 0,
       lambda f: (
           f"eval_every ({f['eval_every']}) must divide n_iterations "
           f"({f['n_iterations']})"
       )),
    _r("grid:square_worker_count", ("topology",),
       lambda f: f["topology"] == "grid"
       and not _is_perfect_square(f["n_workers"]),
       lambda f: (
           f"grid topology requires a perfect-square worker count, got "
           f"{f['n_workers']}"
       )),
    _r("directed×schedule", ("topology",),
       lambda f: f["topology"] in DIRECTED_TOPOLOGIES
       and f["gossip_schedule"] != "synchronous",
       lambda f: (
           f"gossip_schedule={f['gossip_schedule']!r} realizes mutual "
           "matchings, an undirected construction"
       )),
    _r("directed×algorithm", ("topology", "algorithm"),
       lambda f: f["topology"] in DIRECTED_TOPOLOGIES
       and f["algorithm"] != "push_sum",
       lambda f: (
           f"topology {f['topology']!r} is directed: its column-"
           f"stochastic mixing needs algorithm='push_sum', not "
           f"{f['algorithm']!r}"
       )),
    _r("domain:topology_seed", ("topology",),
       lambda f: f["topology_seed"] < -1,
       lambda f: (
           f"topology_seed must be -1 (follow seed) or >= 0, got "
           f"{f['topology_seed']}"
       )),
    _r("domain:data_seed", ("execution",),
       lambda f: f["data_seed"] < -1,
       lambda f: (
           f"data_seed must be -1 (follow seed) or >= 0, got "
           f"{f['data_seed']}"
       )),
    # ---------------------------------------------------------- replicas
    _r("domain:replicas", ("replicas",),
       lambda f: f["replicas"] < 1,
       lambda f: f"replicas must be >= 1, got {f['replicas']}"),
    _r("replicas×backend", ("replicas", "execution"),
       lambda f: f["replicas"] > 1 and f["backend"] != "jax",
       lambda f: (
           f"replicas={f['replicas']} batches seed replicates through "
           "one vmapped XLA program, which only the jax backend compiles"
       )),
    _r("replicas×choco", ("replicas", "algorithm"),
       lambda f: f["replicas"] > 1 and f["backend"] == "jax"
       and f["algorithm"] == "choco",
       lambda f: (
           "replicas > 1 is unsupported for 'choco': its compressor "
           "stream derives from config.seed internally"
       )),
    _r("replicas×compression", ("replicas", "compression"),
       lambda f: f["replicas"] > 1 and f["backend"] == "jax"
       and f["compression"] != "none",
       lambda f: (
           "replicas > 1 is unsupported with compressed gossip: the "
           "compressor stream derives from config.seed internally"
       )),
    # --------------------------------------------------- tensor parallel
    _r("domain:tp_degree", ("worker_mesh",),
       lambda f: f["tp_degree"] < 1,
       lambda f: f"tp_degree must be >= 1, got {f['tp_degree']}"),
    _r("tp×backend", ("worker_mesh", "execution"),
       lambda f: f["tp_degree"] > 1 and f["backend"] != "jax",
       lambda f: "tp_degree > 1 shards the model over a jax device mesh"),
    _r("tp×problem", ("worker_mesh", "algorithm"),
       lambda f: f["tp_degree"] > 1 and f["backend"] == "jax"
       and f["problem_type"] != "softmax",
       lambda f: (
           f"tp_degree={f['tp_degree']} shards the softmax classifier; "
           f"problem_type={f['problem_type']!r} has no model axis"
       )),
    _r("tp×algorithm", ("worker_mesh", "algorithm", "topology"),
       lambda f: f["tp_degree"] > 1 and f["backend"] == "jax"
       and f["problem_type"] == "softmax"
       and (f["algorithm"] != "dsgd" or f["topology"] != "ring"),
       lambda f: (
           "the tensor-parallel path implements D-SGD ring gossip only"
       )),
    _r("tp:class_divisibility", ("worker_mesh",),
       lambda f: f["tp_degree"] > 1 and f["backend"] == "jax"
       and f["problem_type"] == "softmax" and f["algorithm"] == "dsgd"
       and f["topology"] == "ring"
       and f["n_classes"] % f["tp_degree"] != 0,
       lambda f: (
           f"tp_degree={f['tp_degree']} must divide n_classes "
           f"({f['n_classes']})"
       )),
    _r("tp×faults_byzantine", ("worker_mesh", "faults", "byzantine"),
       lambda f: f["tp_degree"] > 1 and f["backend"] == "jax" and (
           f["edge_drop_prob"] > 0.0 or f["straggler_prob"] > 0.0
           or f["mttf"] > 0.0 or f["gossip_schedule"] != "synchronous"
           or f["attack"] != "none" or f["aggregation"] != "gossip"),
       lambda f: (
           "tp_degree > 1 does not compose with fault injection, "
           "matching schedules, or Byzantine machinery"
       )),
    _r("tp×compression", ("worker_mesh", "compression"),
       lambda f: f["tp_degree"] > 1 and f["backend"] == "jax"
       and f["compression"] != "none",
       lambda f: (
           "tp_degree > 1 does not compose with compressed gossip"
       )),
    _r("tp×replicas", ("worker_mesh", "replicas"),
       lambda f: f["tp_degree"] > 1 and f["backend"] == "jax"
       and f["replicas"] > 1,
       lambda f: (
           "tp_degree > 1 and replicas > 1 are mutually exclusive"
       )),
    _r("tp×mixing_impl", ("worker_mesh", "topology"),
       lambda f: f["tp_degree"] > 1 and f["backend"] == "jax"
       and f["mixing_impl"] not in ("auto", "stencil"),
       lambda f: (
           f"tp_degree > 1 realizes ring gossip as its own stencil; "
           f"mixing_impl={f['mixing_impl']!r} would be silently ignored"
       )),
)


def _mesh_base_ok(f: dict) -> bool:
    """worker_mesh >= 2 with the prerequisite layers already satisfied —
    the guard every later mesh×feature rule shares, so each rule fires on
    ITS violation rather than re-reporting an earlier one."""
    return (
        f["worker_mesh"] >= 2 and f["backend"] == "jax"
        and f["algorithm"] != "centralized"
        and f["n_workers"] % f["worker_mesh"] == 0
        and f["topology"] in NEIGHBOR_TOPOLOGIES
    )


def full_fields(overrides: Mapping[str, Any]) -> dict[str, Any]:
    """A complete field map: dataclass defaults + ``overrides``.

    Unknown override names raise ``UnknownFieldError`` (with the nearest
    valid field) — the structured form the spec layer surfaces.
    """
    for name in overrides:
        if name not in DEFAULT_FIELDS:
            raise UnknownFieldError(str(name))
    fields = dict(DEFAULT_FIELDS)
    fields.update(overrides)
    return fields


def explain(cell, *, all_rules: bool = False):
    """Classify one cell of the composition matrix.

    ``cell``: an ``ExperimentConfig``, or a (possibly partial) field
    mapping completed with the config defaults. Returns a ``Verdict`` —
    valid, or the first rejecting rule with its exact reason; with
    ``all_rules=True`` returns the list of EVERY rejecting verdict (a
    cell can violate several composition rules at once)."""
    if isinstance(cell, ExperimentConfig):
        fields = cell.to_dict()
    else:
        fields = full_fields(cell)
    hits = []
    for rule in RULES:
        if rule.when(fields):
            v = Verdict(
                valid=False, rule=rule.name, axes=rule.axes,
                reason=rule.reason(fields),
            )
            if not all_rules:
                return v
            hits.append(v)
    if all_rules:
        return hits
    return VALID


def cross_check(overrides: Mapping[str, Any]) -> Optional[str]:
    """The divergence between this table and ``ExperimentConfig``
    construction for one cell, or None when they agree.

    The drift guard's primitive: tests and the golden-corpus bench run it
    over hundreds of seeded cells and require zero divergences."""
    fields = full_fields(overrides)
    verdict = explain(fields)
    error = ExperimentConfig.construction_error(fields)
    if verdict.valid and error is not None:
        return (
            f"validity table says VALID but construction rejects: {error}"
        )
    if not verdict.valid and error is None:
        return (
            f"validity table rejects ({verdict.rule}: {verdict.reason}) "
            "but construction accepts"
        )
    return None


def rules_by_axis() -> dict[str, list[str]]:
    """Rule names grouped by the axes they couple (docs/SCENARIOS.md's
    catalog view)."""
    out: dict[str, list[str]] = {axis: [] for axis in AXES}
    for rule in RULES:
        for axis in rule.axes:
            out.setdefault(axis, []).append(rule.name)
    return out
