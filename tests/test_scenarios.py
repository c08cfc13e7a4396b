"""Scenario engine (ISSUE-12): validity-table agreement, spec error
paths, seeded generation, the serving-driven engine + invariants, and
the scenarios CLI."""

from __future__ import annotations

import json

import pytest

from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.scenarios import validity
from distributed_optimization_tpu.scenarios.generator import (
    generate,
    merge_cell_fields,
)
from distributed_optimization_tpu.scenarios.spec import (
    SpecError,
    load_spec,
    parse_spec,
)

# --------------------------------------------------------------- fixtures

TINY_BASE = {
    "n_workers": 8, "n_samples": 300, "n_features": 8,
    "n_informative_features": 5, "n_iterations": 40, "eval_every": 10,
    "local_batch_size": 8, "dtype": "float64",
}

# A deliberately wide axis bank covering all 10 orthogonal axes —
# including compositions that MUST be rejected — the agreement sample's
# population.
WIDE_AXES = {
    "algorithm": ["centralized", "dsgd", "gradient_tracking", "extra",
                  "admm", "choco", "push_sum"],
    "topology": [
        {"topology": "ring"}, {"topology": "grid", "n_workers": 16},
        {"topology": "fully_connected"}, {"topology": "erdos_renyi"},
        {"topology": "chain"}, {"topology": "star"},
        {"topology": "directed_ring"},
        {"topology": "ring", "topology_impl": "neighbor"},
        {"topology": "ring", "gossip_schedule": "one_peer"},
        {"topology": "chain", "gossip_schedule": "round_robin"},
    ],
    "faults": [
        {}, {"edge_drop_prob": 0.2},
        {"edge_drop_prob": 0.2, "burst_len": 4.0},
        {"straggler_prob": 0.15}, {"mttf": 40.0, "mttr": 15.0},
        {"mttf": 40.0, "mttr": 15.0, "rejoin": "neighbor_restart"},
        {"burst_len": 3.0}, {"mttf": 40.0},
    ],
    "byzantine": [
        {}, {"attack": "sign_flip", "n_byzantine": 1},
        {"attack": "sign_flip", "n_byzantine": 1,
         "aggregation": "trimmed_mean", "robust_b": 1},
        {"aggregation": "median", "robust_b": 1},
        {"aggregation": "clipped_gossip", "robust_b": 1, "clip_tau": 0.5},
        {"attack": "alie", "n_byzantine": 2, "aggregation": "median",
         "robust_b": 2},
        {"robust_impl": "dense"}, {"aggregation": "trimmed_mean"},
        {"attack": "large_noise"}, {"n_byzantine": 3},
    ],
    "compression": [
        {}, {"compression": "top_k", "compression_k": 4},
        {"compression": "qsgd", "compression_k": 4},
        {"compression": "top_k"},
    ],
    "local_steps": [{}, {"local_steps": 2}, {"local_steps": 4}],
    "participation": [
        {}, {"participation_rate": 0.5}, {"participation_rate": 1.0},
    ],
    "execution": [
        {}, {"execution": "async", "latency_model": "exponential"},
        {"execution": "async", "latency_model": "lognormal",
         "latency_tail": 0.5},
        {"execution": "async", "latency_model": "pareto",
         "latency_tail": 1.5},
        {"execution": "async"}, {"latency_model": "exponential"},
        {"execution": "async", "latency_model": "exponential",
         "backend": "numpy"},
    ],
    "replicas": [{}, {"replicas": 4}],
    "worker_mesh": [
        {}, {"worker_mesh": 2}, {"worker_mesh": 3},
        {"tp_degree": 2, "problem_type": "softmax"},
    ],
}


def wide_spec(**overrides):
    obj = {
        "name": "agreement", "seed": 11, "mode": "sample", "sample": 600,
        "base": dict(TINY_BASE), "axes": WIDE_AXES,
    }
    obj.update(overrides)
    return parse_spec(obj)


def weighted_wide_axes():
    """WIDE_AXES re-weighted toward the 'off' setting of each axis so a
    random cell has a real chance of landing in the VALID region too
    (unweighted, ~10 independent mostly-incompatible axes leave < 1% of
    cells valid — the agreement test must exercise both verdicts)."""
    axes = {k: list(v) for k, v in WIDE_AXES.items()}
    axes["topology"] = [{"topology": "ring"}] * 4 + axes["topology"]
    axes["faults"] = [{}] * 4 + axes["faults"]
    axes["byzantine"] = [{}] * 6 + axes["byzantine"]
    axes["compression"] = [{}] * 2 + axes["compression"]
    axes["execution"] = [{}] * 5 + axes["execution"]
    axes["worker_mesh"] = [{}] * 2 + axes["worker_mesh"]
    axes["replicas"] = [{}] * 2 + axes["replicas"]
    axes["local_steps"] = [{}] + axes["local_steps"]
    axes["participation"] = [{}] + axes["participation"]
    return axes


# --------------------------------------------- validity table + agreement


def test_validity_agreement_500_cell_sample():
    """The acceptance gate: the validity table agrees with
    ``ExperimentConfig`` construction verdict-for-verdict on a >= 500-cell
    seeded sample spanning all 10 axes — zero divergences."""
    sample = generate(wide_spec(sample=700, axes=weighted_wide_axes()))
    assert len(sample.cells) >= 500
    divergences = []
    for cell in sample.cells:
        msg = validity.cross_check(cell.fields)
        if msg is not None:
            divergences.append((cell.fields, msg))
    assert not divergences, divergences[:5]
    counts = sample.counts()
    # The sample must exercise both regions non-trivially (seeded —
    # these are deterministic facts of (axes, seed=11, sample=700)).
    assert counts["valid"] >= 20
    assert counts["rejected"] >= 400
    assert len(counts["rejected_by_rule"]) >= 20


def test_explain_reports_rule_and_reason():
    v = validity.explain(validity.full_fields(
        {"algorithm": "choco", "execution": "async",
         "latency_model": "exponential"}
    ))
    assert not v.valid
    assert v.rule == "async×algorithm"
    assert "dsgd" in v.reason
    assert "execution" in v.axes and "algorithm" in v.axes
    # The exact reason tracks the constructor's own message closely.
    err = ExperimentConfig.construction_error(validity.full_fields(
        {"algorithm": "choco", "execution": "async",
         "latency_model": "exponential"}
    ))
    assert "async" in err and "dsgd" in err


def test_explain_accepts_config_and_reports_all_rules():
    cfg = ExperimentConfig()
    assert validity.explain(cfg).valid
    hits = validity.explain(validity.full_fields({
        "compression": "top_k", "compression_k": 4,
        "edge_drop_prob": 0.2, "attack": "sign_flip", "n_byzantine": 1,
    }), all_rules=True)
    names = {h.rule for h in hits}
    assert "compression×faults" in names
    assert "compression×byzantine" in names
    assert len(hits) >= 2


def test_explain_unknown_field_suggests_nearest():
    with pytest.raises(validity.UnknownFieldError) as ei:
        validity.explain({"particpation_rate": 0.5})
    assert ei.value.suggestion == "participation_rate"
    assert "participation_rate" in str(ei.value)


def test_rules_cover_all_axes():
    by_axis = validity.rules_by_axis()
    for axis in validity.AXES:
        assert by_axis.get(axis), f"axis {axis} has no rules"


# ------------------------------------------------------- spec error paths


def test_spec_malformed_json(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text('{"name": "x", nope')
    with pytest.raises(SpecError, match="malformed JSON"):
        load_spec(p)


def test_spec_yaml_gated_or_parsed(tmp_path):
    p = tmp_path / "spec.yaml"
    p.write_text("name: y\naxes:\n  algorithm: [dsgd]\n")
    try:
        import yaml  # noqa: F401
        has_yaml = True
    except ImportError:
        has_yaml = False
    if has_yaml:
        spec = load_spec(p)
        assert spec.name == "y"
        bad = tmp_path / "bad.yaml"
        bad.write_text("name: [unclosed\n")
        with pytest.raises(SpecError, match="malformed YAML"):
            load_spec(bad)
    else:
        with pytest.raises(SpecError, match="YAML"):
            load_spec(p)


def test_spec_unknown_toplevel_field_suggestion():
    with pytest.raises(SpecError) as ei:
        parse_spec({"name": "x", "axes": {"algorithm": ["dsgd"]},
                    "modee": "sample"})
    assert ei.value.field == "modee"
    assert ei.value.suggestion == "mode"


def test_spec_unknown_axis_suggests_nearest_field():
    with pytest.raises(SpecError) as ei:
        parse_spec({"name": "x", "axes": {"algoritm": ["dsgd"]}})
    assert ei.value.suggestion == "algorithm"


def test_spec_unknown_field_inside_composite_axis():
    with pytest.raises(SpecError) as ei:
        parse_spec({"name": "x", "axes": {
            "faults": [{"edge_drop_probability": 0.2}],
        }})
    assert ei.value.field == "edge_drop_probability"
    assert ei.value.suggestion == "edge_drop_prob"


def test_spec_scalar_inside_composite_axis_blames_the_value():
    with pytest.raises(SpecError, match="must be a field object"):
        parse_spec({"name": "x", "axes": {
            "faults": [{"edge_drop_prob": 0.2}, 0.2],
        }})
    # All-scalar values under a non-field axis: typo path, nearest field
    # suggested AND the composite-dict form explained.
    with pytest.raises(SpecError, match="field objects") as ei:
        parse_spec({"name": "x", "axes": {"algoritm": [1, 2]}})
    assert ei.value.suggestion == "algorithm"


def test_spec_unknown_base_field():
    with pytest.raises(SpecError) as ei:
        parse_spec({"name": "x", "base": {"n_worker": 8},
                    "axes": {"algorithm": ["dsgd"]}})
    assert ei.value.suggestion == "n_workers"


def test_spec_shape_errors():
    with pytest.raises(SpecError, match="non-empty string 'name'"):
        parse_spec({"axes": {"algorithm": ["dsgd"]}})
    with pytest.raises(SpecError, match="mode must be one of"):
        parse_spec({"name": "x", "mode": "enumerat",
                    "axes": {"algorithm": ["dsgd"]}})
    with pytest.raises(SpecError, match="non-empty 'axes'"):
        parse_spec({"name": "x"})
    with pytest.raises(SpecError, match="non-empty list"):
        parse_spec({"name": "x", "axes": {"algorithm": []}})
    with pytest.raises(SpecError, match="sample must be a positive"):
        parse_spec({"name": "x", "sample": 0,
                    "axes": {"algorithm": ["dsgd"]}})
    with pytest.raises(SpecError, match="must be a scalar"):
        parse_spec({"name": "x", "base": {"n_workers": [8]},
                    "axes": {"algorithm": ["dsgd"]}})
    with pytest.raises(SpecError) as ei:
        parse_spec({"name": "x", "axes": {"algorithm": ["dsgd"]},
                    "invariants": ["finte_gap"]})
    assert ei.value.suggestion == "finite_gap"


def test_axis_collision_is_a_spec_error():
    spec = parse_spec({"name": "x", "axes": {
        "a": [{"edge_drop_prob": 0.1}],
        "b": [{"edge_drop_prob": 0.2}],
    }})
    with pytest.raises(SpecError, match="both set config field"):
        merge_cell_fields(
            spec, {"a": {"edge_drop_prob": 0.1},
                   "b": {"edge_drop_prob": 0.2}},
        )


# ------------------------------------------------------------- generator


def test_sample_reproducible_and_distinct():
    a = generate(wide_spec(sample=80))
    b = generate(wide_spec(sample=80))
    assert [c.fields for c in a.cells] == [c.fields for c in b.cells]
    keys = [tuple(sorted(c.fields.items())) for c in a.cells]
    assert len(set(keys)) == len(keys)
    c = generate(wide_spec(sample=80, seed=12))
    assert [x.fields for x in c.cells] != [x.fields for x in a.cells]


def test_enumerate_cap_rejects_oversized_product():
    with pytest.raises(SpecError, match="max_cells"):
        generate(wide_spec(mode="enumerate", max_cells=100))


def test_sample_exhausts_small_matrix():
    spec = parse_spec({
        "name": "small", "mode": "sample", "sample": 50,
        "axes": {"algorithm": ["dsgd", "extra"],
                 "topology": ["ring", "chain"]},
    })
    sample = generate(spec)
    assert len(sample.cells) == 4 and sample.exhausted


# ------------------------------------------------- engine + invariants

ENGINE_BASE = dict(TINY_BASE)


def _engine_spec(axes, *, invariants=None, sample=64, mode="enumerate"):
    obj = {
        "name": "engine-test", "seed": 5, "mode": mode, "sample": sample,
        "base": ENGINE_BASE, "axes": axes,
    }
    if invariants is not None:
        obj["invariants"] = invariants
    return parse_spec(obj)


@pytest.fixture(scope="module")
def engine_report():
    """One engine run shared by the assertions below: a small matrix that
    exercises coalescing (eta variants), the warm cache (explicit-default
    twins), faults, robustness, GT, replicas — and every invariant kind
    except the slow checkpoint one (covered separately)."""
    from distributed_optimization_tpu.scenarios.engine import run_scenarios

    spec = _engine_spec(
        {
            "learning_rate_eta0": [0.05, 0.08],
            "scenario": [
                {"algorithm": "dsgd", "local_steps": 1},
                {"algorithm": "dsgd", "straggler_prob": 0.15},
                {"algorithm": "gradient_tracking"},
                {"algorithm": "dsgd", "attack": "sign_flip",
                 "n_byzantine": 1, "aggregation": "trimmed_mean",
                 "robust_b": 1, "partition": "shuffled"},
                {"algorithm": "dsgd", "aggregation": "median",
                 "robust_b": 1},
                {"algorithm": "dsgd", "replicas": 3},
            ],
        },
        invariants=[
            "finite_gap", "gt_tracking", "robust_envelope",
            "bhat_degradation", "reduction_churn",
            "reduction_zero_budget", "reduction_explicit_defaults",
            "replica_cohort",
        ],
    )
    return run_scenarios(spec)


def test_engine_gates_all_pass(engine_report):
    assert engine_report["gates"] == {
        "validity_agreement": True,
        "all_cells_completed": True,
        "all_invariants_passed": True,
        "warm_replay_ok": True,
    }, engine_report["invariants"]
    # The wave really batched, and the replayed class was served warm
    # and bitwise (the serving-identity reduction).
    assert engine_report["serving"]["any_coalesced_cohort"] is True
    replay = engine_report["warm_replay"]
    assert replay["attempted"] and replay["cache_hit"] and replay["bitwise"]
    # One executable reuse per replayed plan (hits count programs, not
    # requests).
    assert engine_report["serving"]["cache"]["hits"] >= 1


def test_engine_ran_every_requested_invariant(engine_report):
    by_name = engine_report["invariants"]["by_name"]
    for name in ("finite_gap", "gt_tracking", "robust_envelope",
                 "bhat_degradation", "reduction_churn",
                 "reduction_zero_budget", "reduction_explicit_defaults",
                 "replica_cohort"):
        assert by_name.get(name, {}).get("checks", 0) >= 1, (name, by_name)
        assert by_name[name]["failures"] == 0


def test_engine_replica_cells_coalesce(engine_report):
    rows = [
        r for r in engine_report["cells"]
        if r.get("valid") and r["overrides"].get("replicas") == 3
    ]
    assert rows
    for row in rows:
        inv = {i["name"]: i for i in row["invariants"]}
        assert inv["replica_cohort"]["passed"]
        sizes = inv["replica_cohort"]["detail"]["cohort_sizes"]
        # One cohort holding all 3 expanded replicas (possibly merged
        # with other same-class wave traffic).
        assert len(sizes) == 3 and len(set(sizes)) == 1 and sizes[0] >= 3


def test_engine_eta_variants_share_a_cohort(engine_report):
    sizes = [
        (r.get("serving") or {}).get("cohort_size")
        for r in engine_report["cells"] if r.get("valid")
    ]
    assert any(s and s >= 2 for s in sizes), sizes


def test_engine_metrics_gauges_reset_per_run(engine_report):
    from distributed_optimization_tpu.observability.metrics_registry import (
        metrics_registry,
    )

    reg = metrics_registry()
    n_cells = engine_report["counts"]["cells"]
    assert reg.gauge("dopt_scenario_cells_sampled").value() == n_cells
    assert (
        reg.gauge("dopt_scenario_invariant_checks").value()
        == engine_report["invariants"]["checks"]
    )
    assert reg.gauge("dopt_scenario_invariant_failures").value() == 0
    # Per-run reset: a fresh (tiny) run replaces the numbers wholesale.
    from distributed_optimization_tpu.scenarios.engine import run_scenarios

    small = run_scenarios(_engine_spec(
        {"algorithm": ["dsgd"]}, invariants=["finite_gap"],
    ))
    assert small["counts"]["cells"] == 1
    assert reg.gauge("dopt_scenario_cells_sampled").value() == 1


def test_engine_checkpoint_resume_invariant():
    from distributed_optimization_tpu.scenarios.engine import run_scenarios

    report = run_scenarios(_engine_spec(
        {"scenario": [{"algorithm": "dsgd"}]},
        invariants=["checkpoint_resume"],
    ))
    by_name = report["invariants"]["by_name"]
    assert by_name["checkpoint_resume"]["checks"] == 1
    assert by_name["checkpoint_resume"]["failures"] == 0


def test_engine_reduction_burst_invariant():
    from distributed_optimization_tpu.scenarios.engine import run_scenarios

    report = run_scenarios(_engine_spec(
        {"scenario": [{"algorithm": "dsgd", "edge_drop_prob": 0.2}]},
        invariants=["finite_gap", "reduction_burst"],
    ))
    assert report["gates"]["all_invariants_passed"]
    assert report["invariants"]["by_name"]["reduction_burst"]["checks"] == 1


def test_engine_surfaces_backend_rejection_as_run_error():
    """A cell that is config-valid but backend-rejected (robust budget >
    min degree) must be reported as a structured run_error, not crash the
    engine or the other cells."""
    from distributed_optimization_tpu.scenarios.engine import run_scenarios

    report = run_scenarios(_engine_spec(
        {"scenario": [
            {"algorithm": "dsgd"},
            {"algorithm": "dsgd", "attack": "sign_flip", "n_byzantine": 1,
             "aggregation": "trimmed_mean", "robust_b": 3},
        ]},
        invariants=["finite_gap"],
    ))
    rows = {r["index"]: r for r in report["cells"]}
    poisoned = [r for r in rows.values() if r.get("run_error")]
    healthy = [r for r in rows.values()
               if r.get("valid") and not r.get("run_error")]
    assert len(poisoned) == 1 and "robust_b" in poisoned[0]["run_error"]
    assert "Traceback" not in poisoned[0]["run_error"]
    assert healthy and all(
        i["passed"] for r in healthy for i in r["invariants"]
    )
    assert not report["gates"]["all_cells_completed"]


# ------------------------------------------------------------------- CLI


def test_cli_explain_valid_and_invalid(capsys):
    from distributed_optimization_tpu.scenarios.__main__ import main

    assert main(["explain", "algorithm=dsgd"]) == 0
    assert "valid" in capsys.readouterr().out
    assert main(["explain", "algorithm=choco", "execution=async",
                 "latency_model=exponential", "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["valid"] is False and out["rule"] == "async×algorithm"


def test_cli_structured_errors_never_traceback(tmp_path, capsys):
    from distributed_optimization_tpu.scenarios.__main__ import main

    assert main(["explain", "algoritm=dsgd"]) == 2
    err = capsys.readouterr().err
    assert "scenarios: error:" in err and "algorithm" in err
    assert "Traceback" not in err

    bad = tmp_path / "bad.json"
    bad.write_text("{nope")
    assert main(["sample", str(bad)]) == 2
    err = capsys.readouterr().err
    assert "malformed JSON" in err and "Traceback" not in err


def test_cli_sample_counts(tmp_path, capsys):
    from distributed_optimization_tpu.scenarios.__main__ import main

    spec = tmp_path / "spec.json"
    spec.write_text(json.dumps({
        "name": "cli", "mode": "enumerate",
        "axes": {
            "algorithm": ["dsgd", "choco"],
            "execution": [{}, {"execution": "async",
                               "latency_model": "exponential"}],
        },
    }))
    assert main(["sample", str(spec), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["counts"]["cells"] == 4
    # dsgd sync, dsgd async, choco sync are valid; choco async is not.
    assert out["counts"]["valid"] == 3
    assert out["counts"]["rejected_by_rule"].get("async×algorithm") == 1
