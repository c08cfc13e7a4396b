"""Flight-recorder tests (ISSUE-5, docs/OBSERVABILITY.md).

Four guarantees are pinned here:

1. OFF/ON bitwise parity — the trace buffers feed the scan's stacked
   outputs only, so telemetry on or off yields bitwise-identical
   trajectories on the sequential, replica-batched, chunked, and numpy
   paths (and the no-telemetry program is structurally the pre-PR one).
2. Schema parity — the jax backend and the numpy oracle emit EXACTLY the
   ``telemetry.TRACE_FIELDS`` keys, shapes and dtypes; under an injected
   batch schedule in float64 the trace VALUES agree too.
3. ``RunTrace`` manifests round-trip through JSON and reject unknown /
   missing keys and foreign schema versions.
4. Drift guard — every committed ``docs/perf/*.json`` artifact validates
   against the top-level-key registry below; an artifact whose shape
   drifts (or a new artifact nobody registered) fails the suite.
"""

import json
from pathlib import Path

import numpy as np
import pytest
from conftest import batch_schedule as _schedule
from conftest import small_backend_config as small_config

from distributed_optimization_tpu import telemetry
from distributed_optimization_tpu.backends import jax_backend, numpy_backend
from distributed_optimization_tpu.telemetry import (
    BENCH_MANIFEST_KEYS,
    SCHEMA_VERSION,
    TRACE_FIELDS,
    RunTrace,
)
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

REPO = Path(__file__).resolve().parent.parent


def _setup(**kw):
    cfg = small_config(n_iterations=40, eval_every=10, **kw)
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    return cfg, ds, f_opt


FAULTY_BYZ = dict(
    edge_drop_prob=0.2, attack="sign_flip", n_byzantine=1,
    aggregation="trimmed_mean", robust_b=1, partition="shuffled",
)


# ------------------------------------------------------ off/on bitwise parity


def test_telemetry_off_on_bitwise_sequential():
    cfg, ds, f_opt = _setup(**FAULTY_BYZ)
    off = jax_backend.run(cfg, ds, f_opt)
    on = jax_backend.run(cfg.replace(telemetry=True), ds, f_opt)
    assert off.history.trace is None
    assert on.history.trace is not None
    np.testing.assert_array_equal(off.history.objective, on.history.objective)
    np.testing.assert_array_equal(
        off.history.consensus_error, on.history.consensus_error
    )
    np.testing.assert_array_equal(off.final_models, on.final_models)


def test_telemetry_off_on_bitwise_batch():
    cfg, ds, f_opt = _setup(straggler_prob=0.1)
    off = jax_backend.run_batch(cfg.replace(replicas=3), ds, f_opt)
    on = jax_backend.run_batch(
        cfg.replace(replicas=3, telemetry=True), ds, f_opt
    )
    np.testing.assert_array_equal(off.objective, on.objective)
    np.testing.assert_array_equal(off.consensus_error, on.consensus_error)
    for r in range(3):
        assert on.results[r].history.trace is not None
        np.testing.assert_array_equal(
            off.results[r].final_models, on.results[r].final_models
        )


def test_telemetry_off_on_bitwise_numpy():
    # The numpy probe must not consume host-RNG draws: telemetry on/off
    # trajectories are bitwise-identical (the probe reuses the cached
    # last-drawn batch indices).
    cfg, ds, f_opt = _setup(backend="numpy", dtype="float64")
    off = numpy_backend.run(cfg, ds, f_opt)
    on = numpy_backend.run(cfg.replace(telemetry=True), ds, f_opt)
    np.testing.assert_array_equal(off.history.objective, on.history.objective)
    np.testing.assert_array_equal(off.final_models, on.final_models)
    assert on.history.trace is not None


# ------------------------------------------------------------- trace schema


def _check_schema(trace, n_evals, n_workers):
    assert set(trace) == set(TRACE_FIELDS)
    for name, kind in TRACE_FIELDS.items():
        arr = np.asarray(trace[name])
        assert arr.dtype == np.float32, name
        if kind == "per_worker":
            assert arr.shape == (n_evals, n_workers), name
        else:
            assert arr.shape == (n_evals,), name


@pytest.mark.parametrize("overrides", [
    {},  # fault-free decentralized
    {"algorithm": "centralized", "topology": "ring"},
    FAULTY_BYZ,
])
def test_jax_trace_schema(overrides):
    cfg, ds, f_opt = _setup(**overrides)
    r = jax_backend.run(cfg.replace(telemetry=True), ds, f_opt)
    _check_schema(r.history.trace, 4, cfg.n_workers)


def test_jax_numpy_trace_schema_and_value_parity():
    """Same schema on both backends; same VALUES (f64, injected batches,
    shared fault timeline) for every field the two compute independently."""
    cfg, ds, f_opt = _setup(dtype="float64", **FAULTY_BYZ)
    cfg = cfg.replace(telemetry=True)
    sched = _schedule(ds, cfg.n_iterations, cfg.local_batch_size)
    rj = jax_backend.run(cfg, ds, f_opt, batch_schedule=sched)
    rn = numpy_backend.run(
        cfg.replace(backend="numpy"), ds, f_opt, batch_schedule=sched
    )
    tj, tn = rj.history.trace, rn.history.trace
    _check_schema(tj, 4, cfg.n_workers)
    _check_schema(tn, 4, cfg.n_workers)
    # Fault realization is shared bitwise; model-dependent rows agree to
    # float32 rounding of the two f64 pipelines.
    np.testing.assert_array_equal(tj["live_edges"], tn["live_edges"])
    np.testing.assert_array_equal(tj["nodes_up"], tn["nodes_up"])
    np.testing.assert_array_equal(tj["nonfinite"], tn["nonfinite"])
    np.testing.assert_allclose(
        tj["grad_norm"], tn["grad_norm"], rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        tj["param_norm"], tn["param_norm"], rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        tj["clip_frac"], tn["clip_frac"], rtol=1e-5, atol=1e-6
    )


def test_trace_identical_across_execution_forms():
    """Segments of one eval (``measure_timestamps``) record the SAME trace
    rows as the one-shot scan (same t_last, same states)."""
    cfg, ds, f_opt = _setup(edge_drop_prob=0.15)
    cfg = cfg.replace(telemetry=True)
    inline = jax_backend.run(cfg, ds, f_opt)
    chunked = jax_backend.run(cfg, ds, f_opt, measure_timestamps=True)
    for k in TRACE_FIELDS:
        np.testing.assert_array_equal(
            inline.history.trace[k], chunked.history.trace[k]
        )


def test_batch_trace_matches_sequential():
    """Replica r's trace == the sequential run of its per-replica config
    (the run_batch trajectory contract extends to the flight recorder)."""
    cfg, ds, f_opt = _setup(edge_drop_prob=0.2)
    cfg = cfg.replace(telemetry=True)
    batch = jax_backend.run_batch(cfg.replace(replicas=2), ds, f_opt)
    for r, seed in enumerate(batch.seeds):
        seq = jax_backend.run(
            cfg.replace(
                seed=seed, topology_seed=cfg.resolved_topology_seed()
            ),
            ds, f_opt,
        )
        for k in TRACE_FIELDS:
            np.testing.assert_allclose(
                batch.results[r].history.trace[k], seq.history.trace[k],
                rtol=1e-6, atol=1e-6,
            )


def test_robust_activity_positive_under_attack():
    cfg, ds, f_opt = _setup(**FAULTY_BYZ)
    r = jax_backend.run(cfg.replace(telemetry=True), ds, f_opt)
    assert float(np.mean(r.history.trace["clip_frac"])) > 0.0
    # ... and identically zero without a robust rule.
    benign = _setup()[0].replace(telemetry=True)
    rb = jax_backend.run(benign, ds, f_opt)
    assert float(np.max(rb.history.trace["clip_frac"])) == 0.0


def test_telemetry_checkpoint_rejected(tmp_path):
    from distributed_optimization_tpu.utils.checkpoint import CheckpointOptions

    cfg, ds, f_opt = _setup()
    with pytest.raises(ValueError, match="not checkpointed"):
        jax_backend.run(
            cfg.replace(telemetry=True), ds, f_opt,
            checkpoint=CheckpointOptions(directory=str(tmp_path)),
        )


# -------------------------------------------------------- RunTrace manifests


def _one_trace():
    cfg, ds, f_opt = _setup(edge_drop_prob=0.2)
    cfg = cfg.replace(telemetry=True)
    r = jax_backend.run(cfg, ds, f_opt)
    health = telemetry.health_summary(cfg, r.history)
    return telemetry.build_run_trace(
        "unit", cfg, r.history, phases={"run": 1.0}, health=health
    )


def test_runtrace_json_roundtrip(tmp_path):
    tr = _one_trace()
    again = RunTrace.from_json(tr.to_json())
    assert again.to_dict() == tr.to_dict()
    telemetry.write_jsonl(tmp_path / "t.jsonl", [tr, tr])
    back = telemetry.read_jsonl(tmp_path / "t.jsonl")
    assert len(back) == 2 and back[0].to_dict() == tr.to_dict()


def test_runtrace_health_has_connectivity_and_activity():
    tr = _one_trace()
    assert tr.schema_version == SCHEMA_VERSION
    wc = tr.health["windowed_connectivity"]
    assert wc is not None and wc["bhat"] is not None and wc["bhat"] >= 1
    assert tr.health["realized_edge_frac"] is not None
    assert set(tr.trace) == set(TRACE_FIELDS)
    assert tr.cost is None or "flops" in tr.cost


def test_runtrace_nonfinite_values_stay_strict_json():
    """A diverging run's manifest (NaN/Inf trace rows) must still be
    STRICT JSON — bare NaN/Infinity tokens would make the artifact
    unreadable outside Python exactly in the failure cases the flight
    recorder exists to record. Sentinel strings round-trip exactly."""
    import math

    tr = _one_trace()
    tr.health["final_gap"] = float("nan")
    tr.trace["grad_norm"][0][0] = float("inf")
    tr.trace["param_norm"][0][0] = float("-inf")
    blob = tr.to_json()
    strict = json.loads(blob, parse_constant=lambda c: pytest.fail(
        f"non-strict JSON constant {c!r} in manifest"
    ))
    assert strict["health"]["final_gap"] == "NaN"
    back = RunTrace.from_json(blob)
    assert math.isnan(back.health["final_gap"])
    assert back.trace["grad_norm"][0][0] == float("inf")
    assert back.trace["param_norm"][0][0] == float("-inf")


def test_runtrace_rejects_drift():
    d = _one_trace().to_dict()
    with pytest.raises(ValueError, match="unknown keys"):
        RunTrace.from_dict({**d, "surprise": 1})
    missing = dict(d)
    missing.pop("health")
    with pytest.raises(ValueError, match="missing keys"):
        RunTrace.from_dict(missing)
    with pytest.raises(ValueError, match="schema_version"):
        RunTrace.from_dict({**d, "schema_version": SCHEMA_VERSION + 1})


# ------------------------------------------------- CLI / simulator emission


_TINY = [
    "--n-workers", "9", "--n-samples", "360", "--n-features", "8",
    "--n-informative-features", "4", "--n-iterations", "30",
    "--problem-type", "quadratic", "--eval-every", "10", "--quiet",
]


def test_cli_telemetry_jsonl_and_phases(tmp_path):
    from distributed_optimization_tpu.cli import main

    out = tmp_path / "t.jsonl"
    jout = tmp_path / "r.json"
    rc = main(_TINY + ["--edge-drop-prob", "0.2",
                       "--telemetry", str(out), "--json", str(jout)])
    assert rc == 0
    traces = telemetry.read_jsonl(out)
    assert len(traces) == 1
    tr = traces[0]
    assert tr.config["telemetry"] is True
    assert set(tr.trace) == set(TRACE_FIELDS)
    assert tr.health["windowed_connectivity"]["bhat"] >= 1
    # PhaseTimer satellite: phase wall-clock lands in manifest AND --json.
    assert {"data_gen", "oracle", "compile", "run"} <= set(tr.phases)
    blob = json.loads(jout.read_text())
    assert {"data_gen", "oracle", "compile", "run"} <= set(blob["phases"])
    assert "health" in blob["runs"][0]


def test_cli_preflight_named_failure(monkeypatch):
    from distributed_optimization_tpu.cli import main
    from distributed_optimization_tpu.utils import diagnostics

    rc = main(_TINY + ["--preflight"])
    assert rc == 0

    def boom(mesh=None):
        raise AssertionError("identity broken")

    monkeypatch.setattr(
        diagnostics, "PREFLIGHT_CHECKS",
        (("collectives.psum_identity", boom),),
    )
    with pytest.raises(SystemExit, match="collectives.psum_identity"):
        main(_TINY + ["--preflight"])


def test_run_preflight_names():
    from distributed_optimization_tpu.utils.diagnostics import run_preflight

    assert run_preflight() == [
        "collectives.ppermute_roundtrip",
        "collectives.psum_identity",
        "determinism.jit_rng_matmul_sort",
    ]


# -------------------------------------------------- perf-artifact drift guard

# Top-level-key registry for every committed docs/perf artifact. An
# artifact whose keys drift — or a new artifact nobody registers here —
# fails the suite: bench outputs are load-bearing evidence, so their shape
# changes must be deliberate.
PERF_ARTIFACT_KEYS = {
    "async.json": {"config", "device", "gates", "note", "runs"},
    "async_faults.json": {"config", "device", "gates", "note", "runs"},
    "anomaly_rootcause.json": {
        "after_fix_iters_per_sec_median4_same_session",
        "cond_alternative_rejected", "device_trace_evidence", "fix",
        "fused_vs_chunked_at_coarse_cadence", "method", "question"},
    "breakdown.json": {
        "attribution_iters_per_sec", "attribution_us_per_iter", "config",
        "device", "eval_every_iters_per_sec", "sampling_impl_iters_per_sec",
        "scan_unroll"},
    "byzantine.json": {"config", "device", "note", "runs", "trajectories"},
    "churn.json": {"config", "device", "gates", "note", "runs"},
    "compute_bound.json": {
        "cells", "device", "peak_hbm_gbps", "peak_tflops_bf16",
        "published_mfu_floor", "workload"},
    "faults.json": {"config", "device", "note", "runs"},
    "fleet.json": {
        "autoscale", "device", "divergence", "fleet_status", "gates",
        "incidents", "latency", "note", "platform", "protocol", "store",
        "stuck_requests", "traffic", "worker_kill"},
    "federated.json": {
        "device", "platform", "protocol", "note", "local_steps",
        "participation", "scale", "gates"},
    "fused_robust.json": {
        "bytes_vs_gap", "device", "fused_vs_gather", "gates", "note",
        "platform", "protocol"},
    "monitors.json": {
        "device", "platform", "protocol", "note", "overhead", "async",
        "divergence", "halt", "gates"},
    "observatory.json": {
        "device", "platform", "protocol", "note", "heartbeat", "async",
        "scrape", "gates"},
    "northstar_consensus.json": {
        "consensus_definition", "device", "metric", "runs",
        "total_wall_seconds"},
    "pallas_regimes.json": {
        "cycles", "device", "end_to_end", "iters", "n_workers", "note",
        "op_us_per_apply", "verdicts"},
    "presets.json": {"device", "note", "runs"},
    "report_reproduction.json": {"backend", "config", "note", "rows"},
    "robust_scale.json": {
        "crossover_n64", "device", "headline_n256_ring", "note", "protocol"},
    "scaling.json": {"config", "device", "rows"},
    "scenarios.json": {
        "agreement", "chaos", "checkpoint", "device", "gates", "matrix",
        "note", "platform", "protocol", "spec"},
    "serving.json": {
        "device", "platform", "protocol", "note", "workload", "latency",
        "throughput", "parity", "gates"},
    "serving_load.json": {
        "device", "platform", "protocol", "note", "traffic", "latency",
        "saturation", "shed", "fairness", "restart", "parity", "gates"},
    "sweep.json": {
        "cells", "device", "eta_sweep_demo", "floors", "note", "platform",
        "protocol"},
    "telemetry.json": {
        "device", "platform", "protocol", "note", "cells", "gates"},
    "trace_summary.json": {
        "device_total_us", "note", "source", "top_device_ops"},
    "worker_mesh.json": {
        "device", "platform", "protocol", "note", "parity", "scale",
        "gates"},
    "mesh_scale.json": {
        "device", "platform", "protocol", "note", "scale", "er_plan",
        "compression", "gates"},
}


def test_perf_artifact_schemas():
    perf_dir = REPO / "docs" / "perf"
    seen = set()
    for path in sorted(perf_dir.glob("*.json")):
        blob = json.loads(path.read_text())
        if path.name.endswith(".manifest.json"):
            # Bench provenance sidecars validate against the shared
            # bench-manifest schema OF THEIR DECLARED VERSION: committed
            # sidecars are historical evidence — a v1 sidecar produced
            # before the ISSUE-10 provenance block is still valid v1,
            # and silently "upgrading" its version without regenerating
            # it would fabricate provenance. Regeneration (the regen
            # script) rewrites them at the current schema.
            version = blob["schema_version"]
            assert version in (1, SCHEMA_VERSION), path.name
            expected_keys = set(BENCH_MANIFEST_KEYS)
            if version == 1:
                expected_keys -= {"provenance", "spans"}
            assert set(blob) == expected_keys, path.name
            continue
        assert path.name in PERF_ARTIFACT_KEYS, (
            f"unregistered perf artifact {path.name}: add its top-level "
            "keys to PERF_ARTIFACT_KEYS (tests/test_telemetry.py)"
        )
        expected = PERF_ARTIFACT_KEYS[path.name]
        assert set(blob) == expected, (
            f"{path.name} drifted: extra={set(blob) - expected}, "
            f"missing={expected - set(blob)}"
        )
        seen.add(path.name)
    # Registered-but-deleted artifacts are drift too (stale registry rows
    # would silently stop guarding anything).
    missing_files = set(PERF_ARTIFACT_KEYS) - seen
    assert not missing_files, f"registered artifacts not found: {missing_files}"
