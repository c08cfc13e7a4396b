"""Measured wall-clock timestamps (VERDICT r1 item 4).

The framework's own headline metric is wall-clock-to-threshold, so the
``time`` history must be real where claimed: ``measure_timestamps=True``
records one ``perf_counter`` sample per eval chunk (the reference measures
per iteration, trainer.py:63,181); a longer segment keeps the linspace
interpolation but is labeled as such in the report.
"""

import numpy as np
import pytest

from distributed_optimization_tpu.backends import jax_backend, numpy_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.metrics import summarize_run
from distributed_optimization_tpu.utils.checkpoint import CheckpointOptions
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

CFG = ExperimentConfig(
    n_workers=8, n_samples=320, n_features=10, n_informative_features=6,
    n_iterations=60, local_batch_size=8, problem_type="quadratic",
    algorithm="dsgd", topology="ring", eval_every=6,
)


@pytest.fixture(scope="module")
def data():
    ds = generate_synthetic_dataset(CFG)
    _, f_opt = compute_reference_optimum(ds, CFG.reg_param)
    return ds, f_opt


def test_measured_timestamps_are_real_and_trajectory_matches_fused(data):
    ds, f_opt = data
    fused = jax_backend.run(CFG, ds, f_opt)
    timed = jax_backend.run(CFG, ds, f_opt, measure_timestamps=True)

    assert not fused.history.time_measured
    assert timed.history.time_measured
    t = timed.history.time
    assert t.shape == (CFG.n_iterations // CFG.eval_every,)
    assert np.all(t > 0)
    assert np.all(np.diff(t) > 0)  # strictly increasing cumulative clock
    # The same program in segments of one eval: the same trajectory.
    np.testing.assert_array_equal(timed.final_models, fused.final_models)
    np.testing.assert_array_equal(
        timed.history.objective, fused.history.objective
    )


def test_numpy_backend_reports_measured_time(data):
    ds, f_opt = data
    res = numpy_backend.run(CFG.replace(backend="numpy"), ds, f_opt)
    assert res.history.time_measured
    assert np.all(np.diff(res.history.time) > 0)


@pytest.mark.parametrize("measure", [False, True])
def test_resumed_run_carries_cumulative_time(data, tmp_path, measure):
    """Cumulative time across installments, at BOTH checkpointed segment
    sizes: the default ``every_evals`` (per-eval timestamps interpolated
    within a segment, time_measured=False) and the opt-in one eval (real
    per-eval samples, time_measured=True)."""
    ds, f_opt = data
    kw = dict(measure_timestamps=True) if measure else {}
    ckdir = str(tmp_path / "ck")
    half = CFG.replace(n_iterations=30)
    first = jax_backend.run(
        half, ds, f_opt,
        checkpoint=CheckpointOptions(ckdir, every_evals=5, resume=False),
        **kw,
    )
    resumed = jax_backend.run(
        CFG, ds, f_opt, checkpoint=CheckpointOptions(ckdir, every_evals=5),
        **kw,
    )
    t = resumed.history.time
    assert resumed.history.time_measured is measure
    assert t.shape == (10,)
    assert np.all(np.diff(t) > 0)
    # The resumed installment's clock continues from the restored offset.
    np.testing.assert_allclose(t[:5], first.history.time, rtol=1e-9)
    assert t[5] > first.history.time[-1]


def test_report_marks_interpolated_seconds(data):
    from distributed_optimization_tpu.simulator import ExperimentRecord
    from distributed_optimization_tpu.reporting import format_report

    ds, f_opt = data
    # A generous threshold guarantees sec→ε prints for both runs.
    cfg = CFG.replace(suboptimality_threshold=1e6)
    fused = jax_backend.run(cfg, ds, f_opt)
    timed = jax_backend.run(cfg, ds, f_opt, measure_timestamps=True)
    assert fused.history.objective[-1] <= cfg.suboptimality_threshold, (
        "test premise: threshold must be crossed so the sec→ε column prints"
    )

    def record(label, res):
        summary = summarize_run(
            label, res.history, cfg.suboptimality_threshold, cfg.n_workers
        )
        return ExperimentRecord(label, cfg, res, summary)

    text = format_report([record("fused", fused)], cfg, f_opt)
    assert "~" in text and "interpolated" in text

    text = format_report([record("timed", timed)], cfg, f_opt)
    assert "interpolated" not in text


def test_default_is_fused_at_every_cadence(data):
    """measure_timestamps defaults to the whole run in one segment at EVERY
    eval cadence (docs/PERF.md root-cause section). Measured timestamps
    are opt-in, and cadence choices never change the trajectory at shared
    eval points."""
    ds, f_opt = data
    cfg = CFG.replace(n_iterations=60, eval_every=20, local_batch_size=8)
    res = jax_backend.run(cfg, ds, f_opt)
    assert not res.history.time_measured  # fused by default, coarse cadence
    assert res.history.objective.shape == (3,)
    opt_in = jax_backend.run(cfg, ds, f_opt, measure_timestamps=True)
    assert opt_in.history.time_measured
    # Different cadences: same trajectory at the shared eval points.
    fine = jax_backend.run(cfg.replace(eval_every=10), ds, f_opt)
    assert not fine.history.time_measured
    np.testing.assert_allclose(
        res.history.objective, fine.history.objective[1::2], rtol=1e-5,
        atol=1e-7,
    )
    np.testing.assert_allclose(
        res.final_models, fine.final_models, rtol=1e-6, atol=1e-8
    )
    # Cadences that don't divide by the unroll budget (prime k) still land
    # every eval exactly on its boundary via the micro-chunk divisor.
    prime = jax_backend.run(
        cfg.replace(n_iterations=63, eval_every=7, scan_unroll=4), ds, f_opt
    )
    assert prime.history.objective.shape == (9,)
    assert np.all(np.isfinite(prime.history.objective))


def test_default_never_routes_to_chunk_loop(data):
    """Segments of one eval are opt-in only (measure_timestamps=True): they
    pay one host sync per eval, so no default may silently select them —
    the whole run in one segment serves every cadence."""
    ds, f_opt = data
    cfg = CFG.replace(n_iterations=80, eval_every=2, scan_unroll=0)
    assert not jax_backend.run(cfg, ds, f_opt).history.time_measured
    assert not jax_backend.run(
        cfg, ds, f_opt, collect_metrics=False
    ).history.time_measured
    assert jax_backend.run(
        cfg, ds, f_opt, measure_timestamps=True
    ).history.time_measured
