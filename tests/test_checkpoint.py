"""Checkpoint/resume tests (SURVEY.md §5.4 build target).

The load-bearing property: a run that is killed mid-way and resumed from its
latest orbax checkpoint produces EXACTLY the trajectory (models + metric
histories) of an uninterrupted run — possible because batch sampling derives
keys purely from (seed, iteration), never from carried RNG state.
"""

import os

import numpy as np
import pytest

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.utils.checkpoint import (
    CheckpointOptions,
    RunCheckpointer,
)
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

CFG = ExperimentConfig(
    n_workers=8,
    n_samples=320,
    n_features=10,
    n_informative_features=6,
    n_iterations=40,
    local_batch_size=8,
    problem_type="quadratic",
    algorithm="dsgd",
    topology="ring",
    eval_every=4,
)


@pytest.fixture(scope="module")
def data():
    ds = generate_synthetic_dataset(CFG)
    _, f_opt = compute_reference_optimum(ds, CFG.reg_param)
    return ds, f_opt


def test_checkpointed_run_matches_fused_run(data, tmp_path):
    ds, f_opt = data
    fused = jax_backend.run(CFG, ds, f_opt)
    ckpt = jax_backend.run(
        CFG, ds, f_opt,
        checkpoint=CheckpointOptions(str(tmp_path / "ck"), every_evals=3),
    )
    np.testing.assert_allclose(
        ckpt.final_models, fused.final_models, rtol=1e-6, atol=1e-7
    )
    np.testing.assert_allclose(
        ckpt.history.objective, fused.history.objective, rtol=1e-5, atol=1e-7
    )


def test_resume_continues_exactly(data, tmp_path):
    ds, f_opt = data
    ckdir = str(tmp_path / "ck")
    full = jax_backend.run(
        CFG, ds, f_opt, checkpoint=CheckpointOptions(ckdir + "_full")
    )

    # "Interrupted" run: only the first 5 of 10 chunks, saved every 5.
    half_cfg = CFG.replace(n_iterations=20)
    jax_backend.run(
        half_cfg, ds, f_opt,
        checkpoint=CheckpointOptions(ckdir, every_evals=5, resume=False),
    )
    ck = RunCheckpointer(CheckpointOptions(ckdir))
    assert ck.latest_chunk() == 5

    # Resume with the full horizon: picks up at chunk 5, finishes 6..10.
    resumed = jax_backend.run(
        CFG, ds, f_opt, checkpoint=CheckpointOptions(ckdir, every_evals=5)
    )
    np.testing.assert_allclose(
        resumed.final_models, full.final_models, rtol=1e-6, atol=1e-7
    )
    np.testing.assert_allclose(
        resumed.history.objective, full.history.objective, rtol=1e-5, atol=1e-7
    )
    assert len(resumed.history.objective) == CFG.n_iterations // CFG.eval_every


def test_segmented_and_chunked_checkpoints_interoperate(data, tmp_path):
    """A checkpoint does not remember the size of the segments that wrote
    it: a run saved from segments of ``every_evals`` resumes in segments of
    one eval (``measure_timestamps``), and, both being the one program,
    ends bitwise where the uninterrupted run does."""
    ds, f_opt = data
    ckdir = str(tmp_path / "ck")
    full = jax_backend.run(CFG, ds, f_opt)
    jax_backend.run(
        CFG.replace(n_iterations=20), ds, f_opt,
        checkpoint=CheckpointOptions(ckdir, every_evals=5, resume=False),
    )  # segments of five evals
    resumed = jax_backend.run(
        CFG, ds, f_opt, checkpoint=CheckpointOptions(ckdir, every_evals=5),
        measure_timestamps=True,  # segments of one
    )
    np.testing.assert_array_equal(resumed.final_models, full.final_models)
    np.testing.assert_array_equal(
        resumed.history.objective, full.history.objective
    )


def test_segmented_checkpoint_keeps_realized_fault_floats(data, tmp_path):
    """Under fault injection the segmented path must aggregate the per-trip
    realized float counts to the same total the fused run reports (same
    seed ⇒ same fault draws)."""
    ds, f_opt = data
    faulty_cfg = CFG.replace(edge_drop_prob=0.25)
    fused = jax_backend.run(faulty_cfg, ds, f_opt)
    ckpt = jax_backend.run(
        faulty_cfg, ds, f_opt,
        checkpoint=CheckpointOptions(str(tmp_path / "ck"), every_evals=3),
    )
    assert ckpt.history.total_floats_transmitted == pytest.approx(
        fused.history.total_floats_transmitted
    )
    # Faults really dropped edges: realized < fault-free analytic count.
    fault_free = jax_backend.run(CFG, ds, f_opt)
    assert (
        ckpt.history.total_floats_transmitted
        < fault_free.history.total_floats_transmitted
    )


def test_retention_gc(data, tmp_path):
    ds, f_opt = data
    opts = CheckpointOptions(str(tmp_path / "ck"), every_evals=2, max_to_keep=2)
    jax_backend.run(CFG, ds, f_opt, checkpoint=opts)
    ck = RunCheckpointer(opts)
    assert len(ck.completed_chunks()) <= 2
    assert ck.latest_chunk() == 10


def test_resume_rejects_mismatched_config(data, tmp_path):
    ds, f_opt = data
    ckdir = str(tmp_path / "ck")
    jax_backend.run(CFG, ds, f_opt, checkpoint=CheckpointOptions(ckdir))
    with pytest.raises(ValueError, match="different experiment"):
        jax_backend.run(
            CFG.replace(learning_rate_eta0=0.01), ds, f_opt,
            checkpoint=CheckpointOptions(ckdir),
        )
    # A longer horizon with identical hyperparameters IS a valid resume.
    jax_backend.run(
        CFG.replace(n_iterations=80), ds, f_opt,
        checkpoint=CheckpointOptions(ckdir),
    )


def test_resume_rejects_shrunken_horizon(data, tmp_path):
    ds, f_opt = data
    ckdir = str(tmp_path / "ck")
    jax_backend.run(CFG, ds, f_opt, checkpoint=CheckpointOptions(ckdir))
    with pytest.raises(ValueError, match="horizon"):
        jax_backend.run(
            CFG.replace(n_iterations=20), ds, f_opt,
            checkpoint=CheckpointOptions(ckdir),
        )


def test_fully_restored_run_reports_no_throughput(data, tmp_path):
    ds, f_opt = data
    ckdir = str(tmp_path / "ck")
    jax_backend.run(CFG, ds, f_opt, checkpoint=CheckpointOptions(ckdir))
    again = jax_backend.run(CFG, ds, f_opt, checkpoint=CheckpointOptions(ckdir))
    # Zero iterations executed this process -> no throughput claim.
    assert np.isnan(again.history.iters_per_second)


def test_restore_empty_returns_none(tmp_path):
    ck = RunCheckpointer(CheckpointOptions(str(tmp_path / "empty")))
    assert ck.restore() is None
    assert ck.latest_chunk() is None


def test_invalid_options():
    with pytest.raises(ValueError):
        CheckpointOptions("/tmp/x", every_evals=0)


def test_no_resume_clears_stale_directory(data, tmp_path):
    ds, f_opt = data
    ckdir = str(tmp_path / "ck")
    # Directory written by a DIFFERENT experiment, with chunks beyond the
    # fresh run's horizon.
    jax_backend.run(
        CFG.replace(learning_rate_eta0=0.01), ds, f_opt,
        checkpoint=CheckpointOptions(ckdir, every_evals=2),
    )
    assert RunCheckpointer(CheckpointOptions(ckdir)).latest_chunk() == 10

    # resume=False must start fresh instead of raising on the mismatched
    # sidecar, and must clear the stale higher-numbered chunks that would
    # otherwise poison a later resume.
    short = CFG.replace(n_iterations=20)
    jax_backend.run(
        short, ds, f_opt,
        checkpoint=CheckpointOptions(ckdir, every_evals=5, resume=False),
    )
    ck = RunCheckpointer(CheckpointOptions(ckdir))
    assert ck.completed_chunks() == [5]

    # A later resume with the NEW config continues cleanly to the full run.
    full = jax_backend.run(
        CFG, ds, f_opt, checkpoint=CheckpointOptions(ckdir + "_ref")
    )
    resumed = jax_backend.run(
        CFG, ds, f_opt, checkpoint=CheckpointOptions(ckdir, every_evals=5)
    )
    np.testing.assert_allclose(
        resumed.final_models, full.final_models, rtol=1e-6, atol=1e-7
    )


def test_restore_falls_back_on_corrupt_latest_chunk(data, tmp_path):
    """Crash-mid-save robustness (ISSUE 2): a latest chunk directory that
    exists but cannot be restored (truncated orbax payload) must produce a
    warning and a fall-back to the previous intact chunk — and the resumed
    run still ends exactly where the uninterrupted run does (all RNG is
    (seed, t)-derived, so re-executing the lost chunks is free)."""
    import shutil

    ds, f_opt = data
    ckdir = str(tmp_path / "ck")
    full = jax_backend.run(
        CFG, ds, f_opt, checkpoint=CheckpointOptions(ckdir + "_ref")
    )
    jax_backend.run(
        CFG, ds, f_opt,
        checkpoint=CheckpointOptions(ckdir, every_evals=3, max_to_keep=5),
    )
    ck = RunCheckpointer(CheckpointOptions(ckdir))
    latest = ck.latest_chunk()
    assert latest == 10
    # Truncate the latest chunk dir: keep the directory (it still LOOKS
    # like a completed chunk) but gut the orbax payload.
    step_dir = ck._step_dir(latest)
    for name in os.listdir(step_dir):
        p = os.path.join(step_dir, name)
        shutil.rmtree(p) if os.path.isdir(p) else os.remove(p)
    with open(os.path.join(step_dir, "garbage"), "w") as f:
        f.write("crashed mid-save")

    with pytest.warns(UserWarning, match="partial or corrupt"):
        restored = ck.restore()
    assert restored is not None
    assert restored[-1] < latest  # fell back to an earlier intact chunk

    with pytest.warns(UserWarning, match="partial or corrupt"):
        resumed = jax_backend.run(
            CFG, ds, f_opt,
            checkpoint=CheckpointOptions(ckdir, every_evals=3, max_to_keep=5),
        )
    np.testing.assert_allclose(
        resumed.final_models, full.final_models, rtol=1e-6, atol=1e-7
    )


def test_completed_chunks_skips_orbax_tmp_and_empty_dirs(tmp_path):
    ckdir = tmp_path / "ck"
    ck = RunCheckpointer(CheckpointOptions(str(ckdir)))
    # Debris a crash can leave behind: orbax staging dirs, an empty chunk
    # dir (mkdir happened, nothing was written), foreign files.
    (ckdir / "00000003.orbax-checkpoint-tmp-1712").mkdir()
    (ckdir / "00000004").mkdir()  # empty — crashed before first write
    (ckdir / "notes.txt").write_text("junk")
    assert ck.completed_chunks() == []
    assert ck.latest_chunk() is None
    assert ck.restore() is None


CHURN_CFG = CFG.replace(
    edge_drop_prob=0.25, burst_len=6.0, mttf=12.0, mttr=8.0,
)


def test_resume_mid_outage_is_bitwise_exact(data, tmp_path):
    """ISSUE 2 acceptance: checkpoint mid-burst / mid-outage and resume —
    the trajectory must be BITWISE identical to the uninterrupted
    (checkpointed) run, because the fault timeline is rebuilt from
    (seed, horizon) with no carried chain state."""
    from distributed_optimization_tpu.parallel import build_topology
    from distributed_optimization_tpu.parallel.faults import (
        build_fault_timeline,
    )

    ds, f_opt = data
    ckdir = str(tmp_path / "ck")
    # The interruption point is read off this seed's timeline (which
    # moves with the PRNG's implementation): the first eval boundary at
    # which some node is down on both sides of the cut and some link too,
    # so the run is resumed inside an outage and inside a burst.
    topo = build_topology("ring", CHURN_CFG.n_workers)
    tl = build_fault_timeline(
        topo, CHURN_CFG.n_iterations, CHURN_CFG.seed,
        edge_drop_prob=0.25, burst_len=6.0, mttf=12.0, mttr=8.0,
    )

    def down_across(up, t):
        return (~up[t - 1] & ~up[t]).any()

    cuts = [
        t for t in range(
            CHURN_CFG.eval_every, CHURN_CFG.n_iterations, CHURN_CFG.eval_every
        )
        if down_across(tl.node_up, t) and down_across(tl.edge_up, t)
    ]
    assert cuts, "no eval boundary mid-outage and mid-burst for this seed"
    t_cut = cuts[0]

    full = jax_backend.run(
        CHURN_CFG, ds, f_opt,
        checkpoint=CheckpointOptions(ckdir + "_full", every_evals=5),
    )
    jax_backend.run(
        CHURN_CFG.replace(n_iterations=t_cut), ds, f_opt,
        checkpoint=CheckpointOptions(ckdir, every_evals=5, resume=False),
    )
    resumed = jax_backend.run(
        CHURN_CFG, ds, f_opt,
        checkpoint=CheckpointOptions(ckdir, every_evals=5),
    )
    np.testing.assert_array_equal(resumed.final_models, full.final_models)
    np.testing.assert_array_equal(
        resumed.history.objective, full.history.objective
    )
    assert resumed.history.total_floats_transmitted == pytest.approx(
        full.history.total_floats_transmitted
    )
