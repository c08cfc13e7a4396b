"""One program, one driver (ISSUE 28): the sequential scan runs as segments
of whole eval-chunks of ONE device program whose iteration offset is an
argument (``jax_backend._drive_segments``). What the caller asks for
(a heartbeat, a checkpoint, real per-eval stamps) chooses where the
segments end and nothing else, so however a run is split it is bitwise the
unsplit run, and a segment's executable is keyed by its size alone.
CPU, small N and T: what is checked is values, structure and counts, never
a time.
"""

import numpy as np
import pytest
from conftest import small_backend_config

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.observability.spans import Tracer
from distributed_optimization_tpu.serving.cache import ExecutableCache
from distributed_optimization_tpu.utils.checkpoint import (
    CheckpointOptions,
    RunCheckpointer,
)
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset

N_EVALS = 10


@pytest.fixture(scope="module")
def setup():
    # eval_every = 6 under an unroll budget of 4: micro-chunks of 3 steps,
    # two scan trips an eval, so every segment also drops off-cadence rows.
    # Dropped edges make the program record realized floats as well.
    cfg = small_backend_config(
        n_iterations=6 * N_EVALS, eval_every=6, scan_unroll=4,
        edge_drop_prob=0.2,
    )
    ds = generate_synthetic_dataset(cfg)
    return cfg, ds, jax_backend.run(cfg, ds, 0.0)


def run_traced(cfg, ds, **kw):
    """(result, root span, names of its children in order)."""
    tracer = Tracer()
    with tracer.activate():
        result = jax_backend.run(cfg, ds, 0.0, **kw)
    events = tracer.spans()
    (root,) = [e for e in events if e["name"] == "dopt.run"]
    children = sorted(
        (e for e in events if e["parent"] == root["id"]),
        key=lambda e: e["start"],
    )
    return result, root, children


def assert_same_run(got, want):
    np.testing.assert_array_equal(got.history.objective, want.history.objective)
    np.testing.assert_array_equal(
        got.history.consensus_error, want.history.consensus_error
    )
    np.testing.assert_array_equal(got.final_models, want.final_models)
    assert (
        got.history.total_floats_transmitted
        == want.history.total_floats_transmitted
    )


@pytest.mark.parametrize("form,size", [
    ("heartbeat", 1), ("heartbeat", 3), ("heartbeat", N_EVALS),
    ("checkpoint", 1), ("checkpoint", 3), ("checkpoint", N_EVALS),
    ("resumed", 3), ("timed", 1),
])
def test_any_split_is_bitwise_the_one_shot_run(setup, tmp_path, form, size):
    cfg, ds, one_shot = setup
    beats = []
    ck = str(tmp_path / "ck")
    if form == "heartbeat":
        # A cache of its own: the process's holds the fixture's whole run.
        kw = {"progress_cb": beats.append, "progress_every": size,
              "executable_cache": ExecutableCache()}
    elif form == "timed":
        kw = {"measure_timestamps": True}
    else:
        kw = {"checkpoint": CheckpointOptions(ck, every_evals=size)}
    if form == "resumed":
        # Cut after four evals (a boundary that is no multiple of three).
        jax_backend.run(cfg.replace(n_iterations=24), ds, 0.0, **kw)
    got, root, children = run_traced(cfg, ds, **kw)
    assert_same_run(got, one_shot)
    assert got.history.time_measured is (form == "timed")
    assert not one_shot.history.time_measured
    assert root["args"]["path"] == {
        "heartbeat": "segmented", "checkpoint": "segmented",
        "resumed": "segmented", "timed": "chunked",
    }[form]
    # One scan span round all the segments, and the rows after it.
    names = [e["name"].removeprefix("dopt.run.") for e in children]
    assert names.count("scan") == 1
    assert names[-3:] == ["upload_wait", "scan", "harvest"]
    # At most two executables: the full segment and a trailing remainder.
    left = N_EVALS - 4 if form == "resumed" else N_EVALS
    assert names.count("compile") == (2 if left % size else 1)
    stamps = got.history.time
    assert stamps.shape == (N_EVALS,) and np.all(np.diff(stamps) > 0)
    if form in ("heartbeat", "timed"):
        # The scan span closes on the last segment's state: one clock, read
        # once, is the run's seconds and its last stamp, however it is split.
        scan = next(e for e in children if e["name"] == "dopt.run.scan")
        assert stamps[-1] == scan["duration"]
        assert got.history.iters_per_second == (
            cfg.n_iterations / scan["duration"]
        )
    if form == "heartbeat":
        want = sorted(set(range(size, N_EVALS, size)) | {N_EVALS})
        assert [e.iteration for e in beats] == [6 * k for k in want]
        assert beats[-1].wall_seconds == stamps[-1]
        assert beats[-1].gap == one_shot.history.objective[-1]


def test_whole_run_heartbeat_hits_the_one_shot_executable(setup):
    """A segment is keyed by its size, the whole run included: a heartbeat
    run whose one segment is the whole run reuses what the one-shot run
    compiled, and the other way round; another size compiles its own."""
    cfg, ds, one_shot = setup
    cache = ExecutableCache()
    first, root, children = run_traced(cfg, ds, executable_cache=cache)
    assert root["args"]["cache"] == "miss"
    beats = []
    for every in (N_EVALS, N_EVALS + 5):
        got, root, children = run_traced(
            cfg, ds, executable_cache=cache, progress_cb=beats.append,
            progress_every=every,
        )
        assert root["args"]["path"] == "segmented"
        assert root["args"]["cache"] == "hit"
        assert "dopt.run.compile" not in [e["name"] for e in children]
        assert_same_run(got, one_shot)
    assert [e.iteration for e in beats] == [cfg.n_iterations] * 2
    assert len(cache) == 1
    _, root, children = run_traced(
        cfg, ds, executable_cache=cache, progress_cb=beats.append,
        progress_every=5,
    )
    assert root["args"]["cache"] == "miss"
    assert len(cache) == 2
    # ... and the one-shot run now finds the whole-run segment again.
    _, root, _ = run_traced(cfg, ds, executable_cache=cache)
    assert root["args"]["cache"] == "hit"


@pytest.mark.parametrize("form", ["timed", "checkpoint"])
def test_timed_and_checkpointed_runs_compile_every_time(setup, tmp_path, form):
    """The cache policy, written once: only one-shot and heartbeat runs
    consult the cache."""
    cfg, ds, _ = setup
    kw = {"measure_timestamps": True} if form == "timed" else {
        "checkpoint": CheckpointOptions(
            str(tmp_path / "ck"), every_evals=3, resume=False
        )
    }
    cache = ExecutableCache()
    for _ in range(2):
        _, root, children = run_traced(cfg, ds, executable_cache=cache, **kw)
        names = [e["name"] for e in children]
        assert root["args"]["cache"] == "off"
        assert "dopt.run.cache_lookup" not in names
        assert "dopt.run.compile" in names
    assert len(cache) == 0


def test_timed_checkpointed_run_saves_on_cadence_and_resumes_bitwise(
    setup, tmp_path
):
    """``measure_timestamps`` with a checkpoint: segments of one eval (a
    real stamp each), saves only every ``every_evals`` and at the end, and a
    resume that ends bitwise where the uninterrupted run does."""
    cfg, ds, one_shot = setup
    opts = CheckpointOptions(
        str(tmp_path / "ck"), every_evals=3, max_to_keep=N_EVALS
    )
    kw = {"measure_timestamps": True, "checkpoint": opts}
    cut = jax_backend.run(cfg.replace(n_iterations=6 * 7), ds, 0.0, **kw)
    assert cut.history.time_measured
    assert RunCheckpointer(opts).completed_chunks() == [3, 6, 7]
    beats = []
    resumed = jax_backend.run(cfg, ds, 0.0, progress_cb=beats.append, **kw)
    assert_same_run(resumed, one_shot)
    assert resumed.history.time_measured
    # Cadences count from where this installment started: evals 8, 9, 10,
    # a save after three of them, which is also the end.
    assert RunCheckpointer(opts).completed_chunks() == [3, 6, 7, 10]
    assert [e.iteration for e in beats] == [48, 54, 60]
    np.testing.assert_array_equal(
        resumed.history.time[:7], cut.history.time
    )
    assert resumed.history.time[7] > cut.history.time[-1]
    # Only the three evals of this process count towards its throughput.
    assert resumed.history.iters_per_second == pytest.approx(
        18 / (resumed.history.time[-1] - cut.history.time[-1])
    )
