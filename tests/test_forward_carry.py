"""The forward product is carried (ISSUE 31): where a GLM's D-SGD run takes
its gradient over the whole padded shard and records the full-data objective,
the eval's pass over the shard stack X computes two margin arrays in one
reduction, X·x̄ for the objective and X·x for every worker, and X·x rides in
the scan's carry to the next trip's first gradient. Two reads of X an
iteration where there were three; the same mathematics, so the carried run is
the recomputed run to the last places of f32 (two different programs: held to
``assert_ulps_of_scale``), and bitwise wherever one program is replayed or
split. Everything the mechanism does not fit keeps the recomputed program.
CPU, small N and T: values, structure and counts, never a time.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_ulps_of_scale, small_backend_config

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.observability.spans import Tracer
from distributed_optimization_tpu.ops import losses
from distributed_optimization_tpu.utils.checkpoint import CheckpointOptions
from distributed_optimization_tpu.utils.data import generate_synthetic_dataset

FAMILIES = ["logistic", "quadratic", "huber"]
# How the gradient comes to run over the whole padded shard: the dense
# sampler's weights (what 'auto' resolves to on the chip for L <= 64), or a
# batch as large as the shard.
WHOLE_SHARD = {
    "dense": dict(sampling_impl="dense"),
    "full_batch": dict(local_batch_size=64),
}
# The two programs round differently in the last place of a margin (at d = 81
# the paired reduce and the dot associate differently; at d = 10 they are
# bitwise) and the difference rides 60 steps of the trajectory: 2.1 units of
# the rows' scale is the most I read over every case here (jax 0.9, CPU);
# bf16 arithmetic is 2**16 units off.
ULPS = 16


def glm_cfg(**kw):
    """8 ring workers x 50 rows x 81 features (the study's width), f32,
    batch 16."""
    kw.setdefault("sampling_impl", "dense")
    kw.setdefault("n_features", 80)
    kw.setdefault("n_informative_features", 40)
    return small_backend_config(**kw)


def run_rooted(cfg, ds, **kw):
    """(result, the ``dopt.run`` root's arguments)."""
    tracer = Tracer()
    with tracer.activate():
        result = jax_backend.run(cfg, ds, 0.0, executable_cache=False, **kw)
    (root,) = [e for e in tracer.spans() if e["name"] == "dopt.run"]
    return result, root["args"]


def recomputed(monkeypatch):
    """The private switch: every later run of this test recomputes."""
    monkeypatch.setattr(
        jax_backend, "_forward_is_carried", lambda *a, **k: False
    )


def assert_carried_is_recomputed(cfg, monkeypatch, ulps=ULPS, forward_of=None):
    """``forward_of``: what the root says the carried product is of, where
    that is not the state's own models (``restarted``); nothing else."""
    ds = generate_synthetic_dataset(cfg)
    got, root = run_rooted(cfg, ds)
    assert root["forward"] == "carried"
    assert root.get("forward_of") == forward_of
    recomputed(monkeypatch)
    want, root = run_rooted(cfg, ds)
    assert root["forward"] == "recomputed" and "forward_of" not in root
    want32 = lambda a: np.asarray(a, dtype=np.float32)  # noqa: E731
    assert_ulps_of_scale(got.history.objective, want32(want.history.objective), ulps)
    assert_ulps_of_scale(
        got.history.consensus_error, want32(want.history.consensus_error), ulps
    )
    assert_ulps_of_scale(got.final_models, want32(want.final_models), ulps)


@pytest.mark.parametrize("whole", sorted(WHOLE_SHARD))
@pytest.mark.parametrize("family", FAMILIES)
def test_carried_run_is_the_recomputed_run(family, whole, monkeypatch):
    assert_carried_is_recomputed(
        glm_cfg(problem_type=family, **WHOLE_SHARD[whole]), monkeypatch
    )


@pytest.mark.parametrize("faults", [
    dict(straggler_prob=0.3),
    dict(mttf=12.0, mttr=4.0),  # churn, the 'frozen' rejoin
    dict(participation_rate=0.7),
    dict(edge_drop_prob=0.2),
], ids=lambda f: next(iter(f)))
def test_frozen_rows_carry_their_own_margins(faults, monkeypatch):
    """A straggler or a crashed worker takes no step: z is taken from the
    state AFTER the freeze, so a frozen row's margins are its frozen
    model's."""
    assert_carried_is_recomputed(
        glm_cfg(problem_type="logistic", **faults), monkeypatch
    )


@pytest.mark.parametrize("kw", [
    dict(eval_every=25, n_iterations=100, scan_unroll=8),  # micro = 5
    dict(eval_every=6, scan_unroll=4),  # micro = 3, two trips an eval
    dict(local_steps=3),  # later slots sample and read for themselves
    dict(attack="sign_flip", n_byzantine=1, aggregation="trimmed_mean",
         robust_b=1),  # x̄ is the honest mean
], ids=["micro5", "micro3_two_trips", "local_steps3", "byzantine"])
def test_only_the_trips_first_gradient_is_carried(kw, monkeypatch):
    """Within a trip of ``micro`` steps only the first has the carried z; a
    federated round's later slots compute their own."""
    assert_carried_is_recomputed(
        glm_cfg(problem_type="logistic", **kw), monkeypatch
    )


# --- neighbor_restart: the product is of the RESTARTED models (ISSUE 47) ----
#
# The step of round t + 1 replaces the rejoining rows before it differentiates,
# so the eval of trip t restarts them a trip early (row t + 1 of the
# timeline's leaves, which the program holds), takes the product at those
# models and the objective at the unrestarted mean, and hands both on. The
# fault layer's three forms give the one ``rejoin_restart(t, x)``: the ring's
# shifts, any other neighbor table's gather, a dense adjacency.

SHIFT = dict(topology_impl="neighbor")  # the 8-ring's table IS a ring's
RESTARTS = {
    # (config, the root's fault_mixing)
    "shift_mttf12": (dict(mttf=12.0, mttr=4.0, **SHIFT), "shift"),
    "shift_mttf6": (dict(mttf=6.0, mttr=3.0, **SHIFT), "shift"),
    "shift_bursts": (
        dict(mttf=12.0, mttr=4.0, edge_drop_prob=0.3, burst_len=4.0, **SHIFT),
        "shift"),
    "gather_torus": (
        dict(mttf=12.0, mttr=4.0, topology="grid", n_workers=16,
             n_samples=800, **SHIFT), "gather"),
    "gather_chain_bursts": (
        dict(mttf=6.0, mttr=3.0, edge_drop_prob=0.3, burst_len=4.0,
             topology="chain", **SHIFT), "gather"),
    "dense_adjacency": (dict(mttf=12.0, mttr=4.0), None),
    # later steps of a trip restart and read the shards for themselves
    "micro3_two_trips": (
        dict(mttf=6.0, mttr=3.0, eval_every=6, scan_unroll=4, **SHIFT),
        "shift"),
    "local_steps3": (dict(mttf=6.0, mttr=3.0, local_steps=3, **SHIFT), "shift"),
    "full_batch_quadratic": (
        dict(mttf=6.0, mttr=3.0, problem_type="quadratic",
             sampling_impl="auto", local_batch_size=64, **SHIFT), "shift"),
}


def restart_cfg(**kw):
    kw.setdefault("problem_type", "logistic")
    return glm_cfg(rejoin="neighbor_restart", **kw)


@pytest.mark.parametrize("case", sorted(RESTARTS))
def test_under_restart_the_carried_run_is_the_recomputed_run(case, monkeypatch):
    kw, addressing = RESTARTS[case]
    cfg = restart_cfg(**kw)
    _, root = run_rooted(cfg, generate_synthetic_dataset(cfg))
    assert root["rejoin"] == "neighbor_restart" and root["rejoin_rows"] > 0
    assert root.get("fault_mixing") == addressing
    assert_carried_is_recomputed(cfg, monkeypatch, forward_of="restarted")


BYPASS = {
    "glm_dsgd_dense": (dict(problem_type="logistic"), "carried"),
    "glm_dsgd_full_batch": (
        dict(problem_type="quadratic", sampling_impl="auto",
             local_batch_size=64), "carried"),
    "softmax_dsgd": (
        dict(problem_type="softmax", n_classes=4, local_batch_size=64),
        "recomputed"),
    "choco": (
        dict(problem_type="logistic", algorithm="choco", compression="top_k",
             compression_k=3), "recomputed"),
    "dsgd_compressed": (
        dict(problem_type="logistic", compression="top_k", compression_k=3),
        "recomputed"),
    "gradient_tracking": (
        dict(problem_type="logistic", algorithm="gradient_tracking"),
        "recomputed"),
    "glm_gathered_L80": (
        dict(problem_type="logistic", sampling_impl="auto", n_samples=640),
        "recomputed"),
    "glm_gather_forced": (
        dict(problem_type="logistic", sampling_impl="gather"), "recomputed"),
}


@pytest.mark.parametrize("case", sorted(BYPASS))
def test_the_root_says_which(case):
    """``forward`` on the ``dopt.run`` root: ``carried`` for a GLM's D-SGD
    over the whole shard, ``recomputed`` for a matrix parameter, a
    compressed exchange, another rule, gathered batches; on a CPU never
    ``fused`` (ISSUE 41: a TPU's form of ``carried``)."""
    kw, want = BYPASS[case]
    cfg = glm_cfg(n_iterations=10, **kw)
    _, root = run_rooted(cfg, generate_synthetic_dataset(cfg))
    assert root["forward"] == want


def test_injected_batches_and_no_metrics_recompute():
    cfg = glm_cfg(problem_type="logistic", n_iterations=10)
    ds = generate_synthetic_dataset(cfg)
    sched = np.zeros((10, cfg.n_workers, 4), dtype=np.int64)
    _, root = run_rooted(cfg, ds, batch_schedule=sched)
    assert root["forward"] == "recomputed"
    _, root = run_rooted(cfg, ds, collect_metrics=False)
    assert root["forward"] == "recomputed"


# --- the program -----------------------------------------------------------


class _Traced(Exception):
    pass


def seg_scan_of(cfg, ds, monkeypatch):
    """``(seg_scan, arguments)`` of the call's one device program, taken
    where ``_run`` hands it to the driver."""
    def grab(make_seg_scan, trips_per_eval, state0, data_args, mesh, config,
             n_evals, spans, **kw):
        raise _Traced(make_seg_scan(n_evals), (state0, jnp.int32(0), data_args))

    monkeypatch.setattr(jax_backend, "_drive_segments", grab)
    with pytest.raises(_Traced) as caught:
        jax_backend.run(cfg, ds, 0.0, use_mesh=False)
    return caught.value.args


def reads_of(jaxpr, shape):
    """Every reduction (``dot_general``, ``reduce``, ``reduce_sum``) with an
    operand of ``shape`` (or of its per-worker transpose, how ``X.T @ coeff``
    arrives) in a jaxpr, sub-jaxprs included, as (primitive, number of
    results, inside a scan)."""
    shapes = (shape, (shape[0], shape[2], shape[1]))

    def walk(jp, in_scan):
        for eqn in jp.eqns:
            if eqn.primitive.name in ("dot_general", "reduce", "reduce_sum") and any(
                getattr(v.aval, "shape", None) in shapes for v in eqn.invars
            ):
                yield eqn.primitive.name, len(eqn.outvars), in_scan
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub, in_scan or eqn.primitive.name == "scan")

    return list(walk(jaxpr, False))


@pytest.mark.parametrize("family", FAMILIES)
def test_two_reads_of_the_shards_in_the_loop_not_three(family, monkeypatch):
    cfg = glm_cfg(problem_type=family, n_iterations=10)
    ds = generate_synthetic_dataset(cfg)
    stack = (cfg.n_workers, 50, ds.n_features)
    seg_scan, args = seg_scan_of(cfg, ds, monkeypatch)
    assert args[2]["X"].shape == stack
    reads = reads_of(jax.make_jaxpr(seg_scan)(*args).jaxpr, stack)
    in_loop = sorted(r[:2] for r in reads if r[2])
    # Xᵀ·coeff, and ONE reduction with two results for X·x and X·x̄ ...
    assert in_loop == [("dot_general", 1), ("reduce", 2)]
    # ... the same paired reduction once in front of the loop, for z_0.
    assert [r[:2] for r in reads if not r[2]] == [("reduce", 2)]
    text = jax.jit(seg_scan).lower(*args).as_text()
    n, L, _ = stack
    paired = re.findall(
        rf"stablehlo\.reduce.*?-> \(tensor<{n}x{L}xf32>, tensor<{n}x{L}xf32>\)",
        text,
    )
    assert len(paired) == 2  # in the loop's body, and in front of it

    recomputed(monkeypatch)
    seg_scan, args = seg_scan_of(cfg, ds, monkeypatch)
    reads = reads_of(jax.make_jaxpr(seg_scan)(*args).jaxpr, stack)
    assert sorted(r[:2] for r in reads if r[2]) == [("dot_general", 1)] * 3
    assert [r for r in reads if not r[2]] == []


def test_paired_margins_are_the_two_products():
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(6, 9, 5)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(6, 5)), jnp.float32)
    xbar = jnp.asarray(rng.normal(size=(5,)), jnp.float32)
    z, zbar = losses.paired_margins(X, x, xbar)
    assert z.shape == zbar.shape == (6, 9) and z.dtype == X.dtype
    X64, x64, xbar64 = (np.asarray(a, np.float64) for a in (X, x, xbar))
    assert_ulps_of_scale(z, np.einsum("nld,nd->nl", X64, x64).astype(np.float32), 4)
    assert_ulps_of_scale(zbar, (X64 @ xbar64).astype(np.float32), 4)
    # bf16 shards: accumulated in f32, handed back in the shards' type
    zb, _ = losses.paired_margins(X.astype(jnp.bfloat16), x, xbar)
    assert zb.dtype == jnp.bfloat16


@pytest.mark.parametrize("family", FAMILIES)
def test_gradient_at_the_margins_is_the_gradient(family):
    """``link.gradient_at(X @ w, ...)`` IS ``gradient_weighted``: bitwise."""
    from distributed_optimization_tpu.models import get_problem

    problem = get_problem(family)
    rng = np.random.default_rng(5)
    X = jnp.asarray(rng.normal(size=(12, 5)), jnp.float32)
    y = jnp.asarray(rng.choice([-1.0, 1.0], size=12), jnp.float32)
    w = jnp.asarray(rng.normal(size=5), jnp.float32)
    wts = jnp.asarray(rng.uniform(size=12), jnp.float32)
    np.testing.assert_array_equal(
        problem.link.gradient_at(X @ w, w, X, y, wts, 0.1),
        problem.gradient_weighted(w, X, y, wts, 0.1),
    )
    auto = jax.grad(problem.objective_weighted)(w, X, y, wts, 0.1)
    np.testing.assert_allclose(
        problem.gradient_weighted(w, X, y, wts, 0.1), auto, rtol=2e-5, atol=1e-6
    )
    assert get_problem("softmax").link is None


# --- one program, replayed or split: bitwise -------------------------------


@pytest.fixture(scope="module", params=["stragglers", "restarted"])
def whole_run(request):
    # Micro-chunks of 3 steps, two trips an eval; stragglers frozen, or a
    # rejoin most rounds under neighbor_restart (ISSUE 47).
    kw = dict(problem_type="logistic", eval_every=6, scan_unroll=4)
    if request.param == "stragglers":
        cfg, forward_of = glm_cfg(straggler_prob=0.2, **kw), None
    else:
        cfg = restart_cfg(mttf=6.0, mttr=3.0, **kw, **SHIFT)
        forward_of = "restarted"
    ds = generate_synthetic_dataset(cfg)
    whole, root = run_rooted(cfg, ds, return_state=True)
    assert root["forward"] == "carried" and root["path"] == "fused"
    assert root.get("forward_of") == forward_of
    return cfg, ds, whole


@pytest.mark.parametrize("form,size", [
    ("heartbeat", 1), ("heartbeat", 3), ("timed", 1), ("checkpoint", 4),
    ("resumed", 3),
])
def test_a_split_carried_run_is_bitwise_the_unsplit_run(
    whole_run, tmp_path, form, size
):
    """z is the program's, not the state's: every segment makes its z_0 from
    the state it is handed (a checkpoint's, after a resume) with the paired
    pass itself, so a run split at eval boundaries is the unsplit run to the
    bit, as ``tests/test_segments.py`` holds the recomputed program to. So
    are the restarted models z is taken at: a segment hands on the
    UNrestarted state and the next restarts at its own ``t0`` before its
    first pass."""
    cfg, ds, whole = whole_run
    if form == "heartbeat":
        kw = {"progress_cb": lambda ev: None, "progress_every": size}
    elif form == "timed":
        kw = {"measure_timestamps": True}
    else:
        kw = {"checkpoint": CheckpointOptions(
            str(tmp_path / "ck"), every_evals=size
        )}
    if form == "resumed":
        # Cut after four evals (a boundary that is no multiple of three).
        jax_backend.run(cfg.replace(n_iterations=24), ds, 0.0, **kw)
    split, root = run_rooted(cfg, ds, **kw)
    assert root["forward"] == "carried"
    assert root["path"] == ("chunked" if form == "timed" else "segmented")
    assert root.get("forward_of") == (
        "restarted" if cfg.rejoin == "neighbor_restart" else None)
    np.testing.assert_array_equal(split.history.objective, whole.history.objective)
    np.testing.assert_array_equal(
        split.history.consensus_error, whole.history.consensus_error
    )
    np.testing.assert_array_equal(split.final_models, whole.final_models)


def test_telemetry_leaves_the_carried_trajectory_alone():
    """The flight recorder's gradient probe recomputes (it asks at the
    post-step models, for the batch already used) and feeds outputs only."""
    cfg = glm_cfg(problem_type="logistic", eval_every=6, scan_unroll=4)
    ds = generate_synthetic_dataset(cfg)
    off, root = run_rooted(cfg, ds)
    on, root_on = run_rooted(cfg.replace(telemetry=True), ds)
    assert root["forward"] == root_on["forward"] == "carried"
    np.testing.assert_array_equal(on.final_models, off.final_models)
    np.testing.assert_array_equal(on.history.objective, off.history.objective)


def test_the_state_holds_no_margins(whole_run):
    """Nor the restarted models they were taken at."""
    assert sorted(whole_run[2].final_state) == ["x"]


def test_the_horizons_last_trip_restarts_at_a_clamped_row():
    """The last trip's eval asks the restart for row T of leaves that have T
    rows: the index clamps and the product is dropped, so a run of T
    iterations is the first T of a run of 2T over the same chains (a chain's
    round t is a function of (key, t) and the round before), where row T is
    really read."""
    cfg = restart_cfg(mttf=6.0, mttr=3.0, **SHIFT)
    ds = generate_synthetic_dataset(cfg)
    short, root = run_rooted(cfg, ds)
    twice, _ = run_rooted(cfg.replace(n_iterations=2 * cfg.n_iterations), ds)
    assert root["forward_of"] == "restarted"
    T = cfg.n_iterations
    assert np.isfinite(short.history.objective).all()
    np.testing.assert_array_equal(
        short.history.objective, twice.history.objective[:T])
    np.testing.assert_array_equal(
        short.history.consensus_error, twice.history.consensus_error[:T])


# --- fused: the carry is the next gradient (ISSUE 41) ----------------------
#
# On a TPU the eval's pass is ONE visit of the shards by a kernel
# (``ops.pallas_kernels.glm_shard_visit``) that leaves the objective at x̄ AND
# the next trip's first gradient, at the batch weights of t + 1, in the carry.
# A CPU never takes it; here the rule is patched on and the kernel
# interpreted. Three programs, one trajectory: to 1e-12 of each array's scale
# in f64 (4,096 units of 2.2e-16; the sums over d and L run in another
# order), and bitwise wherever one program is replayed or split.

FUSED_ULPS = 4096


def fused(monkeypatch):
    monkeypatch.setattr(
        jax_backend, "_visit_is_fused", lambda carried, X: bool(carried)
    )


def assert_fused_is_carried_and_recomputed(
    cfg, monkeypatch, forward_of=None, **run_kw
):
    cfg = cfg.replace(dtype="float64")
    ds = generate_synthetic_dataset(cfg)
    fused(monkeypatch)
    got, root = run_rooted(cfg, ds, **run_kw)
    assert root["forward"] == "fused" and root.get("forward_of") == forward_of
    monkeypatch.setattr(jax_backend, "_visit_is_fused", lambda *a: False)
    for want_form in ("carried", "recomputed"):
        if want_form == "recomputed":
            recomputed(monkeypatch)
        want, root = run_rooted(cfg, ds, **run_kw)
        assert root["forward"] == want_form
        assert_ulps_of_scale(
            got.history.objective, want.history.objective, FUSED_ULPS)
        assert_ulps_of_scale(
            got.history.consensus_error, want.history.consensus_error,
            FUSED_ULPS)
        assert_ulps_of_scale(got.final_models, want.final_models, FUSED_ULPS)


@pytest.mark.parametrize("whole", sorted(WHOLE_SHARD))
@pytest.mark.parametrize("family", FAMILIES)
def test_fused_run_is_the_carried_and_the_recomputed_run(
    family, whole, monkeypatch
):
    assert_fused_is_carried_and_recomputed(
        small_backend_config(problem_type=family, **WHOLE_SHARD[whole]),
        monkeypatch,
    )


@pytest.mark.parametrize("kw", [
    dict(straggler_prob=0.3),  # a frozen row's gradient is its frozen model's
    dict(eval_every=6, scan_unroll=4),  # micro = 3, two trips an eval
    # later slots draw for themselves and visit the shards once each (ISSUE
    # 51: ``glm_shard_gradient``), two plain passes under the other forms
    dict(local_steps=3),
    dict(n_features=80, n_informative_features=40),  # the study's width
], ids=["stragglers", "micro3_two_trips", "local_steps3", "d81"])
def test_only_the_trips_first_gradient_is_the_carried_one(kw, monkeypatch):
    """The visit draws iteration t + 1's batch a trip early and takes the
    gradient at the state AFTER the freeze; within a trip of ``micro`` steps
    only the first has the carried g."""
    kw.setdefault("sampling_impl", "dense")
    assert_fused_is_carried_and_recomputed(
        small_backend_config(problem_type="logistic", **kw), monkeypatch
    )


@pytest.mark.parametrize("kw", [
    dict(mttf=6.0, mttr=3.0, **SHIFT),
    dict(mttf=12.0, mttr=4.0, edge_drop_prob=0.3, burst_len=4.0,
         eval_every=6, scan_unroll=4, **SHIFT),
    dict(mttf=6.0, mttr=3.0, topology="chain", **SHIFT),
], ids=["shift", "shift_bursts_micro3", "gather_chain"])
def test_under_restart_the_visit_takes_the_gradient_at_the_restarted_models(
    kw, monkeypatch
):
    """The kernel's two model operands come apart: the gradient at the
    restarted stack, the losses at the unrestarted mean."""
    kw.setdefault("sampling_impl", "dense")
    assert_fused_is_carried_and_recomputed(
        small_backend_config(
            problem_type="logistic", rejoin="neighbor_restart", **kw),
        monkeypatch, forward_of="restarted",
    )


@pytest.mark.parametrize("local_steps", [1, 3])
def test_fused_on_one_device_is_fused_over_the_mesh(local_steps, monkeypatch):
    """Under a mesh the visit runs under ``shard_map``, each device on its
    rows: the same kernel on the same numbers (the objective's cross-device
    sum falls in another order); and so does the visit without its objective
    half, a round's later descents (ISSUE 51)."""
    fused(monkeypatch)
    cfg = small_backend_config(
        problem_type="logistic", sampling_impl="dense", local_steps=local_steps)
    ds = generate_synthetic_dataset(cfg)
    sharded, root = run_rooted(cfg, ds)
    one, root_one = run_rooted(cfg, ds, use_mesh=False)
    assert root["forward"] == root_one["forward"] == "fused"
    assert sharded.history.mesh_devices == 8 and one.history.mesh_devices == 1
    want32 = lambda a: np.asarray(a, dtype=np.float32)  # noqa: E731
    assert_ulps_of_scale(sharded.final_models, want32(one.final_models), ULPS)
    assert_ulps_of_scale(
        sharded.history.objective, want32(one.history.objective), ULPS
    )


@pytest.fixture(scope="module")
def whole_fused_run():
    # Micro-chunks of 3 steps, two trips an eval, stragglers frozen.
    cfg = small_backend_config(
        problem_type="logistic", sampling_impl="dense", eval_every=6,
        scan_unroll=4, straggler_prob=0.2,
    )
    ds = generate_synthetic_dataset(cfg)
    with pytest.MonkeyPatch.context() as patch:
        fused(patch)
        whole, root = run_rooted(cfg, ds, return_state=True)
    assert root["forward"] == "fused" and root["path"] == "fused"
    return cfg, ds, whole


@pytest.mark.parametrize("form,size", [
    ("heartbeat", 1), ("heartbeat", 3), ("checkpoint", 4), ("resumed", 3),
])
def test_a_split_fused_run_is_bitwise_the_unsplit_run(
    whole_fused_run, tmp_path, monkeypatch, form, size
):
    """g is the program's, not the state's: every segment makes its first
    one from the state it is handed and its own ``t0`` by the visit itself,
    so no row shifts and a split run is the unsplit run to the bit."""
    cfg, ds, whole = whole_fused_run
    fused(monkeypatch)
    if form == "heartbeat":
        kw = {"progress_cb": lambda ev: None, "progress_every": size}
    else:
        kw = {"checkpoint": CheckpointOptions(
            str(tmp_path / "ck"), every_evals=size
        )}
    if form == "resumed":
        jax_backend.run(cfg.replace(n_iterations=24), ds, 0.0, **kw)
    split, root = run_rooted(cfg, ds, **kw)
    assert root["forward"] == "fused" and root["path"] == "segmented"
    np.testing.assert_array_equal(split.history.objective, whole.history.objective)
    np.testing.assert_array_equal(
        split.history.consensus_error, whole.history.consensus_error
    )
    np.testing.assert_array_equal(split.final_models, whole.final_models)


def test_the_state_holds_no_gradient(whole_fused_run):
    assert sorted(whole_fused_run[2].final_state) == ["x"]


def test_one_visit_of_the_shards_in_the_loop_and_one_before_it(monkeypatch):
    """No reduction over the stack is left in the fused program: one kernel
    call in the loop's body, one in front of it for the first gradient."""
    fused(monkeypatch)
    cfg = glm_cfg(problem_type="logistic", n_iterations=10)
    ds = generate_synthetic_dataset(cfg)
    seg_scan, args = seg_scan_of(cfg, ds, monkeypatch)
    jaxpr = jax.make_jaxpr(seg_scan)(*args).jaxpr
    assert reads_of(jaxpr, args[2]["X"].shape) == []

    def calls(jp, in_scan):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield in_scan
            for sub in jax.core.jaxprs_in_params(eqn.params):
                if eqn.primitive.name != "pallas_call":
                    yield from calls(sub, in_scan or eqn.primitive.name == "scan")

    assert sorted(calls(jaxpr, False)) == [False, True]


class _Shards:
    def __init__(self, rows, dtype=jnp.float32):
        self.shape, self.dtype = (1 << 18, rows, 81), jnp.dtype(dtype)


def test_the_rule_a_cpu_never_fuses_and_a_long_shard_stays_carried(monkeypatch):
    """``_visit_is_fused``: every condition of ``carried``, a TPU, f32 shards,
    and 128 workers' shards in the kernel's budget twice."""
    rule = jax_backend._visit_is_fused
    assert jax.default_backend() == "cpu"
    assert not rule(True, _Shards(53))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert rule(True, _Shards(53))
    assert not rule(False, _Shards(53))
    assert not rule(True, _Shards(100_000))
    assert not rule(True, _Shards(53, jnp.bfloat16))
