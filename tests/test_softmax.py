"""Multinomial softmax regression — the compute-bound objective family
(round 5, VERDICT r4 item 1).

Not in the reference (its GLMs are scalar-output, reference
``obj_problems.py:3-69``); this family exists so the framework has a tier
whose gradients are real [b,d]x[d,K] matmuls that tile onto the MXU
(docs/PERF.md §compute-bound). Pinned here:

- closed-form kernels vs jax.grad of the objective (the same check the
  scalar families get in test_losses),
- numpy twin ≡ jax kernels on identical inputs,
- the parameter layout: [N, d, K] inside the jax scan (``param_shape``; no
  relayout between a flat and a matrix form in any step), flat [d·K] at
  every boundary and in the other backends (state dims, gossip payload
  accounting, ``param_dim``, checkpoints), on every operator path,
- oracle stationarity (gradient ~ 0 at the scipy L-BFGS optimum) and
  backend convergence toward it,
- jax ≡ numpy step-for-step with injected batches,
- the native core's honest rejection (vector-parameter C ABI).
"""

import dataclasses

import jax
import jax.numpy as jnp
from jax import enable_x64
import numpy as np
import pytest

from conftest import batch_schedule as _schedule, small_backend_config
from distributed_optimization_tpu.algorithms import get_algorithm
from distributed_optimization_tpu.backends import jax_backend, numpy_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.models import get_problem
from distributed_optimization_tpu.observability.spans import Tracer
from distributed_optimization_tpu.ops import losses, losses_np
from distributed_optimization_tpu.ops.mixing import make_mixing_op
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.serving.cache import ExecutableCache
from distributed_optimization_tpu.utils.checkpoint import (
    CheckpointOptions,
    RunCheckpointer,
)
from distributed_optimization_tpu.utils.data import (
    generate_digits_dataset,
    generate_synthetic_dataset,
    stack_shards,
)
from distributed_optimization_tpu.utils.oracle import compute_reference_optimum


def _softmax_cfg(**kw):
    defaults = dict(
        problem_type="softmax", n_classes=5, n_samples=400, n_features=12,
        n_informative_features=8, learning_rate_eta0=0.5,
    )
    defaults.update(kw)
    return small_backend_config(**defaults)


@pytest.fixture(scope="module")
def sm_setup():
    cfg = _softmax_cfg(n_iterations=300, eval_every=50)
    ds = generate_synthetic_dataset(cfg)
    w_opt, f_opt = compute_reference_optimum(
        ds, cfg.reg_param, n_classes=cfg.n_classes
    )
    return cfg, ds, w_opt, f_opt


# ----------------------------------------------------------------- kernels


def test_gradient_matches_autodiff(rng):
    d, K, b, lam = 7, 4, 9, 0.01
    w = rng.normal(size=d * K)
    X = rng.normal(size=(b, d))
    y = rng.integers(0, K, size=b).astype(np.float64)
    with enable_x64():
        auto = jax.grad(losses.softmax_objective)(
            jnp.asarray(w), jnp.asarray(X), jnp.asarray(y), lam
        )
        closed = losses.softmax_gradient(
            jnp.asarray(w), jnp.asarray(X), jnp.asarray(y), lam
        )
        np.testing.assert_allclose(np.asarray(closed), np.asarray(auto),
                                   rtol=1e-10, atol=1e-12)
        # Weighted forms with mean weights reproduce the plain forms.
        wts = jnp.full(b, 1.0 / b, dtype=jnp.float64)
        np.testing.assert_allclose(
            np.asarray(losses.softmax_gradient_weighted(
                jnp.asarray(w), jnp.asarray(X), jnp.asarray(y), wts, lam)),
            np.asarray(closed), rtol=1e-10, atol=1e-12,
        )


def test_numpy_twin_matches_jax(rng):
    d, K, b, lam = 6, 3, 11, 0.02
    w = rng.normal(size=d * K)
    X = rng.normal(size=(b, d))
    y = rng.integers(0, K, size=b).astype(np.float64)
    with enable_x64():
        jo = float(losses.softmax_objective(
            jnp.asarray(w), jnp.asarray(X), jnp.asarray(y), lam))
        jg = np.asarray(losses.softmax_gradient(
            jnp.asarray(w), jnp.asarray(X), jnp.asarray(y), lam))
    assert losses_np.softmax_objective(w, X, y, lam) == pytest.approx(
        jo, rel=1e-12
    )
    np.testing.assert_allclose(
        losses_np.softmax_gradient(w, X, y, lam), jg, rtol=1e-10, atol=1e-12
    )


def test_param_dim_plumbing():
    p = get_problem("softmax", n_classes=7)
    assert p.param_shape(13) == (13, 7)
    assert p.param_dim(13) == 91
    for name in ("logistic", "quadratic", "huber"):
        assert get_problem(name).param_shape(13) == (13,)
        assert get_problem(name).param_dim(13) == 13
    # Cached per K: identical callables back for the same class count (jit
    # static-arg stability).
    assert get_problem("softmax", n_classes=7) is p


# ----------------------------------------------------------------- oracle


def test_oracle_stationarity(sm_setup):
    cfg, ds, w_opt, f_opt = sm_setup
    g = losses_np.softmax_gradient(w_opt, ds.X_full, ds.y_full, cfg.reg_param)
    assert np.abs(g).max() < 1e-6
    assert w_opt.shape == (ds.n_features * cfg.n_classes,)


# ---------------------------------------------------------------- backends


def test_backends_converge_and_account(sm_setup):
    cfg, ds, _, f_opt = sm_setup
    rj = jax_backend.run(cfg, ds, f_opt)
    gaps = rj.history.objective
    assert np.all(np.isfinite(gaps))
    assert gaps[-1] < 0.5 * gaps[0]
    # Flat [d·K] models at the boundary (the scan carried [N, d, K]); gossip
    # payload counts the full matrix parameter.
    d_model = ds.n_features * cfg.n_classes
    assert rj.final_models.shape == (cfg.n_workers, d_model)
    assert rj.history.total_floats_transmitted == pytest.approx(
        2 * cfg.n_workers * d_model * cfg.n_iterations  # ring: 2|E| = 2N
    )


def test_jax_matches_numpy_step_for_step(sm_setup):
    cfg, ds, _, f_opt = sm_setup
    T = 40
    sched = _schedule(ds, T, 8, seed=5)
    kw = dict(n_iterations=T, eval_every=1, dtype="float64")
    rj = jax_backend.run(cfg.replace(**kw), ds, f_opt, batch_schedule=sched)
    rn = numpy_backend.run(cfg.replace(**kw), ds, f_opt, batch_schedule=sched)
    np.testing.assert_allclose(rj.final_models, rn.final_models,
                               rtol=1e-9, atol=1e-10)
    np.testing.assert_allclose(rj.history.objective, rn.history.objective,
                               rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(rj.history.consensus_error,
                               rn.history.consensus_error,
                               rtol=1e-8, atol=1e-12)
    assert rj.final_models.shape == rn.final_models.shape == (
        cfg.n_workers, ds.n_features * cfg.n_classes
    )


# ------------------------------------- parameter shape inside the scan (PR 25)


def _run_traced(cfg, ds, f_opt=0.0, **kw):
    """One ``jax_backend.run`` under a tracer of its own: (result, the
    ``dopt.run`` root's ``carry`` argument)."""
    tracer = Tracer()
    with tracer.activate():
        result = jax_backend.run(cfg, ds, f_opt, **kw)
    (root,) = [e for e in tracer.spans() if e["name"] == "dopt.run"]
    return result, root["args"]["carry"]


def _flat_problem(*args, **kw):
    """``get_problem`` with the parameter pinned flat, ``param_shape`` =
    ``(param_dim,)``: patched into the run builder it gives the program every
    run traced before the scan carried the problem's own shape (same
    kernels, flat in → flat out)."""
    p = get_problem(*args, **kw)
    return dataclasses.replace(p, param_shape=lambda d: (p.param_dim(d),))


def _avals(jaxpr):
    """(primitive name, shape) of every operand and result of every equation
    of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        for v in (*eqn.invars, *eqn.outvars):
            if hasattr(v.aval, "shape"):
                yield eqn.primitive.name, tuple(v.aval.shape)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


def test_scan_step_carries_the_matrix_and_never_flattens_it():
    """One D-SGD step + eval of the softmax scan, traced as ``_run`` builds
    it: the carry is [N, d, K] and no equation — a reshape least of all —
    has an [N, d·K] operand or result. The real program agrees: the root
    span says which shape the call carried, and the compiled scan holds no
    [N, d·K] value."""
    cfg = _softmax_cfg(n_workers=6, n_classes=7, n_features=10,
                       n_iterations=10, eval_every=5)
    ds = generate_synthetic_dataset(cfg)
    dev = stack_shards(ds, dtype=np.float32)
    n, d, K = cfg.n_workers, dev.n_features, cfg.n_classes
    problem = get_problem("softmax", n_classes=K)
    topo = build_topology("ring", n)
    pieces = jax_backend._StepPieces(
        algo=get_algorithm("dsgd"), problem=problem, reg=cfg.reg_param,
        config=cfg, batch_size=cfg.local_batch_size, sampling_impl="gather",
        key=jax.random.key(cfg.seed), eta_fn=jax_backend._make_eta_fn(cfg),
        degrees=jnp.asarray(topo.degrees, jnp.float32).reshape(n, 1, 1),
        mix_op=make_mixing_op(topo), faulty=None, byz_mix=None,
        adversary=None, honest_w=None,
        full_objective=jax_backend.make_full_objective_fn(
            problem, cfg.reg_param
        ),
        f_opt=0.0, collect_metrics=True, track_consensus=True,
        edge_payload=None,
    )
    data = {"X": jnp.asarray(dev.X), "y": jnp.asarray(dev.y),
            "n_valid": jnp.asarray(dev.n_valid)}

    def one_trip(state):
        step, eval_metrics, *_ = jax_backend._make_step_eval(pieces, data)
        state, _ = step(state, jnp.int32(0))
        return state, eval_metrics(state, jnp.int32(0), cadence_known=True)[0]

    x0 = jnp.zeros((n, *problem.param_shape(d)), jnp.float32)
    closed = jax.make_jaxpr(one_trip)({"x": x0})
    assert closed.out_avals[0].shape == (n, d, K)
    seen = list(_avals(closed.jaxpr))
    assert ("dot_general", (n, d, K)) in seen  # the gradient, born a matrix
    assert [(p, s) for p, s in seen if s == (n, d * K)] == []

    cache = ExecutableCache()
    result, carry = _run_traced(
        cfg, ds, executable_cache=cache, use_mesh=False  # whole shapes
    )
    assert carry == f"{n}x{d}x{K}"
    assert result.final_models.shape == (n, d * K)
    (entry,) = cache._entries.values()
    assert f"[{n},{d * K}]" not in entry.executable.as_text()
    assert f"[{n},{d},{K}]" in entry.executable.as_text()


@pytest.mark.parametrize("problem_type", ["logistic", "quadratic", "huber"])
def test_rank_one_problems_keep_their_flat_program(problem_type, monkeypatch):
    """``param_shape`` is ``(d,)`` for the scalar GLMs: the carry is [N, d]
    and the run is bitwise the run with the parameter pinned flat."""
    cfg = small_backend_config(problem_type=problem_type, n_iterations=40,
                               eval_every=10)
    ds = generate_synthetic_dataset(cfg)
    own, carry = _run_traced(cfg, ds, executable_cache=False)
    assert carry == f"{cfg.n_workers}x{ds.n_features}"
    monkeypatch.setattr(jax_backend, "get_problem", _flat_problem)
    flat, flat_carry = _run_traced(cfg, ds, executable_cache=False)
    assert flat_carry == carry
    np.testing.assert_array_equal(own.final_models, flat.final_models)
    np.testing.assert_array_equal(own.history.objective,
                                  flat.history.objective)
    np.testing.assert_array_equal(own.history.consensus_error,
                                  flat.history.consensus_error)


# Operators that need ONE parameter axis flatten a model-shaped stack at their
# own boundary (ISSUE 25 item 4); every other layer acts on the worker axis.
# Each path keeps the trajectory of the flat program.
_OPERATOR_PATHS = {
    "top_k": dict(compression="top_k", compression_k=9),
    "top_k_gradient_tracking": dict(
        algorithm="gradient_tracking", compression="top_k", compression_k=9
    ),
    "trimmed_mean": dict(
        aggregation="trimmed_mean", robust_b=1, attack="sign_flip",
        n_byzantine=1,
    ),
    "byzantine_telemetry": dict(
        attack="alie", n_byzantine=1, aggregation="clipped_gossip",
        robust_b=1, topology="fully_connected", telemetry=True,
    ),
    "worker_mesh_halo": dict(worker_mesh=4, topology_impl="neighbor"),
    "worker_mesh_halo_top_k": dict(
        worker_mesh=4, topology_impl="neighbor", compression="top_k",
        compression_k=9,
    ),
    "churn_neighbor_restart": dict(
        mttf=8.0, mttr=3.0, rejoin="neighbor_restart"
    ),
    "push_sum": dict(algorithm="push_sum", topology="directed_ring"),
    "admm": dict(algorithm="admm", topology="erdos_renyi"),
}


@pytest.mark.parametrize("path", sorted(_OPERATOR_PATHS))
def test_operator_paths_keep_the_flat_trajectory(path, monkeypatch):
    cfg = _softmax_cfg(n_iterations=30, eval_every=10, dtype="float64",
                       **_OPERATOR_PATHS[path])
    ds = generate_synthetic_dataset(cfg)
    n, d_model = cfg.n_workers, ds.n_features * cfg.n_classes
    kw = dict(executable_cache=False, return_state=True)
    own, carry = _run_traced(cfg, ds, **kw)
    assert carry == f"{n}x{ds.n_features}x{cfg.n_classes}"
    monkeypatch.setattr(jax_backend, "get_problem", _flat_problem)
    flat, flat_carry = _run_traced(cfg, ds, **kw)
    assert flat_carry == f"{n}x{d_model}"
    assert own.final_models.shape == (n, d_model)
    tol = dict(rtol=1e-12, atol=1e-13)
    np.testing.assert_allclose(own.final_models, flat.final_models, **tol)
    np.testing.assert_allclose(own.history.objective,
                               flat.history.objective, **tol)
    np.testing.assert_allclose(own.history.consensus_error,
                               flat.history.consensus_error, **tol)
    assert own.history.total_floats_transmitted == pytest.approx(
        flat.history.total_floats_transmitted
    )
    # Every leaf of the returned state keeps its flat [rows, D] shape.
    assert sorted(own.final_state) == sorted(flat.final_state)
    for k, v in own.final_state.items():
        assert v.shape == flat.final_state[k].shape
        np.testing.assert_allclose(v, flat.final_state[k], **tol)
    if cfg.telemetry:
        for k, v in own.history.trace.items():
            np.testing.assert_allclose(v, flat.history.trace[k],
                                       rtol=1e-10, atol=1e-12)


@pytest.mark.parametrize("first_half", ["own", "flat"])
@pytest.mark.parametrize("loop", ["segmented", "chunked"])
def test_checkpoint_is_flat_and_resumes(first_half, loop, tmp_path,
                                        monkeypatch):
    """A checkpoint holds flat [N, d·K] leaves whichever shape the scan
    carried, so one written by the flat program (``first_half='flat'``: the
    layout of every checkpoint on disk before this change) resumes in the
    [N, d, K] scan, on both checkpoint loops."""
    cfg = _softmax_cfg(n_iterations=40, eval_every=5, dtype="float64")
    ds = generate_synthetic_dataset(cfg)
    n, d_model = cfg.n_workers, ds.n_features * cfg.n_classes
    kw = dict(measure_timestamps=loop == "chunked")
    full = jax_backend.run(cfg, ds, 0.0, executable_cache=False)
    ckdir = str(tmp_path / "ck")
    with monkeypatch.context() as m:
        if first_half == "flat":
            m.setattr(jax_backend, "get_problem", _flat_problem)
        jax_backend.run(
            cfg.replace(n_iterations=20), ds, 0.0,
            checkpoint=CheckpointOptions(ckdir, every_evals=2, resume=False),
            **kw,
        )
    state, *_, chunk = RunCheckpointer(CheckpointOptions(ckdir)).restore()
    assert chunk == 4 and np.asarray(state["x"]).shape == (n, d_model)
    resumed, carry = _run_traced(
        cfg, ds, checkpoint=CheckpointOptions(ckdir, every_evals=2), **kw
    )
    assert carry == f"{n}x{ds.n_features}x{cfg.n_classes}"
    assert resumed.final_models.shape == (n, d_model)
    np.testing.assert_allclose(resumed.final_models, full.final_models,
                               rtol=1e-12, atol=1e-13)
    np.testing.assert_allclose(resumed.history.objective,
                               full.history.objective, rtol=1e-12, atol=1e-13)


def test_digits_multiclass():
    cfg = _softmax_cfg(n_classes=10, n_samples=600, n_iterations=200,
                       eval_every=200, learning_rate_eta0=0.1)
    ds = generate_digits_dataset(cfg)
    assert set(np.unique(ds.y_full)) <= set(range(10))
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param, n_classes=10)
    r = jax_backend.run(cfg, ds, f_opt)
    assert np.isfinite(r.history.objective[-1])
    with pytest.raises(ValueError, match="10 classes"):
        generate_digits_dataset(cfg.replace(n_classes=5))


def test_cpp_backend_matches_numpy_oracle(sm_setup):
    """Three-tier parity (round 5): the native core's softmax kernels —
    flat [d*K] model rows, labels as class indices in the y doubles —
    reproduce the independent numpy matrix recursions to machine
    precision on deterministic full-batch runs."""
    cpp_backend = pytest.importorskip(
        "distributed_optimization_tpu.backends.cpp_backend"
    )
    try:
        cpp_backend.load_library()
    except cpp_backend.NativeBuildError:  # pragma: no cover
        pytest.skip("native toolchain unavailable")
    cfg, ds, _, f_opt = sm_setup
    full = cfg.replace(local_batch_size=10_000, n_iterations=120,
                       eval_every=20)
    # ALL SEVEN algorithm recursions: the dm-threading (flat [d*K] model
    # rows) touches every branch, and these shapes only occur with softmax
    # (scalar GLMs always run dm == d). choco exercises the relaxed
    # comp_k <= d*K top-k bound with a support wider than d.
    for algo in ("dsgd", "gradient_tracking", "extra", "admm", "choco",
                 "push_sum", "centralized"):
        kw = dict(algorithm=algo)
        if algo == "push_sum":
            kw["topology"] = "directed_erdos_renyi"
        if algo == "choco":
            kw.update(compression="top_k",
                      compression_k=ds.n_features + 7)  # > d, < d*K
        c = full.replace(**kw)
        rc = cpp_backend.run(c, ds, f_opt)
        rn = numpy_backend.run(c, ds, f_opt)
        np.testing.assert_allclose(rc.final_models, rn.final_models,
                                   atol=1e-12)
        np.testing.assert_allclose(rc.history.objective,
                                   rn.history.objective, atol=1e-12)
        assert (
            rc.history.total_floats_transmitted
            == rn.history.total_floats_transmitted
        )


def test_cpp_rejects_out_of_range_labels(sm_setup):
    """An out-of-range class label would index past the native logits
    buffer (a heap write); the core must reject it up front like the
    numpy tier's IndexError."""
    from distributed_optimization_tpu.utils.data import HostDataset

    cpp_backend = pytest.importorskip(
        "distributed_optimization_tpu.backends.cpp_backend"
    )
    try:
        cpp_backend.load_library()
    except cpp_backend.NativeBuildError:  # pragma: no cover
        pytest.skip("native toolchain unavailable")
    cfg, ds, _, f_opt = sm_setup
    bad = HostDataset(
        X_full=ds.X_full,
        y_full=np.full_like(ds.y_full, cfg.n_classes),  # == K: out of range
        shard_indices=ds.shard_indices,
        problem_type="softmax",
    )
    with pytest.raises(RuntimeError, match="rejected"):
        cpp_backend.run(cfg.replace(n_iterations=10, eval_every=10),
                        bad, f_opt)


def test_labels_stay_exact_under_bfloat16():
    """Class indices must survive a bfloat16 run dtype: bf16's 8-bit
    significand rounds odd integers above 256 to their even neighbor
    (301 -> 300), which at K=512 would silently corrupt ~25% of labels.
    Labels therefore stack as int32 regardless of run dtype."""
    from distributed_optimization_tpu.utils.data import (
        HostDataset,
        stack_shards,
    )

    n, K = 4, 512
    rng = np.random.default_rng(0)
    X = rng.standard_normal((K, 8))
    y = np.arange(K).astype(np.float64)  # every class index once
    ds = HostDataset(
        X_full=X, y_full=y,
        shard_indices=[np.arange(i * K // n, (i + 1) * K // n)
                       for i in range(n)],
        problem_type="softmax",
    )
    dev = stack_shards(ds, dtype=np.dtype("bfloat16"))
    assert dev.y.dtype == np.int32
    np.testing.assert_array_equal(
        np.sort(dev.y.ravel()), np.arange(K)
    )
    # The float path this guards against really does corrupt: 301 is not
    # representable in bfloat16.
    assert float(np.asarray(301.0, dtype=np.dtype("bfloat16"))) != 301.0


def test_config_validation():
    with pytest.raises(ValueError, match="n_classes"):
        ExperimentConfig(problem_type="softmax", n_classes=1)
    # The separability constraint is make_classification's and lives with
    # the synthetic generator (the digits path has real classes and never
    # sees n_informative_features).
    with pytest.raises(ValueError, match="informative"):
        generate_synthetic_dataset(
            ExperimentConfig(problem_type="softmax", n_classes=100,
                             n_features=8, n_informative_features=4)
        )
