"""Single-kernel fused robust gather path (ISSUE-6 tentpole).

The fused form (``ops/pallas_kernels.py::make_fused_robust_aggregator`` /
``make_fused_robust_dsgd_step`` behind ``robust_impl='fused'``) must be an
EXECUTION change only, exactly like the gather form it fuses: bitwise-equal
outputs for the count rules (trimmed mean / median — the in-kernel sort
network reproduces jnp.sort's values exactly for finite inputs), ≤ 1e-12
f64 for clipping, through unit calls AND real backend runs composed with
bursty links + crash-recovery churn + Byzantine injection, plus
checkpoint/resume exactness. Routing contract: 'auto' NEVER selects fused
(Mosaic refuses the kernel — tests/test_tpu_lowering.py — so the default
path is the gather form on every platform), explicit 'fused' is honored but
rejected where the kernel cannot run (replica batches, over-wide sort
networks), and interpret-mode selection respects the input's committed
platform (the ``_on_cpu`` satellite fix).
"""

import jax
import jax.numpy as jnp
from jax import enable_x64
import numpy as np
import pytest

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.ops import pallas_kernels as pk
from distributed_optimization_tpu.ops.robust_aggregation import (
    make_gather_robust_aggregator,
    make_robust_aggregator,
    robust_aggregate_np,
)
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.parallel.topology import neighbor_table

RULES = ("trimmed_mean", "median", "clipped_gossip")
COUNT_RULES = ("trimmed_mean", "median")


def _gather_live(A, nbr_idx, nbr_mask):
    return np.take_along_axis(np.asarray(A), nbr_idx, axis=1) * nbr_mask


def _faulted_instance(n=14, seed=3, d=7):
    """An irregular fault-realized graph with wild (attack-like) rows."""
    topo = build_topology("erdos_renyi", n, erdos_renyi_p=0.5, seed=seed)
    rng = np.random.default_rng(11)
    A = np.array(topo.adjacency, copy=True)
    ei, ej = np.nonzero(np.triu(A, 1))
    drop = rng.random(len(ei)) < 0.3
    A[ei[drop], ej[drop]] = A[ej[drop], ei[drop]] = 0.0
    x = rng.standard_normal((n, d))
    x[[1, 5]] *= 1e4
    nbr_idx, nbr_mask = neighbor_table(topo.adjacency)
    live = _gather_live(A, nbr_idx, nbr_mask)
    return A, x, nbr_idx, live


# ------------------------------------------------------ unit kernel parity

@pytest.mark.parametrize("rule", RULES)
def test_fused_matches_gather_dense_and_oracle_f64(rule):
    """The acceptance parity: bitwise vs gather for the count rules,
    ≤ 1e-12 (f64) for clipping; dense and the per-node numpy oracle agree
    to the gather path's own pinned tolerance."""
    A, x, nbr_idx, live = _faulted_instance()
    with enable_x64():
        gather = make_gather_robust_aggregator(rule, 1, nbr_idx)
        fused = pk.make_fused_robust_aggregator(rule, 1, nbr_idx)
        dense = make_robust_aggregator(rule, budget=1)
        lv = jnp.asarray(live, jnp.float64)
        xv = jnp.asarray(x, jnp.float64)
        g_out = np.asarray(gather(lv, xv))
        f_out = np.asarray(fused(lv, xv))
        d_out = np.asarray(
            dense(jnp.asarray(A, jnp.float64), xv)
        )
    if rule in COUNT_RULES:
        np.testing.assert_array_equal(f_out, g_out)
    else:
        np.testing.assert_allclose(f_out, g_out, rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(f_out, d_out, rtol=1e-12, atol=1e-12)
    o_out = robust_aggregate_np(rule, A, x, budget=1)
    np.testing.assert_allclose(f_out, o_out, rtol=1e-12, atol=1e-12)


def test_fused_fixed_clip_tau_matches_gather():
    A, x, nbr_idx, live = _faulted_instance(n=12, seed=9, d=5)
    with enable_x64():
        gather = make_gather_robust_aggregator(
            "clipped_gossip", 1, nbr_idx, clip_tau=0.7
        )
        fused = pk.make_fused_robust_aggregator(
            "clipped_gossip", 1, nbr_idx, clip_tau=0.7
        )
        lv = jnp.asarray(live, jnp.float64)
        xv = jnp.asarray(x, jnp.float64)
        np.testing.assert_allclose(
            np.asarray(fused(lv, xv)), np.asarray(gather(lv, xv)),
            rtol=0, atol=1e-12,
        )


@pytest.mark.parametrize("rule", RULES)
def test_fused_dsgd_step_is_aggregate_then_subtract(rule):
    """The whole-update kernel == the two-op sequence it fuses. Not
    asserted bitwise: XLA may contract the − η·g multiply-subtract into
    an FMA inside one program shape and not the other, a 1-ulp
    discrepancy — the tolerance admits exactly that (≪ the 1e-12
    acceptance floor)."""
    A, x, nbr_idx, live = _faulted_instance()
    rng = np.random.default_rng(21)
    g = rng.standard_normal(x.shape)
    with enable_x64():
        fused_step = pk.make_fused_robust_dsgd_step(rule, 1, nbr_idx)
        fused_agg = pk.make_fused_robust_aggregator(rule, 1, nbr_idx)
        lv = jnp.asarray(live, jnp.float64)
        xv = jnp.asarray(x, jnp.float64)
        gv = jnp.asarray(g, jnp.float64)
        eta = jnp.asarray(0.05, jnp.float64)
        got = np.asarray(fused_step(lv, xv, gv, eta))
        want = np.asarray(fused_agg(lv, xv) - eta * gv)
    np.testing.assert_allclose(got, want, rtol=1e-14, atol=1e-14)


def test_fused_f32_matches_gather_f32():
    """Same accumulation-dtype floor as the gather form: f32 inputs agree
    bitwise for the count rules (both run the identical op sequence in
    f32)."""
    _, x, nbr_idx, live = _faulted_instance()
    for rule in COUNT_RULES:
        gather = make_gather_robust_aggregator(rule, 1, nbr_idx)
        fused = pk.make_fused_robust_aggregator(rule, 1, nbr_idx)
        lv = jnp.asarray(live, jnp.float32)
        xv = jnp.asarray(x, jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(fused(lv, xv)), np.asarray(gather(lv, xv))
        )


def test_identity_row_degradation_matches_gather():
    """Faulted-down neighborhoods (realized closed count ≤ 2b / deg ≤ b)
    keep the worker's own model in the fused form exactly like gather."""
    topo = build_topology("ring", 10)
    rng = np.random.default_rng(8)
    x = rng.standard_normal((10, 4))
    A = np.array(topo.adjacency, copy=True)
    A[0, :] = A[:, 0] = 0.0
    A[3, 4] = A[4, 3] = 0.0
    nbr_idx, nbr_mask = neighbor_table(topo.adjacency)
    live = _gather_live(A, nbr_idx, nbr_mask)
    with enable_x64():
        for rule in RULES:
            fused = pk.make_fused_robust_aggregator(rule, 1, nbr_idx)
            out = np.asarray(
                fused(jnp.asarray(live, jnp.float64),
                      jnp.asarray(x, jnp.float64))
            )
            np.testing.assert_array_equal(out[0], x[0])
            gather = make_gather_robust_aggregator(rule, 1, nbr_idx)
            g_out = np.asarray(
                gather(jnp.asarray(live, jnp.float64),
                       jnp.asarray(x, jnp.float64))
            )
            if rule in COUNT_RULES:
                np.testing.assert_array_equal(out, g_out)
            else:
                np.testing.assert_allclose(out, g_out, rtol=0, atol=1e-12)


def test_sort_network_matches_jnp_sort():
    """The in-kernel odd-even transposition network is bitwise jnp.sort
    for finite inputs, +inf padding included (the property the count-rule
    bitwise parity rests on)."""
    rng = np.random.default_rng(5)
    v = rng.standard_normal((40, 9, 6))
    v[rng.random(v.shape) < 0.2] = np.inf  # masked-slot padding
    with enable_x64():
        got = np.asarray(pk._sort_columns(jnp.asarray(v, jnp.float64)))
        want = np.asarray(jnp.sort(jnp.asarray(v, jnp.float64), axis=1))
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------ e2e backend equivalence

E2E_CFG = ExperimentConfig(
    n_workers=12, n_samples=360, n_features=8, n_informative_features=5,
    n_iterations=80, local_batch_size=8, problem_type="quadratic",
    algorithm="dsgd", topology="erdos_renyi", erdos_renyi_p=0.6,
    eval_every=20, dtype="float64", partition="shuffled",
    attack="sign_flip", n_byzantine=2, attack_scale=2.0,
    aggregation="trimmed_mean", robust_b=1,
)


@pytest.fixture(scope="module")
def e2e_data():
    from distributed_optimization_tpu.utils.data import (
        generate_synthetic_dataset,
    )
    from distributed_optimization_tpu.utils.oracle import (
        compute_reference_optimum,
    )

    ds = generate_synthetic_dataset(E2E_CFG)
    _, f_opt = compute_reference_optimum(ds, E2E_CFG.reg_param)
    return ds, f_opt


@pytest.mark.parametrize("rule", RULES)
def test_e2e_fused_matches_gather_under_composed_faults(e2e_data, rule):
    """The full composition — bursty links + crash-recovery churn +
    Byzantine sign-flip — through real backend runs: robust_impl='fused'
    consumes the per-iteration gather-form liveness inside the kernel, so
    the trajectory must match the gather path's at the repo's e2e parity
    floor, ≤ 1e-12 in f64 (the same convention as gather-vs-dense:
    kernel-level parity IS bitwise for the count rules — the unit tests
    above — but across two differently-shaped compiled programs XLA's
    FMA-contraction choices for the surrounding step ops admit ulp-level
    trajectory drift)."""
    ds, f_opt = e2e_data
    cfg = E2E_CFG.replace(
        aggregation=rule, edge_drop_prob=0.2, burst_len=3.0,
        mttf=8.0, mttr=3.0,
    )
    from conftest import batch_schedule

    sched = batch_schedule(ds, cfg.n_iterations, cfg.local_batch_size)
    rg = jax_backend.run(
        cfg.replace(robust_impl="gather"), ds, f_opt, batch_schedule=sched,
        use_mesh=False,
    )
    rf = jax_backend.run(
        cfg.replace(robust_impl="fused"), ds, f_opt, batch_schedule=sched,
        use_mesh=False,
    )
    np.testing.assert_allclose(
        rf.final_models, rg.final_models, rtol=1e-12, atol=1e-12
    )
    np.testing.assert_allclose(
        rf.history.objective, rg.history.objective, rtol=1e-12
    )


def test_e2e_gt_fused_aggregate(e2e_data):
    """Non-dsgd byzantine algorithms (gradient tracking) take the fused
    AGGREGATOR (screen+mix kernel; the SGD fusion is dsgd's) — same
    trajectory as gather."""
    ds, f_opt = e2e_data
    cfg = E2E_CFG.replace(algorithm="gradient_tracking")
    rg = jax_backend.run(
        cfg.replace(robust_impl="gather"), ds, f_opt, use_mesh=False
    )
    rf = jax_backend.run(
        cfg.replace(robust_impl="fused"), ds, f_opt, use_mesh=False
    )
    # e2e parity floor (see the composed-faults test's docstring).
    np.testing.assert_allclose(
        rf.final_models, rg.final_models, rtol=1e-12, atol=1e-12
    )


def test_fused_resume_exactness(e2e_data, tmp_path):
    """Killed-and-resumed fused run == uninterrupted fused run (the kernel
    is stateless; liveness and corruption derive from (seed, t))."""
    from distributed_optimization_tpu.utils.checkpoint import (
        CheckpointOptions,
    )

    ds, f_opt = e2e_data
    cfg = E2E_CFG.replace(
        robust_impl="fused", n_iterations=120, eval_every=20,
    )
    full = jax_backend.run(cfg, ds, f_opt, use_mesh=False)
    ckdir = str(tmp_path / "fused_ck")
    jax_backend.run(
        cfg.replace(n_iterations=60), ds, f_opt, use_mesh=False,
        checkpoint=CheckpointOptions(ckdir, every_evals=3),
    )
    resumed = jax_backend.run(
        cfg, ds, f_opt, use_mesh=False,
        checkpoint=CheckpointOptions(ckdir, every_evals=3),
    )
    np.testing.assert_allclose(
        resumed.final_models, full.final_models, rtol=1e-12
    )


# ------------------------------------------------------- routing contract

def test_auto_never_builds_the_fused_kernel(e2e_data, monkeypatch):
    """Static topology + supported rule + telemetry off + no mesh — the
    configuration 'auto' used to promote: it now IS the gather program
    (bitwise the explicit 'gather' run; the fused builders never called)."""
    ds, f_opt = e2e_data

    def boom(*a, **kw):
        raise AssertionError("robust_impl='auto' built the fused kernel")

    monkeypatch.setattr(pk, "make_fused_robust_aggregator", boom)
    monkeypatch.setattr(pk, "make_fused_robust_dsgd_step", boom)
    ra = jax_backend.run(E2E_CFG, ds, f_opt, use_mesh=False)
    rg = jax_backend.run(
        E2E_CFG.replace(robust_impl="gather"), ds, f_opt, use_mesh=False
    )
    np.testing.assert_array_equal(ra.final_models, rg.final_models)


def test_auto_stays_gather_under_faults_and_telemetry(e2e_data):
    """Time-varying graphs and an active telemetry activity probe run the
    measured gather routing, like every other 'auto' configuration."""
    ds, f_opt = e2e_data
    faulty = E2E_CFG.replace(edge_drop_prob=0.2)
    ra = jax_backend.run(faulty, ds, f_opt, use_mesh=False)
    rg = jax_backend.run(
        faulty.replace(robust_impl="gather"), ds, f_opt, use_mesh=False
    )
    np.testing.assert_array_equal(ra.final_models, rg.final_models)
    tele = E2E_CFG.replace(telemetry=True)
    rt = jax_backend.run(tele, ds, f_opt, use_mesh=False)
    rtg = jax_backend.run(
        tele.replace(robust_impl="gather"), ds, f_opt, use_mesh=False
    )
    np.testing.assert_array_equal(rt.final_models, rtg.final_models)


def test_resolved_robust_impl_never_fused():
    cfg = E2E_CFG
    assert cfg.resolved_robust_impl(4) == "gather"
    # Fully connected keeps dense.
    assert cfg.resolved_robust_impl(11) == "dense"
    # Explicit forms are never overridden — fused stays an opt-in.
    assert cfg.replace(robust_impl="fused").resolved_robust_impl(4) == "fused"
    assert cfg.replace(robust_impl="gather").resolved_robust_impl(4) == "gather"


def test_fused_rejects_over_wide_sort_network():
    """Rules whose in-kernel sort would exceed the network width bound
    are not fused-eligible: explicit 'fused' raises. Clipping sorts
    nothing at a FIXED
    radius (any degree), but the ADAPTIVE radius ranks the [N, k_max]
    norms through the same quadratic network, so it carries the bound
    too."""
    topo = build_topology("fully_connected", 24)
    nbr_idx, _ = neighbor_table(topo.adjacency)
    assert not pk.fused_robust_supported("median", 23)
    assert not pk.fused_robust_supported("clipped_gossip", 23)  # adaptive
    assert pk.fused_robust_supported("clipped_gossip", 23, clip_tau=0.7)
    assert pk.fused_robust_supported("clipped_gossip", 12)
    with pytest.raises(ValueError, match="sort network"):
        pk.make_fused_robust_aggregator("median", 1, nbr_idx)
    with pytest.raises(ValueError, match="sort network"):
        pk.make_fused_robust_aggregator("clipped_gossip", 1, nbr_idx)
    # Fixed-radius clipping stays constructible at the same degree.
    pk.make_fused_robust_aggregator("clipped_gossip", 1, nbr_idx,
                                    clip_tau=0.7)


def test_run_batch_rejects_fused(e2e_data):
    ds, f_opt = e2e_data
    with pytest.raises(ValueError, match="robust_impl='fused'"):
        jax_backend.run_batch(
            E2E_CFG.replace(robust_impl="fused"), ds, f_opt,
            seeds=[1, 2],
        )


def test_config_rejects_fused_with_replicas_and_without_rule():
    with pytest.raises(ValueError, match="fused"):
        E2E_CFG.replace(robust_impl="fused", replicas=2)
    with pytest.raises(ValueError, match="robust_impl"):
        ExperimentConfig(robust_impl="fused")


# ------------------------------------- interpret-mode selection satellite

def test_resolve_interpret_explicit_override_wins():
    x = jnp.zeros((4, 4))
    assert pk.resolve_interpret(x, interpret=True) is True
    assert pk.resolve_interpret(x, interpret=False) is False


def test_resolve_interpret_uses_committed_platform():
    """On this CPU-only container every committed array lives on cpu, and
    the resolver must read THAT (not the global devices list) — including
    under an explicit jax.default_device scope, in BOTH forms jax
    accepts (a Device object and a platform string — the latter leaves a
    plain str in jax.config.jax_default_device)."""
    x = jax.device_put(jnp.zeros((4, 4)), jax.devices("cpu")[0])
    assert pk.resolve_interpret(x) is True
    with jax.default_device(jax.devices("cpu")[0]):
        assert pk.resolve_interpret(None) is True
    with jax.default_device("cpu"):
        assert pk.resolve_interpret(None) is True


def test_resolve_interpret_handles_tracers():
    """Inside jit the operand is a tracer with no committed device; the
    resolver must fall back to the ambient platform instead of raising."""
    seen = {}

    @jax.jit
    def probe(x):
        seen["interp"] = pk.resolve_interpret(x)
        return x

    probe(jnp.zeros((2, 2)))
    assert seen["interp"] is True  # cpu container
