"""The tracker cell (ISSUE 39) at a size a test run can hold: gradient
tracking on least squares over an 8 x 8 torus through the program's normal
path (the grid stencil, the gather sampler, three state leaves), against the
benchmark's plain reference (``benchmark/reference/gt_torus.py``: the rule
written out, nothing of the package), by the limits of the cell's own
configuration file; the three controls outside them; the tracking
invariant; the stencil against the dense matrix; the sampler's rows against
the documented rule; what the ``dopt.run`` root says of the rule, its state
and its sampler; the dataset and the compulsory bytes from their files
alone. CPU, N = 64, T = 40: numbers against limits, never a time.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, datasets, program  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.flops import glm_step  # noqa: E402
from benchmark.reference import gt_torus  # noqa: E402
from benchmark.reference.dsgd_ring import batch_weights  # noqa: E402

from distributed_optimization_tpu.backends import jax_backend  # noqa: E402
from distributed_optimization_tpu.observability.spans import Tracer  # noqa: E402
from distributed_optimization_tpu.ops.mixing import make_mixing_op  # noqa: E402
from distributed_optimization_tpu.ops.sampling import sample_worker_batches  # noqa: E402
from distributed_optimization_tpu.parallel import topology  # noqa: E402

NAME, MIX = "quad81_gt_torus16k", "track1k"
SEEDS = [3, 4, 2147483999]


def load_cell(name, mix):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    _, config, traffic = harness.load_cell(bench, f"{name}.{mix}", rehearse=True)
    return config, traffic


@pytest.fixture(scope="module")
def cell():
    """(config, traffic) at the files' rehearsal sizes: an 8 x 8 torus of
    24 rows a worker on the neighbor table, 40 iterations, the check
    following 12."""
    config, traffic = load_cell(NAME, MIX)
    exp = config["experiment"]
    assert (exp["n_workers"], exp["topology_impl"]) == (64, "neighbor")
    assert (exp["algorithm"], exp["topology"], exp["problem_type"]) == (
        "gradient_tracking", "grid", "quadratic")
    assert (traffic["n_iterations"], traffic["check_iterations"]) == (40, 12)
    return config, traffic


def run_program(config, traffic, seed, return_state=False, **replace):
    X, y, L = datasets.make(config, seed)
    cfg, dataset = program.build(config, traffic, X, y, L, program.seed_for(seed))
    if replace:
        cfg = cfg.replace(**replace)
    tracer = Tracer()
    with tracer.activate():
        result = jax_backend.run(cfg, dataset, 0.0, measure_compile=False,
                                 return_state=return_state)
    (root,) = [e for e in tracer.spans() if e["name"] == "dopt.run"]
    return result, root["args"], (X, y, program.seed_for(seed))


def judged(produced, ref, config):
    said = []
    ok = compare.judge(compare.numbers(produced, ref), config["limits"][MIX], said.append)
    return ok, said


@pytest.mark.parametrize("topology_impl", ["auto", "neighbor"])
@pytest.mark.parametrize("seed", SEEDS)
def test_the_program_is_within_the_cells_limits(cell, seed, topology_impl):
    """Objective and consensus at every evaluation the reference follows, by
    the cell's limits, on the dense and on the matrix-free graph, the batch
    drawn by ``top_k`` and gathered; the first rows are f(0) and exactly 0."""
    config, traffic = cell
    result, args, (X, y, pseed) = run_program(
        config, traffic, seed, topology_impl=topology_impl, sampling_impl="gather")
    assert (args["mixing"], args["sampling"], args["grid_shape"]) == ("stencil", "gather", "8x8")
    ref = gt_torus.run(config, traffic, X, y, pseed)
    ok, said = judged(harness.produced_of(result), ref, config)
    assert ok, said
    assert result.history.consensus_error[0] == 0.0 == ref["consensus"][0]
    assert result.history.objective[0] == pytest.approx(0.5 * np.mean(y.astype(np.float64) ** 2),
                                                        rel=1e-5)
    assert not harness.gate_failures(result, traffic)
    # the other sound order of the stencil's five additions passes too
    ok, said = judged(harness.produced_of(result),
                      gt_torus.run(config, traffic, X, y, pseed, stencil="pairs"), config)
    assert ok, said


@pytest.mark.parametrize("control", ["bfloat16", "grad_at_old_x", "no_tracking"])
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_each_control_is_not_correct(cell, control, seed):
    """The reference computed another way, in the program's place, against
    the cell's own limits: the precision below the stated one, the gradient
    at the old models and no tracker at all are each over at least one."""
    config, traffic = cell
    assert control == config["precision"]["control"] or control in config["rule_controls"]
    X, y, _ = datasets.make(config, seed)
    ref = gt_torus.run(config, traffic, X, y, seed)
    how = dict(precision=control) if control == "bfloat16" else dict(rule=control)
    ok, said = judged(gt_torus.run(config, traffic, X, y, seed, **how), ref, config)
    assert not ok, said


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_the_tracker_keeps_the_mean_gradient(cell, seed):
    """mean_i y_t = mean_i g_t after 50 iterations, to rounding: W is doubly
    stochastic, so neither round moves a mean (the configuration's
    guarantee)."""
    config, traffic = cell
    result, args, _ = run_program(
        config, dict(traffic, n_iterations=50), seed, return_state=True)
    state = result.final_state
    assert sorted(state) == ["g_prev", "x", "y"] and args["state_leaves"] == 3
    mean_y, mean_g = state["y"].mean(axis=0), state["g_prev"].mean(axis=0)
    scale = np.abs(state["g_prev"]).mean()
    assert scale > 1.0  # targets of hundreds: gradients are not small
    assert np.max(np.abs(mean_y - mean_g)) < 1e-5 * scale
    # and the tracker is not the gradient: it has been mixed
    assert np.max(np.abs(state["y"] - state["g_prev"])) > 1e-2 * scale


@pytest.mark.parametrize("impl", ["dense", "neighbor"])
def test_the_grid_stencil_is_the_matrix(impl):
    """One round of the stencil on a 5 x 5 torus against W @ x with the
    Metropolis-Hastings matrix of the same graph (five weights of 1/5), and
    the neighbours it sums."""
    topo = topology.build_topology("grid", 25, impl=impl)
    assert topo.grid_shape == (5, 5)
    op = make_mixing_op(topo, impl="auto", dtype=jnp.float32)
    assert op.impl == "stencil"
    dense = topology.build_topology("grid", 25, impl="dense")
    W = topology.metropolis_hastings_weights(np.asarray(dense.adjacency, np.float64))
    np.testing.assert_allclose(W[W > 0], 0.2)
    x = np.random.default_rng(5).standard_normal((25, 7)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(op.apply(jnp.asarray(x))), W @ x, atol=1e-6)
    np.testing.assert_allclose(np.asarray(op.neighbor_sum(jnp.asarray(x))),
                               np.asarray(dense.adjacency, np.float64) @ x, atol=1e-5)
    # the reference's own stencil, both orders
    for order in gt_torus.STENCILS:
        np.testing.assert_allclose(np.asarray(gt_torus.torus_mix(jnp.asarray(x), 5, order)),
                                   W @ x, atol=1e-6)


@pytest.mark.parametrize("impl", ["dense", "neighbor"])
def test_a_torus_needs_a_square_number_of_workers(impl):
    with pytest.raises(ValueError, match="perfect square"):
        topology.build_topology("grid", 21, impl=impl)


@pytest.mark.parametrize("seed,t", [(0, 0), (7, 3), (2147483646, 299), (1234567, 41)])
def test_the_gather_sampler_draws_the_references_rows(seed, t):
    """The b rows ``sample_worker_batches`` fetches for (seed, t, worker) are
    the support of the reference's ``batch_weights`` (the documented rule,
    slot 0), and the weights are 1/b."""
    N, L, b = 9, 40, 16
    rows = jnp.broadcast_to(jnp.arange(L, dtype=jnp.float32)[None, :, None], (N, L, 3))
    slot_key = jax.random.fold_in(jax.random.key(seed), 0)
    Xb, yb, w = sample_worker_batches(
        slot_key, jnp.asarray(t, jnp.int32), rows, rows[:, :, 0],
        jnp.full((N,), L, jnp.int32), b)
    assert Xb.shape == (N, b, 3) and np.all(np.asarray(w) == np.float32(1.0 / b))
    got = np.sort(np.asarray(Xb[:, :, 0]).astype(np.int64), axis=1)
    np.testing.assert_array_equal(np.asarray(yb), np.asarray(Xb[:, :, 0]))
    want = np.asarray(batch_weights(seed, jnp.asarray(t, jnp.int32), N, L, b))
    assert np.all(np.sum(want > 0, axis=1) == b)
    for i in range(N):
        np.testing.assert_array_equal(got[i], np.flatnonzero(want[i]))


def test_the_root_says_the_rule_its_state_and_its_sampler(cell):
    config, traffic = cell
    _, args, _ = run_program(config, traffic, 5)
    assert (args["algorithm"], args["gossip_rounds"]) == ("gradient_tracking", 2)
    assert (args["state_leaves"], args["state_bytes"]) == (3, 3 * 64 * 81 * 4)
    assert (args["sampling"], args["batch_rows"]) == ("gather", 64 * 16)
    # drawn by a counted threshold over the uniforms' 32 bits, two a pass; one
    # gather a draw, the targets riding in the rows
    assert (args["select"], args["batch_gathers"]) == ("threshold:16", 1)
    assert (args["mixing"], args["grid_shape"], args["forward"]) == ("stencil", "8x8", "recomputed")
    # both x and y cross every edge every round
    assert args["wire_floats_per_edge"] == 2 * 81


@pytest.mark.parametrize("sampling_impl,batch,said", [
    ("dense", 16, "dense"), ("gather", 16, "gather"), ("auto", 24, "full"), ("auto", 99, "full")])
def test_the_root_of_dsgd_says_one_round_and_one_leaf(sampling_impl, batch, said):
    """The control's rule on its ring: one gossip round, the models alone,
    no grid; and the sampler's three forms by name."""
    config, traffic = load_cell("glm81_ring262k", "steady2k")
    _, args, _ = run_program(config, dict(traffic, n_iterations=4), 5,
                             sampling_impl=sampling_impl, local_batch_size=batch)
    assert (args["algorithm"], args["gossip_rounds"]) == ("dsgd", 1)
    assert (args["state_leaves"], args["state_bytes"]) == (1, 64 * 81 * 4)
    assert (args["sampling"], args["batch_rows"]) == (said, 64 * min(batch, 24))
    assert args["select"] == ("threshold:16" if said == "gather" else "none")
    assert args.get("batch_gathers") == (1 if said == "gather" else None)
    assert "grid_shape" not in args


def test_the_dataset_is_a_function_of_the_seed_alone(cell):
    config, _ = cell
    X, y, L = datasets.make(config, 11)
    X2, y2, _ = datasets.make(config, 11)
    X3, y3, _ = datasets.make(config, 12)
    assert L == 24 and X.shape == (64 * 24, 81) and X.dtype == y.dtype == np.float32
    np.testing.assert_array_equal(X, X2)
    np.testing.assert_array_equal(y, y2)
    assert not np.array_equal(y, y3) and not np.array_equal(X, X3)
    assert np.all(np.diff(y) >= 0)  # sorted by target: a shard is a narrow slice
    assert np.all(X[:, -1] == 1.0)
    # unit-variance features, targets of hundreds (coefficients 100 U(0, 1)
    # on 50 of 80 columns), noise 10 around the linear model
    assert 0.9 < X[:, :-1].std() < 1.1 and 250 < y.std() < 600
    coef, *_ = np.linalg.lstsq(X.astype(np.float64), y.astype(np.float64), rcond=None)
    assert np.all(np.abs(coef[50:80]) < 3.0) and np.all(coef[:50] > -3.0)
    assert 5.0 < (y - X @ coef).std() < 15.0


def test_the_compulsory_bytes_are_the_files():
    """By hand at a small size, and at the cell's: one read of the shards
    and their targets, a read and a write of a leaf for each gossip round."""
    small = {"experiment": {"n_workers": 9, "n_features": 4, "algorithm": "gradient_tracking"},
             "dataset": {"rows_per_worker": 7}}
    assert glm_step.compulsory_bytes(small) == 9 * 7 * (5 + 1) * 4 + 2 * 2 * 9 * 5 * 4
    small["experiment"]["algorithm"] = "dsgd"
    assert glm_step.compulsory_bytes(small) == 9 * 7 * 6 * 4 + 1 * 2 * 9 * 5 * 4
    with open(os.path.join(ROOT, "benchmark", "configs", NAME + ".json")) as fh:
        config = json.load(fh)
    assert config["step_bytes"] == "glm_step" and config["architecture"] is None
    assert glm_step.compulsory_bytes(config) == 16384 * 800 * 82 * 4 + 2 * 2 * 16384 * 81 * 4
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as fh:
        peak = json.load(fh)["TPU v5 lite"]["hbm_bytes_per_s"]
    assert 5.0e-3 < glm_step.compulsory_bytes(config) / peak < 5.5e-3
