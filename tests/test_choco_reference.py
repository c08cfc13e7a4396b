"""CHOCO-SGD with top-k gossip against the benchmark's plain reference
(ISSUE 26): ``benchmark/reference/choco_ring.py`` restates the algorithm in
flat ``jax.numpy`` with a selection of its own (a sort of the magnitudes and
an explicit tie rule, no top-k primitive), and imports nothing of the
package. Seeded, small, on the CPU: the program's rows and final models agree
with it for a matrix-shaped (softmax, ``[N, d, K]`` in the scan) and a
vector-shaped (logistic, ``[N, d]``, sampled batches) model; every row of q
holds exactly k numbers and the public copies are the sum of what was sent;
the floats on the wire are counted as 2k an edge.
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
from benchmark.reference import choco_ring, dsgd_ring  # noqa: E402

from distributed_optimization_tpu.backends import jax_backend  # noqa: E402
from distributed_optimization_tpu.ops.compression import (  # noqa: E402
    make_error_feedback,
    row_dim,
)
from distributed_optimization_tpu.parallel import build_topology  # noqa: E402

T = 20
TRAFFIC = {"n_iterations": T, "eval_every": 5}
CHOCO = {"algorithm": "choco", "compression": "top_k", "choco_gamma": 0.04,
         "lr_schedule": "sqrt_decay"}


def small(model, **experiment):
    """A benchmark configuration at its rehearsal size, as CHOCO with top-k."""
    name = {"softmax": "softmax4096_choco_ring96", "logistic": "glm81_ring262k"}[model]
    config = json.load(open(os.path.join(ROOT, "benchmark", "configs", name + ".json")))
    for section, values in config["rehearse"].items():
        config[section].update(values)
    config["experiment"].update(CHOCO, matmul_precision="highest", **experiment)
    return config


def program_run(config, seed, **kw):
    X, y, L = datasets.make(config, seed)
    cfg, dataset = program.build(config, TRAFFIC, X, y, L, seed)
    return (X, y), cfg, jax_backend.run(cfg, dataset, 0.0, return_state=True, **kw)


MODELS = {
    # [N, d, K] inside the scan, full batch: 8 workers, a row of 33 * 8 = 264
    "softmax": dict(compression_k=3),
    # [N, d], b = 16 of 24 rows sampled: 16 workers, a row of 81
    "logistic": dict(compression_k=2, n_workers=16),
}


@pytest.mark.parametrize("schedule", ["sqrt_decay", "constant"])
@pytest.mark.parametrize("model", sorted(MODELS))
def test_program_agrees_with_the_plain_reference(model, schedule):
    config = small(model, lr_schedule=schedule, **MODELS[model])
    (X, y), cfg, got = program_run(config, seed=7)
    rows, x, xhat = choco_ring.follow(config, TRAFFIC, X, y, 7)
    nums = compare.numbers(harness.produced_of(got), rows)
    assert max(nums.values()) < 5e-6, nums
    scale = np.abs(got.final_models).max()
    assert scale > 1e-3
    np.testing.assert_allclose(got.final_models, np.asarray(x), atol=2e-6 * scale)
    np.testing.assert_allclose(got.final_state["xhat"], np.asarray(xhat), atol=2e-6 * scale)
    # The wire: T iterations, every directed edge of the ring, k values + k indices.
    k = config["experiment"]["compression_k"]
    topo = build_topology("ring", cfg.n_workers)
    assert topo.floats_per_iteration == 2 * cfg.n_workers
    assert got.history.total_floats_transmitted == T * topo.floats_per_iteration * 2 * k


@pytest.mark.parametrize("precision", ["bfloat16", "identity"])
def test_a_control_is_another_trajectory(precision):
    """What the limits must tell apart: the reference with bfloat16 state and
    operands, and with no compressor at all."""
    config = small("softmax", compression_k=3)
    X, y, _ = datasets.make(config, 9)
    ref = choco_ring.run(config, TRAFFIC, X, y, 9)
    ctl = choco_ring.run(config, TRAFFIC, X, y, 9, precision=precision)
    nums = compare.numbers(ctl, ref)
    assert nums["consensus_max_rel"] > 2e-5, nums


def test_identity_at_gamma_one_is_adapt_then_combine_dsgd():
    """No compressor and gamma = 1: x <- W (x - eta g). ``dsgd_ring`` combines
    first (x <- W x - eta g), so from x0 = 0 the mean models, and with them the
    loss, are equal after one iteration, and the consensus errors are not: the
    ring has averaged the first half-steps once. Later rows drift apart by
    O(eta^2)."""
    config = small("softmax", compression_k=3, choco_gamma=1.0)
    plain = json.loads(json.dumps(config))
    plain["experiment"].update(algorithm="dsgd", compression="none")
    X, y, _ = datasets.make(config, 11)
    one = {"n_iterations": 8, "eval_every": 1}
    atc = choco_ring.run(config, one, X, y, 11, precision="identity")
    cta = dsgd_ring.run(plain, one, X, y, 11)
    assert abs(atc["objective"][0] - cta["objective"][0]) < 1e-6 * cta["objective"][0]
    assert atc["consensus"][0] < 0.7 * cta["consensus"][0]
    gap = np.abs(atc["objective"] - cta["objective"]) / cta["objective"]
    assert 1e-6 < gap[-1] < 1e-3, gap
    # and the program's CHOCO with compression 'none' at gamma = 1 is that
    # same adapt-then-combine recursion
    cfg, dataset = program.build(
        {**config, "experiment": {**config["experiment"], "compression": "none",
                                  "compression_k": 0}},
        one, X, y, config["dataset"]["rows_per_worker"], 11)
    got = jax_backend.run(cfg, dataset, 0.0)
    nums = compare.numbers(harness.produced_of(got), atc)
    assert max(nums.values()) < 5e-6, nums


@pytest.mark.parametrize("shape", [(6, 11, 5), (6, 55)])
def test_q_has_k_entries_a_row_and_xhat_is_their_sum(shape):
    """The exchange the scan runs, on model-shaped and flat stacks: each q =
    xhat' - xhat has exactly k non-zeros in every worker's row, they are the k
    largest magnitudes of what was offered, and after t exchanges xhat is the
    sum of the q's (error feedback: what was not sent stays on offer)."""
    k, steps = 4, 6
    rng = np.random.default_rng(5)
    ef = make_error_feedback("top_k", row_dim(jnp.zeros(shape)), k, 0.04)
    mix = lambda v: (jnp.roll(v, 1, 0) + v + jnp.roll(v, -1, 0)) / 3.0  # noqa: E731
    x = jnp.zeros(shape, jnp.float32)
    xhat = ef.init(x)
    sent = np.zeros(shape, np.float32)
    for _ in range(steps):
        v = x + jnp.asarray(rng.standard_normal(shape), jnp.float32)
        x, xhat_new = ef.exchange(None, v, xhat, mix)
        q = np.asarray(xhat_new - xhat).reshape(shape[0], -1)
        offered = np.abs(np.asarray(v - xhat).reshape(shape[0], -1))
        assert (np.count_nonzero(q, axis=1) == k).all()
        kth = np.sort(offered, axis=1)[:, -k]
        assert (offered[q != 0].reshape(shape[0], k) >= kth[:, None]).all()
        sent += q.reshape(shape)
        xhat = xhat_new
    np.testing.assert_allclose(np.asarray(xhat), sent, atol=1e-6)


def test_the_reference_selection_keeps_k_with_ties_to_the_lower_index():
    v = jnp.asarray([[1.0, -2.0, 2.0, 0.5, -2.0, 2.0],
                     [0.0, 3.0, 0.0, -3.0, 3.0, 1.0]], jnp.float32)
    got = np.asarray(choco_ring.top_k_rows(v, 2))
    np.testing.assert_array_equal(got, [[0, -2.0, 2.0, 0, 0, 0], [0, 3.0, 0, -3.0, 0, 0]])
    got = np.asarray(choco_ring.top_k_rows(v, 3))
    np.testing.assert_array_equal(got, [[0, -2.0, 2.0, 0, -2.0, 0], [0, 3.0, 0, -3.0, 3.0, 0]])
    # and the package's operator selects the same entries
    from distributed_optimization_tpu.ops.compression import make_compressor

    for k in (2, 3):
        np.testing.assert_array_equal(
            np.asarray(make_compressor("top_k", 6, k).apply(None, v)),
            np.asarray(choco_ring.top_k_rows(v, k)))
