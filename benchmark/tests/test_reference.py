"""The plain reference is a true copy of the semantics: it agrees with the
package's own numpy backend (full batch, so no sampling is involved), and
with the jax backend when batches are sampled, which pins the seed-pure
sampling rule the reference restates. The lower-precision controls fail the
limits that sound runs pass, at a size a test run can hold."""

import json
import os

import numpy as np
import pytest

from benchmark import compare, datasets, program
from benchmark import run as harness
from benchmark.flops import softmax_dsgd
from benchmark.reference import dsgd_ring

from .conftest import ROOT


def small(name, traffic, **experiment):
    config = json.load(open(os.path.join(ROOT, "benchmark", "configs", name + ".json")))
    for section, values in config["rehearse"].items():
        config[section].update(values)
    config["experiment"].update(experiment)
    return config, traffic


def follow(config, traffic, seed, precision="reference"):
    X, y, L = datasets.make(config, seed)
    return (X, y, L), dsgd_ring.run(config, traffic, X, y, seed, precision=precision)


@pytest.mark.parametrize("name", ["glm81_ring262k", "softmax4096_ring96"])
def test_reference_agrees_with_numpy_backend(name):
    from distributed_optimization_tpu.backends import numpy_backend

    traffic = {"n_iterations": 20, "eval_every": 5}
    # batch >= shard: every backend takes the whole shard, nothing is sampled
    config, traffic = small(name, traffic, local_batch_size=64, dtype="float32",
                            matmul_precision="highest")
    (X, y, L), ref = follow(config, traffic, seed=5)
    cfg, dataset = program.build(config, traffic, X.astype(np.float64), y.astype(np.float64), L, 5)
    want = numpy_backend.run(cfg, dataset, 0.0)
    nums = compare.numbers(harness.produced_of(want), ref)
    assert max(nums.values()) < 2e-5, nums


def test_reference_restates_the_sampling_rule():
    """b < L: the jax backend samples; the reference draws the same batches."""
    from distributed_optimization_tpu.backends import jax_backend

    traffic = {"n_iterations": 12, "eval_every": 1}
    config, traffic = small("glm81_ring262k", traffic)
    assert config["experiment"]["local_batch_size"] < config["dataset"]["rows_per_worker"]
    (X, y, L), ref = follow(config, traffic, seed=11)
    cfg, dataset = program.build(config, traffic, X, y, L, 11)
    for impl in ("gather", "dense"):
        got = jax_backend.run(cfg.replace(sampling_impl=impl), dataset, 0.0)
        nums = compare.numbers(harness.produced_of(got), ref)
        assert max(nums.values()) < 1e-5, (impl, nums)


def test_a_longer_horizon_leaves_the_first_rows_as_they_were():
    """The check follows a prefix: the program's rows up to there are the
    same whatever the horizon, so limits read at one horizon hold at another."""
    from distributed_optimization_tpu.backends import jax_backend

    rows = {}
    for T in (12, 40):
        traffic = {"n_iterations": T, "eval_every": 1}
        config, traffic = small("glm81_ring262k", traffic)
        X, y, L = datasets.make(config, 13)
        cfg, dataset = program.build(config, traffic, X, y, L, 13)
        hist = jax_backend.run(cfg, dataset, 0.0).history
        rows[T] = (np.asarray(hist.objective)[:12], np.asarray(hist.consensus_error)[:12])
    assert np.array_equal(rows[12][0], rows[40][0])
    assert np.array_equal(rows[12][1], rows[40][1])


@pytest.mark.parametrize("name,mix", [("glm81_ring262k", "steady2k"),
                                      ("softmax4096_ring96", "train2k")])
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_precision_is_not_correct(name, mix, seed):
    """The reference in the control precision, in the program's place, against
    the cell's own limits: not correct."""
    traffic = json.load(open(os.path.join(ROOT, "benchmark", "traffic", mix + ".json")))
    traffic.update(traffic["rehearse"])
    config, traffic = small(name, traffic)
    _, ref = follow(config, traffic, seed)
    _, ctl = follow(config, traffic, seed, precision=config["precision"]["control"])
    said = []
    assert not compare.judge(compare.numbers(ctl, ref), config["limits"][mix], said.append), said


def test_flop_count_of_the_compute_bound_tier():
    config = {"experiment": {"n_workers": 8, "local_batch_size": 2048, "n_features": 4096,
                             "n_classes": 512},
              "dataset": {"rows_per_worker": 2048}}
    assert softmax_dsgd.per_iteration(config) == 4 * 8 * 2048 * 4097 * 512
