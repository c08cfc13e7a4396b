"""The four-chip deployment (ISSUE 30) at a size a test run can hold, on the
forced host devices: the program at ``worker_mesh=4`` against the benchmark's
plain reference for more workers than one device holds
(``benchmark/reference/dsgd_ring_blocks.py``: the shards in blocks, the state
whole on one device, the ring a ``jnp.roll``; no ``shard_map``, no
collective), by the limits of the cell's own configuration file.
CPU, N = 64: what is checked is numbers against limits, never a time.
"""

import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compare, datasets, program  # noqa: E402
from benchmark import run as harness  # noqa: E402
from benchmark.reference import dsgd_ring, dsgd_ring_blocks  # noqa: E402

from distributed_optimization_tpu.backends import jax_backend  # noqa: E402
from distributed_optimization_tpu.observability.spans import Tracer  # noqa: E402

NAME, MIX = "glm81_ring1m_mesh4", "halo1k"
SEEDS = [3, 4, 2147483999]


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cell():
    """(config, traffic) at the files' rehearsal sizes: 64 workers of 24
    rows over four devices, 40 iterations, the check following 12."""
    bench = load("..", "BENCHMARK.json")
    _, config, traffic = harness.load_cell(bench, f"{NAME}.{MIX}", rehearse=True)
    assert config["experiment"]["n_workers"] == 64
    assert config["experiment"]["worker_mesh"] == 4
    return config, traffic


@pytest.mark.parametrize("precision", ["reference", "bfloat16"])
@pytest.mark.parametrize("seed", SEEDS[:2])
def test_the_blocked_reference_is_the_plain_one(cell, precision, seed):
    """Cutting the shards into blocks changes where the numbers lie, not
    one of them: every row ``dsgd_ring`` gives, bit for bit."""
    config, traffic = cell
    assert 64 % config["reference_blocks"] == 0 < config["reference_blocks"]
    X, y, _ = datasets.make(config, seed)
    want = dsgd_ring.run(config, traffic, X, y, seed, precision=precision)
    got = dsgd_ring_blocks.run(config, traffic, X, y, seed, precision=precision)
    assert want["objective"].shape == (traffic["check_iterations"],)
    np.testing.assert_array_equal(got["objective"], want["objective"])
    np.testing.assert_array_equal(got["consensus"], want["consensus"])


def run_program(config, traffic, seed):
    X, y, L = datasets.make(config, seed)
    cfg, dataset = program.build(config, traffic, X, y, L, program.seed_for(seed))
    tracer = Tracer()
    with tracer.activate():
        result = program.run_experiment(cfg, dataset)
    (root,) = [e for e in tracer.spans() if e["name"] == "dopt.run"]
    ref = dsgd_ring_blocks.run(config, traffic, X, y, program.seed_for(seed))
    return result, root["args"], ref


@pytest.mark.parametrize("seed", SEEDS)
def test_the_sharded_program_is_within_the_cells_limits(cell, seed):
    config, traffic = cell
    result, args, ref = run_program(config, traffic, seed)
    assert result.history.mesh_devices == 4
    assert (args["mesh"], args["mixing"]) == ("4x16", "halo_shift")
    assert args["placement"] == "mesh4:direct"
    assert args["ici_bytes_per_round"] == 2 * 81 * 4
    said = []
    nums = compare.numbers(harness.produced_of(result), ref)
    assert compare.judge(nums, config["limits"][MIX], said.append), said
    assert not harness.gate_failures(result, traffic)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_bfloat16_control_is_not_correct(cell, seed):
    """The reference in the control precision, in the program's place,
    against the cell's own limits: over at least one."""
    config, traffic = cell
    X, y, _ = datasets.make(config, seed)
    ref = dsgd_ring_blocks.run(config, traffic, X, y, seed)
    ctl = dsgd_ring_blocks.run(
        config, traffic, X, y, seed, precision=config["precision"]["control"])
    said = []
    assert not compare.judge(
        compare.numbers(ctl, ref), config["limits"][MIX], said.append), said


def test_boundary_rows_not_exchanged_are_not_correct(cell, monkeypatch):
    """A halo exchange that delivers nothing (every ``ppermute`` hands back
    zeros): each block's two end workers mix with 0 where their neighbour's
    model belongs, and the cell's limits say so."""
    config, traffic = cell
    real = jax.lax.ppermute
    monkeypatch.setattr(
        jax.lax, "ppermute",
        lambda x, axis_name, perm: real(x, axis_name, perm) * 0)
    # a seed of its own: the process's executable cache holds the sound
    # programs of the seeds above
    result, _, ref = run_program(config, traffic, 5)
    said = []
    nums = compare.numbers(harness.produced_of(result), ref)
    assert not compare.judge(nums, config["limits"][MIX], said.append), said
    assert nums["consensus_max_rel"] > 100 * config["limits"][MIX]["consensus_max_rel"]


def test_the_file_states_what_the_cell_runs():
    config = load("configs", NAME + ".json")
    sibling = load("configs", "glm81_ring262k.json")
    exp = config["experiment"]
    # every per-worker shape is the one-chip sibling's; the workers a chip too
    same = set(sibling["experiment"]) - {"n_workers"}
    assert {k: exp[k] for k in same} == {k: sibling["experiment"][k] for k in same}
    assert set(exp) - set(sibling["experiment"]) == {"worker_mesh"}
    assert config["dataset"] == sibling["dataset"]
    assert config["precision"] == sibling["precision"]
    assert exp["n_workers"] == 1 << 20 and exp["worker_mesh"] == config["chips"] == 4
    assert exp["n_workers"] // exp["worker_mesh"] == sibling["experiment"]["n_workers"]
    bench = load("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == config["reduced"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    assert config["reference"] == "dsgd_ring_blocks"
    for key in ("source", "assumed", "layout", "guarantees", "limits_set_from"):
        assert config[key], key
    (cell,) = [w for w in bench["workloads"] if w["config"] == NAME]
    assert (cell["name"], cell["traffic"], cell["chips"]) == (f"{NAME}.{MIX}", MIX, 4)
    traffic = load("traffic", MIX + ".json")
    assert (traffic["n_iterations"], traffic["eval_every"],
            traffic["check_iterations"], traffic["trace_calls"]) == (1000, 1, 100, 1)
    assert set(config["limits"][MIX]) == {"objective_max_rel", "consensus_max_rel"}
    # one block of the reference goes up as one copy under the runtime's cliff
    from distributed_optimization_tpu.parallel.mesh import (
        FLAT_MIN_TILED_BYTES,
        tiled_bytes,
    )

    block = exp["n_workers"] // config["reference_blocks"]
    L = config["dataset"]["rows_per_worker"]
    assert tiled_bytes((block, L, exp["n_features"] + 1), 4) < FLAT_MIN_TILED_BYTES
