"""Compiled for a described v5e, at real sizes, without the chip (the TPU's
compiler is installed here; nothing runs, so no time and no result comes out
of this file). The one file that describes a topology: only the worker that
is given it loads the TPU's library, inside a fixture, never at import."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributed_optimization_tpu.observability import device_scopes
from distributed_optimization_tpu.ops.mixing import make_mixing_op
from distributed_optimization_tpu.parallel import topology


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as err:
        pytest.skip(f"no v5e:2x2 topology can be described here: {err}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def drawn_graph_round(one_chip):
    """One gossip round of the gather form at the drawn-graph cell's width
    (k_max 30 of the cell's 2^18 rows of 81 floats; the graph itself a small
    one, its tables' SHAPES the cell's), tables as arguments."""
    n, k_max, d = 1 << 18, 30, 81
    small = topology.build_neighbor_topology(
        "erdos_renyi", 256, erdos_renyi_p=0.05, seed=7, sampler="sparse")
    op = make_mixing_op(small, impl="gather")

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    tables = {"nbr": shape((k_max, n), jnp.int32), "w_nbr": shape((k_max, n), jnp.float32),
              "w_self": shape((n,), jnp.float32)}
    assert {k: v.dtype for k, v in tables.items()} == {
        k: v.dtype for k, v in op.tables.items()}
    return jax.jit(lambda x, tb: op.bind(tb).apply(x)).lower(
        shape((n, d), jnp.float32), tables).compile()


def test_a_gather_round_holds_one_slot_not_thirty(drawn_graph_round):
    """The round's temporaries are a few copies of the models, whatever
    k_max is: 0.4 GB where the [N, 30, 81] stack and its product are 8.3
    and thirty gathers hoisted ahead of their sum 4.2."""
    memory = drawn_graph_round.memory_analysis()
    assert memory.temp_size_in_bytes < 600_000_000, memory.temp_size_in_bytes


def test_a_gather_round_has_no_table_among_its_constants(drawn_graph_round):
    text = drawn_graph_round.as_text()
    constants = [
        device_scopes._shape_bytes(ins[1])
        for ins in map(device_scopes._instruction, text.splitlines())
        if ins is not None and ins[2] == "constant"
    ]
    assert max(constants) < 2**20
    entry = text[text.index("ENTRY"):].split("\n", 1)[0]
    assert "s32[30,262144]" in entry and "f32[30,262144]" in entry
