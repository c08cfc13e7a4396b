"""What tier-1 (CPU) can pin about the program the chip compiles.

The sandbox has no accelerator, but two things about the on-chip program
are checkable here: (1) whether the forms a run takes LOWER for the TPU —
the gossip round of every static graph, the fault layer's round, the robust
gather round, and the shard visit's Pallas kernel (``jax.export`` runs the
StableHLO and Mosaic lowering rules without a device; whether the chip's
compiler then takes the result is for tests/test_tpu_compile.py and a chip
run), and (2) that the program form ``auto`` picks on an accelerator — dense sampling, scan unroll 8 — computes the same run as the
CPU default (gather sampling, unroll 1).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.config import ExperimentConfig
from distributed_optimization_tpu.ops import pallas_kernels as pk
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.parallel.topology import neighbor_table

# The headline model stack: whole-array VMEM blocks, 81 is not a multiple
# of the 128-lane tile.
N, D = 256, 81
X = jax.ShapeDtypeStruct((N, D), jnp.float32)


def _lower_for_tpu(fn, *args):
    return export.export(jax.jit(fn), platforms=["tpu"])(*args)


def _stablehlo_ops(text):
    return set(re.findall(r"\b(?:stablehlo|chlo)\.\w+", text))


def _gathers(text):
    """The ``stablehlo.gather`` operations of a module (its attribute
    ``#stablehlo.gather<...>`` names the op a second time)."""
    return len(re.findall(r'stablehlo\.gather"?\(', text))


# The forms ``auto`` or the neighbor table choose for a static graph, each as
# (topology, its arguments, the form). No option reaches any of them.
MIXING_FORMS = {
    "ring_stencil": ("ring", {}, "stencil"),
    "grid_stencil": ("grid", {}, "stencil"),
    "fully_connected_mean": ("fully_connected", {}, "stencil"),
    "drawn_graph_live_slot_gather": (
        "erdos_renyi", dict(impl="neighbor", erdos_renyi_p=0.05, seed=7),
        "gather",
    ),
    "chain_gather": ("chain", dict(impl="neighbor"), "gather"),
}


@pytest.mark.parametrize("stack", [(D,), (33, 16)], ids=["Nd", "NdK"])
@pytest.mark.parametrize("form", sorted(MIXING_FORMS))
def test_mixing_forms_lower_for_tpu(form, stack):
    """One round of each form a run's gossip takes, on the stack in the rank
    the scan carries (``[N, d]``, and ``[N, d, K]`` as it is: none flattens
    it), the gather's tables arguments of the program as the scan hands them:
    the chip is given a program with no sort and no scatter."""
    from distributed_optimization_tpu.ops.mixing import make_mixing_op

    name, kwargs, impl = MIXING_FORMS[form]
    op = make_mixing_op(build_topology(name, N, **kwargs))
    assert op.impl == impl
    x = jax.ShapeDtypeStruct((N, *stack), jnp.float32)

    def one_round(v, tables):
        bound = op if tables is None else op.bind(tables)
        return bound.apply(v), bound.neighbor_sum(v)

    exported = _lower_for_tpu(one_round, x, op.tables)
    assert exported.platforms == ("tpu",)
    assert all(out.shape == x.shape for out in exported.out_avals)
    text = exported.mlir_module()
    ops = _stablehlo_ops(text)
    assert not ops & {"stablehlo.sort", "stablehlo.scatter", "chlo.top_k"}, ops
    assert len(stack) == 1 or f"tensor<{N}x{int(np.prod(stack))}x" not in text


@pytest.mark.parametrize("topology,addressing", [
    ("ring", "shift"), ("chain", "gather"),
])
def test_faulty_ring_round_lowers_for_tpu(topology, addressing):
    """A round of the fault layer (memoryless drops and stragglers, the bits
    drawn in the step from (seed, t)) in its two forms: neighbours by shifts
    where the table is a ring's, by the table's gather anywhere else."""
    from distributed_optimization_tpu.parallel.faults import make_faulty_mixing

    topo = build_topology(topology, N, impl="neighbor")
    faulty = make_faulty_mixing(topo, 0.3, seed=5, straggler_prob=0.1)
    assert faulty.addressing == addressing and faulty.timeline is None

    def one_round(t, v, tables):
        bound = faulty if tables is None else faulty.bind(tables)
        return bound.mix(t, v), bound.active(t)

    exported = _lower_for_tpu(
        one_round, jax.ShapeDtypeStruct((), jnp.int32), X, faulty.tables
    )
    assert exported.platforms == ("tpu",)
    assert "stablehlo.scatter" not in _stablehlo_ops(exported.mlir_module())


@pytest.mark.parametrize("liveness", ["all_live", "faulted"])
@pytest.mark.parametrize("rule", ["trimmed_mean", "median", "clipped_gossip"])
@pytest.mark.parametrize("graph", ["ring", "grid"])
def test_robust_gather_round_lowers_for_tpu(graph, rule, liveness):
    """The screening round every Byzantine run takes (``robust_impl`` auto is
    the gather form on any graph but the complete one): over the static
    table's mask, and over the liveness bits the fault layer draws for the
    same table at t. Clipping's adaptive radius holds a sort (the count
    rules order a ring's three slots and a torus's five by compare-and-select
    since PR 44). On a RING the count rules read their two neighbours by
    shifts of the transmitted stack (ISSUE 45): no ``stablehlo.gather`` in
    the round, all-live or faulted; a torus's holds its ONE row gather, and
    clipping keeps its own on either."""
    from distributed_optimization_tpu.ops.robust_aggregation import (
        make_gather_robust_aggregator,
    )
    from distributed_optimization_tpu.parallel.faults import make_faulty_mixing

    topo = build_topology(graph, N)
    nbr_idx, nbr_mask = neighbor_table(topo.adjacency)
    aggregate = make_gather_robust_aggregator(rule, 1, nbr_idx)
    if liveness == "all_live":
        live = jnp.asarray(nbr_mask, dtype=jnp.float32)
        exported = _lower_for_tpu(lambda v: aggregate(live, v), X)
        before = 0
    else:
        live_fn = make_faulty_mixing(
            topo, 0.3, seed=5, straggler_prob=0.1
        ).make_neighbor_liveness(nbr_idx, nbr_mask)
        exported = _lower_for_tpu(
            lambda t, v: aggregate(live_fn(t), v),
            jax.ShapeDtypeStruct((), jnp.int32), X,
        )
        # the fault layer's own reads of its bits through the table
        before = _gathers(_lower_for_tpu(
            live_fn, jax.ShapeDtypeStruct((), jnp.int32)
        ).mlir_module())
    assert exported.platforms == ("tpu",)
    assert exported.out_avals[0].shape == (N, D)
    gathers = _gathers(exported.mlir_module()) - before
    if rule == "clipped_gossip":
        assert gathers >= 2  # the rows and the neighbours' degrees
    else:
        assert gathers == (0 if graph == "ring" else 1)


def test_accelerator_program_form_matches_cpu_default():
    """Off CPU, ``auto`` resolves sampling to 'dense' (shards <= 64 rows)
    and the scan unroll to 8; on CPU to 'gather' and 1. Forced together at
    a tiny headline shape (logistic D-SGD ring, L=49, eval_every=1) the two
    forms draw the same batches and differ only in f32 summation order:
    measured 9e-8 on models of order 0.2 over 400 steps, bound at 1e-5 — a
    different batch subset or a skipped step is orders of magnitude above."""
    from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
    from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

    cfg = ExperimentConfig(
        problem_type="logistic", algorithm="dsgd", topology="ring",
        n_workers=16, n_samples=16 * 49, n_features=20,
        n_informative_features=12, n_iterations=400, eval_every=1,
    )
    assert cfg.resolved_sampling_impl("cpu", 49) == "gather"
    assert cfg.resolved_sampling_impl("tpu", 49) == "dense"
    assert (cfg.resolved_scan_unroll("cpu"), cfg.resolved_scan_unroll("tpu")) == (1, 8)
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    cpu_form = jax_backend.run(cfg, ds, f_opt, use_mesh=False)
    chip_form = jax_backend.run(
        cfg.replace(sampling_impl="dense", scan_unroll=8), ds, f_opt,
        use_mesh=False,
    )
    np.testing.assert_allclose(
        chip_form.final_models, cpu_form.final_models, rtol=0, atol=1e-5
    )
    np.testing.assert_allclose(
        chip_form.history.objective, cpu_form.history.objective,
        rtol=0, atol=1e-5,
    )


@pytest.mark.parametrize("shape", [(96, 81), (8, 33, 16)], ids=["Nd", "NdK"])
def test_top_k_exchange_lowers_without_sort_or_scatter(shape):
    """The top-k of a compressed exchange is a counted threshold
    (``ops.compression.select_top_scored``): the program the chip is given
    holds no sort, no top-k call and no scatter. On [96, 2097664] rows the
    sort ``lax.top_k`` became was 542 ms of every 639 ms CHOCO iteration and
    the scatter 41 (PERF.md section 6, PR 26), so neither may come back
    unnoticed; nor may a flatten of the model-shaped stack (13 ms there)."""
    from distributed_optimization_tpu.ops.compression import (
        make_error_feedback,
        row_dim,
    )

    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    ef = make_error_feedback("top_k", row_dim(x), 7, 0.2)

    def exchange(v, memory):
        return ef.exchange(None, v, memory, lambda m: jnp.roll(m, 1, axis=0))

    text = _lower_for_tpu(exchange, x, x).mlir_module()
    ops = _stablehlo_ops(text)
    assert {"stablehlo.while", "stablehlo.reduce", "stablehlo.compare"} <= ops
    assert not ops & {
        "stablehlo.sort", "chlo.top_k", "stablehlo.scatter",
        "stablehlo.custom_call",
    }, ops
    flat = f"tensor<{shape[0]}x{row_dim(x)}x"
    assert (flat in text) == (len(shape) == 2)


def test_paired_margins_reach_the_chip_as_one_reduce_with_two_results():
    """The carried forward product (``ops.losses.paired_margins``): what the
    TPU's compiler is handed for X·x and X·x̄ is ONE ``stablehlo.reduce``
    over d with two results and no ``dot_general``, at the GLM cells' shard
    shape. XLA:TPU runs that as one ``multiply_reduce_fusion`` reading the
    4.76 GB stack once (7.31 ms an iteration where two dots were 13.19:
    PERF.md section 6, PR 31); two dots side by side it runs apart."""
    from distributed_optimization_tpu.ops.losses import paired_margins

    n, L, d = 1024, 53, 81
    exported = _lower_for_tpu(
        paired_margins,
        jax.ShapeDtypeStruct((n, L, d), jnp.float32),
        jax.ShapeDtypeStruct((n, d), jnp.float32),
        jax.ShapeDtypeStruct((d,), jnp.float32),
    )
    text = exported.mlir_module()
    assert "dot_general" not in text
    reduces = re.findall(r"stablehlo\.reduce[^\n]*", text)
    assert len(reduces) == 1
    assert len(re.findall(
        rf"-> \(tensor<{n}x{L}xf32>, tensor<{n}x{L}xf32>\)", text
    )) == 1


VISIT_SHAPES = {"two_blocks_of_the_cell": (1024, 53, 81), "ragged": (700, 13, 9)}


def _visit_arguments(n, rows, d):
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    return (f32(n, rows, d), f32(n, rows), f32(n, d), f32(d), f32(n, rows),
            jax.ShapeDtypeStruct((n,), jnp.int32))


@pytest.mark.parametrize("family", ["logistic", "quadratic", "huber"])
@pytest.mark.parametrize("shape", sorted(VISIT_SHAPES))
def test_shard_visit_lowers_for_tpu(shape, family):
    """``glm_shard_visit`` (ISSUE 41) through Mosaic's lowering rules at the
    GLM cells' block ([81, 53, 512], twice) and at a ragged stack (13 rows,
    a last block of 188 workers), for each family's pair: ONE custom call,
    no reduction and no matmul left to XLA (whether Mosaic then compiles it
    is for ``tests/test_tpu_compile.py`` and the chip)."""
    from distributed_optimization_tpu.models import get_problem

    link = get_problem(family).link
    text = _lower_for_tpu(
        lambda *a: pk.glm_shard_visit(link, *a, interpret=False),
        *_visit_arguments(*VISIT_SHAPES[shape]),
    ).mlir_module()
    assert len(re.findall(r"stablehlo\.custom_call @tpu_custom_call", text)) == 1
    assert "stablehlo.reduce" not in text and "dot_general" not in text


@pytest.mark.parametrize("family", ["logistic", "quadratic", "huber"])
@pytest.mark.parametrize("shape", sorted(VISIT_SHAPES))
def test_shard_gradient_lowers_for_tpu(shape, family):
    """``glm_shard_gradient`` (ISSUE 51: the visit without its objective
    half) through the same rules at the same shapes: ONE custom call of four
    operands (no x̄ in SMEM, no row counts) and one result."""
    from distributed_optimization_tpu.models import get_problem

    link = get_problem(family).link
    X, y, x, _, wts, _ = _visit_arguments(*VISIT_SHAPES[shape])
    text = _lower_for_tpu(
        lambda *a: pk.glm_shard_gradient(link, *a, interpret=False), X, y, x, wts,
    ).mlir_module()
    (call,) = re.findall(r"stablehlo\.custom_call @tpu_custom_call\(([^)]*)\)", text)
    assert len(call.split(",")) == 4
    assert "stablehlo.reduce" not in text and "dot_general" not in text


def test_the_fused_scan_reaches_the_chip_as_one_visit_a_trip(monkeypatch):
    """The program a TPU is handed where ``forward`` = ``fused``: in the
    loop's body ONE kernel call and no ``dot_general`` or ``reduce`` over
    the shard stack; the same call once in front of the loop for the first
    gradient (the CPU's scan unrolls one step a trip)."""
    from test_forward_carry import fused, glm_cfg, seg_scan_of

    from distributed_optimization_tpu.utils.data import generate_synthetic_dataset

    fused(monkeypatch)
    monkeypatch.setattr(pk, "resolve_interpret", lambda *a, **k: False)
    cfg = glm_cfg(problem_type="logistic", n_iterations=10)
    ds = generate_synthetic_dataset(cfg)
    seg_scan, args = seg_scan_of(cfg, ds, monkeypatch)
    text = _lower_for_tpu(seg_scan, *args).mlir_module()
    assert len(re.findall(r"stablehlo\.custom_call @tpu_custom_call", text)) == 2
    n, rows, d = args[2]["X"].shape
    stack = rf"tensor<(?:{n}x{rows}x{d}|{d}x{rows}x{n})xf32>"
    over_the_stack = [
        line for line in text.splitlines()
        if re.search(r"stablehlo\.(dot_general|reduce)\b", line)
        and re.search(stack, line)
    ]
    assert over_the_stack == []
    body = text[text.index("stablehlo.while"):]
    assert len(re.findall(r"@tpu_custom_call", body)) >= 1
