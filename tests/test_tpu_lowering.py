"""What tier-1 (CPU) can pin about the program the chip compiles.

The sandbox has no accelerator, but two things about the on-chip program
are checkable here: (1) whether each Pallas kernel LOWERS for the TPU
(``jax.export`` runs the Mosaic lowering rules without a device; whether
Mosaic then compiles the result is for a chip run — CHANGES.md PR 21 lists
it kernel by kernel), and (2) that the program form ``auto`` picks on an
accelerator — dense sampling, scan unroll 8 — computes the same run as the
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


@pytest.mark.parametrize("kernel", [
    pk.ring_mix, pk.fc_mix, pk.ring_neighbor_sum, pk.fc_neighbor_sum,
])
def test_mixing_kernels_lower_for_tpu(kernel):
    """mixing_impl='pallas' (explicit opt-in) reaches these four."""
    exported = _lower_for_tpu(lambda x: kernel(x, interpret=False), X)
    assert exported.platforms == ("tpu",)


def test_fused_ring_dsgd_step_lowers_for_tpu():
    exported = _lower_for_tpu(
        lambda x, g: pk.fused_ring_dsgd_step(x, g, 0.05, interpret=False), X, X
    )
    assert exported.platforms == ("tpu",)


@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="Mosaic's _gather_lowering_rule rejects the in-kernel "
           "jnp.take(xa, nbr, axis=0): 'Shape mismatch in input, indices "
           "and output'. robust_impl='auto' therefore never selects "
           "'fused'; when this starts passing, ROADMAP A4/C3 can re-open.",
)
@pytest.mark.parametrize("with_sgd", [False, True])
@pytest.mark.parametrize("rule", ["trimmed_mean", "median", "clipped_gossip"])
def test_fused_robust_kernel_lowers_for_tpu(rule, with_sgd):
    nbr_idx, nbr_mask = neighbor_table(build_topology("ring", N).adjacency)
    live = jax.ShapeDtypeStruct(nbr_mask.shape, jnp.float32)
    if with_sgd:
        step = pk.make_fused_robust_dsgd_step(rule, 1, nbr_idx, interpret=False)
        _lower_for_tpu(lambda lv, x, g: step(lv, x, g, 0.05), live, X, X)
    else:
        agg = pk.make_fused_robust_aggregator(rule, 1, nbr_idx, interpret=False)
        _lower_for_tpu(agg, live, X)


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
    ops = set(re.findall(r"\b(?:stablehlo|chlo)\.\w+", text))
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
