"""The Pallas TPU kernel of the scan, under its two entries.

``glm_shard_visit``: ONE read of a GLM's shard stack ``[N, L, d]`` for
everything an iteration of the fused scan asks of it: the margins X·x and
X·x̄, the weighted gradient Xᵀ·coeff and the objective's partial sums at x̄,
over blocks of workers with the worker axis on the lanes.
``glm_shard_gradient`` (ISSUE 51): the same kernel built without its
objective half (z alone, g alone), for a gradient no eval rides with: the
τ − 1 later descents of a round of ``local_steps`` = τ > 1. One maker, one
set of block specs, one VMEM rule; two names, because a trace row is billed
to a device scope by its kind and the two serve different scopes. Both are
taken by what a run is (``jax_backend._visit_is_fused``), never by an
option, and are here because the ledger says the kernel wins (PERF.md §6,
PR 41 and PR 51); a kernel without such a line has no place in this module.

The kernel compiles via Mosaic on a TPU and runs in interpreter mode on the
CPU (tests). Which of the two follows the INPUT's committed platform — not
the global ``jax.devices()[0]`` — so routing stays correct under
``jax.default_device`` / mixed-platform setups; pass ``interpret=`` to force
either mode (tests). tests/test_tpu_lowering.py and tests/test_tpu_compile.py
pin that it lowers and compiles for the chip.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def resolve_interpret(x=None, interpret: Optional[bool] = None) -> bool:
    """Should a pallas call interpret (CPU) or compile (Mosaic/TPU)?

    Precedence: the explicit ``interpret`` override (tests and callers
    that already resolved their platform) → the input array's COMMITTED
    device platform (concrete arrays carry one; tracers do not) → the
    ambient ``jax.default_device`` context → ``jax.default_backend()``.
    The old global ``jax.devices()[0]`` probe mis-routed under
    ``jax.default_device(cpu)`` on a TPU host (compiling Mosaic for
    arrays that live on CPU) and vice versa.
    """
    if interpret is not None:
        return bool(interpret)
    platform = None
    if x is not None and not isinstance(x, jax.core.Tracer):
        try:
            devices = x.devices()
            if devices:
                platform = next(iter(devices)).platform
        except Exception:
            platform = None
    if platform is None:
        default = getattr(jax.config, "jax_default_device", None)
        if default is None:
            platform = jax.default_backend()
        elif isinstance(default, str):
            # jax accepts jax.default_device("cpu") — the config then
            # holds the platform STRING, not a Device.
            platform = default
        else:
            platform = default.platform
    return platform == "cpu"


# ---------------------------------------------------------------------------
# The shard visit (ISSUE 41): one read of a GLM's shards an iteration.
#
# The runtime keeps the stack ``f32[N, L, d]`` with the WORKER axis minor
# (``{0,1,2:T(8,128)}`` at the cells' size: L on the sublanes, d major), and
# the models ``[N, d]`` likewise. Seen as ``[d, L, N]`` the same bytes are d
# slabs of ``[L, N]``: X·x is a multiply by the row ``x[k, :]`` broadcast down
# the sublanes and an add, slab after slab, and Xᵀ·coeff a multiply and a sum
# over the sublanes of each slab — elementwise work on the VPU with no
# cross-lane step, every quantity a worker's own. A grid step holds a block of
# workers' shards whole in VMEM (double-buffered by the pipeline) and sweeps
# it twice: the forward sweep makes z and z̄ with two accumulators, the second
# sweep reads the RESIDENT block again for g. XLA cannot fuse the two (a
# reduction over d, a coefficient, a reduction over L of the same operand): it
# read the stack twice (docs/PERF.md §7 has the d-on-the-lanes kernel that
# lost to it).
# ---------------------------------------------------------------------------

LANES = 128
# Workers a grid step holds; derived, not an option. Read on a v5e at the GLM
# cells' shape, the call alone | the cell's scan, ms an iteration (PERF.md §6,
# PR 41): 128 lanes 7.001 | 10.151, 256 — | 10.211, 512 6.960 | 9.972,
# 1,024 6.822 | 9.976. The widest is fastest alone (longer DMA pieces) and
# ties 512 inside the scan, where the VMEM it holds is VMEM XLA cannot keep
# the models in: 512, the narrower of the two.
SHARD_VISIT_LANES = 512
# What a visit may ask of VMEM for its double-buffered blocks (a v5e core
# has 128 MiB; the compiler's default scope is 16): 512 lanes of the cells'
# shape take 18.9 MiB twice over, a shard four times as long still gets 256.
SHARD_VISIT_VMEM_BYTES = 48 << 20
# Beside the blocks: the kernel's own temporaries.
SHARD_VISIT_VMEM_SPARE = 8 << 20


def _visit_block_bytes(d: int, rows: int, lanes: int, itemsize: int) -> int:
    """VMEM bytes of one grid step's operands and results, tiles and all:
    the shards ``[d, L, lanes]``, x and g ``[d, lanes]``, y and the batch
    weights ``[L, lanes]``, the row counts and the partial ``[1, lanes]``."""
    up8 = lambda n: -(-n // 8) * 8  # noqa: E731
    lanes = -(-lanes // LANES) * LANES
    return (
        d * up8(rows) + 2 * up8(d) + 2 * up8(rows) + 16
    ) * lanes * itemsize


def shard_visit_lanes(n: int, rows: int, d: int, itemsize: int = 4):
    """How many workers a grid step of ``glm_shard_visit`` holds, or None
    where even ``LANES`` of them do not fit the budget twice (a long shard:
    the caller keeps XLA's two passes)."""
    fit = SHARD_VISIT_VMEM_BYTES // (
        2 * _visit_block_bytes(d, rows, LANES, itemsize)
    )
    if fit < 1:
        return None
    lanes = min(SHARD_VISIT_LANES, fit * LANES)
    return n if n <= lanes else lanes


def _make_shard_visit_kernel(link, d: int, lanes: int, objective: bool):
    """The visit's body; ``objective`` says whether the objective at x̄ is
    asked for beside the gradient. Without it a strip makes z alone and
    writes g: no ``xbar`` (SMEM), no ``n_valid``, no ``f``."""
    full, tail = divmod(lanes, LANES)

    def strip(refs, cols):
        """One strip of at most 128 workers: both sweeps of its slabs."""
        if objective:
            xbar_ref, X_ref, x_ref, y_ref, w_ref, nv_ref, g_ref, f_ref = refs
        else:
            X_ref, x_ref, y_ref, w_ref, g_ref = refs
        sums = None  # z, and z̄ beside it where the objective is asked for
        for k in range(d):
            slab = X_ref[k, :, cols]
            terms = [slab * x_ref[k:k + 1, cols]]
            if objective:
                terms.append(slab * xbar_ref[k])
            sums = terms if sums is None else [
                s + t for s, t in zip(sums, terms)
            ]
        z = sums[0]
        y = y_ref[:, cols]
        c = w_ref[:, cols] * link.coeff(z, y)
        for k in range(d):
            g_ref[k:k + 1, cols] = jnp.sum(
                X_ref[k, :, cols] * c, axis=0, keepdims=True
            )
        if objective:
            zbar = sums[1]
            row = jax.lax.broadcasted_iota(jnp.int32, z.shape, 0)
            f_ref[:, cols] = jnp.sum(
                jnp.where(row < nv_ref[:, cols], link.loss(zbar, y), 0.0),
                axis=0, keepdims=True,
            )

    def kernel(*refs):
        if full:
            def body(i, _):
                strip(refs, pl.ds(pl.multiple_of(i * LANES, LANES), LANES))

            jax.lax.fori_loop(0, full, body, None)
        if tail:
            strip(refs, slice(full * LANES, lanes))

    return kernel


def _shard_visit_call(link, name, X, y, x, wts, objective=None,
                      interpret: Optional[bool] = None):
    """Both entries' one ``pallas_call``: a grid over blocks of
    ``shard_visit_lanes`` workers, the block specs and the VMEM rule the
    same whether ``objective`` = ``(xbar, n_valid)`` is asked for or not.
    Returns the kernel's own results, ``[g [d, N]]`` or ``[g, f [1, N]]``."""
    n, rows, d = X.shape
    lanes = shard_visit_lanes(n, rows, d, X.dtype.itemsize)
    if lanes is None:
        raise ValueError(
            f"a block of {LANES} shards [{rows}, {d}] does not fit the "
            f"visit's VMEM budget twice ({SHARD_VISIT_VMEM_BYTES} B)"
        )
    by_lanes = lambda i: (0, i)  # noqa: E731
    rows_spec = pl.BlockSpec((rows, lanes), by_lanes)
    model_spec = pl.BlockSpec((d, lanes), by_lanes)
    worker_spec = pl.BlockSpec((1, lanes), by_lanes)
    in_specs = [
        pl.BlockSpec((d, rows, lanes), lambda i: (0, 0, i)),
        model_spec, rows_spec, rows_spec,
    ]
    operands = [X.transpose(2, 1, 0), x.T, y.T, wts.T]
    out_specs = [model_spec]
    out_shape = [jax.ShapeDtypeStruct((d, n), X.dtype)]
    if objective is not None:
        xbar, n_valid = objective
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM), *in_specs,
                    worker_spec]
        operands = [xbar, *operands, n_valid.astype(jnp.int32)[None, :]]
        out_specs.append(worker_spec)
        out_shape.append(jax.ShapeDtypeStruct((1, n), X.dtype))
    return pl.pallas_call(
        _make_shard_visit_kernel(link, d, lanes, objective is not None),
        grid=(pl.cdiv(n, lanes),),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=2 * _visit_block_bytes(
                d, rows, lanes, X.dtype.itemsize
            ) + SHARD_VISIT_VMEM_SPARE,
        ),
        interpret=resolve_interpret(X, interpret),
        name=name,
    )(*operands)


def glm_shard_visit(link, X, y, x, xbar, wts, n_valid,
                    interpret: Optional[bool] = None):
    """One visit of a GLM's shards for all an iteration asks of them.

    ``X [N, L, d]``, ``y [N, L]``, the workers' models ``x [N, d]``, the mean
    model ``x̄ [d]``, the batch weights ``[N, L]`` and each shard's count of
    real rows ``n_valid [N]``; ``link`` the problem's ``MarginLink``. Returns
    ``(g, f)``: ``g [N, d]`` = Xᵀ(wts · link.coeff(X·x, y)) per worker (the
    weighted gradient less its ``λx``) and ``f [N]`` = the sum of
    link.loss(X·x̄, y) over a worker's real rows (the data term of the
    objective at x̄, worker by worker: the caller sums and divides by the
    rows, so a cross-chip sum stays XLA's). Products and sums in the arrays'
    own precision on the VPU; no matmul, so no matmul precision applies. The
    kernel reads the transposed views ``[d, L, N]``, ``[d, N]``, ``[L, N]``:
    bitcasts where the worker axis is the minor one in memory, as the TPU
    runtime keeps these shapes (tests/test_tpu_compile.py)."""
    g, f = _shard_visit_call(
        link, "glm_shard_visit", X, y, x, wts, (xbar, n_valid), interpret
    )
    return g.T, f[0]


def glm_shard_gradient(link, X, y, x, wts, interpret: Optional[bool] = None):
    """The visit without its objective half: ``g [N, d]`` alone, the same
    sums in the same order as ``glm_shard_visit``'s g (to the bit), from one
    read of the shards, for a gradient that no eval rides with (a round's
    later descents). A kernel of its own NAME, so that a trace shows it as a
    row of its own and a program's scope table bills that row to the scope
    its callers carry (``benchmark/scope_reduce.py`` bills a row only where
    every instruction of its kind shares one scope)."""
    (g,) = _shard_visit_call(
        link, "glm_shard_gradient", X, y, x, wts, None, interpret
    )
    return g.T
