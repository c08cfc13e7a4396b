"""Pallas TPU kernels for the hot gossip ops.

The framework's hot loop is elementwise-dominated (the model stack ``[N, d]``
is small enough to live in VMEM outright), so the win from hand-written
kernels is FUSION: one VMEM-resident kernel per gossip step instead of
several XLA ops bouncing through HBM. Kernel families:

- ``ring_mix`` — the ring stencil W x = (x + roll(x,+1) + roll(x,−1)) / 3
  (uniform Metropolis–Hastings weights for degree-2 rings, see
  ``ops/mixing.py``), one VMEM pass.
- ``fused_ring_dsgd_step`` — the ENTIRE D-SGD update
  x ← W x − η g (reference ``trainer.py:173-175``) in a single kernel:
  mixing + SGD step fused, x and g each read from HBM exactly once.
- ``make_fused_robust_aggregator`` / ``make_fused_robust_dsgd_step`` — the
  Byzantine/fault hot path (ISSUE-6 tentpole): neighbor-gather through the
  static ``[N, k_max]`` table + robust screen (trimmed mean / median via an
  odd-even transposition sort network; self-centered clipping) + mixing
  (+ the SGD update for D-SGD) in ONE kernel over the ``[N, d]`` stack and
  ``[N, k_max]`` liveness bits. The ``[N, k_max, d]`` neighbor stack exists
  only inside the kernel (VMEM), never as an HBM-materialized XLA buffer —
  the separate gather → sort → mix → update ops of the 'gather' path each
  round-trip it through HBM.

- ``glm_shard_visit`` — ONE read of a GLM's shard stack ``[N, L, d]`` for
  everything an iteration of the fused scan asks of it: the margins X·x and
  X·x̄, the weighted gradient Xᵀ·coeff and the objective's partial sums at
  x̄, over blocks of workers with the worker axis on the lanes.

All kernels run in interpreter mode on CPU (tests / virtual-device CI) and
compile via Mosaic on real TPU. Every gossip kernel holds ONE ``[N, D]`` block: a
model-shaped stack (``[N, d, K]``, what the softmax scan carries) is
flattened at the call and the result restored — the identity, and no traced
op, for the ``[N, d]`` stacks the kernels were sized for. Interpreter-mode
selection respects the INPUT's committed platform — not the global
``jax.devices()[0]`` — so routing stays correct under
``jax.default_device`` / mixed-platform setups; pass ``interpret=`` to force
either mode (tests). A run that selects a kernel logs which of the two it took (``log_kernel_mode``). No ``auto``
selector picks a gossip kernel: they are reached by ``mixing_impl='pallas'``
and ``robust_impl='fused'`` only (the shard visit is taken by what a run is,
``jax_backend._visit_is_fused``), and Mosaic does not lower the fused robust
kernels at all (tests/test_tpu_lowering.py pins which lower).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_optimization_tpu.config import AGGREGATIONS
from distributed_optimization_tpu.log import get_logger

_log = get_logger("pallas")

# Width bound for the in-kernel odd-even transposition sort network the
# count-based rules (trimmed mean / median) screen with: the network is
# width compare-exchange passes of jnp.minimum/maximum over the closed
# slot axis [N, k_max+1, d] — pure VPU elementwise ops Mosaic lowers
# everywhere, unlike a general jnp.sort. Quadratic in width, so past this
# bound the network's O(k_max²·N·d) work dominates the fusion win and the
# rule is not fused-eligible (``fused_robust_supported``); clipping sorts
# only the [N, k_max] norms and stays eligible at any degree.
FUSED_MAX_SORT_WIDTH = 16


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


def log_kernel_mode(what: str) -> None:
    """Say which mode a run's pallas kernels take (tracers carry no
    platform, so under ``jit`` this is what ``resolve_interpret`` returns
    inside the kernels too)."""
    mode = (
        "the pallas INTERPRETER (CPU)" if resolve_interpret()
        else "Mosaic-compiled kernels"
    )
    _log.info("%s runs through %s", what, mode)


def _roll(x, shift: int, interp: bool):
    # pltpu.roll lowers to a VMEM rotate on TPU (it requires a non-negative
    # shift, so normalize modulo N); the interpreter path and non-TPU
    # backends use jnp.roll (identical semantics).
    if interp:
        return jnp.roll(x, shift, axis=0)
    return pltpu.roll(x, shift=shift % x.shape[0], axis=0)


THIRD = 1.0 / 3.0


def _make_ring_mix_kernel(interp: bool):
    def kernel(x_ref, out_ref):
        x = x_ref[:]
        out_ref[:] = (
            x + _roll(x, 1, interp) + _roll(x, -1, interp)
        ) * THIRD

    return kernel


def _make_fused_ring_step_kernel(interp: bool):
    def kernel(eta_ref, x_ref, g_ref, out_ref):
        x = x_ref[:]
        mixed = (x + _roll(x, 1, interp) + _roll(x, -1, interp)) * THIRD
        out_ref[:] = mixed - eta_ref[0] * g_ref[:]

    return kernel


def _fc_mix_kernel(x_ref, out_ref):
    x = x_ref[:]
    out_ref[:] = jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape)


def _make_ring_neighbor_sum_kernel(interp: bool):
    def kernel(x_ref, out_ref):
        x = x_ref[:]
        out_ref[:] = _roll(x, 1, interp) + _roll(x, -1, interp)

    return kernel


def _fc_neighbor_sum_kernel(x_ref, out_ref):
    x = x_ref[:]
    out_ref[:] = jnp.broadcast_to(jnp.sum(x, axis=0, keepdims=True), x.shape) - x


def ring_mix(x: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    """W x for a ring of N >= 3 workers; [N, d] -> [N, d], one VMEM pass."""
    interp = resolve_interpret(x, interpret)
    return _unary_call(_make_ring_mix_kernel(interp), x, interp)


def fused_ring_dsgd_step(
    x: jax.Array, g: jax.Array, eta, interpret: Optional[bool] = None
) -> jax.Array:
    """One fused D-SGD iteration on a ring: W x − eta g, single kernel."""
    interp = resolve_interpret(x, interpret)
    eta_arr = jnp.asarray(eta, dtype=x.dtype).reshape(1)
    shape = x.shape
    x, g = x.reshape(shape[0], -1), g.reshape(shape[0], -1)
    return pl.pallas_call(
        _make_fused_ring_step_kernel(interp),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interp,
    )(eta_arr, x, g).reshape(shape)


def _unary_call(kernel, x: jax.Array, interp: bool) -> jax.Array:
    shape = x.shape
    x = x.reshape(shape[0], -1)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interp,
    )(x).reshape(shape)


def fc_mix(x: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    """W x for the fully-connected graph: the global mean, one VMEM pass."""
    return _unary_call(_fc_mix_kernel, x, resolve_interpret(x, interpret))


def ring_neighbor_sum(x: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    """A x for the ring: roll(+1) + roll(−1), computed directly (exact)."""
    interp = resolve_interpret(x, interpret)
    return _unary_call(_make_ring_neighbor_sum_kernel(interp), x, interp)


def fc_neighbor_sum(x: jax.Array, interpret: Optional[bool] = None) -> jax.Array:
    """A x for the fully-connected graph: column sums minus self."""
    return _unary_call(
        _fc_neighbor_sum_kernel, x, resolve_interpret(x, interpret)
    )


# ---------------------------------------------------------------------------
# Fused robust gather path (ISSUE-6 tentpole).
#
# Math is a term-for-term mirror of ops/robust_aggregation.py's
# ``make_gather_robust_aggregator`` — same +inf padding, same accumulation
# dtype floor, same identity-row degradation — so the fused form is an
# EXECUTION change only: bitwise-equal outputs for trimmed_mean/median
# (the sort network produces the identical sorted values jnp.sort does for
# finite inputs) and ≤ 1e-12 f64 for clipping, pinned in
# tests/test_fused_robust.py. The difference is WHERE the intermediates
# live: one pallas kernel holds the gathered neighbor stack, the sorted
# closed neighborhood, and the screened aggregate in VMEM and writes only
# the [N, d] result, where the gather path materializes each of them as an
# HBM-backed XLA buffer between ops.
# ---------------------------------------------------------------------------


def fused_robust_supported(name: str, k_max: int, clip_tau=0.0) -> bool:
    """Is ``name`` fused-eligible at this maximum degree?

    The count-based rules sort the closed [N, k_max+1, d] stack through
    the transposition network, which must fit ``FUSED_MAX_SORT_WIDTH``
    (see the constant's rationale). Clipping sorts nothing at a FIXED
    radius (eligible at any degree), but the ADAPTIVE radius
    (``clip_tau <= 0``, the default) ranks the [N, k_max] norms through
    the same quadratic network — the width bound applies to it equally
    (the same host fixed-vs-adaptive decision the aggregators make: a
    traced clip_tau is always the fixed form).
    """
    if name not in AGGREGATIONS or name == "gossip":
        return False
    if name == "clipped_gossip":
        adaptive = isinstance(clip_tau, (int, float)) and clip_tau <= 0.0
        return not adaptive or k_max <= FUSED_MAX_SORT_WIDTH
    return (k_max + 1) <= FUSED_MAX_SORT_WIDTH


def _sort_columns(v: jax.Array) -> jax.Array:
    """Ascending sort along axis 1 via odd-even transposition network.

    ``width`` compare-exchange passes of jnp.minimum/maximum — elementwise
    VPU ops at every stage, so the whole sort lowers on Mosaic where a
    general jnp.sort does not. For finite inputs the result is bitwise the
    multiset-sorted output jnp.sort produces (each min/max returns one of
    its operands exactly); the +inf padding of masked slots sorts to the
    tail like the gather form's. Width is static and small
    (``FUSED_MAX_SORT_WIDTH``), so the unrolled network stays cheap.
    """
    width = v.shape[1]
    cols = [v[:, i] for i in range(width)]
    for parity in range(width):
        for i in range(parity % 2, width - 1, 2):
            lo = jnp.minimum(cols[i], cols[i + 1])
            hi = jnp.maximum(cols[i], cols[i + 1])
            cols[i], cols[i + 1] = lo, hi
    return jnp.stack(cols, axis=1)


def _kernel_adaptive_clip_tau(lv, norms, budget: int, k_max: int):
    """In-kernel twin of robust_aggregation._adaptive_clip_tau: the
    (deg−b)-th smallest realized neighbor-distance norm per node, with the
    rank selection done over the network-sorted [N, k_max] norms via a
    one-hot contraction instead of take_along_axis (Mosaic-friendly)."""
    deg = jnp.sum(lv, axis=1)
    masked = jnp.where(lv > 0, norms, jnp.inf)
    ranked = _sort_columns(masked)
    k = jnp.clip(deg - budget - 1.0, 0.0, float(k_max - 1))
    pos = jnp.arange(k_max, dtype=ranked.dtype)[None, :]
    onehot = (pos == k[:, None]).astype(ranked.dtype)
    kth = jnp.sum(jnp.where(onehot > 0, ranked, 0.0), axis=1)
    return jnp.where(deg - budget >= 1.0, kth, 0.0)


def _fused_robust_body(name, budget, nbr, k_max, adaptive_tau,
                       lv_raw, x, tau_in):
    """The screen+mix math shared by the aggregate-only and fused-SGD
    kernels; runs entirely on VMEM-resident values. Returns the screened
    aggregate in the accumulation dtype (caller casts / applies the SGD
    update)."""
    acc = jnp.promote_types(jnp.float32, x.dtype)
    xa = x.astype(acc)
    lv = lv_raw.astype(acc)
    if name in ("trimmed_mean", "median"):
        gathered = jnp.take(xa, nbr, axis=0)  # [N, k_max, d], VMEM-only
        vals = jnp.where(lv[:, :, None] > 0, gathered, jnp.inf)
        closed = jnp.concatenate([xa[:, None, :], vals], axis=1)
        s = _sort_columns(closed)
        counts = jnp.sum(lv, axis=1) + 1.0
        if name == "trimmed_mean":
            pos = jnp.arange(k_max + 1, dtype=acc)
            keep = (pos[None, :] >= budget) & (
                pos[None, :] < (counts - budget)[:, None]
            )
            kept = jnp.maximum(counts - 2 * budget, 0.0)
            total = jnp.sum(jnp.where(keep[:, :, None], s, 0.0), axis=1)
            mean = total / jnp.maximum(kept, 1.0)[:, None]
            return jnp.where((kept >= 1.0)[:, None], mean, xa)
        # median: rank selection as one-hot contractions over the slot axis
        # (take_along_axis has no Mosaic lowering); 0.5·(s[lo] + s[hi]).
        c = counts  # float, exact for counts <= k_max+1
        lo = jnp.maximum(jnp.floor((c - 1.0) / 2.0), 0.0)
        hi = jnp.maximum(jnp.floor(c / 2.0), 0.0)
        pos = jnp.arange(k_max + 1, dtype=acc)[None, :]
        sel_lo = (pos == lo[:, None]).astype(acc)
        sel_hi = (pos == hi[:, None]).astype(acc)
        pick = lambda sel: jnp.sum(  # noqa: E731
            jnp.where(sel[:, :, None] > 0, s, 0.0), axis=1
        )
        return 0.5 * (pick(sel_lo) + pick(sel_hi))
    # clipped_gossip
    gathered = jnp.take(xa, nbr, axis=0)
    diffs = gathered - xa[:, None, :]
    norms = jnp.sqrt(jnp.sum(diffs * diffs, axis=-1))
    deg = jnp.sum(lv, axis=1)
    if adaptive_tau:
        tau = _kernel_adaptive_clip_tau(lv, norms, budget, k_max)
    else:
        tau = jnp.broadcast_to(tau_in[0].astype(acc), (nbr.shape[0],))
    w = lv / (1.0 + jnp.maximum(deg[:, None], jnp.take(deg, nbr)))
    factor = jnp.minimum(
        1.0, tau[:, None] / jnp.maximum(norms, jnp.finfo(acc).tiny)
    )
    moved = jnp.sum(w[:, :, None] * diffs * factor[:, :, None], axis=1)
    return xa + moved


def _make_fused_robust(
    name: str,
    budget: int,
    nbr_idx: np.ndarray,
    clip_tau,
    *,
    with_sgd: bool,
    interpret: Optional[bool],
):
    if name not in AGGREGATIONS or name == "gossip":
        raise ValueError(
            f"no robust aggregator named {name!r}; plain gossip is built by "
            "ops/mixing.py / parallel/faults.py"
        )
    if budget < 1:
        raise ValueError(f"{name} needs a positive attack budget, got {budget}")
    nbr_host = np.asarray(nbr_idx, dtype=np.int32)
    k_max = nbr_host.shape[1]
    if not fused_robust_supported(name, k_max, clip_tau):
        raise ValueError(
            f"robust_impl='fused' cannot screen {name!r} at k_max={k_max}: "
            f"the in-kernel sort network is bounded at width "
            f"{FUSED_MAX_SORT_WIDTH} (the closed neighborhood for the "
            "count rules; the adaptive-radius norm ranking for clipping) "
            "— use robust_impl='gather', or a fixed clip_tau for clipping"
        )
    # Same host decision as the gather twin: a traced clip_tau (a swept
    # replica axis) is the fixed form; only a concrete <= 0.0 is adaptive.
    adaptive_tau = (
        name == "clipped_gossip"
        and isinstance(clip_tau, (int, float))
        and clip_tau <= 0.0
    )
    nbr_dev = jnp.asarray(nbr_host)

    def make_kernel(dtype):
        acc = jnp.promote_types(jnp.float32, dtype)

        if with_sgd:
            def kernel(tau_ref, eta_ref, nbr_ref, live_ref, x_ref, g_ref,
                       out_ref):
                x = x_ref[:]
                agg = _fused_robust_body(
                    name, budget, nbr_ref[:], k_max,
                    adaptive_tau, live_ref[:], x, tau_ref,
                )
                # Cast-then-step in the run dtype: the same values as the
                # unfused ``aggregate(...) − eta·g`` two-op sequence (up
                # to XLA's FMA-contraction choice, ≤ 1 ulp).
                out_ref[:] = agg.astype(dtype) - eta_ref[0] * g_ref[:]
        else:
            def kernel(tau_ref, nbr_ref, live_ref, x_ref, out_ref):
                x = x_ref[:]
                agg = _fused_robust_body(
                    name, budget, nbr_ref[:], k_max,
                    adaptive_tau, live_ref[:], x, tau_ref,
                )
                out_ref[:] = agg.astype(dtype)

        return kernel, acc

    def call(live, x, g=None, eta=None):
        interp = resolve_interpret(x, interpret)
        kernel, acc = make_kernel(x.dtype)
        shape = x.shape
        x = x.reshape(shape[0], -1)
        # Fixed-radius clipping threads tau as a [1] SMEM scalar (possibly
        # traced — the replica-swept axis); the count rules and adaptive
        # clipping ignore it (adaptive recomputes per node in-kernel).
        tau_val = clip_tau if not adaptive_tau else 0.0
        tau_arr = jnp.asarray(tau_val, dtype=acc).reshape(1)
        specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
        args = [tau_arr]
        if with_sgd:
            specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            args.append(jnp.asarray(eta, dtype=x.dtype).reshape(1))
        specs += [
            pl.BlockSpec(memory_space=pltpu.VMEM),  # nbr table
            pl.BlockSpec(memory_space=pltpu.VMEM),  # liveness bits
            pl.BlockSpec(memory_space=pltpu.VMEM),  # model stack
        ]
        args += [nbr_dev, live, x]
        if with_sgd:
            specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
            args.append(g.reshape(x.shape))
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            in_specs=specs,
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            interpret=interp,
        )(*args).reshape(shape)

    return call


def make_fused_robust_aggregator(
    name: str,
    budget: int,
    nbr_idx: np.ndarray,
    clip_tau=0.0,
    *,
    interpret: Optional[bool] = None,
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Fused ``aggregate(live, x) -> x_new``: one pallas kernel performing
    the degree-bounded gather + screen + mix pass of
    ``make_gather_robust_aggregator`` without materializing the
    [N, k_max, d] neighbor stack in HBM. Drop-in for the gather form
    (same liveness/transmitted-stack contract, same outputs — bitwise for
    the count rules, ≤ 1e-12 f64 for clipping)."""
    call = _make_fused_robust(
        name, budget, nbr_idx, clip_tau, with_sgd=False, interpret=interpret
    )
    return lambda live, x: call(live, x)


def make_fused_robust_dsgd_step(
    name: str,
    budget: int,
    nbr_idx: np.ndarray,
    clip_tau=0.0,
    *,
    interpret: Optional[bool] = None,
) -> Callable[..., jax.Array]:
    """Fused ``step(live, x, g, eta) -> x_new``: the ENTIRE robust D-SGD
    update — gather + screen + mix + (− η g) — in one VMEM-resident kernel
    (the Byzantine twin of ``fused_ring_dsgd_step``). Bitwise the
    ``aggregate → subtract`` two-op sequence for the count rules."""
    call = _make_fused_robust(
        name, budget, nbr_idx, clip_tau, with_sgd=True, interpret=interpret
    )
    return lambda live, x, g, eta: call(live, x, g=g, eta=eta)


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


def _make_shard_visit_kernel(link, d: int, lanes: int):
    full, tail = divmod(lanes, LANES)

    def strip(refs, cols):
        """One strip of at most 128 workers: both sweeps of its slabs."""
        xbar_ref, X_ref, x_ref, y_ref, w_ref, nv_ref, g_ref, f_ref = refs
        z = zbar = None
        for k in range(d):
            slab = X_ref[k, :, cols]
            own, mean = slab * x_ref[k:k + 1, cols], slab * xbar_ref[k]
            z, zbar = (own, mean) if z is None else (z + own, zbar + mean)
        y = y_ref[:, cols]
        c = w_ref[:, cols] * link.coeff(z, y)
        for k in range(d):
            g_ref[k:k + 1, cols] = jnp.sum(
                X_ref[k, :, cols] * c, axis=0, keepdims=True
            )
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
    g, f = pl.pallas_call(
        _make_shard_visit_kernel(link, d, lanes),
        grid=(pl.cdiv(n, lanes),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((d, rows, lanes), lambda i: (0, 0, i)),
            model_spec, rows_spec, rows_spec, worker_spec,
        ],
        out_specs=[model_spec, worker_spec],
        out_shape=[
            jax.ShapeDtypeStruct((d, n), X.dtype),
            jax.ShapeDtypeStruct((1, n), X.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=2 * _visit_block_bytes(
                d, rows, lanes, X.dtype.itemsize
            ) + SHARD_VISIT_VMEM_SPARE,
        ),
        interpret=resolve_interpret(X, interpret),
        name="glm_shard_visit",
    )(xbar, X.transpose(2, 1, 0), x.T, y.T, wts.T,
      n_valid.astype(jnp.int32)[None, :])
    return g.T, f[0]
