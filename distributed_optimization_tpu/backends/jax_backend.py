"""The JAX/XLA execution backend — the TPU-native north star.

Where the reference runs T × N Python-level worker iterations with per-iter
host-side full-dataset metric evaluations (reference ``trainer.py:41-71``,
``161-193``), this backend compiles the ENTIRE run into one XLA program:

- state is an ``[N, d]``-stacked pytree sharded over the worker mesh axis;
- one iteration = one pure function: per-worker minibatch sampling
  (counter-based keys) → per-worker gradients (vmapped, MXU matmuls) →
  gossip collective (ppermute stencil / psum / dense contraction) → step;
- the T-iteration loop is a single ``jax.lax.scan``; suboptimality and
  consensus metrics accumulate on-device in the scan outputs and are fetched
  ONCE at the end (the reference pays a host round-trip per iteration);
- compile and execute are measured separately via AOT lowering, so iters/sec
  reflects steady-state throughput.

Reference call-stack parity: this file replaces SURVEY.md §3.2/§3.3's hot
loops end to end.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import sys
import threading
import time
import weakref
from typing import Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distributed_optimization_tpu.algorithms import get_algorithm
from distributed_optimization_tpu.algorithms.base import StepContext
from distributed_optimization_tpu.backends.base import BackendRunResult
from distributed_optimization_tpu.metrics import (
    RunHistory,
    centralized_floats_per_iteration,
    decentralized_floats_per_iteration,
)
from distributed_optimization_tpu.models import get_problem
from distributed_optimization_tpu.observability import device_scopes
from distributed_optimization_tpu.observability.spans import current_tracer
from distributed_optimization_tpu.ops.compression import selection_label
from distributed_optimization_tpu.ops.losses import paired_margins, sq_norm
from distributed_optimization_tpu.ops.mixing import make_mixing_op
from distributed_optimization_tpu.ops.sampling import (
    batch_table,
    sample_table_batches,
    sample_worker_batch_weights,
    targets_ride,
)
from distributed_optimization_tpu.ops.robust_aggregation import (
    make_gather_robust_activity,
    make_gather_robust_aggregator,
    make_robust_activity,
    make_robust_aggregator,
    screen_fetch,
    screen_order,
    validate_budget,
)
from distributed_optimization_tpu.telemetry import cost_from_lowered
from distributed_optimization_tpu.serving.cache import (
    batch_cache_key,
    resolve_cache,
    sequential_cache_key,
)
from distributed_optimization_tpu.parallel.adversary import (
    attackers_per_honest_neighbourhood,
    byzantine_set,
    make_adversary,
    make_byzantine_mixing,
)
from distributed_optimization_tpu.parallel.faults import (
    make_faulty_mixing,
    make_round_robin_mixing,
    timeline_counters,
)
from distributed_optimization_tpu.parallel import build_topology
from distributed_optimization_tpu.parallel.topology import (
    cached_topology,
    neighbor_tables_for,
)
from distributed_optimization_tpu.parallel.mesh import (
    WORKER_AXIS,
    make_worker_mesh,
    place_shards,
    replicate,
    shard_over_workers,
    zeros_over_workers,
)
from distributed_optimization_tpu.utils.data import HostDataset, stack_shards


# Forcing --sampling-impl dense beyond this padded shard length warns: the
# [L, L] ranking matrix is quadratic and the measured crossover to gather is
# ~L=250 (docs/perf/breakdown.json). Single source for the backend warning
# and the CLI help.
DENSE_SAMPLING_WARN_ROWS = 256


def _total_rows(X, n_valid):
    """The real rows of all shards together, at least 1, in X's type."""
    return jnp.maximum(jnp.sum(n_valid).astype(X.dtype), 1.0)


def _full_data_weights(X, n_valid):
    """``[N, L]`` weights of the global mean over the stacked shards:
    padding rows 0, every real row 1/total."""
    mask = (jnp.arange(X.shape[1])[None, :] < n_valid[:, None]).astype(X.dtype)
    return mask / _total_rows(X, n_valid)


def make_full_objective_fn(problem, reg):
    """Full-dataset objective of a single model w, computed from the stacked
    per-worker shards (so it shards over the mesh and reduces with one psum).

    Equals the reference's objective over the concatenated dataset
    (trainer.py:67,189): padding rows carry zero weight and every real row
    weighs 1/total, so Σ_workers Σ_rows w_il·loss_il is the global mean.

    X/y/n_valid are arguments (not captured) so the traced computation never
    closes over globally-sharded arrays — closing over arrays that span
    non-addressable devices is an error in multi-process runs.
    """

    def full_objective(w, X, y, n_valid):
        per_worker = jax.vmap(
            lambda Xi, yi, wi: problem.objective_weighted(w, Xi, yi, wi, 0.0)
        )(X, y, _full_data_weights(X, n_valid))
        return jnp.sum(per_worker) + 0.5 * reg * sq_norm(w)

    return full_objective


def _fetch_to_host(tree):
    """Bring possibly sharded device arrays to host numpy.

    In a multi-process (multi-host) run the worker axis spans
    non-addressable devices, so a plain np.asarray would raise; gather the
    full value on every host first. Single-process runs skip the gather.
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        tree = multihost_utils.process_allgather(tree, tiled=True)
    return jax.tree.map(np.asarray, tree)


def _flat_rows(tree):
    """Host leaves as ``[rows, D]``: the flat contract of every boundary
    (``final_models``, ``final_state``, checkpoints), whatever parameter
    shape the scan carried (``Problem.param_shape``). A view where the leaf
    is C-contiguous (every rank-2 leaf), else a copy (``_cast_f64``)."""
    return jax.tree.map(lambda a: a.reshape(a.shape[0], -1), tree)


def _cast_f64(a, dst):
    """A fetched leaf as the float64 ``[rows, D]`` array the results hold,
    written into ``dst`` (float64, C order, ``a``'s shape) in one pass.

    The copy writes C order BEFORE the flatten: the TPU runtime hands a
    rank-3 array to the host in the device's own dimension order (the
    softmax models ``[96, 4097, 512]`` arrive with the strides of a
    ``[4097, 96, 512]`` buffer — my chip run, PR 25), so flattening the
    fetched array first would copy the whole leaf once more, where the
    cast, which touches every element anyway, reorders for nothing."""
    np.copyto(dst, a, casting="unsafe")  # ``astype``'s own casting rule
    return _flat_rows(dst)


class _ResultBuffers:
    """The float64 host buffers the last two harvests wrote, handed to a
    later harvest once nothing holds them (ISSUE 49).

    Why: glibc maps an allocation of a result's size anew and unmaps it on
    free, so a cast into a new array faults in every page it writes (1.5 s
    of a 1.6 s cast at the softmax cells' 1.61 GB, PERF.md §6, PR 48); the
    same cast into memory the process has touched is the arithmetic alone.

    Who owns a result's memory: whoever holds the result. A result is a
    view (``_flat_rows``) of the buffer it was cast into, and numpy gives
    every view, every slice of a view and every buffer export the OWNING
    array as its base, so the owner's reference count says whether a live
    object can still reach the memory. ``take`` hands a kept buffer out
    only at the idle count (this store's list and the question's own
    argument) and with no weak reference to it; a held result, a held
    slice, a ``memoryview``, an array another library aliased all read as
    held, and the harvest allocates as if there were no store. The question
    and the hand-out happen under one lock: the serving plane harvests from
    threads, and two never receive one buffer. The count is CPython's, with
    its lock: on any other interpreter nothing is kept and every harvest
    allocates.

    Why two harvests: a sweep written ``for ...: r = run(...)`` (every
    sweep under ``examples/``) still holds call n's result while call n + 1
    harvests, and lets it go when ``r`` is bound again. So ``keep`` holds
    on, for ONE more call, to the last harvest's buffers that are held as
    it ends: call n + 2 finds them free, and the loop reuses from its third
    call, two buffers taking turns (what the loop has alive at its peak
    without the store). A caller who drops the result before the next call
    reuses from the second. A buffer held through two later harvests is
    forgotten, its holders' alone; a free one no harvest took (another
    shape) goes. So the store reaches at most the last two harvests'
    buffers, and of bytes no caller holds at most those two harvests'
    until the next harvest ends, then at most its own."""

    def __init__(self):
        self._lock = threading.Lock()
        self._kept: list[np.ndarray] = []  # the last harvest's
        self._older: list[np.ndarray] = []  # the one before's, held as it ended
        # The idle count as THIS interpreter counts it, read the way
        # ``_is_free`` reads it: one owner in a list, asked by subscript.
        probe = [np.empty(0)]
        self._idle_refs = sys.getrefcount(probe[0])
        self._counts_say = sys.implementation.name == "cpython" and getattr(
            sys, "_is_gil_enabled", lambda: True)()

    def _is_free(self, pool, i: int) -> bool:
        return (
            sys.getrefcount(pool[i]) == self._idle_refs
            and weakref.getweakrefcount(pool[i]) == 0
        )

    def take(self, shape) -> tuple[np.ndarray, bool]:
        """A C-order float64 array of ``shape`` to write a result into, and
        whether it is a kept buffer (its pages touched) or a new one."""
        with self._lock:
            for pool in (self._kept, self._older):
                for i in range(len(pool)):
                    if pool[i].shape == shape and self._is_free(pool, i):
                        return pool.pop(i), True
        return np.empty(shape, np.float64), False

    def keep(self, buffers) -> None:
        """The buffers of the harvest that just ended become the last
        harvest's; the last harvest's that are still held, the one
        before's; whatever else was kept is let go."""
        if not self._counts_say:
            return
        with self._lock:
            kept = self._kept
            self._older = [
                kept[i] for i in range(len(kept)) if not self._is_free(kept, i)
            ]
            self._kept = list(buffers)

    def clear(self) -> None:
        """Let go of everything kept: a long-lived process hands back what
        its dropped results left here (at most two harvests' bytes)."""
        with self._lock:
            self._kept, self._older = [], []


_RESULT_BUFFERS = _ResultBuffers()


def _restored_state(state_np, like):
    """A checkpoint's flat leaves in the shapes of the run's own state
    ``like`` (the inverse of ``_flat_rows``)."""
    return jax.tree.map(
        lambda a, ref: np.asarray(a).reshape(ref.shape), state_np, like
    )


def _make_eta_fn(config, eta0=None):
    """LR schedule closure; ``eta0`` overrides the config scalar — the
    replica-batched path passes a per-replica traced value (a swept axis)."""
    if eta0 is None:
        eta0 = config.learning_rate_eta0
    if config.resolved_lr_schedule() == "sqrt_decay":
        # Parity: reference trainer.py:17-19, eta0 / sqrt(t + 1).
        return lambda t: eta0 / jnp.sqrt(t + 1.0)
    return lambda t: jnp.asarray(eta0)


def _fanout_progress(progress_cb, monitors):
    """Compose the user progress callback with a ``MonitorBank`` observer
    (ISSUE-13): each consumer is shielded individually, so a broken user
    callback cannot starve the monitors of heartbeats (or vice versa).
    Returns None when both are absent — progress off stays the pre-PR
    code path."""
    cbs = []
    if progress_cb is not None:
        cbs.append(progress_cb)
    if monitors is not None:
        cbs.append(monitors.observe)
    if not cbs:
        return None
    if len(cbs) == 1:
        return cbs[0]
    from distributed_optimization_tpu.log import get_logger

    log = get_logger("progress")

    def fan(ev):
        for cb in cbs:
            try:
                cb(ev)
            except Exception:  # observability never kills the run
                log.exception("progress consumer failed; continuing run")

    return fan


def _progress_emitter(
    config, progress_cb, *, t0: int = 0, kind="chunk", with_bhat=True,
):
    """Heartbeat closure for the round-based paths (ISSUE-10 progress
    streaming; ``observability/progress.py``).

    Returns ``emit(done_evals, gap_list, cons_list, elapsed, **extra)`` or
    None when progress is off. The emitter derives the live B̂ view once
    (host-side timeline rebuild, bitwise the backend's realization — the
    ``realized_bhat`` convention, cost-capped) and shields the run from a
    broken callback: observability must never kill optimization.
    ``with_bhat=False`` suppresses the live B̂: the replica-batched path
    realizes R DISTINCT fault timelines (one per replica seed), so a
    single heartbeat has no B̂ that is true for the cohort — emitting the
    base config's would misattribute replica 0's realization to everyone.

    When the live-B̂ probe is ACTIVE but reports None — the executed
    prefix's union graph is disconnected, so no finite B exists — the
    event carries ``extra={"bhat_disconnected": True}``: a bare
    ``bhat=None`` is ambiguous (it also means "not applicable"), and the
    connectivity-loss monitor must be able to tell assumption violation
    from absence (ISSUE-13).
    """
    if progress_cb is None:
        return None
    from distributed_optimization_tpu.log import get_logger
    from distributed_optimization_tpu.observability.progress import (
        ProgressEvent,
        make_live_bhat,
        progress_heartbeat_counter,
    )

    log = get_logger("progress")
    live_bhat = make_live_bhat(config) if with_bhat else None
    counter = progress_heartbeat_counter()
    horizon = t0 + config.n_iterations

    def emit(done_evals, gap_list, cons_list, elapsed, **extra):
        iteration = t0 + done_evals * config.eval_every
        gap = float(gap_list[-1]) if len(gap_list) else None
        cons = float(cons_list[-1]) if cons_list is not None and len(
            cons_list
        ) else None
        bhat = None
        if live_bhat is not None:
            bhat = live_bhat(iteration)
            if bhat is None:
                extra = dict(extra)
                extra["extra"] = {
                    **(extra.get("extra") or {}), "bhat_disconnected": True,
                }
        ev = ProgressEvent(
            kind=kind,
            iteration=int(iteration),
            n_iterations=int(horizon),
            wall_seconds=float(elapsed),
            gap=gap,
            consensus=cons,
            bhat=bhat,
            **extra,
        )
        counter.inc()
        try:
            progress_cb(ev)
        except Exception:  # observability never kills the run
            log.exception("progress callback failed; continuing run")

    return emit


@dataclasses.dataclass(frozen=True)
class _StepPieces:
    """Everything the per-iteration step/eval closures bind to.

    One bundle serves BOTH execution paths: ``_run`` fills it from the
    config's own seed-derived randomness (concrete arrays), and
    ``run_batch`` fills it per replica inside the vmapped trace (leaves
    may be tracers carrying the replica axis) — so a batched replica runs
    the IDENTICAL program as a sequential run, just under ``vmap``.
    """

    algo: object
    problem: object
    reg: float
    config: object
    batch_size: int
    sampling_impl: str
    key: object          # per-run sampling PRNG key
    eta_fn: object
    degrees: object
    mix_op: object       # MixingOp or None (centralized)
    faulty: object       # FaultyMixing or None
    byz_mix: object      # composed Byzantine mix or None
    adversary: object    # Adversary or None
    honest_w: object     # [N] f32 honest mask or None
    full_objective: object
    f_opt: float
    collect_metrics: bool
    track_consensus: bool
    edge_payload: object
    # --- flight recorder (config.telemetry; telemetry.TRACE_FIELDS) ---
    telemetry: bool = False
    # ``activity(t, x) -> scalar``: robust-aggregation screening fraction
    # over the realized graph at t (corruption composed upstream, like the
    # aggregate itself); None when no robust rule is active.
    robust_activity: object = None
    # Nominal Σ_i deg_i of the static topology (the fault-free live_edges
    # row; 0.0 for centralized runs).
    static_degree_sum: float = 0.0
    # Sharded compressed-exchange wire form (q, x̂⁺, halo) -> (W x̂⁺, halo⁺)
    # (collectives.make_halo_compressed_mixing_op); only set on the
    # worker-mesh path with compression != 'none'.
    compressed_mix: object = None
    # What of the next trip's first gradient the eval's pass over X leaves
    # in the scan's carry: nothing (``recomputed``), the margins X·x
    # (``carried``: ``_forward_is_carried``) or the gradient itself
    # (``fused``: ``_visit_is_fused``). Never carried on the replica-batched
    # path.
    forward: str = "recomputed"
    # The worker mesh the scan's rows are sharded over (None: one device).
    mesh: object = None


class _Forward(NamedTuple):
    """What a trip's eval leaves the next trip's first gradient, in the
    program's own carry: ``product``, the margins X·x (``carried``) or the
    gradient itself (``fused``), and ``of``, the models it was taken at
    where they are not the state's own: under ``neighbor_restart`` the
    stepped models with the rows that rejoin NEXT round already restarted,
    made once, by the eval, and taken over by the step. None (no leaf)
    everywhere else: the product is of ``state["x"]``."""

    product: jax.Array
    of: Optional[jax.Array] = None


def _forward_is_carried(algo, problem, config, *, rows, batch_size,
                        sampling_impl, scheduled, collect_metrics):
    """Whether the scan carries the margins ``z = X·x`` from a trip's eval to
    the next trip's first gradient (two reads of the shard stack an
    iteration, not three): decided by what the run is, never by an option.
    All of: a GLM with a vector parameter (``[N, L]`` margins, a
    bandwidth-bound pass; softmax's logits are a matmul); the gradient over
    the whole padded shard (full batch or the dense sampler, no gathered or
    injected batches); metrics collected (the eval makes the paired pass); a
    rule whose first gradient is at the carried models
    (``Algorithm.first_grad_at_x``; its compressed branch is left as it
    was). Anything else recomputes, its program unchanged. A restart of x at
    a rejoin decides nothing here: the product is then of the restarted
    models (``_Forward.of``)."""
    return bool(
        problem.link is not None
        and algo.first_grad_at_x and config.compression == "none"
        and collect_metrics and not scheduled
        and (batch_size >= rows or sampling_impl == "dense")
    )


def _visit_is_fused(carried, X):
    """Whether the forward product a scan carries is the next gradient
    itself, made with the objective at x̄ by ONE read of the shards
    (``ops.pallas_kernels.glm_shard_visit``) where ``carried`` reads them
    twice: decided by what the run is, never by an option. All of
    ``_forward_is_carried``'s conditions and: the program is compiled for a
    TPU (an interpreted kernel is no program to run users on, so a CPU keeps
    ``carried``), float32 shards, and a block of 128 workers' shards fits the
    kernel's VMEM budget twice. Anything else stays as it was."""
    from distributed_optimization_tpu.ops.pallas_kernels import (
        LANES,
        shard_visit_lanes,
    )

    return bool(
        carried and jax.default_backend() == "tpu"
        and X.dtype == jnp.float32
        and shard_visit_lanes(LANES, *X.shape[1:], X.dtype.itemsize)
    )


def _make_step_eval(p: _StepPieces, data):
    """Bind the step/eval/floats closures to the data pytree passed through
    jit (shared by the sequential and replica-batched paths — see
    ``_StepPieces``). Where ``p.forward`` is not ``recomputed``,
    ``step(state, t, fwd)`` takes the forward product (a ``_Forward``) of
    the models iteration t differentiates at: ``state["x"]`` (its margins
    X·x under ``carried``; under ``fused`` its gradient at iteration t's
    batch, less ``λx``) or, where rejoining rows are restarted before the
    step, ``fwd.of``; ``eval_metrics`` returns the one of the state it was
    shown (``(rows, fwd)``, for iteration ``t_last + 1``) and
    ``init_forward(state, t0)`` makes the one a scan starts from; otherwise
    fwd is None throughout."""
    X, y, n_valid = data["X"], data["y"], data["n_valid"]
    schedule = data.get("schedule")
    batch_size = p.batch_size
    faulty, mix_op, byz_mix, adversary = (
        p.faulty, p.mix_op, p.byz_mix, p.adversary
    )
    if "faults" in data:
        # The gather fault layer over the tables this program was handed,
        # not over constants of its own.
        faulty = faulty.bind(data["faults"])
    if "mixing" in data:
        # The gather mixing likewise (ISSUE 36).
        mix_op = mix_op.bind(data["mixing"])
    restarts = faulty is not None and faulty.rejoin_restart is not None

    # Full-batch fast path: sampling b >= L rows without replacement IS
    # the whole shard with 1/n_i weights (the reference's b=min(b, n_i)
    # semantics, worker.py:21), so skip the per-iteration RNG + selection +
    # gather entirely — in the compute-bound tier the gather alone would
    # otherwise copy the full [N, L, d] every iteration, doubling HBM
    # traffic for no semantic effect.
    full_batch = schedule is None and batch_size >= X.shape[1]
    if full_batch:
        Lr = X.shape[1]
        fmask = (
            jnp.arange(Lr)[None, :] < n_valid[:, None]
        ).astype(X.dtype)
        full_wts = fmask / jnp.maximum(
            n_valid[:, None].astype(X.dtype), 1.0
        )

    if not full_batch and schedule is None and p.sampling_impl != "dense":
        # The gather sampler's own table, made once, before the loop: a
        # draw is one gather of whole rows, the targets riding in them.
        with device_scopes.scope("sampling"):
            table = batch_table(X, y)

    if p.forward != "recomputed":
        link = p.problem.link
    if p.forward == "carried":
        eval_wts = _full_data_weights(X, n_valid)
    elif p.forward == "fused":
        total_rows = _total_rows(X, n_valid)

    def dense_weights(t, slot):
        """The dense sampler's weights over the padded shard: a function of
        (key, slot, t, worker) alone, so a trip can draw the next one's."""
        return sample_worker_batch_weights(
            jax.random.fold_in(p.key, slot), t, n_valid, X.shape[1],
            batch_size,
        ).astype(X.dtype)

    @device_scopes.scope("faults")
    def restarted(t, x):
        """neighbor_restart rejoin policy: the models as iteration t's step
        takes them. A node coming back from an outage at t replaces its
        stale model row with the realized-neighborhood average (auxiliary
        leaves stay frozen-stale — only the model is warm-restarted); the
        restarted value is what it differentiates at and gossips that
        round. A function of x and of row t of the timeline's leaves."""
        return faulty.rejoin_restart(t, x)

    def on_own_rows(kernel, in_specs, out_specs):
        """A kernel call whose every quantity is a worker's own: under a
        mesh each device runs it on its rows (GSPMD cannot partition a
        custom call) and no collective is added."""
        if p.mesh is None:
            return kernel
        return jax.shard_map(
            kernel, mesh=p.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    def shard_visit(x, xbar, t_next):
        """ONE read of the shards: the gradient (less ``λx``) of iteration
        ``t_next`` at x and each worker's sum of losses at x̄ over its real
        rows. Every quantity is a worker's own, so under a mesh each device
        visits its rows and no collective is added."""
        from distributed_optimization_tpu.ops.pallas_kernels import (
            glm_shard_visit,
        )

        with device_scopes.scope("sampling"):
            wts = full_wts if full_batch else dense_weights(t_next, 0)
        rows, whole = P(WORKER_AXIS), P()
        visit = on_own_rows(
            functools.partial(glm_shard_visit, link),
            (rows, rows, rows, whole, rows, rows), (rows, rows),
        )
        with device_scopes.scope("gradient"):
            return visit(X, y, x, xbar, wts, n_valid)

    def shard_gradient(x, wts):
        """ONE read of the shards for a gradient no eval rides with (less
        ``λx``): the visit's kernel without its objective half, for a
        round's later descents under ``fused``. A worker's own, as the
        visit's; the caller's scope is the call's."""
        from distributed_optimization_tpu.ops.pallas_kernels import (
            glm_shard_gradient,
        )

        rows = P(WORKER_AXIS)
        return on_own_rows(
            functools.partial(glm_shard_gradient, link), (rows,) * 4, rows
        )(X, y, x, wts)

    def forward_pass(x, xbar, t_next):
        """ONE pass over X for both of its readers: ``(fwd, at_xbar)``, fwd
        the product iteration ``t_next``'s first gradient takes, at the
        models it will really be taken at (x, or x with the rows that
        rejoin at ``t_next`` restarted a trip early: ``fwd.of``), and x̄'s
        half (each worker's sum of losses under ``fused``, the margins X·x̄
        under ``carried``), which knows of no restart. The horizon's last
        trip asks the restart for row T of leaves that have T rows: a traced
        ``[t]`` clamps to the last row, and that product is dropped at the
        scan's end, read by no row of the history."""
        x_at = restarted(t_next, x) if restarts else x
        product, at_xbar = (
            shard_visit(x_at, xbar, t_next) if p.forward == "fused"
            else paired_margins(X, x_at, xbar)
        )
        return _Forward(product, x_at if restarts else None), at_xbar

    def full_objective(x, xbar, t_next):
        """``(f(x̄) over the full data, fwd)``; both from ONE pass over X
        where a forward product is carried: the margins ``X·x``
        (``carried``) or the gradient of iteration ``t_next`` at x
        (``fused``). Else fwd is None."""
        if p.forward == "recomputed":
            return p.full_objective(xbar, X, y, n_valid), None
        fwd, at_xbar = forward_pass(x, xbar, t_next)
        if p.forward == "fused":
            data_loss = jnp.sum(at_xbar) / total_rows
        else:
            data_loss = jnp.sum(
                jnp.sum(eval_wts * link.loss(at_xbar, y), axis=1)
            )
        return data_loss + 0.5 * p.reg * sq_norm(xbar), fwd

    def grad_fn_factory(t, fwd=None, fwd_of=None):
        """The iteration's ``ctx.grad``; ``fwd`` is the forward product of
        ``fwd_of`` as the scan carried it (a ``_Forward``), used where the
        rule asks at that very array, slot 0. Under ``fused`` a round's
        LATER descents (``local_steps`` > 1: a slot above 0) visit the
        shards once each (``shard_gradient``); any other call is
        ``gradient_weighted``, two reads."""
        def grad(params, slot):
            # A round's first gradient, or one of its τ − 1 later descents
            # (a static slot above 0, or the traced slot of the
            # ``fori_loop`` form), which under ``fused`` visit the shards.
            first = isinstance(slot, int) and slot == 0
            visited = p.forward == "fused" and not first
            with device_scopes.scope("sampling"):
                if schedule is not None:
                    idx = schedule[t]  # [N, b] injected batch indices
                    Xb = jnp.take_along_axis(X, idx[:, :, None], axis=1)
                    yb = jnp.take_along_axis(y, idx, axis=1)
                    wts = jnp.full(
                        idx.shape, 1.0 / idx.shape[1], dtype=X.dtype
                    )
                elif full_batch:
                    Xb, yb, wts = X, y, full_wts
                elif p.sampling_impl == "dense":
                    # Dense-weights sampling: no indices, no gather — the
                    # weighted gradient runs over the full padded shard
                    # with 1/b weights on the sampled rows (same subsets as
                    # the gather path for the same key; see
                    # ops/sampling.py).
                    t_due = t
                    if visited:
                        # as the gather sampler below: a later descent's
                        # draw is made when its gradient is due
                        params, t_due = jax.lax.optimization_barrier(
                            (params, t)
                        )
                    Xb, yb, wts = X, y, dense_weights(t_due, slot)
                else:
                    slot_key = jax.random.fold_in(p.key, slot)
                    # A batch is drawn when its gradient is due: the draw
                    # depends on nothing the scan carries, and XLA would make
                    # a whole unrolled trip's keys at its start, [N, L] each.
                    params, t_due = jax.lax.optimization_barrier((params, t))
                    Xb, yb, wts = sample_table_batches(
                        slot_key, t_due, table, n_valid, batch_size
                    )
                    wts = wts.astype(X.dtype)  # keep bf16 carries unpromoted
            # The first is ``gradient``, the later ones ``local``. Metadata
            # only.
            with device_scopes.scope("gradient" if first else "local"):
                if fwd is not None and params is fwd_of and slot == 0:
                    if p.forward == "fused":
                        return fwd.product + p.reg * params
                    return jax.vmap(
                        link.gradient_at, in_axes=(0, 0, 0, 0, 0, None)
                    )(fwd.product, params, Xb, yb, wts, p.reg)
                if visited:
                    return shard_gradient(params, wts) + p.reg * params
                return jax.vmap(
                    p.problem.gradient_weighted, in_axes=(0, 0, 0, 0, None)
                )(params, Xb, yb, wts, p.reg)

        return grad

    def step(state, t, fwd=None):
        if restarts:
            # BEFORE the step at the rejoin round; where the last trip's
            # eval made the restarted models for its product, those.
            state = {
                **state,
                "x": fwd.of if fwd is not None else restarted(t, state["x"]),
            }
        fwd_of = state["x"]  # the array the carried forward product is of
        if faulty is not None:
            mix_fn = lambda v: faulty.mix(t, v)  # noqa: E731
            nbr_fn = lambda v: faulty.neighbor_sum(t, v)  # noqa: E731
        elif mix_op is not None:
            mix_fn, nbr_fn = mix_op.apply, mix_op.neighbor_sum
        else:
            mix_fn, nbr_fn = (lambda v: v), (lambda v: v * 0)
        if byz_mix is not None:
            # Corrupt outgoing models, then (robustly) aggregate — the
            # composed per-iteration mix from parallel/adversary.py.
            # neighbor_sum sees the corrupted stack too (consistency;
            # no byzantine-supported algorithm consumes it today).
            base_nbr = nbr_fn
            mix_fn = lambda v: byz_mix(t, v)  # noqa: E731
            if adversary is not None:
                nbr_fn = lambda v: base_nbr(  # noqa: E731
                    adversary.corrupt(t, v)
                )
        # Whatever realizes them (stencil, gather, halo exchange, the fault
        # layer's weighted sum, a robust aggregate): the gossip of the step.
        mix_fn, nbr_fn = (
            device_scopes.scope("gossip")(f) for f in (mix_fn, nbr_fn)
        )
        ctx = StepContext(
            grad=grad_fn_factory(t, fwd, fwd_of),
            mix=mix_fn,
            neighbor_sum=nbr_fn,
            # Cast to the run dtype so low-precision carries (bfloat16)
            # aren't silently promoted by the f32 schedule scalar.
            eta=p.eta_fn(t).astype(X.dtype),
            t=t,
            degrees=p.degrees,
            config=p.config,
            compressed_mix=p.compressed_mix,
        )
        with device_scopes.scope("update"):
            new_state = p.algo.step(state, ctx)
        if faulty is not None and (
            faulty.straggler_prob > 0.0 or faulty.churn_active
            or faulty.participation_active
        ):
            # A straggler/crashed/sampled-out node takes no step at all:
            # freeze its rows across every state leaf (each leaf leads
            # with the worker axis) — for churn, across the WHOLE
            # outage, so a 'frozen' rejoin resumes the stale pre-crash
            # state for free. Its mixing row already degenerated to
            # identity via the dropped edges.
            with device_scopes.scope("faults"):
                m = faulty.active(t)
                new_state = jax.tree.map(
                    lambda new, old: jnp.where(
                        m.reshape((-1,) + (1,) * (new.ndim - 1)) > 0,
                        new, old,
                    ),
                    new_state,
                    state,
                )
        return new_state, None

    @device_scopes.scope("recorder")
    def trace_row(state, t):
        """One flight-recorder row (telemetry.TRACE_FIELDS) at iteration t:
        pure observability computed from the post-step state, feeding the
        scan's stacked OUTPUTS only — the carry and the step dataflow are
        untouched, so trajectories are bitwise-identical with telemetry on
        or off (tests/test_telemetry.py pins it). The gradient uses the
        same (key, t) batch realization the iteration-t step consumed."""
        x = state["x"]
        param_axes = tuple(range(1, x.ndim))
        acc = jnp.promote_types(jnp.float32, x.dtype)
        g = grad_fn_factory(t)(x, 0).astype(acc)
        nonfinite = jnp.zeros((), dtype=jnp.float32)
        for leaf in jax.tree.leaves(state):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                nonfinite = nonfinite + jnp.sum(
                    ~jnp.isfinite(leaf)
                ).astype(jnp.float32)
        if faulty is not None:
            nodes_up = faulty.active(t)
            live_edges = faulty.realized_degree_sum(t).astype(jnp.float32)
        else:
            nodes_up = jnp.ones(x.shape[0], dtype=jnp.float32)
            live_edges = jnp.asarray(p.static_degree_sum, dtype=jnp.float32)
        clip_frac = (
            p.robust_activity(t, x).astype(jnp.float32)
            if p.robust_activity is not None
            else jnp.zeros((), dtype=jnp.float32)
        )
        return {
            "grad_norm": jnp.sqrt(jnp.sum(g * g, axis=param_axes)).astype(
                jnp.float32
            ),
            "param_norm": jnp.sqrt(
                jnp.sum(x.astype(acc) ** 2, axis=param_axes)
            ).astype(jnp.float32),
            "nodes_up": nodes_up,
            "nonfinite": nonfinite,
            "live_edges": live_edges,
            "clip_frac": clip_frac,
        }

    def _zero_trace(state):
        n = state["x"].shape[0]
        z = jnp.zeros((), dtype=jnp.float32)
        zn = jnp.zeros(n, dtype=jnp.float32)
        return {
            "grad_norm": zn, "param_norm": zn, "nodes_up": zn,
            "nonfinite": z, "live_edges": z, "clip_frac": z,
        }

    def eval_metrics(state, t_last, cadence_known=False):
        """Per-eval metrics + flight-recorder row at iteration ``t_last``.

        ``cadence_known=True`` promises t_last IS an eval boundary (a scan
        of one trip per eval); otherwise the scan computes its eval every
        trip and discards off-cadence rows, so there the trace row —
        whose gradient probe is NOT latency-hidden the way the stacked-
        output eval is — hides behind a ``lax.cond`` on the boundary
        predicate instead of running every trip (measured 36% → <10%
        steady overhead on the CPU container; docs/perf/telemetry.json).
        """
        out = {}
        fwd_next = None
        if p.telemetry:
            if cadence_known:
                out["trace"] = trace_row(state, t_last)
            else:
                on_boundary = (t_last + 1) % p.config.eval_every == 0
                out["trace"] = jax.lax.cond(
                    on_boundary,
                    lambda s: trace_row(s, t_last),
                    _zero_trace,
                    state,
                )
        with device_scopes.scope("eval"):
            if p.collect_metrics:
                x = state["x"]
                # Every parameter axis: [N, d] for the GLMs, [N, d, K] for
                # softmax on the sequential path (Problem.param_shape).
                param_axes = tuple(range(1, x.ndim))
                if adversary is not None:
                    # Honest-only metrics (docs/BYZANTINE.md): the gap is
                    # f(x̄_honest) − f* on the unchanged global objective,
                    # consensus is the honest spread — Byzantine rows are
                    # adversary-controlled and would poison both.
                    hw = p.honest_w.astype(x.dtype)
                    nh = jnp.sum(hw)
                    xbar = jnp.sum(
                        x * jnp.expand_dims(hw, param_axes), axis=0
                    ) / nh
                    f_bar, fwd_next = full_objective(x, xbar, t_last + 1)
                    out["gap"] = f_bar - p.f_opt
                    if p.track_consensus:
                        out["cons"] = jnp.sum(
                            hw * jnp.sum(
                                (x - xbar[None]) ** 2, axis=param_axes
                            )
                        ) / nh
                else:
                    xbar = jnp.mean(x, axis=0)
                    f_bar, fwd_next = full_objective(x, xbar, t_last + 1)
                    out["gap"] = f_bar - p.f_opt
                    if p.track_consensus:
                        out["cons"] = jnp.mean(
                            jnp.sum((x - xbar[None]) ** 2, axis=param_axes)
                        )
        return out, fwd_next

    @device_scopes.scope("eval")
    def init_forward(state, t0):
        """The forward product a scan starts from at iteration ``t0``, by
        the eval's own pass. The barrier keeps x̄'s half alive: with it
        thrown away the compiler makes another reduction of the half that is
        left, and a segment's first trip is no longer bitwise the unsplit
        run's (at d = 81, CPU)."""
        if p.forward == "recomputed":
            return None
        x = state["x"]
        fwd, at_xbar = forward_pass(x, jnp.mean(x, axis=0), t0)
        return fwd._replace(product=jax.lax.optimization_barrier(
            (fwd.product, at_xbar)
        )[0])

    @device_scopes.scope("faults")
    def floats_for(ts):
        # Honest comms accounting under faults: floats actually
        # exchanged over realized edges for these iterations (recomputed
        # from the fault keys, so it costs one tiny mask redraw per
        # iteration, no extra communication).
        return (
            jnp.sum(jax.vmap(faulty.realized_degree_sum)(ts))
            * p.edge_payload
        )

    return step, eval_metrics, floats_for, init_forward


def _flat_scan_cadence(scan_unroll: int, eval_every: int):
    """(micro, trips_per_eval, flat_unroll) for the flat fused scan.

    ``micro`` is the largest divisor of ``eval_every`` within the unroll
    budget, so some scan trip lands exactly on every eval boundary. One
    derivation shared by the sequential and replica-batched paths — their
    eval cadence must not be able to drift apart.
    """
    micro = next(
        d for d in range(min(scan_unroll, eval_every), 0, -1)
        if eval_every % d == 0
    )
    return micro, eval_every // micro, max(1, scan_unroll // micro)


def _device_bytes(tree) -> float:
    """What a pytree's arrays take on the device, in the device's own tiles."""
    return float(sum(
        leaf.on_device_size_in_bytes() for leaf in jax.tree.leaves(tree)
    ))


def _fault_root_args(config, faulty, tables) -> dict:
    """What the ``dopt.run`` root says of a call that ran faults: ``faults``
    (every active process with its rate), ``fault_form`` (``drawn``: each
    round's bits made inside the step from (seed, t); ``timeline``: read
    from a precomputed ``[horizon, ·]`` table) and, where the fault layer's
    arrays are arguments of the program (``FaultyMixing.tables``),
    ``fault_bytes``: what they take on the device, timeline leaves
    included (0 where the form needs none), and ``fault_mixing``
    (``shift`` / ``gather``: how that layer addresses a neighbour). Of a
    call whose faults have memory, and of no other: ``fault_chains`` (each
    two-state chain with its rates: ``burst:<p>x<B>``,
    ``churn:<mttf>/<mttr>``), under churn ``rejoin`` (the policy) and
    ``down_share``, under ``neighbor_restart`` ``rejoin_rows`` (the rows it
    is asked to restart over the horizon: the restart's engagement
    counter), and ``timeline_placement`` (``device`` / ``host``: where the
    leaves the scan reads were made; ``faults.timeline_counters``). Of a
    call with participation sampling, and of no other:
    ``sampled_out_share`` (the share of the (round, worker) pairs the
    sampling froze) and, again, ``timeline_placement``."""
    parts = [
        f"{name}:{value:g}" for name, value, on in (
            ("edge_drop", config.edge_drop_prob, config.edge_drop_prob > 0.0),
            ("burst", config.burst_len, config.burst_len >= 1.0),
            ("straggler", config.straggler_prob, config.straggler_prob > 0.0),
            ("mttf", config.mttf, config.mttf > 0.0),
            ("mttr", config.mttr, config.mttf > 0.0),
            ("participation", config.participation_rate,
             config.participation_rate < 1.0),
        ) if on
    ]
    if config.gossip_schedule != "synchronous":
        parts.append(f"schedule:{config.gossip_schedule}")
    args = {
        "faults": ",".join(parts),
        "fault_form": "drawn" if faulty.timeline is None else "timeline",
    }
    if tables is not None:
        args["fault_bytes"] = _device_bytes(tables)
    if faulty.addressing is not None:
        args["fault_mixing"] = faulty.addressing
    chains = [
        text for text, on in (
            (f"burst:{config.edge_drop_prob:g}x{config.burst_len:g}",
             config.edge_drop_prob > 0.0 and config.burst_len >= 1.0),
            (f"churn:{config.mttf:g}/{config.mttr:g}", config.mttf > 0.0),
        ) if on
    ]
    sampled = faulty.participation_active
    if (chains or sampled) and faulty.timeline is not None:
        counted = timeline_counters(faulty.timeline)
        args["timeline_placement"] = counted["timeline_placement"]
        if chains:
            args["fault_chains"] = ",".join(chains)
        if faulty.churn_active:
            args.update(rejoin=faulty.rejoin, down_share=counted["down_share"])
        if faulty.rejoin_restart is not None:
            args["rejoin_rows"] = counted["rejoin_rows"]
        if sampled:
            args["sampled_out_share"] = counted["sampled_out_share"]
    return args


# Reads of the shard stack that a round's FIRST gradient and the eval's
# objective take together, by what the eval leaves that gradient.
_FIRST_READS = {"fused": 1, "carried": 2, "recomputed": 3}


def _local_root_args(local_steps: int, forward: str) -> dict:
    """What the ``dopt.run`` root says of a call whose gossip round holds
    more than one gradient step (``local_steps`` = τ > 1), and of no other:
    ``local_steps``; ``local_forward``, how the τ − 1 later gradients were
    built (``visited`` under ``forward`` = ``fused``: each ONE read of the
    shards by ``glm_shard_gradient``, the visit's kernel without its
    objective half; ``recomputed`` everywhere else: ``gradient_weighted``
    over the whole padded shard, X·x and then Xᵀ·c: the engagement counter
    of the path that serves them); and ``shard_reads``, the reads of the
    shard stack one round was BUILT with, from ``forward`` and τ and nothing
    measured: the first gradient and the objective one, two or three, each
    later gradient one where visited and two where recomputed. A plan, as
    ``ici_bytes_per_round`` is."""
    visited = forward == "fused"
    return {
        "local_steps": local_steps,
        "local_forward": "visited" if visited else "recomputed",
        "shard_reads": _FIRST_READS[forward]
        + (1 if visited else 2) * (local_steps - 1),
    }


def _gather_root_args(topo, tables) -> dict:
    """What the ``dopt.run`` root says of a call whose static graph mixes
    through neighbor tables: the table's width, the graph's edges, the
    share of the table's slots that hold one (the rest are padding), the
    rows a round gathers (the live list's chunks, whole, and the sums'
    way back into the workers' order where the graph is not regular) and
    what the tables the scan was handed take on the device."""
    k_max = max(int(np.asarray(topo.degrees).max()), 1)
    live = float(np.asarray(topo.degrees).sum())
    return {
        "k_max": k_max,
        "edges": int(live // 2),
        "live_slot_share": live / (topo.n * k_max),
        "gathered_rows": int(tables["nbr"].size)
        + (topo.n if "inverse" in tables else 0),
        "table_bytes": _device_bytes(tables),
    }


def _halo_gather_root_args(mix_op) -> dict:
    """What the ``dopt.run`` root says of a ``worker_mesh`` call whose static
    graph mixes by the halo gather, for the fullest shard: what
    ``_gather_root_args`` says of the one-chip gather (the table's width,
    the rows a round gathers from the halo-extended block: every slot of
    every row, padded ones too), the rotations the plan holds, and what the
    per-shard tables and the exchange's index lists take on a device and
    how the program holds them (``constant``: closed into the executable;
    ``argument`` where an op has a ``bind``)."""
    tables = mix_op.tables
    _, k_max, shard_rows = tables["nbr"].shape
    return {
        "k_max": int(k_max),
        "gathered_rows": int(shard_rows * k_max),
        "halo_steps": len(tables["send"]),
        "halo_table_bytes": _device_bytes(tables),
        "halo_tables": "constant" if mix_op.bind is None else "argument",
    }


def _byzantine_root_args(config, topo, adversary, halo_mesh) -> dict:
    """What the ``dopt.run`` root says of a call that was attacked,
    screened or both (none of it on a benign call): ``attack`` (payload,
    attackers of workers) with ``byzantine_placement`` and ``budget_max``
    (the most attackers any honest worker counts among its neighbours: the
    screening rules' guarantee as a counter); ``aggregation`` (rule and
    budget) with ``robust_impl`` (``gather`` / ``dense`` / ``halo_gather``:
    the form that ran, the engagement counter), ``screen_order`` (how a
    closed neighbourhood was put in order and over how many slots:
    ``network:3`` / ``sort:31``, read off the table's width), ``screen_fetch``
    (how the received rows were fetched: ``shift`` on a ring's table,
    ``gather`` through any other, from the predicate the rule asks),
    ``screened_rows``
    (the rows of numbers a round orders: every closed neighbourhood's) and
    ``robust_bytes`` (what the round's tables take on the device as
    arguments of the scan: 0 while they are constants of it)."""
    args = {}
    if adversary is not None:
        args.update(
            attack=f"{config.attack}:{config.n_byzantine}/{config.n_workers}",
            byzantine_placement=config.byzantine_placement,
            budget_max=attackers_per_honest_neighbourhood(
                np.asarray(adversary.byzantine), *neighbor_tables_for(topo)
            ),
            aggregation="gossip",
        )
    if config.aggregation != "gossip" and config.robust_b > 0:
        k_max = int(topo.degrees.max())
        impl = (
            "halo_gather" if halo_mesh is not None
            else config.resolved_robust_impl(k_max)
        )
        args.update(
            aggregation=f"{config.aggregation}:b={config.robust_b}",
            robust_impl=impl,
            screen_order=screen_order(
                config.aggregation, impl, topo.n, k_max
            ),
            screen_fetch=screen_fetch(
                config.aggregation, impl,
                None if impl == "dense" else neighbor_tables_for(topo)[0],
            ),
            screened_rows=topo.n * (topo.n if impl == "dense" else k_max + 1),
            robust_bytes=0.0,
        )
    return args


def _build_faulty(config, algo, topo, T, *, drop_prob=None, keys=None,
                  timeline=None, horizon=None, halo_mesh=None):
    """Time-varying gossip wiring shared by ``_run`` and ``run_batch``.

    Returns a ``FaultyMixing`` (or None for a static graph) after the
    algorithm-support validation. The keyword overrides are the replica-
    batched hooks: ``drop_prob`` a per-replica (possibly traced) scalar,
    ``keys`` pre-derived per-replica PRNG keys, ``timeline`` a prebuilt
    per-replica ``FaultTimeline`` view, ``horizon`` the timeline length
    (t0 + T for continued batches; defaults to T). ``halo_mesh``: the
    worker-mesh route (``config.worker_mesh >= 2``) — node-process fault
    mixing then runs sharded with per-shard timeline slices
    (``parallel/faults.py::make_halo_faulty_mixing``).
    """
    time_varying = (
        config.edge_drop_prob > 0.0
        or config.straggler_prob > 0.0
        or config.mttf > 0.0
        or config.participation_rate < 1.0
        or config.gossip_schedule != "synchronous"
        or drop_prob is not None
    )
    if not time_varying:
        return None
    if not algo.supports_edge_faults:
        raise ValueError(
            f"time-varying gossip is unsupported for {algo.name!r}: "
            "the step rule is not faithful under per-iteration "
            "graphs — participation sampling included (ADMM pairs "
            "neighbor sums with static degrees; CHOCO's shared "
            "estimate state cannot represent undelivered updates; "
            "EXTRA's fixed-point argument requires a static W)"
        )
    if config.mttf > 0.0 and not algo.supports_churn:
        raise ValueError(
            f"crash-recovery churn is unsupported for {algo.name!r}: "
            "multi-round outages freeze a node's whole state and "
            "may warm-restart its model on rejoin, which only "
            "mix-based rules tolerate (push-sum's (num, w) mass "
            "pair cannot be restarted consistently; EXTRA/ADMM/"
            "CHOCO already reject time-varying graphs) — use "
            "'dsgd' or 'gradient_tracking'"
        )
    if config.gossip_schedule == "round_robin":
        return make_round_robin_mixing(topo)
    return make_faulty_mixing(
        topo,
        config.edge_drop_prob if drop_prob is None else drop_prob,
        config.seed,
        straggler_prob=config.straggler_prob,
        one_peer=config.gossip_schedule == "one_peer",
        burst_len=config.burst_len,
        mttf=config.mttf, mttr=config.mttr,
        rejoin=config.rejoin,
        horizon=T if horizon is None else horizon,
        keys=keys, timeline=timeline,
        participation_rate=config.participation_rate,
        mesh=halo_mesh,
    )


def _bind_byzantine(config, algo, topo, faulty, mix_op, *, clip_tau=None,
                    byz=None, noise_key=None, halo_mesh=None):
    """Byzantine adversary + robust-aggregation wiring shared by ``_run``
    and ``run_batch`` (docs/BYZANTINE.md). Returns ``(adversary, byz_mix,
    activity_t)`` — all None when the config is benign.
    ``activity_t(t, x)`` is the flight recorder's screening-fraction probe
    (the telemetry twin of the robust rule, over the same realized graph
    and the same corrupted stack; None without a robust rule).
    The keyword overrides are the replica-batched hooks: ``clip_tau`` a
    per-replica (possibly traced) radius, ``byz``/``noise_key`` the
    per-replica Byzantine set and large-noise stream.
    """
    byzantine_active = config.attack != "none" or (
        config.aggregation != "gossip" and config.robust_b > 0
    )
    if not byzantine_active:
        return None, None, None
    if not algo.supports_byzantine:
        raise ValueError(
            f"Byzantine injection / robust aggregation is "
            f"unsupported for {algo.name!r}: only step rules whose "
            "updates go through the gossip mix alone compose with "
            "screened aggregation (EXTRA's fixed point needs the "
            "static linear W; ADMM pairs neighbor sums with static "
            "degrees; CHOCO's shared estimates cannot represent "
            "screened-out updates; push-sum's debiasing needs the "
            "column-stochastic mass conservation screening breaks) "
            "— use 'dsgd' or 'gradient_tracking'"
        )
    if byz is None and config.attack != "none":
        byz = byzantine_set(config, topo)
    adversary = make_adversary(
        config.n_workers, config.attack, config.n_byzantine,
        config.attack_scale, config.seed, byz=byz, noise_key=noise_key,
    )
    robust_aggregate_t = None
    activity_src = None
    if config.aggregation != "gossip" and config.robust_b > 0:
        validate_budget(
            int(topo.degrees.min()), config.robust_b,
            config.aggregation,
        )
        ct = config.clip_tau if clip_tau is None else clip_tau
        k_max_topo = int(topo.degrees.max())
        # The screened-rule execution form (docs/BYZANTINE.md
        # "Degree-bounded gather path"): 'gather' screens over the
        # static [N, k_max] neighbor table — O(N·k_max·d·log k_max)
        # — instead of the dense [N, N, d] node-axis sort; 'auto' routes
        # between the two by the measured crossover.
        robust_impl = config.resolved_robust_impl(k_max_topo)
        if topo.is_matrix_free and robust_impl != "gather":
            # Unreachable through config validation (neighbor topologies
            # never have k_max + 1 >= N, so 'auto' resolves to gather and
            # an explicit dense is rejected up front) — guard anyway
            # so a future resolver change fails loudly, not silently
            # through a None adjacency.
            raise ValueError(
                f"matrix-free robust aggregation runs in gather form; "
                f"resolved robust_impl={robust_impl!r} needs the dense "
                "[N, N] adjacency"
            )
        if halo_mesh is not None:
            # Sharded worker mesh (docs/PERF.md §16): screening runs in
            # halo-gather form — corrupted boundary rows travel over the
            # same ppermute exchange as benign gossip, each shard screens
            # its own closed neighborhoods locally. Node-process faults
            # compose through the availability row; config already
            # rejected everything without a sharded form (edge chains,
            # alie, the dense impl, the telemetry activity probe)
            # with the missing piece named.
            if robust_impl != "gather":
                raise ValueError(
                    f"worker_mesh screens in halo-gather form; resolved "
                    f"robust_impl={robust_impl!r} has no sharded twin"
                )
            from distributed_optimization_tpu.parallel.collectives import (
                make_halo_robust_aggregator_t,
            )

            robust_aggregate_t = make_halo_robust_aggregator_t(
                config.aggregation, config.robust_b, topo, halo_mesh,
                ct, faulty.active if faulty is not None else None,
            )
        elif robust_impl == "gather":
            # Native tables for matrix-free topologies (the satellite:
            # Byzantine screening accepted on the neighbor path), derived
            # from the dense adjacency otherwise — identical layout.
            nbr_idx, nbr_mask = neighbor_tables_for(topo)
            gather_agg = make_gather_robust_aggregator(
                config.aggregation, config.robust_b, nbr_idx, ct,
            )
            if faulty is not None:
                live_fn = faulty.make_neighbor_liveness(
                    nbr_idx, nbr_mask
                )
            else:
                static_live = jnp.asarray(
                    nbr_mask, dtype=jnp.float32
                )
                live_fn = lambda t: static_live  # noqa: E731
            robust_aggregate_t = (
                lambda t, v: gather_agg(live_fn(t), v)  # noqa: E731
            )
            gather_act = make_gather_robust_activity(
                config.aggregation, config.robust_b, nbr_idx, ct,
            )
            activity_src = (
                lambda t, v: gather_act(live_fn(t), v)  # noqa: E731
            )
        else:
            dense_agg = make_robust_aggregator(
                config.aggregation, config.robust_b, ct
            )
            if faulty is not None:
                adj_fn = faulty.realized_adjacency
            else:
                static_A = jnp.asarray(
                    topo.adjacency, dtype=jnp.float32
                )
                adj_fn = lambda t: static_A  # noqa: E731
            robust_aggregate_t = (
                lambda t, v: dense_agg(adj_fn(t), v)  # noqa: E731
            )
            dense_act = make_robust_activity(
                config.aggregation, config.robust_b, ct
            )
            activity_src = (
                lambda t, v: dense_act(adj_fn(t), v)  # noqa: E731
            )
    if faulty is not None:
        base_mix_t = faulty.mix
    else:
        base_mix_t = lambda t, v: mix_op.apply(v)  # noqa: E731
    byz_mix = make_byzantine_mixing(
        adversary, base_mix_t, aggregate_t=robust_aggregate_t,
    )
    activity_t = None
    if activity_src is not None:
        # The probe sees exactly what the screening rule sees: the stack
        # AS TRANSMITTED (attack payloads applied) over the realized graph.
        if adversary is not None:
            activity_t = (
                lambda t, v: activity_src(t, adversary.corrupt(t, v))  # noqa: E731
            )
        else:
            activity_t = activity_src
    return adversary, byz_mix, activity_t


def _on_cadence_rows(ys, n_seg_evals, trips_per_eval):
    """On-cadence rows of one segment's stacked outputs, on the host:
    ``({"gap", "cons", "floats"} as recorded, trace buffers, the bytes that
    came down for them)``. The scan
    evaluates every trip, so only every ``trips_per_eval``-th row sits on
    an eval boundary: the others hold real evals the requested cadence
    discards, and the trace selects the same rows. Faults' realized floats
    are summed per eval."""
    sel = slice(trips_per_eval - 1, None, trips_per_eval)
    down = 0

    def host(a):
        nonlocal down
        a = np.asarray(a)
        down += a.nbytes
        return a

    rows = {
        k: host(ys[k][sel]).astype(np.float64, copy=False)
        for k in ("gap", "cons") if k in ys
    }
    if "floats" in ys:
        rows["floats"] = (
            host(ys["floats"]).astype(np.float64, copy=False)
            .reshape(n_seg_evals, trips_per_eval).sum(axis=1)
        )
    trace = {k: host(v)[sel] for k, v in ys.get("trace", {}).items()}
    return rows, trace, down


def _drive_segments(
    make_seg_scan, trips_per_eval, state0, data_args, mesh, config, n_evals,
    spans, *, checkpoint, measure_timestamps, progress_hook, progress_every,
    halt_check, exec_cache, cache_key_fn, measure_compile,
):
    """The driver of the sequential scan: the run as segments of whole
    eval-chunks of ONE program, ``make_seg_scan(size)(state, t0, data)``,
    whose iteration offset ``t0`` is an argument, so one executable serves
    every segment of its size and a run split at eval boundaries is bitwise
    the unsplit run (tests/test_segments.py).

    A segment is as long as what the caller asked for lets it be: one eval
    under ``measure_timestamps`` (a real ``perf_counter`` stamp per eval),
    else ``checkpoint.every_evals``, else ``progress_every`` when a
    heartbeat is wanted, else the whole run. Only a segment's end is a
    real stamp; the evals inside it are spread evenly. The host looks at a
    boundary only where someone reads it: the rows come down for a
    heartbeat (``progress_hook(done_evals, gap_list, cons_list, elapsed)``
    every ``progress_every`` evals of this call and at the end) or a save
    (every ``checkpoint.every_evals`` and at the end), and otherwise stay
    on the device until the run is over. ``halt_check()`` is asked at
    every boundary; on a halt the executed prefix is the full run's.

    Executables are looked up (``exec_cache``, ``cache_key_fn(segment=size)``)
    or compiled, one per distinct size, before the clock starts. The
    ``scan`` span opens once the inputs are resident and closes on the
    last segment's final state: its duration less the saves inside it is
    ``run_seconds`` AND the last stamp, one clock read once; the last
    boundary's rows, heartbeat and save fall in ``harvest``. Saves are
    checkpoint I/O, not optimization time, and are subtracted from every
    later stamp.

    Returns (final_state, gap_hist, cons_hist, time_hist, realized_floats,
    executed_iters, compile_seconds, run_seconds, trace, cost).
    ``executed_iters`` counts only iterations run in THIS process, so a
    resumed run reports honest throughput; ``time_hist`` is cumulative
    across installments (the restored stamps are its offset);
    ``trace``/``cost`` are the flight-recorder buffers and XLA cost
    analysis (None when ``config.telemetry`` is off).
    """
    eval_every = config.eval_every
    state = state0
    # Per-eval rows as Python floats, in the order a checkpoint holds them.
    hist = {"gap": [], "cons": [], "floats": [], "time": []}
    trace_lists: dict[str, list] = {}
    start_chunk = 0
    ckptr = None
    if checkpoint is not None:
        from distributed_optimization_tpu.utils.checkpoint import (
            RunCheckpointer,
        )

        ckptr = RunCheckpointer(checkpoint)
        restored = None
        if checkpoint.resume:
            ckptr.validate_or_record_config(config)
            restored = ckptr.restore()
        else:
            # Explicit fresh start: clear stale chunks (they would poison a
            # later resume) and rewrite the sidecar instead of validating.
            ckptr.reset(config)
        if restored is not None:
            state_np, *rows, start_chunk = restored
            if start_chunk > n_evals:
                raise ValueError(
                    f"checkpoint at chunk {start_chunk} exceeds this run's "
                    f"horizon of {n_evals} chunks (n_iterations shrank below "
                    "the checkpointed progress)"
                )
            state = shard_over_workers(
                mesh, _restored_state(state_np, state0)
            )
            hist = {k: [float(v) for v in r] for k, r in zip(hist, rows)}
    # Cumulative-time offset from previous installments of a resumed run.
    time_offset = hist["time"][-1] if hist["time"] else 0.0

    remaining = n_evals - start_chunk
    if measure_timestamps:
        seg_evals = 1
    elif checkpoint is not None:
        seg_evals = checkpoint.every_evals
    elif progress_hook is not None:
        seg_evals = int(progress_every)
    else:
        seg_evals = remaining
    seg_evals = max(min(seg_evals, remaining), 1)
    # (size, t0) of every segment; the offsets go up before the clock.
    segments = [
        (
            min(seg_evals, n_evals - first),
            replicate(mesh, jnp.asarray(first * eval_every, dtype=jnp.int32)),
        )
        for first in range(start_chunk, n_evals, seg_evals)
    ]

    # AOT, so that compile time and steady state are separable: at most two
    # sizes, the full segment and a trailing remainder.
    compiled_by_size = {}
    cost = None
    cold_compile = 0.0
    spans.note_root(cache="off" if exec_cache is None else "hit")
    with jax.default_matmul_precision(config.matmul_precision):
        for size in sorted({size for size, _ in segments}):
            key = cached = None
            if exec_cache is not None:
                spans.enter("cache_lookup")
                key = cache_key_fn(segment=size)
                cached = exec_cache.get(key)
            if cached is not None:
                compiled_by_size[size] = cached.executable
                if config.telemetry and cost is None:
                    cost = cached.cost
                continue
            spans.enter("compile")
            if exec_cache is not None:
                spans.note_root(cache="miss")
            t_cold = time.perf_counter()
            lowered = jax.jit(make_seg_scan(size)).lower(
                state, segments[0][1], data_args
            )
            size_cost = cost_from_lowered(lowered) if config.telemetry else None
            if cost is None:
                cost = size_cost
            compiled_by_size[size] = device_scopes.compile_keeping_scopes(
                lowered
            )
            this_cold = time.perf_counter() - t_cold
            cold_compile += this_cold
            if exec_cache is not None:
                exec_cache.put(
                    key, compiled_by_size[size], cost=size_cost,
                    compile_seconds=this_cold,
                )
    compile_seconds = cold_compile if measure_compile else 0.0
    if segments:
        # Which executable this call ran, for whoever asks later what its
        # instructions belong to (observability/device_scopes.py).
        spans.note_root(**device_scopes.note_program(
            compiled_by_size[segments[0][0]],
            held_elsewhere=exec_cache is not None,
        ))

    pending = []  # (ys, size) of the segments whose rows are on the device
    # What ``fetch_rows`` brought down; once ``harvest`` has begun, the
    # arguments of its ``rows`` part.
    counted = {"bytes": 0}
    rows_part = contextlib.ExitStack()

    def fetch_rows():
        for ys, size in pending:
            rows, trace_seg, down = _on_cadence_rows(ys, size, trips_per_eval)
            counted["bytes"] += down
            for k, v in rows.items():
                hist[k].extend(v.tolist())
            for k, v in trace_seg.items():
                trace_lists.setdefault(k, []).append(v)
        pending.clear()

    def enter_harvest():
        """``harvest`` and its ``rows`` part, open until the driver
        returns: the last boundary's rows, heartbeat and save."""
        spans.enter("harvest")
        counted["bytes"] = 0  # what came down inside the scan was the scan's
        rows_part.enter_context(spans.part("rows"))["args"] = counted

    # The shards' copy drains here, after all host preparation and outside
    # the scan's clock: the program could not start before its inputs were
    # resident anyway, but ``run_seconds`` (and so ``iters_per_second``)
    # does not count the copy.
    spans.enter("upload_wait")
    jax.block_until_ready((state, data_args))
    scan = spans.enter("scan")
    save_seconds = 0.0  # orbax saves inside the scan span
    run_seconds = 0.0
    done = start_chunk
    last = False
    with rows_part:  # closes the ``rows`` part, on an exception too
        for size, t0 in segments:
            state, ys = compiled_by_size[size](state, t0, data_args)
            state = jax.block_until_ready(state)
            pending.append((ys, size))
            done += size
            last = done == n_evals
            prev = time_offset + run_seconds
            if last:
                enter_harvest()
                run_seconds = scan["duration"] - save_seconds
            else:
                run_seconds = (
                    time.perf_counter() - scan["start"] - save_seconds
                )
            stamp = time_offset + run_seconds
            stamps = np.linspace(prev + (stamp - prev) / size, stamp, size)
            stamps[-1] = stamp
            hist["time"].extend(stamps.tolist())
            evals_here = done - start_chunk
            beat = progress_hook is not None and (
                last or evals_here % progress_every == 0
            )
            save = ckptr is not None and (
                last or evals_here % checkpoint.every_evals == 0
            )
            if beat or save:
                fetch_rows()
            if beat:
                progress_hook(done, hist["gap"], hist["cons"], stamp)
            if save:
                t_save = time.perf_counter()
                ckptr.save(
                    done, _flat_rows(_fetch_to_host(state)), hist["gap"],
                    hist["cons"], hist["floats"], hist["time"],
                )
                save_seconds += time.perf_counter() - t_save
            if halt_check is not None and halt_check():
                # Early-halt policy (ISSUE-13): a fatal anomaly fired on this
                # boundary's heartbeat. The executed prefix is the full run's
                # prefix; the remaining segments never execute.
                break
        if not last:
            enter_harvest()
        fetch_rows()
        return (
            state,
            np.asarray(hist["gap"], dtype=np.float64) if hist["gap"] else None,
            np.asarray(hist["cons"], dtype=np.float64)
            if hist["cons"] else None,
            np.asarray(hist["time"], dtype=np.float64),
            float(np.sum(hist["floats"])) if hist["floats"] else None,
            (done - start_chunk) * eval_every,
            compile_seconds,
            run_seconds,
            {k: np.concatenate(v, axis=0) for k, v in trace_lists.items()}
            or None,
            cost,
        )


class _RunSpans:
    """The spans of one ``_run`` call (docs/OBSERVABILITY.md, "Span
    tracing"): the ``dopt.run`` root and, under it, one open child at a
    time — ``enter(name)`` closes the open child and opens
    ``dopt.run.<name>``, so the children are disjoint, in order, and leave
    the root next to no time of its own. None aggregates into the tracer's
    flat ``phases`` table."""

    def __init__(self, tracer, root: dict):
        self._tracer = tracer
        self._root = root
        self._open = None
        self._event = None

    def enter(self, name: str, **args) -> dict:
        """Open ``dopt.run.<name>`` (closing the open child); returns its
        event, whose ``duration`` is set once the next ``enter`` or
        ``close`` has closed it."""
        self.close()
        self._open = self._tracer.span(
            "dopt.run." + name, aggregate=False, **args
        )
        self._event = self._open.__enter__()
        return self._event

    def close(self) -> None:
        if self._open is not None:
            span, self._open = self._open, None
            span.__exit__(None, None, None)

    @contextlib.contextmanager
    def part(self, name: str, **args) -> Iterator[dict]:
        """A named part of the open child: ``dopt.run.<child>.<name>``
        under it (the tracer's thread stack makes the child its parent).
        Parts of one child are disjoint and in order, and what they leave
        is the child's own time; yields the part's event, whose ``args``
        take counts until it closes."""
        with self._tracer.span(
            f"{self._event['name']}.{name}", aggregate=False, **args
        ) as event:
            event.setdefault("args", {})
            yield event

    def note(self, **args) -> None:
        """Add arguments to the open child: counts known only after the
        work it names."""
        self._event.setdefault("args", {}).update(args)

    def note_root(self, **args) -> None:
        self._root.setdefault("args", {}).update(args)


@contextlib.contextmanager
def _run_spans():
    """The root span of one ``_run`` call on ``current_tracer()``: under the
    caller's open span if the caller activated a tracer of its own
    (``Simulator.run_one``, a serving plan), else a root of the process
    tracer."""
    tracer = current_tracer()
    with tracer.span("dopt.run", aggregate=False) as root:
        spans = _RunSpans(tracer, root)
        try:
            yield spans
        finally:
            spans.close()


def run(
    config,
    dataset: HostDataset,
    f_opt: float,
    *,
    mesh=None,
    use_mesh: bool = True,
    batch_schedule: Optional[np.ndarray] = None,
    collect_metrics: bool = True,
    measure_compile: bool = True,
    checkpoint=None,
    measure_timestamps: Optional[bool] = None,
    return_state: bool = False,
    executable_cache=None,
    progress_cb=None,
    progress_every: int = 1,
    monitors=None,
) -> BackendRunResult:
    """Run one experiment on the JAX backend; returns histories + final models.

    A synchronous run is ONE device program, the flat scan with the eval
    inline (``_run``), driven as segments of whole eval-chunks
    (``_drive_segments``). The keywords below choose where the segments
    end, never another program: however a run is split, its trajectory and
    final models are bitwise the unsplit run's.

    ``measure_timestamps=True`` makes every segment one eval, so the
    history carries a real ``perf_counter`` stamp per eval
    (``time_measured=True``) at the price of one host round-trip per
    ``eval_every`` iterations. The default (``None`` == ``False``) spreads
    each segment's time evenly over its evals (``time_measured=False``).

    ``checkpoint`` (``utils.checkpoint.CheckpointOptions``): segments of
    ``every_evals`` eval-chunks with an orbax save after each and after
    the last; with ``resume`` the run continues from the latest intact
    chunk. Under ``measure_timestamps`` the segments stay one eval and the
    saves keep their cadence.

    ``progress_cb`` (the live observatory): a host callback receiving one
    ``observability.progress.ProgressEvent`` every ``progress_every``
    eval-chunks and at the end; without a checkpoint that is the segment
    size. ``None`` (default) is the whole run in one segment. The async
    event loop honours the same cadence.

    ``monitors``: an ``observability.monitors.MonitorBank`` observing the
    run's heartbeats online (it rides ``progress_cb``'s segments).
    Detectors fire structured anomalies into the bank, and under
    ``halt_on='fatal'`` a fatal anomaly stops the run at the next segment
    boundary with the executed prefix returned as a partial result
    (``monitors.halted_at`` records where). Trace-derived detectors are
    fed the flight-recorder buffers after the run when ``config.telemetry``
    is on.

    ``executable_cache`` controls AOT compile reuse (docs/SERVING.md): the
    default ``None`` consults the process-wide
    ``serving.cache.process_executable_cache()`` — a repeated identical run
    in one process re-executes the cached compiled program instead of
    re-tracing and re-compiling it (bitwise-identical results; the cache
    key pins the full config, f*, data/mesh signatures, the jax
    environment and the segment's size, so anything that could change the
    program misses). ``False`` forces a cold compile (benches that MEASURE
    compile cost use this); an ``ExecutableCache`` instance scopes reuse
    explicitly (the serving layer passes its own). One-shot and heartbeat
    runs consult the cache — a heartbeat run whose one segment is the whole
    run hits the one-shot run's executable; checkpointed and
    ``measure_timestamps`` runs always compile. On a cache hit
    ``history.compile_seconds`` is 0.0.

    A float64 config runs under a scoped ``enable_x64`` — without it jax
    silently truncates every array to float32, defeating the fidelity dtype.
    """
    from distributed_optimization_tpu.backends.base import x64_scope

    if config.execution == "async":
        # Event-driven asynchronous gossip (docs/ASYNC.md): a scan over
        # the precomputed event schedule instead of rounds. The
        # round-based execution knobs below have no event form — reject
        # loudly rather than silently ignoring them.
        from distributed_optimization_tpu.backends import async_scan

        if measure_timestamps:
            raise ValueError(
                "execution='async' reports the event schedule's simulated "
                "VIRTUAL clock (telemetry.async health block), not "
                "host-driven per-eval timestamps"
            )
        if mesh is not None:
            raise ValueError(
                "execution='async' runs unsharded: events are a totally "
                "ordered sequential schedule, which a worker mesh cannot "
                "partition"
            )
        return async_scan.run_async(
            config, dataset, f_opt, batch_schedule=batch_schedule,
            collect_metrics=collect_metrics,
            measure_compile=measure_compile, return_state=return_state,
            executable_cache=executable_cache,
            progress_cb=progress_cb, progress_every=progress_every,
            monitors=monitors, checkpoint=checkpoint,
        )
    with x64_scope(config), _run_spans() as spans:
        return _run(
            config, dataset, f_opt, spans, mesh=mesh, use_mesh=use_mesh,
            batch_schedule=batch_schedule, collect_metrics=collect_metrics,
            measure_compile=measure_compile, checkpoint=checkpoint,
            measure_timestamps=measure_timestamps,
            return_state=return_state,
            executable_cache=executable_cache,
            progress_cb=progress_cb, progress_every=progress_every,
            monitors=monitors,
        )


def _run(
    config,
    dataset: HostDataset,
    f_opt: float,
    spans: _RunSpans,
    *,
    mesh=None,
    use_mesh: bool = True,
    batch_schedule: Optional[np.ndarray] = None,
    collect_metrics: bool = True,
    measure_compile: bool = True,
    checkpoint=None,
    measure_timestamps: Optional[bool] = None,
    return_state: bool = False,
    executable_cache=None,
    progress_cb=None,
    progress_every: int = 1,
    monitors=None,
) -> BackendRunResult:
    """Backend implementation (see ``run``).

    ``mesh``: an explicit ``jax.sharding.Mesh`` (1-D, axis 'workers');
    ``use_mesh=True`` builds one over all visible devices that evenly divide
    N. ``batch_schedule [T, N, b]`` injects fixed batch indices (equivalence
    testing vs the numpy oracle — SURVEY.md §4c). ``spans``: the call's
    ``dopt.run`` root (``_run_spans``); each stretch of this function runs
    under the child span that names it.
    """
    spans.enter("prepare")
    if config.telemetry and checkpoint is not None:
        raise ValueError(
            "telemetry trace buffers are not checkpointed: a resumed run "
            "would silently emit a truncated trace — record telemetry "
            "without checkpointing, or checkpoint without telemetry"
        )
    if progress_every < 1:
        raise ValueError(
            f"progress_every must be >= 1 eval-chunks, got {progress_every}"
        )
    # Monitors ride the progress machinery (ISSUE-13): the bank's observe
    # joins the callback chain, and under halt_on='fatal' the segmented
    # loops consult should_halt() at every chunk boundary.
    progress_emit = _progress_emitter(
        config, _fanout_progress(progress_cb, monitors)
    )
    halt_check = (
        monitors.should_halt
        if monitors is not None and monitors.halt_on != "never" else None
    )
    algo = get_algorithm(config.algorithm)
    problem = get_problem(
        config.problem_type, huber_delta=config.huber_delta,
        n_classes=config.n_classes,
    )
    reg = config.reg_param
    T = config.n_iterations
    n = config.n_workers

    spans.enter("stack_shards")
    device_data = stack_shards(dataset, dtype=np.dtype(config.dtype))
    spans.note(bytes=device_data.X.nbytes + device_data.y.nbytes)
    spans.enter("prepare")
    # One worker's parameter: (n_features,) for the scalar GLMs,
    # (n_features, K) for softmax. The scan carries every model-shaped leaf
    # as [n, *param_shape] — the shape the gradient kernels read and write —
    # and the models are flattened once, on the host, at harvest; d_model,
    # the flat length, is what the boundaries speak (gossip payload
    # accounting, the compressed halo's leaves, final_models).
    param_shape = tuple(problem.param_shape(device_data.n_features))
    d_model = problem.param_dim(device_data.n_features)
    carry_shape = (n,) + param_shape
    spans.note_root(carry="x".join(map(str, carry_shape)))

    # --- topology & collectives (centralized needs none) ---
    halo_mesh = None
    compressed_mix = None
    if algo.is_decentralized:
        # ``topology``: the graph's making, or its finding in the process's
        # cache (``cache`` = ``miss`` / ``hit``): a drawn graph at scale is
        # seconds of host code, paid once a structural identity.
        spans.enter("topology")
        topo, topo_hit = cached_topology(
            config.topology, n, erdos_renyi_p=config.erdos_renyi_p,
            seed=config.resolved_topology_seed(),
            impl=config.resolved_topology_impl(),
            sampler=config.resolved_topology_sampler(),
        )
        # The power iteration of a drawn matrix-free graph is part of its
        # making: here, once, not in every later ``prepare``.
        spectral_gap = topo.spectral_gap
        spans.note(cache="hit" if topo_hit else "miss")
        if topo.grid_shape is not None:
            spans.note_root(grid_shape="x".join(map(str, topo.grid_shape)))
        spans.enter("prepare")
        if config.worker_mesh >= 2:
            # Sharded worker mesh (ISSUE-11 tentpole, docs/PERF.md §16):
            # exactly config.worker_mesh devices, contiguous row blocks.
            # The halo-exchange gather path IS the mixing operator; state,
            # data, and timeline columns shard over the same mesh below.
            if mesh is not None:
                if (
                    WORKER_AXIS not in mesh.shape
                    or mesh.shape[WORKER_AXIS] != config.worker_mesh
                    or mesh.size != config.worker_mesh
                ):
                    raise ValueError(
                        f"worker_mesh={config.worker_mesh} needs a 1-D "
                        f"mesh with a {WORKER_AXIS!r} axis of exactly that "
                        f"size (the halo plan, timeline slices and ICI "
                        f"accounting are all built for that P); got "
                        f"axes {dict(mesh.shape)}"
                    )
            else:
                from distributed_optimization_tpu.parallel.mesh import (
                    make_sized_worker_mesh,
                )

                mesh = make_sized_worker_mesh(config.worker_mesh)
            halo_mesh = mesh
            from distributed_optimization_tpu.parallel.collectives import (
                make_halo_compressed_mixing_op,
                make_halo_mixing_op,
            )

            # ``halo_plan``: the static plan of the exchange (which rows
            # each shard sends on which rotation) and the per-shard tables
            # over the halo-extended block, made on the host and put on a
            # device; a ring's two shifts plan nothing
            # (docs/OBSERVABILITY.md).
            spans.enter("halo_plan")
            mix_op = make_halo_mixing_op(topo, mesh, dtype=device_data.X.dtype)
            if config.compression != "none":
                # Compressed halo exchange (ISSUE-18): the error-feedback
                # algorithms route their wire rounds through this instead
                # of mix_op.apply — only q boundary rows cross devices,
                # with the receiver-side estimate copies persisted in the
                # *_halo state leaves seeded below.
                compressed_mix = make_halo_compressed_mixing_op(
                    topo, mesh, dtype=device_data.X.dtype
                )
            spans.note(form=mix_op.impl)
            spans.enter("prepare")
        else:
            if (
                mesh is None and use_mesh and len(jax.devices()) > 1
                and not topo.is_matrix_free
            ):
                # The GSPMD grid stencil blocks grid ROWS over devices, so
                # the mesh size must divide the row count, not just N (else
                # auto lands on a device count the row reshape cannot
                # split). The matrix-free path runs unsharded unless
                # worker_mesh asks for the halo route above: gather indices
                # under plain GSPMD would all-gather.
                if topo.grid_shape is not None and config.mixing_impl in (
                    "stencil", "auto"
                ):
                    mesh = make_worker_mesh(topo.grid_shape[0])
                else:
                    mesh = make_worker_mesh(n)
            # No platform-specific resolution: make_mixing_op resolves 'auto'.
            mix_op = make_mixing_op(
                topo, impl=config.mixing_impl, dtype=device_data.X.dtype
            )
        degrees = jnp.asarray(
            topo.degrees, dtype=device_data.X.dtype
        ).reshape((n,) + (1,) * len(param_shape))
        # Per-edge payload: d · gossip_rounds for full-vector exchange, or the
        # algorithm's override (compressed gossip transmits less).
        if algo.comm_payload is not None:
            edge_payload = algo.comm_payload(config, d_model)
            floats_per_iter = topo.floats_per_iteration * edge_payload
        else:
            edge_payload = d_model * algo.gossip_rounds
            floats_per_iter = decentralized_floats_per_iteration(
                topo, d_model, algo.gossip_rounds
            )
        time_varying = (
            config.edge_drop_prob > 0.0
            or config.straggler_prob > 0.0
            or config.mttf > 0.0
            or config.participation_rate < 1.0
            or config.gossip_schedule != "synchronous"
        )
        byzantine_active = config.attack != "none" or (
            config.aggregation != "gossip" and config.robust_b > 0
        )
        # Time-varying gossip and the Byzantine adversary + robust
        # aggregation composition (docs/BYZANTINE.md) — wiring shared with
        # the replica-batched path (``_build_faulty``/``_bind_byzantine``).
        # Byzantine is active when there is an attack to simulate OR a
        # robust rule with a positive budget to defend with; robust_b == 0
        # keeps the plain gossip path bitwise (a robust rule degrades to
        # MH gossip at zero budget by definition).
        faulty, fault_tables = None, None
        if time_varying:
            # ``faults``: whatever this call spends making, fetching or
            # placing fault realizations and their tables
            # (docs/OBSERVABILITY.md).
            spans.enter("faults")
            faulty = _build_faulty(
                config, algo, topo, T, halo_mesh=halo_mesh
            )
            if faulty.tables is not None:
                # Where the leaves stayed on the device the chains' scans
                # are still running: the span holds them.
                fault_tables = jax.block_until_ready(
                    replicate(mesh, faulty.tables)
                )
            spans.note_root(**_fault_root_args(config, faulty, fault_tables))
            spans.enter("prepare")
        mixing_tables = None
        if halo_mesh is None:
            # How the static graph mixes: the mechanism's engagement
            # counter, as the mesh branch below says it for the halo forms.
            spans.note_root(mixing=mix_op.impl)
            if faulty is None and mix_op.tables is not None:
                # The gather form's tables are ARGUMENTS of the scan
                # (``data['mixing']``), never constants of it; under faults
                # the fault layer mixes, over tables of its own.
                mixing_tables = replicate(mesh, mix_op.tables)
                spans.note_root(**_gather_root_args(topo, mixing_tables))
        if byzantine_active:
            # ``adversary``: placing the attackers on the graph and binding
            # the screening rule (docs/OBSERVABILITY.md).
            spans.enter("adversary")
        adversary, byz_mix, robust_activity = _bind_byzantine(
            config, algo, topo, faulty, mix_op, halo_mesh=halo_mesh,
        )
        if byzantine_active:
            spans.note_root(
                **_byzantine_root_args(config, topo, adversary, halo_mesh)
            )
            spans.enter("prepare")
        # == adjacency.sum() for both orientations; degree-based so the
        # matrix-free representation needs no [N, N] array.
        static_degree_sum = float(np.asarray(topo.degrees).sum())
        if halo_mesh is not None:
            # Real-collective traffic accounting (ISSUE-11): the halo
            # plan is static, so bytes over ICI per device per round are
            # exact — surfaced as per-device gauges in the PR-10 metrics
            # registry (scraped at /metrics). One pricing source:
            # ``telemetry.ici_summary`` (also the report's bytes-over-ICI
            # line), fed the already-built topology per its one-build
            # convention, so /metrics and the report can never disagree.
            from distributed_optimization_tpu.observability.metrics_registry import (  # noqa: E501
                metrics_registry,
            )
            from distributed_optimization_tpu.telemetry import ici_summary

            _ici = ici_summary(
                config, topo=topo, d_features=device_data.n_features
            )
            _reg = metrics_registry()
            _g = _reg.gauge(
                "dopt_worker_mesh_ici_bytes_per_round",
                "Halo-exchange bytes each device ships per gossip round "
                "(static plan: rotation-padded wire rows x per-config "
                "row payload)",
            )
            _g.reset()  # a smaller mesh must not leave stale devices
            for _p, _bytes in enumerate(
                _ici["bytes_per_device_per_round"]
            ):
                _g.set(float(_bytes), device=str(_p))
            _reg.gauge(
                "dopt_worker_mesh_devices",
                "Worker-mesh shard count of the most recent sharded run",
            ).set(float(config.worker_mesh))
            _halo_g = _reg.gauge(
                "dopt_worker_mesh_halo_rows",
                "Boundary rows each device fetches per gossip round",
            )
            _halo_g.reset()
            for _p, _rows in enumerate(_ici["halo_rows_per_device"]):
                _halo_g.set(float(_rows), device=str(_p))
            # The same numbers on the call's root span, the fullest
            # device's: what the benchmark's readers see (ISSUE 30).
            spans.note_root(
                mesh=f"{config.worker_mesh}x{n // config.worker_mesh}",
                mixing=mix_op.impl,
                halo_rows=int(max(_ici["halo_rows_per_device"])),
                ici_bytes_per_round=float(
                    max(_ici["bytes_per_device_per_round"])
                ),
            )
            if faulty is None and mix_op.tables is not None:
                # The halo gather says what the one-chip gather says
                # (ISSUE 52); under faults the fault layer mixes, over
                # tables of its own.
                spans.note_root(**_halo_gather_root_args(mix_op))
    else:
        if (
            config.edge_drop_prob > 0.0
            or config.straggler_prob > 0.0
            or config.mttf > 0.0
            or config.gossip_schedule != "synchronous"
            or config.attack != "none"
            or (config.aggregation != "gossip" and config.robust_b > 0)
        ):
            raise ValueError(
                "fault injection / matching-based gossip / Byzantine "
                "injection model peer exchanges and apply only to "
                "decentralized algorithms; the centralized pattern has no "
                "peer edges"
            )
        byzantine_active = False
        adversary = None
        byz_mix = None
        robust_activity = None
        static_degree_sum = 0.0
        topo = None
        mix_op = None
        mixing_tables = None
        faulty = None
        fault_tables = None
        edge_payload = None
        degrees = jnp.zeros(
            (n,) + (1,) * len(param_shape), dtype=device_data.X.dtype
        )
        floats_per_iter = centralized_floats_per_iteration(n, d_model)
        spectral_gap = None
        if mesh is None and use_mesh and len(jax.devices()) > 1:
            mesh = make_worker_mesh(n)

    # What ran and what one edge carries an iteration: the compressor with
    # its count (kept coordinates, or qsgd bits) over the row it works on,
    # and how it picks what it keeps.
    spans.note_root(
        algorithm=config.algorithm,
        # Model-sized exchanges an iteration (the rule's own count; the
        # parameter-server pattern has no peer rounds).
        gossip_rounds=algo.gossip_rounds if algo.is_decentralized else 0,
        compress=(
            "none" if config.compression == "none"
            else f"{config.compression}:{config.compression_k}/{d_model}"
        ),
        select=selection_label(config.compression, device_data.X.dtype),
        wire_floats_per_edge=float(edge_payload or 0.0),
    )

    # --- device placement (sharded over the worker axis where it matters) ---
    # ``upload`` is the enqueue; the wait for the copy is ``upload_wait``,
    # directly before the scan's clock starts.
    spans.enter(
        "upload",
        bytes=device_data.X.nbytes + device_data.y.nbytes
        + device_data.n_valid.nbytes,
    )
    X, placement, waits = place_shards(mesh, device_data.X)
    spans.note(**waits)  # ``blocks``, ``wait_s``, ...: a flat placement's
    spans.note_root(stack=device_data.stacked_by, placement=placement)
    # Host arrays, so under a mesh each device's rows go to that device
    # and nothing is staged whole on the first (ISSUE 30).
    y = shard_over_workers(mesh, device_data.y)
    n_valid = shard_over_workers(mesh, device_data.n_valid)
    spans.enter("prepare")
    x0 = zeros_over_workers(mesh, carry_shape, device_data.X.dtype)
    state0 = algo.init(
        x0, config,
        neighbor_sum=mix_op.neighbor_sum if mix_op is not None else None,
    )
    if compressed_mix is not None:
        # Seed the persistent receiver-side halo copies (one per estimate
        # leaf; [P·(h_max+1), d] row-sharded, zeros — agreeing with the
        # zero xhat memories, which is what the bitwise induction vs the
        # unsharded exchange starts from). A resumed state that already
        # carries the leaves passes through untouched.
        for _leaf in ("xhat", "yhat"):
            if _leaf in state0 and f"{_leaf}_halo" not in state0:
                state0[f"{_leaf}_halo"] = zeros_over_workers(
                    mesh, (compressed_mix.halo_rows, d_model),
                    device_data.X.dtype,
                )
    # What the scan carries from iteration to iteration, as the device
    # holds it: the models, and whatever else the rule keeps (a tracker and
    # the last gradients, CHOCO's copies, a halo's receiver side).
    spans.note_root(
        state_leaves=len(jax.tree.leaves(state0)),
        state_bytes=_device_bytes(state0),
    )
    key = jax.random.key(config.seed)

    schedule = None
    if batch_schedule is not None:
        spans.enter("upload", bytes=4 * int(np.size(batch_schedule)))
        schedule = replicate(mesh, jnp.asarray(batch_schedule, dtype=jnp.int32))
        spans.enter("prepare")

    full_objective = make_full_objective_fn(problem, reg)
    eta_fn = _make_eta_fn(config)
    batch_size = config.local_batch_size
    sampling_impl = config.resolved_sampling_impl(
        jax.devices()[0].platform, device_data.X.shape[1]
    )
    if (
        config.sampling_impl == "dense"
        and device_data.X.shape[1] > DENSE_SAMPLING_WARN_ROWS
    ):
        import warnings

        # The auto rule gates dense to L <= 64 and the measured crossover to
        # gather is around L ~ 250 (docs/perf/breakdown.json); an explicit
        # force beyond that silently pays the [L, L] ranking matrix.
        warnings.warn(
            f"--sampling-impl dense builds an [L, L] per-worker ranking "
            f"matrix every iteration (O(N·L²) work/memory); at L = "
            f"{device_data.X.shape[1]} rows the measured crossover favors "
            "'gather' — forcing dense anyway as requested",
            stacklevel=2,
        )

    # How an iteration's batch is had (the sampler's engagement counter) and
    # the rows it holds, all workers together: ``full`` (b >= L: the shard,
    # nothing drawn), ``dense`` (a ranking over the shard, weights on every
    # row), ``gather`` (the b rows picked by a counted threshold over the
    # uniforms' bits, then fetched), ``scheduled`` (injected indices,
    # fetched).
    batch_rows = (
        schedule.shape[-1] if schedule is not None
        else min(batch_size, device_data.X.shape[1])
    )
    sampler = {
        "sampling": (
            "scheduled" if schedule is not None
            else "full" if batch_size >= device_data.X.shape[1]
            else sampling_impl
        ),
        "batch_rows": n * int(batch_rows),
    }
    if sampler["sampling"] == "gather":
        # A draw's gathers: one where the targets ride in the rows. Its
        # selection is a ``random_k`` over a worker's L uniforms; ``select``
        # stays the compressor's where there is one.
        sampler["batch_gathers"] = (
            1 if targets_ride(device_data.X.dtype, device_data.y.dtype) else 2
        )
        if config.compression == "none":
            sampler["select"] = selection_label(
                "random_k", device_data.X.dtype
            )
    spans.note_root(**sampler)

    # Sharded arrays are threaded through jit as ARGUMENTS, never captured:
    # a traced function that closes over an array spanning non-addressable
    # devices raises in multi-process runs (caught by
    # examples/multihost_smoke.py).
    data_args = {"X": X, "y": y, "n_valid": n_valid}
    if schedule is not None:
        data_args["schedule"] = schedule
    if fault_tables is not None:
        # The gather fault layer's tables (and a persistent process's
        # timeline) too: a closed-over [horizon, N] leaf is a constant of
        # the executable, in the device's tiles (ROADMAP A9).
        data_args["faults"] = fault_tables
    if mixing_tables is not None:
        data_args["mixing"] = mixing_tables

    track_consensus = (
        collect_metrics and algo.is_decentralized and config.record_consensus
    )
    eval_every = config.eval_every
    scan_unroll = config.resolved_scan_unroll(jax.devices()[0].platform)

    honest_w = None
    if adversary is not None:
        honest_w = jnp.asarray(adversary.honest.astype(np.float32))

    # What the eval's pass over the shards leaves the next trip's first
    # gradient (the engagement counter of both mechanisms).
    carried = _forward_is_carried(
        algo, problem, config, rows=device_data.X.shape[1],
        batch_size=batch_size, sampling_impl=sampling_impl,
        scheduled=schedule is not None, collect_metrics=collect_metrics,
    )
    forward = (
        "fused" if _visit_is_fused(carried, X)
        else "carried" if carried else "recomputed"
    )
    pieces = _StepPieces(
        algo=algo, problem=problem, reg=reg, config=config,
        batch_size=batch_size, sampling_impl=sampling_impl, key=key,
        eta_fn=eta_fn, degrees=degrees, mix_op=mix_op, faulty=faulty,
        byz_mix=byz_mix, adversary=adversary, honest_w=honest_w,
        full_objective=full_objective,
        f_opt=f_opt, collect_metrics=collect_metrics,
        track_consensus=track_consensus, edge_payload=edge_payload,
        telemetry=config.telemetry, robust_activity=robust_activity,
        static_degree_sum=static_degree_sum,
        compressed_mix=compressed_mix,
        forward=forward, mesh=mesh,
    )
    spans.note_root(forward=forward)
    if config.local_steps > 1:
        spans.note_root(**_local_root_args(config.local_steps, forward))
    if (
        forward != "recomputed" and faulty is not None
        and faulty.rejoin_restart is not None
    ):
        # The product is of the models as the NEXT round's restart leaves
        # them (``_Forward.of``); absent on every call without a restart.
        spans.note_root(forward_of="restarted")

    n_evals = T // eval_every
    measure_timestamps = bool(measure_timestamps)

    # The device program, the only one the sequential path has: ONE flat
    # scan over micro-chunks of ``micro`` Python-unrolled steps with the
    # metric eval computed INLINE every trip — never a scan nested inside a
    # scan, and no lax.cond round the eval. Non-flat control flow in the hot
    # loop body defeats XLA:TPU's pipelining across iterations: a scan of
    # steps nested under a loop of chunks ran identical fusions ~6.4x
    # slower per execution, and a cond-guarded eval re-serialized the loop
    # harder still (docs/PERF.md "root cause"). The inline eval feeds only
    # the scan's stacked outputs, never the carry, so it overlaps the next
    # steps; the off-cadence rows are discarded on the host
    # (``_on_cadence_rows``). ``micro`` is the largest divisor of
    # eval_every within the unroll budget, so some trip lands exactly on
    # every eval boundary; at eval_every=1 this is the plain step scan.
    micro, trips_per_eval, flat_unroll = _flat_scan_cadence(
        scan_unroll, eval_every
    )

    def make_seg_scan(n_seg_evals):
        """``n_seg_evals`` eval-chunks of the flat scan, starting at the
        iteration ``t0``: an argument, so one executable serves every
        segment of this size, the whole run (``n_evals`` at 0) included."""
        n_trips_seg = n_seg_evals * trips_per_eval

        def seg_scan(state_init, t0, data):
            step, eval_metrics, floats_for, init_forward = _make_step_eval(
                pieces, data
            )

            # The carry is (state, fwd): the forward product of the carried
            # models (their margins X·x, or the next gradient itself; a
            # ``_Forward``, which under ``neighbor_restart`` also holds the
            # restarted models it is of) where one is carried, else None (no
            # leaf: the program is the state's alone). It is the program's,
            # not the state's contract: made here, dropped at the end.
            def microchunk(carry, ts_row):
                state, fwd = carry
                for j in range(micro):
                    state, _ = step(state, ts_row[j], fwd if j == 0 else None)
                out, fwd = eval_metrics(
                    state, ts_row[-1], cadence_known=trips_per_eval == 1
                )
                if faulty is not None:
                    out["floats"] = floats_for(ts_row)
                return (state, fwd), out

            ts = (
                t0 + jnp.arange(n_trips_seg * micro, dtype=jnp.int32)
            ).reshape(n_trips_seg, micro)
            (state, _), ys = jax.lax.scan(
                microchunk, (state_init, init_forward(state_init, t0)), ts,
                unroll=flat_unroll,
            )
            return state, ys

        return seg_scan

    # ``path`` names what the caller asked for, not another program: real
    # per-eval stamps (chunked), segments for saves or heartbeats
    # (segmented), or neither (fused).
    spans.note_root(path=(
        "chunked" if measure_timestamps
        else "fused" if checkpoint is None and progress_emit is None
        else "segmented"
    ))
    # AOT executable reuse (docs/SERVING.md): the program bakes its PRNG
    # key, scalars and f*, so the key is the FULL config hash, the
    # call-level trace facts and the segment's size — a hit means the
    # identical experiment ran a segment of this size before in this
    # process, and re-executing its program is bitwise the same. One-shot
    # and heartbeat runs consult the cache (the serving daemon heartbeats
    # every request); checkpointed and measured runs always compile.
    exec_cache = (
        resolve_cache(executable_cache)
        if checkpoint is None and not measure_timestamps else None
    )
    cache_key_fn = functools.partial(
        sequential_cache_key, config, f_opt, device_data,
        schedule_signature=(
            tuple(batch_schedule.shape) if batch_schedule is not None
            else None
        ),
        collect_metrics=collect_metrics,
        mesh_signature=(
            tuple(str(d) for d in mesh.devices.flat)
            if mesh is not None else None
        ),
    )
    (final_state, gap_hist, cons_hist, time_hist, realized_floats,
     executed_iters, compile_seconds, run_seconds, trace, cost) = (
        _drive_segments(
            make_seg_scan, trips_per_eval, state0, data_args, mesh, config,
            n_evals, spans, checkpoint=checkpoint,
            measure_timestamps=measure_timestamps,
            progress_hook=progress_emit, progress_every=progress_every,
            halt_check=halt_check, exec_cache=exec_cache,
            cache_key_fn=cache_key_fn, measure_compile=measure_compile,
        )
    )
    if gap_hist is None:
        gap_hist = np.full(len(time_hist), np.nan)
    if realized_floats is not None and executed_iters and static_degree_sum:
        # Of the static graph's links, the share that carried a model, over
        # the iterations this call ran: the counter floats_transmitted keeps.
        spans.note_root(live_edge_share=float(
            realized_floats
            / (static_degree_sum * edge_payload * executed_iters)
        ))

    # Early-halt bookkeeping (ISSUE-13): a loop that stopped before the
    # horizon left fewer per-eval rows than n_evals. The histories stay
    # honestly partial (their eval axis names the executed prefix), the
    # bank records where, and the analytic floats accounting covers only
    # the executed iterations — a halted run must not bill the horizon.
    n_done_evals = len(time_hist)
    halted = monitors is not None and n_done_evals < n_evals
    if halted:
        monitors.note_halt(n_done_evals * eval_every)
    if monitors is not None and trace is not None:
        # The iteration axis starts at eval_every unconditionally: trace
        # buffers exist only under config.telemetry, which is rejected
        # with checkpointing above — a trace can never belong to a
        # resumed run whose rows would need a start-chunk offset.
        monitors.scan_trace(
            trace,
            np.arange(eval_every, T + 1, eval_every)[:n_done_evals],
        )

    total_floats = (
        realized_floats if realized_floats is not None
        else floats_per_iter * (n_done_evals * eval_every if halted else T)
    )
    x_final = final_state["x"]
    mesh_devices = n // x_final.sharding.shard_shape(x_final.shape)[0]
    # Every leaf that comes down: the models, and under ``return_state`` the
    # whole state (CHOCO's xhat, a tracker), the models among them again.
    spans.note(bytes=x_final.nbytes + (
        sum(v.nbytes for v in jax.tree.leaves(final_state))
        if return_state else 0
    ))

    harvested = []  # this call's float64 buffers: the store's when it ends
    was_reused = set()  # over the leaves: {True}, {False} or both

    def host_f64(leaf):
        """A device leaf as the results' float64 ``[rows, D]`` array, in
        ``harvest``'s two parts: ``fetch`` (the copy to the host alone) and
        ``cast`` (the float64 C-order copy, into a buffer an earlier
        harvest wrote where one is free: ``_ResultBuffers``; the flatten)."""
        with spans.part("fetch", bytes=leaf.nbytes, leaves=1) as part:
            host = _fetch_to_host(leaf)
            # 1 where the runtime handed the leaf over in its own
            # dimension order (``_cast_f64``).
            part["args"]["strided"] = int(not host.flags.c_contiguous)
        with spans.part("cast") as part:
            dst, reused = _RESULT_BUFFERS.take(host.shape)
            out = _cast_f64(host, dst)
            harvested.append(dst)
            was_reused.add(reused)
            part["args"].update(
                bytes=out.nbytes, reused_bytes=out.nbytes if reused else 0)
        return out

    final_models = host_f64(x_final)
    # The reported model under attack is the HONEST average — Byzantine
    # rows are adversary-controlled state, not part of the solution.
    with spans.part("average") as part:
        if adversary is None:
            n_averaged = final_models.shape[0]
            final_avg = final_models.mean(axis=0)
        else:
            # One masked reduction over the buffer the cast just wrote: the
            # additions of the mean over the honest rows' indexed copy, in
            # its order (bitwise, tests/test_result_buffers.py), and no
            # model-sized copy into pages never touched.
            honest = adversary.honest
            n_averaged = int(honest.sum())
            final_avg = np.add.reduce(
                final_models, axis=0, where=honest[:, None]
            ) / n_averaged
        part["args"]["rows"] = n_averaged
    host_state = (
        {k: host_f64(v) for k, v in final_state.items()}
        if return_state else None
    )
    _RESULT_BUFFERS.keep(harvested)
    spans.note_root(result_buffers=(
        "mixed" if len(was_reused) > 1
        else "reused" if True in was_reused else "fresh"
    ))

    history = RunHistory(
        objective=gap_hist,
        consensus_error=cons_hist,
        time=time_hist,
        # Only a segment's end is a real sample: measured where every
        # segment is one eval, interpolated otherwise.
        time_measured=measure_timestamps,
        mesh_devices=mesh_devices,
        # Truncated to the executed prefix when the run halted early.
        eval_iterations=np.arange(eval_every, T + 1, eval_every)[
            :n_done_evals
        ],
        total_floats_transmitted=total_floats,
        # Throughput counts only iterations executed in THIS process, so a
        # resumed run doesn't claim credit for checkpointed progress.
        iters_per_second=(
            executed_iters / run_seconds if run_seconds > 0 and executed_iters
            else float("nan")
        ),
        compile_seconds=compile_seconds,
        spectral_gap=spectral_gap,
        trace=trace,
        cost=cost,
    )
    return BackendRunResult(
        history=history,
        final_models=final_models,
        final_avg_model=final_avg,
        final_state=host_state,
    )


# --------------------------------------------------------------------------
# Replica-batched execution (ISSUE-4 tentpole): R independent runs — seed
# replicates and/or swept scalar hyperparameters — as ONE vmapped compiled
# program. The headline hot loop is latency/dispatch-bound (a
# [256, 81] model stack at ~103k iters/sec leaves the vector lanes mostly
# idle), so stacking R runs into [R, N, d] buys aggregate sweep throughput
# for near-free: every seed replicate a suite row needs, and every
# robustness experiment's mean ± std over fault realizations, costs ~one
# run's wall-clock instead of R (measured: examples/bench_sweep.py →
# docs/perf/sweep.json, asserted ≥ 8× aggregate at R=32).
# --------------------------------------------------------------------------


@dataclasses.dataclass
class BatchRunResult:
    """R replica trajectories from one ``run_batch`` call.

    ``results[r]`` is a per-replica ``BackendRunResult`` whose history is
    trajectory-equivalent to a sequential ``run`` of
    ``config.replace(seed=seeds[r], **{f: sweep[f][r]})`` (pinned ≤ 1e-12
    in f64 by tests/test_batch.py, fault and Byzantine layers included).
    Per-replica ``iters_per_second`` is the aggregate divided by R (the
    batch time-slices the chip evenly); ``aggregate_iters_per_second`` is
    the batch's R·T / run_seconds — the sweep-throughput headline.
    ``final_states`` holds the raw stacked state pytree ([R, ...] leaves,
    run dtype) — pass it back as ``state0`` with ``t0`` advanced to
    continue the batch exactly (per-replica resume-exactness is tested).
    """

    results: list
    seeds: list
    sweep: Optional[dict]
    objective: np.ndarray  # [R, n_evals] suboptimality gaps
    consensus_error: Optional[np.ndarray]  # [R, n_evals] or None
    aggregate_iters_per_second: float
    run_seconds: float
    compile_seconds: float
    final_states: dict


def batch_unsupported_reason(config) -> Optional[str]:
    """Why ``run_batch`` cannot execute this config, or None when it can.

    The single source of the batched path's rejection logic:
    ``_run_batch`` raises exactly these strings, and the serving
    coalescer (``serving/coalescer.py``) consults the same function to
    route unbatchable requests down the sequential fallback instead of
    discovering the rejection mid-cohort.
    """
    if config.backend != "jax":
        return (
            "replica-batched execution vmaps the jax scan; backend="
            f"{config.backend!r} runs one trajectory at a time — use "
            "backend='jax' or loop single runs"
        )
    if config.algorithm == "choco":
        return (
            "run_batch does not support 'choco': its step rule derives "
            "the compressor stream from config.seed internally, which the "
            "batched per-replica seed axis cannot reach — replicas would "
            "silently share compression draws"
        )
    if config.compression != "none":
        return (
            "run_batch does not support compressed gossip: the "
            "error-feedback step derives its compressor stream from "
            "config.seed internally, which the batched per-replica seed "
            "axis cannot reach — replicas would silently share "
            "compression draws"
        )
    if config.tp_degree > 1:
        return (
            "run_batch and tp_degree > 1 are mutually exclusive: the TP "
            "path pins a 2-D (workers, model) device mesh that the "
            "replica vmap axis cannot wrap"
        )
    if config.execution == "async":
        return (
            "run_batch does not support execution='async': the event "
            "path is a sequential scan over one totally ordered schedule "
            "per seed, and the per-replica schedules have different "
            "event ORDERS (the order is data, but the staleness replay "
            "is not) — run seeds sequentially"
        )
    if config.worker_mesh >= 2:
        return (
            "run_batch and worker_mesh are mutually exclusive: the "
            "replica axis vmaps one unsharded program (it fills the chip "
            "instead of the worker mesh), and the halo-exchange shard_map "
            "pins a fixed device mesh — run sharded seeds sequentially"
        )
    return None


def run_batch(
    config,
    dataset: HostDataset,
    f_opt: float,
    *,
    seeds=None,
    sweep=None,
    collect_metrics: bool = True,
    measure_compile: bool = True,
    state0=None,
    t0: int = 0,
    executable_cache=None,
    progress_cb=None,
    progress_every: int = 1,
    monitors=None,
) -> BatchRunResult:
    """Run R replicas of ``config`` as one vmapped XLA program.

    ``monitors`` (ISSUE-13): a ``MonitorBank`` observing the cohort
    heartbeats (which carry per-replica gaps — the divergence detector
    judges the WORST replica, so one sick replica cannot hide behind the
    cohort mean); under ``halt_on='fatal'`` the whole batch stops at the
    next segment boundary (the replica axis is one compiled program — it
    cannot halt per replica). Rides the same segmented machinery as
    ``progress_cb``; trajectories with nothing firing stay bitwise.

    ``progress_cb``/``progress_every`` (ISSUE-10): when set, the batched
    program executes as segments of ``progress_every`` eval-chunks (the
    continuation machinery — one executable serves every same-size
    segment, trajectories bitwise the one-shot call's) with one
    ``ProgressEvent`` per boundary carrying the replica-mean gap and the
    per-replica gaps. ``None`` changes nothing.

    ``seeds``: per-replica seed vector (default ``config.replica_seeds()``
    — seed, seed+1, ..., seed+replicas−1). ``sweep``: optional dict
    mapping a ``SWEEPABLE_FIELDS`` name to R per-replica values; replica r
    then behaves exactly like a sequential run of ``config.replace(
    seed=seeds[r], **{field: values[r]})``. ``state0``/``t0`` continue a
    previous batch from its ``final_states`` (iteration indices — and the
    counter-based sampling/fault draws with them — resume at t0, so the
    continuation is exactly the one-shot program split in two).

    Structural axes (topology, n_workers, algorithm, ...) cannot batch —
    they change the traced program — and are rejected; so are the config
    combinations whose execution cannot wrap in vmap (tensor
    parallelism, choco's internal seed derivation) — see
    ``batch_unsupported_reason``. The batched program runs unsharded (the
    replica axis fills the chip instead of the worker mesh) and always
    uses the fused flat scan.

    ``executable_cache`` controls AOT compile reuse (docs/SERVING.md; same
    convention as ``run``): seeds, swept scalars, fault timelines,
    Byzantine masks and f* are traced INPUTS of the batched program, so a
    cached executable is reusable across seed AND sweep variants of one
    structural config — the serving layer's whole amortization story. The
    default ``None`` consults the process-wide cache; ``False`` forces a
    cold compile.
    """
    from distributed_optimization_tpu.backends.base import x64_scope

    if config.worker_mesh >= 2:
        # Sequential-mesh dispatch (ISSUE-18 satellite): the halo-exchange
        # shard_map pins a fixed device mesh the replica vmap axis cannot
        # wrap, so a sharded cohort runs as R sequential mesh runs sharing
        # one AOT executable (seeds and swept scalars are traced inputs —
        # replica 2..R hit the executable cache replica 1 compiled).
        # ``batch_unsupported_reason`` still names worker_mesh so the
        # serving coalescer routes these down its sequential path; this
        # entry point dispatches them itself so ``replicas=R`` sweeps work
        # at N=100k (docs/perf/scenarios.json agreement gate).
        return _run_sequential_mesh_batch(
            config, dataset, f_opt, seeds=seeds, sweep=sweep,
            collect_metrics=collect_metrics,
            measure_compile=measure_compile, state0=state0, t0=t0,
            executable_cache=executable_cache,
            progress_cb=progress_cb, progress_every=progress_every,
            monitors=monitors,
        )
    with x64_scope(config):
        return _run_batch(
            config, dataset, f_opt, seeds=seeds, sweep=sweep,
            collect_metrics=collect_metrics,
            measure_compile=measure_compile, state0=state0, t0=t0,
            executable_cache=executable_cache,
            progress_cb=progress_cb, progress_every=progress_every,
            monitors=monitors,
        )


def _run_sequential_mesh_batch(
    config,
    dataset: HostDataset,
    f_opt: float,
    *,
    seeds,
    sweep,
    collect_metrics: bool,
    measure_compile: bool,
    state0,
    t0: int,
    executable_cache=None,
    progress_cb=None,
    progress_every: int = 1,
    monitors=None,
) -> BatchRunResult:
    """R sequential worker-mesh runs presented as one ``BatchRunResult``.

    Each replica r executes the IDENTICAL sharded program a direct
    ``run(config.replace(replicas=1, seed=seeds[r], ...))`` would — same
    halo exchange, same per-device bytes — so per-replica trajectories
    are exactly the sequential ones (not merely equivalent). The topology
    seed is pinned to the base config's resolved value so every replica
    gossips over the SAME graph, matching the batched path's convention.
    ``final_states`` leaves are host-fetched float64 ([R, ...] stacked);
    batch continuation (``state0``/``t0``) is not supported here — the
    sequential runs have no state-injection port yet.
    """
    from distributed_optimization_tpu.config import SWEEPABLE_FIELDS

    if state0 is not None or t0 != 0:
        raise ValueError(
            "worker_mesh batches run as R sequential mesh runs, which "
            "cannot resume from a stacked state0/t0 — continue each "
            "replica with its own sequential run instead"
        )
    if seeds is None:
        seeds = config.replica_seeds()
    seeds = [int(s) for s in seeds]
    if not seeds:
        raise ValueError("run_batch needs at least one replica seed")
    R = len(seeds)
    sweep = {k: list(v) for k, v in (sweep or {}).items()}
    for field, values in sweep.items():
        if field not in SWEEPABLE_FIELDS:
            raise ValueError(
                f"cannot sweep {field!r} across a replica cohort: only "
                f"the per-replica scalar axes ({', '.join(SWEEPABLE_FIELDS)}) "
                "sweep this way; structural axes change the program — run "
                "separate calls per value"
            )
        if len(values) != R:
            raise ValueError(
                f"sweep[{field!r}] has {len(values)} values for {R} "
                "replicas; every swept axis must match the seed vector's "
                "length"
            )

    topo_seed = config.resolved_topology_seed()
    results = []
    compile_seconds = 0.0
    run_seconds = 0.0
    for r in range(R):
        overrides = {f: v[r] for f, v in sweep.items()}
        rep_cfg = config.replace(
            replicas=1, seed=seeds[r], topology_seed=topo_seed, **overrides
        )
        res = run(
            rep_cfg, dataset, f_opt,
            collect_metrics=collect_metrics,
            measure_compile=measure_compile,
            executable_cache=executable_cache,
            progress_cb=progress_cb, progress_every=progress_every,
            monitors=monitors, return_state=True,
        )
        compile_seconds += float(res.history.compile_seconds or 0.0)
        ips = float(res.history.iters_per_second)
        run_seconds += (
            config.n_iterations / ips if ips > 0 else float("nan")
        )
        results.append(res)
        if monitors is not None and monitors.halt_on != "never" and (
            monitors.should_halt()
        ):
            break

    objective = np.stack(
        [np.asarray(res.history.objective, dtype=np.float64)
         for res in results]
    )
    cons = (
        np.stack([
            np.asarray(res.history.consensus_error, dtype=np.float64)
            for res in results
        ])
        if all(res.history.consensus_error is not None for res in results)
        else None
    )
    final_states = {
        k: np.stack([res.final_state[k] for res in results])
        for k in results[0].final_state
    }
    done_R = len(results)
    aggregate_ips = (
        done_R * config.n_iterations / run_seconds
        if run_seconds > 0 else float("nan")
    )
    return BatchRunResult(
        results=results,
        seeds=seeds[:done_R],
        sweep=sweep or None,
        objective=objective,
        consensus_error=cons,
        aggregate_iters_per_second=aggregate_ips,
        run_seconds=run_seconds,
        compile_seconds=compile_seconds,
        final_states=final_states,
    )


def _run_batch(
    config,
    dataset: HostDataset,
    f_opt: float,
    *,
    seeds,
    sweep,
    collect_metrics: bool,
    measure_compile: bool,
    state0,
    t0: int,
    executable_cache=None,
    progress_cb=None,
    progress_every: int = 1,
    monitors=None,
) -> BatchRunResult:
    from distributed_optimization_tpu.config import SWEEPABLE_FIELDS
    from distributed_optimization_tpu.parallel.adversary import (
        _BYZ_NOISE_TAG,
    )
    from distributed_optimization_tpu.parallel.faults import (
        FaultTimeline,
        stack_fault_timelines,
        timeline_for_config,
    )

    # --- resolve and validate the replica axis -------------------------
    if seeds is None:
        seeds = config.replica_seeds()
    seeds = [int(s) for s in seeds]
    if not seeds:
        raise ValueError("run_batch needs at least one replica seed")
    R = len(seeds)
    sweep = {k: list(v) for k, v in (sweep or {}).items()}
    for field, values in sweep.items():
        if field not in SWEEPABLE_FIELDS:
            raise ValueError(
                f"cannot sweep {field!r} inside one batched program: only "
                f"per-replica scalars that enter the compiled program as "
                f"data batch this way ({', '.join(SWEEPABLE_FIELDS)}); "
                "structural axes change the traced program itself — run "
                "separate (possibly batched) calls per value"
            )
        if len(values) != R:
            raise ValueError(
                f"sweep[{field!r}] has {len(values)} values for {R} "
                "replicas; every swept axis must match the seed vector's "
                "length"
            )
    # The backend field routes dispatch (run_algorithm_batch), not this
    # entry point — a direct call compiles on jax regardless, so only the
    # execution-structure rejections apply here.
    unbatchable = batch_unsupported_reason(config.replace(backend="jax"))
    if unbatchable is not None:
        raise ValueError(unbatchable)
    if t0 < 0:
        raise ValueError(f"t0 must be >= 0, got {t0}")
    if not get_algorithm(config.algorithm).is_decentralized and (
        config.edge_drop_prob > 0.0
        or config.straggler_prob > 0.0
        or config.mttf > 0.0
        or config.gossip_schedule != "synchronous"
        or config.attack != "none"
        or (config.aggregation != "gossip" and config.robust_b > 0)
        or "edge_drop_prob" in sweep
    ):
        # Mirror the sequential path's centralized rejection: silently
        # running a benign program here would break the replica-r ==
        # run(rep_cfgs[r]) contract (the sequential run raises).
        raise ValueError(
            "fault injection / matching-based gossip / Byzantine "
            "injection model peer exchanges and apply only to "
            "decentralized algorithms; the centralized pattern has no "
            "peer edges"
        )
    if "edge_drop_prob" in sweep and not all(
        0.0 < float(v) < 1.0 for v in sweep["edge_drop_prob"]
    ):
        raise ValueError(
            "swept edge_drop_prob values must all be in (0, 1): the "
            "batched fault threshold is traced data, so every replica "
            "must run the fault-sampling path (p = 0 rows belong in a "
            "separate fault-free batch)"
        )
    if "clip_tau" in sweep:
        if config.aggregation != "clipped_gossip" or config.robust_b <= 0:
            raise ValueError(
                "sweeping clip_tau requires aggregation='clipped_gossip' "
                "with robust_b > 0 — otherwise the radius is silently "
                "ignored"
            )
        if not all(float(v) > 0.0 for v in sweep["clip_tau"]):
            raise ValueError(
                "swept clip_tau values must all be > 0: the adaptive "
                "radius (clip_tau=0) is a different traced program — run "
                "it as its own batch"
            )
    # Per-replica sequential-equivalent configs: this DEFINES the batched
    # semantics (replica r == run(rep_cfgs[r])) and validates every cell
    # through the frozen dataclass's own cross-field checks. The topology
    # seed is pinned to the base config's resolved value — the graph is
    # structural (a per-replica graph cannot batch), so a seed sweep
    # varies run randomness over ONE fixed graph instance, and each
    # rep_cfg names exactly that run.
    rep_cfgs = [
        config.replace(
            seed=s,
            topology_seed=config.resolved_topology_seed(),
            **{f: type(getattr(config, f))(vals[r])
               for f, vals in sweep.items()},
        )
        for r, s in enumerate(seeds)
    ]

    algo = get_algorithm(config.algorithm)
    problem = get_problem(
        config.problem_type, huber_delta=config.huber_delta,
        n_classes=config.n_classes,
    )
    reg = config.reg_param
    T = config.n_iterations
    n = config.n_workers
    horizon = t0 + T  # fault timelines are prefix-stable in the horizon

    device_data = stack_shards(dataset, dtype=np.dtype(config.dtype))
    d_model = problem.param_dim(device_data.n_features)

    # --- static (replica-shared) topology & mixing ---------------------
    # The graph is anchored on the BASE config's seed: the replica axis
    # sweeps run randomness (sampling, faults, adversary draws) over one
    # fixed problem instance + topology, which is what mean ± std over
    # replicates measures.
    if algo.is_decentralized:
        topo = build_topology(
            config.topology, n, erdos_renyi_p=config.erdos_renyi_p,
            seed=config.resolved_topology_seed(),
            # Resolve from a PER-REPLICA config, not the base: a swept
            # edge_drop_prob axis (base 0.0, positive per replica) is a
            # dense-only feature the base config's auto rule cannot see —
            # all rep_cfgs resolve identically because swept edge values
            # are validated positive above, and each rep_cfg IS the
            # sequential run this batch must reproduce.
            impl=rep_cfgs[0].resolved_topology_impl(),
            sampler=rep_cfgs[0].resolved_topology_sampler(),
        )
        mix_op = make_mixing_op(
            topo, impl=config.mixing_impl, dtype=device_data.X.dtype
        )
        degrees = jnp.asarray(topo.degrees, dtype=device_data.X.dtype)[:, None]
        if algo.comm_payload is not None:
            edge_payload = algo.comm_payload(config, d_model)
            floats_per_iter = topo.floats_per_iteration * edge_payload
        else:
            edge_payload = d_model * algo.gossip_rounds
            floats_per_iter = decentralized_floats_per_iteration(
                topo, d_model, algo.gossip_rounds
            )
        spectral_gap = topo.spectral_gap
    else:
        topo = None
        mix_op = None
        edge_payload = None
        degrees = jnp.zeros((n, 1), dtype=device_data.X.dtype)
        floats_per_iter = centralized_floats_per_iteration(n, d_model)
        spectral_gap = None

    time_varying = (
        config.edge_drop_prob > 0.0
        or config.straggler_prob > 0.0
        or config.mttf > 0.0
        or config.participation_rate < 1.0
        or config.gossip_schedule != "synchronous"
        or "edge_drop_prob" in sweep
    )
    byzantine_active = config.attack != "none" or (
        config.aggregation != "gossip" and config.robust_b > 0
    )
    use_timeline = (
        config.burst_len >= 1.0 or config.mttf > 0.0
        or config.participation_rate < 1.0
        # Matrix-free node faults always route through the timeline
        # (parallel/faults.py convention — bitwise the iid draws).
        or (topo is not None and topo.is_matrix_free and time_varying)
    )

    # --- per-replica randomness, derived host-side ---------------------
    # Identical formulas to the sequential path's (jax.random.key(seed) +
    # the fault/adversary stream tags), stacked over the replica axis.
    rp: dict = {"key": jnp.stack([jax.random.key(s) for s in seeds])}
    if algo.is_decentralized and time_varying:
        rp["fault_key"] = jnp.stack([
            jax.random.fold_in(jax.random.key(s), 0x0FA17) for s in seeds
        ])
        rp["node_key"] = jnp.stack([
            jax.random.fold_in(jax.random.key(s), 0x57A66) for s in seeds
        ])
        rp["match_key"] = jnp.stack([
            jax.random.fold_in(jax.random.key(s), 0x3A7C4) for s in seeds
        ])
    stacked_tl = None
    if algo.is_decentralized and use_timeline:
        # One canonical config -> timeline mapping (parallel/faults.py):
        # the host-side consumers (realized B̂, live heartbeats, incident
        # forensics) rebuild bitwise these realizations from it.
        stacked_tl = stack_fault_timelines([
            timeline_for_config(c, topo, horizon) for c in rep_cfgs
        ])
        if stacked_tl.edge_up is not None:
            rp["tl_edge_up"] = jnp.asarray(stacked_tl.edge_up)
        if stacked_tl.node_up is not None:
            rp["tl_node_up"] = jnp.asarray(stacked_tl.node_up)
        if stacked_tl.rejoin is not None:
            rp["tl_rejoin"] = jnp.asarray(stacked_tl.rejoin)
        if stacked_tl.part_up is not None:
            rp["tl_part_up"] = jnp.asarray(stacked_tl.part_up)
    byz_hosts = None
    if byzantine_active and config.attack != "none":
        byz_hosts = np.stack([
            byzantine_set(config, topo, seed=s) for s in seeds
        ])
        rp["byz"] = jnp.asarray(byz_hosts)
        rp["noise_key"] = jnp.stack([
            jax.random.fold_in(jax.random.key(s), _BYZ_NOISE_TAG)
            for s in seeds
        ])
    if "learning_rate_eta0" in sweep:
        rp["eta0"] = jnp.asarray(
            np.asarray(sweep["learning_rate_eta0"], dtype=np.float64)
        )
    if "clip_tau" in sweep:
        rp["clip_tau"] = jnp.asarray(
            np.asarray(sweep["clip_tau"], dtype=np.float64)
        )
    if "edge_drop_prob" in sweep:
        # float32: the fault threshold's comparison dtype everywhere.
        rp["edge_drop_prob"] = jnp.asarray(
            np.asarray(sweep["edge_drop_prob"], dtype=np.float32)
        )

    # --- data + initial state (unsharded; replica axis fills the chip) --
    # f* rides along as a TRACED scalar (replica-shared), not a closure
    # constant like the sequential path bakes: the executable cache reuses
    # one compiled batched program across requests whose datasets — and
    # therefore optima — differ (docs/SERVING.md). Cast to the run dtype
    # up front, exactly the cast the weak Python float would get at the
    # subtraction, so traced-vs-baked trajectories stay bitwise.
    data_args = {
        "X": jnp.asarray(device_data.X),
        "y": jnp.asarray(device_data.y),
        "n_valid": jnp.asarray(device_data.n_valid),
        "f_opt": jnp.asarray(f_opt, dtype=device_data.X.dtype),
    }
    x0 = jnp.zeros((n, d_model), dtype=device_data.X.dtype)
    st0 = algo.init(
        x0, config,
        neighbor_sum=mix_op.neighbor_sum if mix_op is not None else None,
    )
    if state0 is None:
        state0_R = jax.tree.map(
            lambda a: jnp.repeat(a[None], R, axis=0), st0
        )
    else:
        if set(state0) != set(st0):
            raise ValueError(
                f"state0 leaves {sorted(state0)} do not match the "
                f"algorithm's state {sorted(st0)}"
            )
        state0_R = {
            k: jnp.asarray(v).astype(st0[k].dtype) for k, v in state0.items()
        }
        for k, v in state0_R.items():
            if v.shape != (R,) + st0[k].shape:
                raise ValueError(
                    f"state0[{k!r}] has shape {v.shape}; expected "
                    f"{(R,) + st0[k].shape} ([replicas, ...])"
                )

    full_objective = make_full_objective_fn(problem, reg)
    batch_size = config.local_batch_size
    platform = jax.devices()[0].platform
    sampling_impl = config.resolved_sampling_impl(
        platform, device_data.X.shape[1]
    )
    track_consensus = (
        collect_metrics and algo.is_decentralized and config.record_consensus
    )
    eval_every = config.eval_every
    n_evals = T // eval_every
    scan_unroll = config.resolved_scan_unroll(platform)
    micro, trips_per_eval, flat_unroll = _flat_scan_cadence(
        scan_unroll, eval_every
    )
    n_trips = n_evals * trips_per_eval

    static_degree_sum = (
        float(np.asarray(topo.degrees).sum()) if topo is not None else 0.0
    )

    def make_replica_scan(n_trips_call):
        """Factory over the per-call trip count: the one-shot program runs
        all ``n_trips`` in one call; progress streaming runs segments of
        ``progress_every * trips_per_eval`` trips through the same traced
        body (``t0_dev`` offsets the iteration indices, so one executable
        serves every same-size segment)."""
        return functools.partial(_replica_scan, n_trips_call)

    def _replica_scan(n_trips_call, rp_r, state_init, t0_dev, data):
        """One replica's flat fused scan — the sequential program, traced
        with this replica's randomness/scalars bound from ``rp_r``."""
        faulty = None
        adversary = None
        byz_mix = None
        robust_activity = None
        honest_w = None
        if algo.is_decentralized:
            tl = None
            if stacked_tl is not None:
                tl = FaultTimeline(
                    horizon=horizon,
                    directed=topo.directed,
                    edge_index=stacked_tl.edge_index,
                    edge_up=rp_r.get("tl_edge_up"),
                    node_up=rp_r.get("tl_node_up"),
                    rejoin=rp_r.get("tl_rejoin"),
                    part_up=rp_r.get("tl_part_up"),
                )
            if time_varying:
                faulty = _build_faulty(
                    config, algo, topo, T,
                    drop_prob=rp_r.get("edge_drop_prob"),
                    keys=(
                        rp_r["fault_key"], rp_r["node_key"],
                        rp_r["match_key"],
                    ),
                    timeline=tl, horizon=horizon,
                )
            adversary, byz_mix, robust_activity = _bind_byzantine(
                config, algo, topo, faulty, mix_op,
                clip_tau=rp_r.get("clip_tau"),
                byz=rp_r.get("byz"),
                noise_key=rp_r.get("noise_key"),
            )
            if adversary is not None:
                honest_w = jnp.asarray(
                    adversary.honest.astype(np.float32)
                )
        pieces = _StepPieces(
            algo=algo, problem=problem, reg=reg, config=config,
            batch_size=batch_size, sampling_impl=sampling_impl,
            key=rp_r["key"],
            eta_fn=_make_eta_fn(config, eta0=rp_r.get("eta0")),
            degrees=degrees, mix_op=mix_op, faulty=faulty,
            byz_mix=byz_mix, adversary=adversary, honest_w=honest_w,
            full_objective=full_objective,
            f_opt=data["f_opt"], collect_metrics=collect_metrics,
            track_consensus=track_consensus, edge_payload=edge_payload,
            telemetry=config.telemetry, robust_activity=robust_activity,
            static_degree_sum=static_degree_sum,
        )
        step, eval_metrics, floats_for, _ = _make_step_eval(pieces, data)

        def microchunk(state, ts_row):
            for j in range(micro):
                state, _ = step(state, ts_row[j])
            out, _ = eval_metrics(
                state, ts_row[-1], cadence_known=trips_per_eval == 1
            )
            if faulty is not None:
                out["floats"] = floats_for(ts_row)
            return state, out

        ts = (
            t0_dev + jnp.arange(n_trips_call * micro, dtype=jnp.int32)
        ).reshape(n_trips_call, micro)
        return jax.lax.scan(microchunk, state_init, ts, unroll=flat_unroll)

    rp_axes = {k: 0 for k in rp}
    t0_dev = jnp.asarray(t0, dtype=jnp.int32)

    # AOT executable reuse (docs/SERVING.md): the batched program takes
    # seeds/sweeps/timelines/f* as data, so its cache key is the config's
    # STRUCTURAL hash + call-level trace facts — one cached executable
    # serves every seed/sweep variant of this structural config at this R.
    exec_cache = resolve_cache(executable_cache)

    def _compile_trips(n_trips_call, segment):
        """Lower/compile (or fetch from the cache) the batched program
        executing ``n_trips_call`` scan trips per call. Returns
        (compiled, cost, cold_seconds)."""
        batched = jax.vmap(
            make_replica_scan(n_trips_call), in_axes=(rp_axes, 0, None, None)
        )
        cache_key = cached = None
        if exec_cache is not None:
            cache_key = batch_cache_key(
                config, device_data, R=R, t0=t0, rp_keys=rp.keys(),
                sweep_fields=sweep.keys(), collect_metrics=collect_metrics,
                segment=segment,
            )
            cached = exec_cache.get(cache_key)
        if cached is not None:
            return (
                cached.executable,
                cached.cost if config.telemetry else None,
                0.0,
            )
        t_c = time.perf_counter()
        with jax.default_matmul_precision(config.matmul_precision):
            lowered = jax.jit(batched).lower(rp, state0_R, t0_dev, data_args)
            cost = cost_from_lowered(lowered) if config.telemetry else None
            if cost is not None:
                # The analysis covers the WHOLE R-replica vmapped program;
                # the same dict is attached to every per-replica history,
                # so record the replica count rather than letting a
                # consumer read R runs' FLOPs as one run's (divide by
                # program_replicas for an approximate per-replica share —
                # shared data reads make an exact split ill-defined).
                cost = {**cost, "program_replicas": float(R)}
            compiled = lowered.compile()
        cold_seconds = time.perf_counter() - t_c
        if exec_cache is not None:
            exec_cache.put(
                cache_key, compiled, cost=cost, compile_seconds=cold_seconds,
            )
        return compiled, cost, cold_seconds

    n_done_evals = n_evals
    if progress_cb is None and monitors is None:
        compiled, cost, cold_seconds = _compile_trips(n_trips, None)
        compile_seconds = cold_seconds if measure_compile else 0.0
        t_r = time.perf_counter()
        final_states, ys = compiled(rp, state0_R, t0_dev, data_args)
        final_states = jax.block_until_ready(final_states)
        run_seconds = time.perf_counter() - t_r
    else:
        # Progress streaming (ISSUE-10): run the SAME program in segments
        # of ``progress_every`` eval-chunks through the continuation
        # machinery (t0 traced, state carried), one heartbeat per
        # boundary. One executable per segment size; trajectories bitwise
        # the one-shot call (tests/test_observatory.py pins it).
        if progress_every < 1:
            raise ValueError(
                f"progress_every must be >= 1 eval-chunks, got "
                f"{progress_every}"
            )
        emit = _progress_emitter(
            config, _fanout_progress(progress_cb, monitors),
            t0=t0, with_bhat=False,
        )
        halt_check = (
            monitors.should_halt
            if monitors is not None and monitors.halt_on != "never"
            else None
        )
        seg_evals = min(max(int(progress_every), 1), max(n_evals, 1))
        sizes = {min(seg_evals, n_evals)}
        if n_evals % seg_evals:
            sizes.add(n_evals % seg_evals)
        compiled_by_size = {}
        cost = None
        compile_cold = 0.0
        for size in sorted(sizes):
            compiled_by_size[size], size_cost, cold = _compile_trips(
                size * trips_per_eval, ("seg", int(size * trips_per_eval)),
            )
            if cost is None:
                cost = size_cost
            compile_cold += cold
        compile_seconds = compile_cold if measure_compile else 0.0

        t_r = time.perf_counter()
        state_R = state0_R
        ys_segments = []
        gap_means: list[float] = []
        cons_means: list[float] = []
        done = 0
        while done < n_evals:
            this_evals = min(seg_evals, n_evals - done)
            t0_seg = jnp.asarray(
                t0 + done * eval_every, dtype=jnp.int32
            )
            state_R, ys_seg = compiled_by_size[this_evals](
                rp, state_R, t0_seg, data_args
            )
            jax.block_until_ready(state_R)
            ys_segments.append(ys_seg)
            done += this_evals
            extra = {}
            if "gap" in ys_seg:
                # The segment's last trip IS an eval boundary (segments
                # are whole eval-chunks), so the [-1] column is the
                # on-cadence row.
                g = np.asarray(ys_seg["gap"], dtype=np.float64)[:, -1]
                gap_means.append(float(g.mean()))
                extra["gap_per_replica"] = [float(v) for v in g]
            if "cons" in ys_seg:
                c = np.asarray(ys_seg["cons"], dtype=np.float64)[:, -1]
                cons_means.append(float(c.mean()))
            if emit is not None:
                emit(
                    done, gap_means, cons_means,
                    time.perf_counter() - t_r, **extra,
                )
            if halt_check is not None and halt_check():
                # Early-halt policy (ISSUE-13): the whole cohort stops at
                # this segment boundary — one compiled program, one halt.
                break
        final_states = state_R
        ys = jax.tree.map(
            lambda *vs: jnp.concatenate(vs, axis=1), *ys_segments
        ) if len(ys_segments) > 1 else ys_segments[0]
        run_seconds = time.perf_counter() - t_r
        n_done_evals = done
        if monitors is not None and done < n_evals:
            monitors.note_halt(t0 + done * eval_every)

    # --- harvest [R, n_trips, ...] scan outputs to per-eval rows --------
    # ``n_done_evals`` < n_evals only when the early-halt policy stopped
    # the batch: the histories then honestly cover the executed prefix.
    sel = slice(trips_per_eval - 1, None, trips_per_eval)
    gap = (
        np.asarray(ys["gap"], dtype=np.float64)[:, sel]
        if "gap" in ys else None
    )
    cons = (
        np.asarray(ys["cons"], dtype=np.float64)[:, sel]
        if "cons" in ys else None
    )
    floats = (
        np.asarray(ys["floats"], dtype=np.float64)
        .reshape(R, n_done_evals, trips_per_eval).sum(axis=2)
        if "floats" in ys else None
    )
    # Trace-buffer rows select like the gap (eval-boundary trips), with the
    # replica axis leading: [R, n_evals] scalars / [R, n_evals, N] rows.
    trace_R = (
        {k: np.asarray(v)[:, sel] for k, v in ys["trace"].items()}
        if "trace" in ys else None
    )
    objective = (
        gap if gap is not None else np.full((R, n_done_evals), np.nan)
    )

    final_states_np = {
        k: np.asarray(v) for k, v in final_states.items()
    }
    final_models = final_states_np["x"].astype(np.float64)  # [R, N, d]
    executed_T = n_done_evals * eval_every
    aggregate_ips = (
        R * executed_T / run_seconds if run_seconds > 0 else float("nan")
    )
    time_hist = np.linspace(
        run_seconds / max(n_done_evals, 1), run_seconds, n_done_evals
    )
    eval_iterations = np.arange(
        t0 + eval_every, t0 + T + 1, eval_every
    )[:n_done_evals]

    results = []
    for r in range(R):
        total_floats = (
            float(floats[r].sum()) if floats is not None
            else floats_per_iter * (
                executed_T if n_done_evals < n_evals else T
            )
        )
        history = RunHistory(
            objective=objective[r],
            consensus_error=cons[r] if cons is not None else None,
            time=time_hist,
            time_measured=False,
            eval_iterations=eval_iterations,
            total_floats_transmitted=total_floats,
            # The batch time-slices the chip evenly: each replica's share
            # of the aggregate throughput.
            iters_per_second=aggregate_ips / R,
            compile_seconds=compile_seconds,
            spectral_gap=spectral_gap,
            trace=(
                {k: v[r] for k, v in trace_R.items()}
                if trace_R is not None else None
            ),
            cost=cost,
        )
        models_r = final_models[r]
        if byz_hosts is not None:
            final_avg = models_r[~byz_hosts[r]].mean(axis=0)
        else:
            final_avg = models_r.mean(axis=0)
        results.append(BackendRunResult(
            history=history,
            final_models=models_r,
            final_avg_model=final_avg,
        ))

    return BatchRunResult(
        results=results,
        seeds=seeds,
        sweep=sweep or None,
        objective=objective,
        consensus_error=cons,
        aggregate_iters_per_second=aggregate_ips,
        run_seconds=run_seconds,
        compile_seconds=compile_seconds,
        final_states=final_states_np,
    )
