"""Tensor parallelism for the compute-bound softmax tier: DP × TP mesh.

The framework's base layout is 1-D data parallelism — worker rows sharded
over the ``'workers'`` mesh axis, gossip crossing chip boundaries as
collectives (``parallel/collectives.py``). The softmax family
(``models/softmax.py``) adds the second axis TPUs are built around: its
[d, K] classifier matrix shards column-blocks over a ``'model'`` mesh
axis, so a 2-D ``(workers, model)`` mesh runs BOTH parallelisms at once —
the execution layout of the scaling-book recipe (mesh + shardings +
XLA/explicit collectives), demonstrated here with explicit ``shard_map``
collectives so the communication pattern is auditable in compiled HLO:

- every FLOP-heavy tensor is sharded: X by worker rows, W/logits/grads by
  worker rows AND class columns — no device ever holds a full [d, K];
- the ONLY cross-model-shard traffic is the softmax normalization: a
  ``pmax`` + ``psum`` of [n_local, b] scalars per step (payload O(b) per
  worker, INDEPENDENT of K — asserted against compiled HLO in
  tests/test_tensor_parallel.py);
- ring gossip runs over the workers axis exactly as in the DP layout, but
  each device exchanges only its OWN class slice — boundary ppermute
  payload d·K/tp floats per device instead of d·K (TP shards the gossip
  traffic too, also HLO-asserted);
- the update rule is bitwise the same math as the replicated path: the
  three-tier oracles (numpy matrix recursion, single-mesh jax backend)
  pin the TP trajectory to fp tolerance in the tests.

Scope: D-SGD + softmax + ring, full local batches (the compute tier's
measured configuration — the per-iteration RNG of subsampling is a
data-parallel concern the DP path already covers). This module is the
multi-chip execution path for the tier `docs/perf/compute_bound.json`
measures single-chip; ``__graft_entry__.dryrun_multichip`` validates it
end-to-end on the virtual mesh (compile + execute + optimize).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_optimization_tpu.backends.base import x64_scope
from distributed_optimization_tpu.parallel.mesh import WORKER_AXIS

MODEL_AXIS = "model"

# Metric evals run BETWEEN per-cadence scans (a Python-unrolled segment
# sequence), so a run computes exactly n_evals full-dataset evaluations;
# the limit bounds traced program size.
EVAL_SEGMENT_LIMIT = 64


def make_dp_tp_mesh(dp: int, tp: int, devices=None) -> Mesh:
    """2-D ``(workers, model)`` mesh over dp·tp devices."""
    devices = list(devices if devices is not None else jax.devices())
    if dp * tp > len(devices):
        raise ValueError(
            f"dp*tp = {dp * tp} exceeds the {len(devices)} visible devices"
        )
    grid = np.asarray(devices[: dp * tp]).reshape(dp, tp)
    return Mesh(grid, (WORKER_AXIS, MODEL_AXIS))


def build_tp_softmax_dsgd(
    config,
    dataset,
    mesh: Mesh,
    *,
    collect_metrics: bool = True,
):
    """Build the jitted TP program and its sharded inputs.

    Returns ``(jitted_fn, args)`` with ``jitted_fn(*args) -> (W_final
    [N, d, K] sharded, per-cadence gaps [n_evals])`` — exposed separately
    from :func:`run_tp_softmax_dsgd` so tests can assert on the compiled
    HLO.
    """
    from distributed_optimization_tpu.utils.data import stack_shards

    if config.algorithm != "dsgd" or config.topology != "ring":
        raise ValueError("the TP demo path implements dsgd on a ring")
    if config.problem_type != "softmax":
        raise ValueError("tensor parallelism shards the softmax [d, K] tier")
    n, K, T = config.n_workers, config.n_classes, config.n_iterations
    dp, tp = mesh.devices.shape
    if n % dp != 0:
        raise ValueError(f"n_workers {n} must divide over dp={dp}")
    if K % tp != 0:
        raise ValueError(f"n_classes {K} must divide over tp={tp}")
    if n < 3:
        raise ValueError("ring gossip needs n_workers >= 3")
    max_shard = max(len(idx) for idx in dataset.shard_indices)
    if config.local_batch_size < max_shard:
        raise ValueError(
            f"the TP path runs FULL local batches (the compute tier's "
            f"measured configuration); local_batch_size="
            f"{config.local_batch_size} < shard size {max_shard} would "
            "silently train a different trajectory than the DP backend — "
            "set local_batch_size >= the shard size"
        )
    eval_every = config.eval_every
    n_evals = T // eval_every
    if collect_metrics and n_evals > EVAL_SEGMENT_LIMIT:
        raise ValueError(
            f"{n_evals} eval segments exceed EVAL_SEGMENT_LIMIT="
            f"{EVAL_SEGMENT_LIMIT} (each is a Python-unrolled scan in the "
            "traced program); coarsen eval_every or pass "
            "collect_metrics=False"
        )

    device_data = stack_shards(dataset, dtype=np.dtype(config.dtype))
    d = device_data.n_features
    reg = config.reg_param
    eta0 = config.learning_rate_eta0
    sqrt_decay = config.resolved_lr_schedule() == "sqrt_decay"
    total_rows = float(np.sum(device_data.n_valid))

    # Placement: X/y/n_valid worker-sharded, replicated over 'model';
    # W worker-sharded rows × class-sharded columns — no full [d, K] on
    # any device.
    def put(a, spec):
        return jax.device_put(jnp.asarray(a), NamedSharding(mesh, spec))

    X = put(device_data.X, P(WORKER_AXIS, None, None))
    y = put(device_data.y.astype(np.int32), P(WORKER_AXIS, None))
    n_valid = put(device_data.n_valid, P(WORKER_AXIS))
    W0 = put(
        np.zeros((n, d, K), dtype=device_data.X.dtype),
        P(WORKER_AXIS, None, MODEL_AXIS),
    )

    # The boundary-exchange ring stencil is the SAME operator the explicit
    # DP collectives use — _ring_block_mix works on axis 0 of any block
    # shape, so the [nw, d, Kp] TP slice reuses it unchanged.
    from distributed_optimization_tpu.parallel.collectives import (
        _ring_block_mix,
    )

    ring_mix, _ = _ring_block_mix(WORKER_AXIS, dp, 1.0 / 3.0)

    def block_body(Wb, Xb, yb, nvb):
        """Per-device block program. Shapes (local): Wb [nw, d, Kp],
        Xb [nw, L, d], yb [nw, L], nvb [nw]."""
        nw, L = Xb.shape[0], Xb.shape[1]
        Kp = Wb.shape[-1]
        k_off = jax.lax.axis_index(MODEL_AXIS) * Kp
        mask = (
            jnp.arange(L)[None, :] < nvb[:, None]
        ).astype(Xb.dtype)  # [nw, L]
        wts = mask / jnp.maximum(nvb[:, None].astype(Xb.dtype), 1.0)

        def logits_of(Wcur):
            return jnp.einsum("nld,ndk->nlk", Xb, Wcur)

        def softmax_parts(logits):
            """Globally-normalized P from K-sharded logits: the ONLY
            cross-model-shard traffic — [nw, L] scalars, K-independent."""
            m = jax.lax.pmax(
                jnp.max(logits, axis=-1), axis_name=MODEL_AXIS
            )  # [nw, L]
            e = jnp.exp(logits - m[..., None])
            se = jax.lax.psum(
                jnp.sum(e, axis=-1), axis_name=MODEL_AXIS
            )  # [nw, L]
            return e / se[..., None], m, se

        def grad(Wcur):
            logits = logits_of(Wcur)
            Pl, _, _ = softmax_parts(logits)
            ks = k_off + jnp.arange(Kp)
            Y = (yb[..., None] == ks[None, None, :]).astype(Xb.dtype)
            coef = wts[..., None] * (Pl - Y)  # masked mean weights
            return jnp.einsum("nld,nlk->ndk", Xb, coef) + reg * Wcur

        def eval_gap(Wcur):
            """Full-dataset objective of the worker-mean model."""
            xbar = (
                jax.lax.psum(jnp.sum(Wcur, axis=0), axis_name=WORKER_AXIS)
                / n
            )  # [d, Kp], same on every worker shard
            logits = jnp.einsum("nld,dk->nlk", Xb, xbar)
            _, m, se = softmax_parts(logits)
            true_local = jnp.where(
                (yb >= k_off) & (yb < k_off + Kp),
                jnp.take_along_axis(
                    logits, jnp.clip(yb - k_off, 0, Kp - 1)[..., None],
                    axis=-1,
                )[..., 0],
                0.0,
            )
            true = jax.lax.psum(true_local, axis_name=MODEL_AXIS)
            ce = (m + jnp.log(se)) - true
            data_term = (
                jax.lax.psum(
                    jnp.sum(mask * ce), axis_name=WORKER_AXIS
                )
                / total_rows
            )
            sq = jax.lax.psum(
                jax.lax.psum(jnp.sum(xbar * xbar), axis_name=MODEL_AXIS),
                axis_name=WORKER_AXIS,
            ) / dp  # xbar replicated over workers: divide the worker psum
            return data_term + 0.5 * reg * sq

        def step(Wcur, t):
            # t is an int32 scan index; the schedule is computed in the
            # carry dtype so f64 runs match the replicated backend's
            # eta0/sqrt(t+1) bit for bit (an f32 arange here drifted ~4e-8
            # relative per step against the f64 oracles — round-5 ADVICE).
            eta = (
                eta0 / jnp.sqrt((t + 1.0).astype(Wcur.dtype)) if sqrt_decay
                else jnp.asarray(eta0, dtype=Wcur.dtype)
            ).astype(Wcur.dtype)
            g = grad(Wcur)
            # D-PSGD: grads at the pre-mix models; boundary ppermutes
            # carry [1, d, Kp] rows — d·K/tp floats per device, 1/tp of
            # the DP-only payload (ring gossip on the LOCAL class slice).
            return ring_mix(Wcur) - eta * g, None

        # Exact-cadence metrics: a Python-unrolled sequence of eval-free
        # scans with the full-dataset eval computed BETWEEN them, so a run
        # pays exactly n_evals evaluations instead of one per step.
        # Metrics off: one flat scan, no segments.
        if not collect_metrics:
            Wcur, _ = jax.lax.scan(
                step, Wb, jnp.arange(T, dtype=jnp.int32)
            )
            return Wcur, jnp.zeros(n_evals, dtype=Wb.dtype)
        ts = jnp.arange(T, dtype=jnp.int32).reshape(n_evals, eval_every)
        outs = []
        Wcur = Wb
        for e in range(n_evals):
            Wcur, _ = jax.lax.scan(step, Wcur, ts[e])
            outs.append(eval_gap(Wcur))
        return Wcur, jnp.stack(outs)

    sharded = jax.jit(
        shard_map(
            block_body,
            mesh=mesh,
            in_specs=(
                P(WORKER_AXIS, None, MODEL_AXIS),
                P(WORKER_AXIS, None, None),
                P(WORKER_AXIS, None),
                P(WORKER_AXIS),
            ),
            out_specs=(P(WORKER_AXIS, None, MODEL_AXIS), P()),
            check_vma=False,
        )
    )
    return sharded, (W0, X, y, n_valid)


def run_tp_backend(
    config,
    dataset,
    f_opt: float,
    *,
    collect_metrics: bool = True,
    measure_compile: bool = True,
    **unsupported,
):
    """Config-driven entry for ``tp_degree > 1`` (``backends.run_algorithm``
    routes here): build the DP × TP mesh from the visible devices, run the
    sharded program, and report the same ``BackendRunResult`` every other
    backend returns — so the simulator, CLI, report, and JSON layers need
    no TP-specific code.

    Mesh shape: ``tp = config.tp_degree`` model shards; the workers axis
    takes the largest device count that divides ``n_workers`` within the
    remaining budget (1 is always valid — TP with a single worker-shard
    row is still class-sharded). Compile and run are AOT-split like the
    DP backend, so iters/sec is steady-state.
    """
    import time

    from distributed_optimization_tpu.backends.base import BackendRunResult
    from distributed_optimization_tpu.metrics import (
        RunHistory,
        decentralized_floats_per_iteration,
    )
    from distributed_optimization_tpu.parallel.topology import build_topology

    if unsupported:
        raise ValueError(
            f"tensor-parallel runs do not support {sorted(unsupported)}: "
            "the TP path has no checkpointing, measured-timestamp, or "
            "batch-schedule machinery — run those on the data-parallel "
            "backend (tp_degree=1)"
        )
    tp = config.tp_degree
    devices = jax.devices()
    if tp > len(devices):
        raise ValueError(
            f"tp_degree={tp} exceeds the {len(devices)} visible devices"
        )
    dp = len(devices) // tp
    while dp > 1 and config.n_workers % dp != 0:
        dp -= 1
    mesh = make_dp_tp_mesh(dp, tp)

    from distributed_optimization_tpu.backends.base import x64_scope

    T = config.n_iterations
    n_evals = T // config.eval_every
    with x64_scope(config):
        sharded, args = build_tp_softmax_dsgd(
            config, dataset, mesh, collect_metrics=collect_metrics
        )
        t0 = time.perf_counter()
        with jax.default_matmul_precision(config.matmul_precision):
            compiled = sharded.lower(*args).compile()
        compile_seconds = (
            time.perf_counter() - t0 if measure_compile else 0.0
        )
        t1 = time.perf_counter()
        W_final, gaps = compiled(*args)
        W_final = jax.block_until_ready(W_final)
        run_seconds = time.perf_counter() - t1

    n, K = config.n_workers, config.n_classes
    d = W_final.shape[1]
    final_models = np.asarray(
        jax.device_get(W_final), dtype=np.float64
    ).reshape(n, d * K)
    objective = (
        np.asarray(gaps, dtype=np.float64) - f_opt
        if collect_metrics else np.full(n_evals, np.nan)
    )
    # Comms accounting stays at the MODEL level (comparable with the DP
    # rows): Σ deg·d·K floats per iteration — TP shards each exchange to
    # d·K/tp per device, but the full model still crosses the ring.
    topo = build_topology("ring", n)
    history = RunHistory(
        objective=objective,
        consensus_error=None,
        time=np.linspace(
            run_seconds / max(n_evals, 1), run_seconds, n_evals
        ),
        time_measured=False,
        eval_iterations=np.arange(
            config.eval_every, T + 1, config.eval_every
        ),
        total_floats_transmitted=decentralized_floats_per_iteration(
            topo, d * K
        ) * T,
        iters_per_second=T / run_seconds if run_seconds > 0 else float("nan"),
        compile_seconds=compile_seconds,
        spectral_gap=topo.spectral_gap,
    )
    return BackendRunResult(
        history=history,
        final_models=final_models,
        final_avg_model=final_models.mean(axis=0),
    )


def run_tp_softmax_dsgd(
    config,
    dataset,
    mesh: Mesh,
    *,
    f_opt: float = 0.0,
    collect_metrics: bool = True,
):
    """Run D-SGD + softmax + ring on a 2-D (workers, model) mesh.

    Full local batches (b = shard size), sqrt-decay or constant eta per
    the config. Returns ``(final_models [N, d·K] numpy float64, gaps
    [n_evals] numpy)`` — the same quantities/layout the backends report,
    so the oracles compare directly.
    """
    with x64_scope(config):
        sharded, args = build_tp_softmax_dsgd(
            config, dataset, mesh, collect_metrics=collect_metrics
        )
        with jax.default_matmul_precision(config.matmul_precision):
            W_final, gaps = sharded(*args)
    n, K = config.n_workers, config.n_classes
    d = W_final.shape[1]
    W_np = np.asarray(jax.device_get(W_final), dtype=np.float64)
    if not collect_metrics:
        # No evals ran: an empty history, not placeholder zeros that would
        # read as (negative) gaps after the f_opt shift.
        return W_np.reshape(n, d * K), np.empty(0, dtype=np.float64)
    gaps_np = np.asarray(gaps, dtype=np.float64) - f_opt
    return W_np.reshape(n, d * K), gaps_np
