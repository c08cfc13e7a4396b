"""Gradient tracking (DIGing; Nedić-Olshevsky-Shi 2017, Koloskova et al. 2020).

Not present in the reference (SURVEY.md §0 lists it as a planned capability
from BASELINE.json). Each worker maintains a tracker y_i estimating the
*network-average* gradient alongside its model:

    x_{t+1} = W x_t − η y_t
    y_{t+1} = W y_t + g(x_{t+1}) − g_prev

which preserves the tracking invariant  mean(y_t) = mean(g_t)  and removes the
non-IID bias floor that plain D-SGD suffers under heterogeneous data — the
setting this study's sorted-partition data generator creates on purpose.

Initialization: y_0 = 0, g_prev = 0, so iteration 0 performs a pure gossip
step and y_1 = g_1 exactly; the invariant mean(y_t) = mean(g_t) holds for all
t ≥ 1 by induction. This avoids needing a batch draw before the scan starts.

Costs two gossip rounds per iteration (x and y), i.e. 2·Σdeg·d floats —
reflected in ``gossip_rounds=2`` for the comms metric.

The constant step against the graph (ISSUE 39; sandbox CPU runs and a plain
full-batch restatement of the rule, the same readings): on target-sorted
shards a worker's rows share one value of X·coef, so in the plane of that
direction and the bias its Hessian is rank one, the null direction turning
smoothly from worker to worker; a graph that mixes slowly enough no longer
holds the tracker at a step that a small graph takes. At the ``gt-torus-64``
preset's η = 0.01 on least squares (d = 81, b = 16) a 128 × 128 torus holds
(consensus error peaking at 32,611 near iteration 200, then falling: 10,992
at 1,500), a 256 × 256 torus runs away (33,430 at iteration 200, 520,646 at
300) and a 512 × 512 one sooner (42,993 at 100, 5,727,491 at 150), whatever
the rows a worker (53, 195 or full batch). It is the rule on this partition
at this step, not the program: a larger torus takes a smaller step.

Fault tolerance (``supports_edge_faults=True``, the default) is
evidence-backed, not assumed: the tracking invariant is an algebraic
identity whenever every realized W_t is doubly stochastic and a straggler's
freeze covers all three state leaves — pinned through the real backend
fault paths in tests/test_faults.py (invariant to ~1e-10 over 400 faulty
float64 iterations) and measured in docs/perf/faults.json.

Byzantine injection (``supports_byzantine=True``): both gossip rounds go
through the corrupt/screen composition. Note the caveat in
docs/BYZANTINE.md — robust (screened) aggregation is not doubly
stochastic, so the tracking invariant above holds only on the
plain-gossip attack path; with a robust rule GT composes mechanically but
the invariant (and with it GT's bias-removal guarantee) is lost, and the
breakdown benches use D-SGD.
"""

from __future__ import annotations

import jax.numpy as jnp

from distributed_optimization_tpu.algorithms.base import (
    Algorithm,
    State,
    StepContext,
    local_descent_loop,
    register_algorithm,
)


def _init(x0, config, *, neighbor_sum=None) -> State:
    zeros = jnp.zeros_like(x0)
    state = {"x": x0, "y": zeros, "g_prev": zeros}
    if config.compression != "none":
        from distributed_optimization_tpu.ops.compression import (
            make_error_feedback,
            row_dim,
        )

        ef = make_error_feedback(
            config.compression, row_dim(x0), config.compression_k,
            config.choco_gamma,
        )
        # One estimate memory per gossiped leaf: both the model and the
        # tracker exchange compressed differences (see _step).
        state["xhat"] = ef.init(x0)
        state["yhat"] = ef.init(x0)
    return state


def _step(state: State, ctx: StepContext) -> State:
    x, y, g_prev = state["x"], state["y"], state["g_prev"]
    if "xhat" in state:
        # Error-feedback compressed gossip (ISSUE-6 tentpole), applied to
        # BOTH gossip rounds through the shared machinery generalized out
        # of CHOCO (ops/compression.py): each round's W-mix is replaced by
        # v + γ(W − I)X̂⁺ over the per-leaf estimate carries, transmitting
        # only Q(v − x̂) per edge — the compressed-gradient-tracking family
        # (CHOCO-style memory on x and y; rounds 0/1 draw distinct
        # compressor keys so the two exchanges never share randomness).
        from distributed_optimization_tpu.ops.compression import (
            compression_key,
            make_error_feedback,
            row_dim,
        )

        cfg = ctx.config
        ef = make_error_feedback(
            cfg.compression, row_dim(x), cfg.compression_k,
            cfg.choco_gamma,
        )
        if ctx.compressed_mix is not None:
            # Worker-mesh wire form: both rounds ship only q boundary rows
            # over ppermute; each gossiped leaf carries its own persistent
            # receiver-side halo (xhat_halo / yhat_halo, zero-seeded by
            # the backend). Local algebra matches the unsharded branch
            # below term for term — bitwise at matched N.
            x_mixed, xhat_new, xh_halo = ef.exchange_sharded(
                compression_key(cfg.seed, ctx.t, round=0), x,
                state["xhat"], state["xhat_halo"], ctx.compressed_mix,
            )
            x_new = x_mixed - ctx.eta * y
            g_new = ctx.grad(x_new, 0)
            y_mixed, yhat_new, yh_halo = ef.exchange_sharded(
                compression_key(cfg.seed, ctx.t, round=1), y,
                state["yhat"], state["yhat_halo"], ctx.compressed_mix,
            )
            return {
                "x": x_new, "y": y_mixed + g_new - g_prev,
                "g_prev": g_new, "xhat": xhat_new, "yhat": yhat_new,
                "xhat_halo": xh_halo, "yhat_halo": yh_halo,
            }
        x_mixed, xhat_new = ef.exchange(
            compression_key(cfg.seed, ctx.t, round=0), x, state["xhat"],
            ctx.mix,
        )
        x_new = x_mixed - ctx.eta * y
        g_new = ctx.grad(x_new, 0)
        y_mixed, yhat_new = ef.exchange(
            compression_key(cfg.seed, ctx.t, round=1), y, state["yhat"],
            ctx.mix,
        )
        return {
            "x": x_new, "y": y_mixed + g_new - g_prev, "g_prev": g_new,
            "xhat": xhat_new, "yhat": yhat_new,
        }
    x_new = ctx.mix(x) - ctx.eta * y
    g_new = ctx.grad(x_new, 0)
    y_new = ctx.mix(y) + g_new - g_prev
    # Federated local updates (config.local_steps = τ; docs/PERF.md §14):
    # τ−1 extra LOCAL descents along the tracker-corrected direction
    # y_new + (g(v, s) − g_new) — the K-GT-style drift correction: the
    # tracker supplies the network-average gradient estimate and the
    # local term only contributes its deviation from the round's base
    # gradient, so local steps keep GT's heterogeneity correction
    # instead of re-introducing client drift. The tracker recursion
    # itself is untouched (y_new above), so the tracking invariant
    # mean(y_t) = mean(g_prev_t) holds for every τ, and τ = 1 adds zero
    # ops — bitwise the historical round.
    v = local_descent_loop(
        x_new, ctx, lambda vv, s: y_new + ctx.grad(vv, s) - g_new
    )
    return {"x": v, "y": y_new, "g_prev": g_new}


def _comm_payload(config, d: int) -> float:
    # Two compressed exchanges per iteration (x and y); == 2d for
    # compression='none', so uncompressed accounting is unchanged.
    from distributed_optimization_tpu.ops.compression import make_compressor

    return 2.0 * make_compressor(
        config.compression, d, config.compression_k
    ).floats_per_edge


GRADIENT_TRACKING = register_algorithm(
    Algorithm(name="gradient_tracking", init=_init, step=_step,
              gossip_rounds=2, supports_byzantine=True, supports_churn=True,
              supports_local_steps=True, comm_payload=_comm_payload)
)
