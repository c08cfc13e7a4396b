"""Decentralized SGD (D-SGD / DGD, D-PSGD form of Lian et al. 2017).

Capability parity with reference ``trainer.py:154-197``: each iteration every
worker computes its stochastic gradient at its *local, pre-mix* model
(trainer.py:166 — the D-PSGD ordering), gossips models through the mixing
matrix, and steps:

    x_{i,t+1} = Σ_j W_ij x_{j,t} − η_t g_i(x_{i,t})

Communication cost is Σ_i deg_i · d floats per iteration (trainer.py:169-170).

TPU-native form: the gossip Σ_j W_ij x_j is ``ctx.mix`` — a ppermute stencil
(ring/torus), an all-reduce mean (fully connected), or a dense contraction
(irregular graphs) — instead of the reference's simulated ``W @ models``.

Compressed gossip (``config.compression != 'none'``, ISSUE-6 tentpole): the
exchange routes through the shared error-feedback machinery
(``ops/compression.py::ErrorFeedbackGossip`` — generalized out of CHOCO):
the state carries a per-worker estimate x̂ and each round transmits only
Q(x_half − x̂), the adapt-then-combine recursion

    x_{t+1/2} = x_t − η g(x_t);   x̂⁺ = x̂ + Q(x_{t+1/2} − x̂)
    x_{t+1}   = x_{t+1/2} + γ (W − I) X̂⁺

— i.e. compressed D-SGD IS CHOCO-SGD run under the D-SGD registration,
which is exactly the point: the algorithm the production gather path runs
gains the bytes-per-round knob without changing rule. ``comm_payload``
feeds the compressor's per-edge float cost into the analytic and realized
comms accounting (what the bytes-vs-gap benches measure).
"""

from __future__ import annotations

from distributed_optimization_tpu.algorithms.base import (
    Algorithm,
    State,
    StepContext,
    local_descent_loop,
    register_algorithm,
)


def _init(x0, config, *, neighbor_sum=None) -> State:
    if config.compression != "none":
        from distributed_optimization_tpu.ops.compression import (
            make_error_feedback,
            row_dim,
        )

        ef = make_error_feedback(
            config.compression, row_dim(x0), config.compression_k,
            config.choco_gamma,
        )
        return {"x": x0, "xhat": ef.init(x0)}
    return {"x": x0}


def _step(state: State, ctx: StepContext) -> State:
    x = state["x"]
    if "xhat" in state:
        # Error-feedback compressed gossip (see the module docstring).
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
        g = ctx.grad(x, 0)
        x_half = x - ctx.eta * g
        if ctx.compressed_mix is not None:
            # Worker-mesh wire form (collectives.make_halo_compressed_
            # mixing_op): q's boundary rows over ppermute, receiver copies
            # in the xhat_halo leaf. Same local algebra — bitwise vs the
            # unsharded branch below at matched N.
            x_new, xhat_new, halo_new = ef.exchange_sharded(
                compression_key(cfg.seed, ctx.t), x_half, state["xhat"],
                state["xhat_halo"], ctx.compressed_mix,
            )
            return {"x": x_new, "xhat": xhat_new, "xhat_halo": halo_new}
        x_new, xhat_new = ef.exchange(
            compression_key(cfg.seed, ctx.t), x_half, state["xhat"],
            ctx.mix,
        )
        return {"x": x_new, "xhat": xhat_new}
    grads = ctx.grad(x, 0)  # at the local pre-mix models (D-PSGD ordering)
    x_new = ctx.mix(x) - ctx.eta * grads
    # Federated local updates (config.local_steps = τ; docs/PERF.md §14):
    # the gossip-fused first descent above is local step 0 of the round;
    # τ−1 purely-local SGD descents follow, each on its own batch draw
    # (slot s) at the round's step size — Koloskova et al. '20's
    # local-update regime with the D-PSGD ordering kept for step 0, so
    # τ = 1 is bitwise the historical one-step round.
    x_new = local_descent_loop(x_new, ctx, lambda v, s: ctx.grad(v, s))
    return {"x": x_new}


def _comm_payload(config, d: int) -> float:
    # Per-edge floats per iteration: the compressor's payload (== d for
    # compression='none', so uncompressed accounting is unchanged).
    from distributed_optimization_tpu.ops.compression import make_compressor

    return make_compressor(
        config.compression, d, config.compression_k
    ).floats_per_edge


DSGD = register_algorithm(
    Algorithm(name="dsgd", init=_init, step=_step, gossip_rounds=1,
              supports_byzantine=True, supports_churn=True,
              supports_local_steps=True, first_grad_at_x=True,
              comm_payload=_comm_payload)
)
