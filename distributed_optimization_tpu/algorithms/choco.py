"""CHOCO-SGD: decentralized SGD with compressed gossip.

Not in the reference (full d-vectors on every edge, reference
``trainer.py:169-173``); this is the compressed-communication capability from
Koloskova, Stich & Jaggi '19 ("Decentralized Stochastic Optimization and
Gossip Algorithms with Compressed Communication" — the report's ref [13]
authors), which trades gossip bandwidth for a consensus step size:

    x_i^{t+1/2} = x_i^t − η_t g_i(x_i^t)
    q_i^t       = Q(x_i^{t+1/2} − x̂_i^t)          ← the ONLY bits transmitted
    x̂_i^{t+1}   = x̂_i^t + q_i^t                    (neighbors update copies)
    x_i^{t+1}   = x_i^{t+1/2} + γ Σ_j W_ij (x̂_j^{t+1} − x̂_i^{t+1}·δ_ij…)
                = x_i^{t+1/2} + γ [(W − I) X̂^{t+1}]_i

With identity compression and γ = 1 this is exactly D-SGD in its
"adapt-then-combine" form, x^{t+1} = W (x^t − η g) (the property the tests
pin down). The stacked form keeps X and X̂ as two [N, d] leaves; the estimate
update is local, and (W − I) X̂ reuses the standard ``mix`` collective, so
compression composes with every mixing implementation. Edge-failure
injection is rejected for CHOCO: a dropped edge means the neighbor's copy of
x̂_j goes stale (it never received q_j), which the single shared X̂ leaf
cannot represent — faithful modeling needs per-edge [N, N, d] staleness
state, so rather than report fault-free convergence with fault-discounted
bandwidth, the combination raises.

Comms accounting: each edge carries the compressor's payload instead of d
floats per iteration (``comm_payload``, consumed by the backends' float
accounting) — top-k/random-k count k values + k indices.
"""

from __future__ import annotations

from distributed_optimization_tpu.algorithms.base import (
    Algorithm,
    State,
    StepContext,
    register_algorithm,
)
from distributed_optimization_tpu.ops.compression import (
    compression_key,
    make_compressor,
    make_error_feedback,
    row_dim,
)


def _init(x0, config, *, neighbor_sum=None) -> State:
    ef = make_error_feedback(
        config.compression, row_dim(x0), config.compression_k,
        config.choco_gamma,
    )
    return {"x": x0, "xhat": ef.init(x0)}


def _step(state: State, ctx: StepContext) -> State:
    # The original CHOCO recursion, now phrased through the SHARED
    # error-feedback exchange (ops/compression.py::ErrorFeedbackGossip —
    # the same machinery compressed dsgd/gradient_tracking run): ops and
    # the counter-based compressor stream are term-for-term the
    # pre-refactor step, so trajectories are bitwise-unchanged
    # (tests/test_choco.py pins the identity-compression == D-SGD
    # equivalence and the refactor parity).
    cfg = ctx.config
    x, xhat = state["x"], state["xhat"]
    ef = make_error_feedback(
        cfg.compression, row_dim(x), cfg.compression_k, cfg.choco_gamma
    )
    g = ctx.grad(x, 0)
    x_half = x - ctx.eta * g
    if ctx.compressed_mix is not None:
        # Worker-mesh wire form: only q's boundary rows cross devices; the
        # persistent receiver-side copy rides the xhat_halo state leaf
        # (seeded to zeros by the backend). Local algebra is term-for-term
        # the branch below — bitwise vs unsharded at matched N.
        x_new, xhat_new, halo_new = ef.exchange_sharded(
            compression_key(cfg.seed, ctx.t), x_half, xhat,
            state["xhat_halo"], ctx.compressed_mix,
        )
        return {"x": x_new, "xhat": xhat_new, "xhat_halo": halo_new}
    x_new, xhat_new = ef.exchange(
        compression_key(cfg.seed, ctx.t), x_half, xhat, ctx.mix
    )
    return {"x": x_new, "xhat": xhat_new}


def _comm_payload(config, d: int) -> float:
    return make_compressor(config.compression, d, config.compression_k).floats_per_edge


CHOCO = register_algorithm(
    Algorithm(
        name="choco",
        init=_init,
        step=_step,
        gossip_rounds=1,
        comm_payload=_comm_payload,
        # See module docstring: lost q deliveries imply per-neighbor stale
        # estimate copies the shared-X̂ simulation cannot represent.
        supports_edge_faults=False,
    )
)
