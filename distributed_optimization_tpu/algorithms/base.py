"""Algorithm abstraction: a pure init/step pair over an [N, d] model stack.

The reference hard-wires its two algorithms as stateful trainer classes with
Python worker loops (reference ``trainer.py:7-74`` centralized,
``trainer.py:154-197`` D-SGD). Here an algorithm is a *pure step rule* over a
pytree state whose leaves are ``[N, d]``-stacked arrays, so the same rule

- runs inside ``jax.lax.scan`` under ``jit`` on the TPU path,
- runs step-at-a-time under numpy on the fidelity path, and
- is agnostic to how its collectives are realized (the ``StepContext``
  carries ``mix``/``neighbor_sum`` closures that may be a dense matmul, a
  GSPMD stencil, a table-driven gather, or the worker mesh's explicit
  ppermute halo forms).

``[N, d]`` stands for ``[N, *param_shape]``: the jax scan carries the
problem's own parameter shape (``[N, d, K]`` for softmax — models/base.py),
so a rule touches the worker axis only and is elementwise over the rest.

Every state pytree has an ``x: [N, d]`` leaf (per-worker models). The
centralized algorithm keeps all rows identical — its "mixing" is the exact
all-reduce mean a parameter server performs, which on the mesh compiles to a
single ``psum`` (SURVEY.md C3).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax

Array = Any  # jax.Array or np.ndarray — algorithms are backend-polymorphic
State = Dict[str, Array]


@dataclasses.dataclass(frozen=True)
class StepContext:
    """Everything a step rule may touch, with backend-supplied semantics.

    ``grad(params, slot)``: stochastic gradient of the local objective at
    ``params`` ([N, d] -> [N, d]); ``slot`` (an int) distinguishes multiple
    independent batch draws within one iteration, so algorithms that need two
    gradient evaluations stay reproducible.
    ``mix``: x -> W x (gossip averaging).
    ``neighbor_sum``: x -> A x (sum over graph neighbors, for ADMM).
    ``eta``: learning rate for this iteration (scalar).
    ``degrees``: [N, 1] node degrees (a unit axis per parameter axis).
    ``config``: the ExperimentConfig (static hyperparameters only).
    ``compressed_mix``: optional sharded wire form of the error-feedback
    exchange, (q, x̂⁺, halo) -> (W x̂⁺, halo⁺)
    (``collectives.make_halo_compressed_mixing_op``) — present only on the
    worker-mesh path with compression, where the state carries the
    persistent receiver-side halo leaves; algorithms route their
    ``ErrorFeedbackGossip`` exchanges through ``exchange_sharded`` with it.
    """

    grad: Callable[[Array, int], Array]
    mix: Callable[[Array], Array]
    neighbor_sum: Callable[[Array], Array]
    eta: Array
    t: Array
    degrees: Array
    config: Any
    compressed_mix: Any = None


@dataclasses.dataclass(frozen=True)
class Algorithm:
    """A named pure step rule.

    ``init(x0, config, *, neighbor_sum=None) -> state``: build the state
    pytree from the [N, d] init; ``neighbor_sum`` (x -> A x), when supplied by
    the backend, lets algorithms that carry a neighbor aggregate (ADMM)
    materialize it for arbitrary x0 once, eagerly, outside the scanned loop.
    ``step(state, ctx) -> state``: one synchronous iteration.
    ``gossip_rounds``: model-sized gossip exchanges per iteration (for the
    analytic floats-transmitted metric, reference trainer.py:169-170).
    ``is_decentralized``: False for the parameter-server pattern (its comms
    cost is 2·N·d per iteration instead, reference trainer.py:44-61).
    """

    name: str
    init: Callable[..., State]
    step: Callable[[State, StepContext], State]
    gossip_rounds: int = 1
    is_decentralized: bool = True
    # Whether the step rule stays correct when the graph varies over time
    # (edge-failure injection). True for mix-based rules — any doubly
    # stochastic W_t preserves the average. False for rules that combine
    # ``neighbor_sum`` with static degree constants (ADMM's dual update),
    # which a dropped edge would bias.
    supports_edge_faults: bool = True
    # Whether the step rule tolerates crash-recovery churn (mttf/mttr:
    # multi-round outages with frozen state and a rejoin policy —
    # parallel/faults.py). Opt-in and STRICTER than supports_edge_faults:
    # beyond per-round doubly stochastic realizations, the rule must stay
    # meaningful when a node's whole state is frozen for many consecutive
    # rounds and may be warm-restarted from the neighborhood average on
    # rejoin. True for D-SGD and gradient tracking (the freeze covers
    # every leaf and each realized W_t keeps the frozen row at identity,
    # so GT's tracking invariant mean(y)=mean(g_prev) survives outages of
    # any length; neighbor_restart touches only the model row). False for
    # push-sum — a warm restart of z cannot be split consistently across
    # its (num, w) mass pair, so rejoin policies would silently break the
    # debiasing — and for EXTRA/ADMM/CHOCO, which already reject
    # time-varying graphs.
    supports_churn: bool = False
    # Whether the step rule tolerates Byzantine injection + robust
    # neighbor aggregation (docs/BYZANTINE.md). Opt-in: only rules whose
    # updates go through ``ctx.mix`` alone and whose analyses cover
    # screened (non-doubly-stochastic) aggregation qualify — D-SGD and
    # gradient tracking (He-Karimireddy-Jaggi 2022). False for EXTRA
    # (fixed point needs the static linear W), ADMM (dual updates pair
    # neighbor sums with static degrees), CHOCO (shared compressed
    # estimates cannot represent screened-out updates), push-sum (clipping
    # breaks the column-stochastic mass conservation its debiasing needs),
    # and the centralized pattern (no peer edges to attack).
    supports_byzantine: bool = False
    # Whether the step rule accepts ``config.local_steps`` > 1 — τ gradient
    # descents per gossip round, the federated local-update regime
    # (Koloskova et al. '20; docs/PERF.md §14). True only for rules whose
    # round structure survives extra purely-local descents: D-SGD (plain
    # local SGD between gossips) and gradient tracking (tracker-corrected
    # local steps). config.LOCAL_STEP_ALGORITHMS mirrors this flag so
    # validation stays jax-free.
    supports_local_steps: bool = False
    # Whether ``step`` takes its first gradient at the carried models
    # themselves: ``ctx.grad(state["x"], 0)``, that very array. The jax scan
    # may then hand that call the margins X·x it carried from the last eval
    # instead of reading the shards for them (jax_backend.
    # _forward_is_carried), or the gradient itself where the eval's one
    # visit of the shards made it (_visit_is_fused); a gradient anywhere
    # else (x/w, a mixed or half-stepped model, a later slot) is computed
    # as ever.
    first_grad_at_x: bool = False
    # Optional override of the per-edge float payload for comms accounting:
    # (config, d) -> floats per edge per iteration. None = d · gossip_rounds
    # (full-vector exchange). Compressed-gossip algorithms set this.
    comm_payload: Optional[Callable[[Any, int], float]] = None


# Python-unroll budget for the τ−1 extra local descents inside one scan
# trip: beyond it the jax path switches to ``lax.fori_loop`` so program
# size stays bounded (the numpy oracle always takes the Python loop).
LOCAL_UNROLL_MAX = 8


def local_descent_loop(v: Array, ctx: "StepContext", direction) -> Array:
    """Run the round's τ−1 extra LOCAL descents (``config.local_steps``).

    ``direction(v, s)`` maps the current iterate and the in-round slot
    index s ∈ [1, τ) to the descent direction for that local step (plain
    ``ctx.grad(v, s)`` for D-SGD; the tracker-corrected direction for
    gradient tracking). τ = 1 returns ``v`` untouched — ZERO added ops,
    which is what makes the τ=1 reduction bitwise. Unrolled in Python up
    to ``LOCAL_UNROLL_MAX`` (also the only form the backend-polymorphic
    numpy path takes); larger τ on the jax backend runs a ``fori_loop``
    (the slot index reaches ``grad`` as traced data — counter-based batch
    keys fold it in like any other integer).
    """
    tau = ctx.config.local_steps
    if tau <= 1:
        return v
    if ctx.config.backend == "jax" and tau - 1 > LOCAL_UNROLL_MAX:
        from jax import lax

        return lax.fori_loop(
            1, tau, lambda s, vv: vv - ctx.eta * direction(vv, s), v
        )
    for s in range(1, tau):
        v = v - ctx.eta * direction(v, s)
    return v


_REGISTRY: dict[str, Algorithm] = {}


def register_algorithm(algo: Algorithm) -> Algorithm:
    _REGISTRY[algo.name] = algo
    return algo


def get_algorithm(name: str) -> Algorithm:
    from distributed_optimization_tpu.algorithms import (  # noqa: F401
        admm,
        centralized,
        choco,
        dsgd,
        extra,
        gradient_tracking,
        push_sum,
    )

    if name not in _REGISTRY:
        raise ValueError(f"Unknown algorithm: {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]
