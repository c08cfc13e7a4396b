"""Push-sum stochastic gradient (SGP) over directed graphs.

Not present in the reference, whose Metropolis-Hastings construction
(reference ``trainer.py:118-126``) requires symmetric links. Push-sum
(Kempe-Dobra-Gehrke 2003; Nedić-Olshevsky 2016; stochastic-gradient form
SGP, Assran-Loizou-Markopoulos-Rabbat 2019, Algorithm 1) is the directed
continuation of that family: with only a COLUMN-stochastic mixing matrix A
(each node splits its mass over its out-neighbors — all a node can control
when links are one-way), plain gossip converges to the Perron-weighted
average instead of the true one. Push-sum tracks the induced mass imbalance
with a scalar weight per node and divides it back out:

    num_{t+1} = A (num_t − η_t ∇F(z_t))     — gradient-push on the numerator
    w_{t+1}   = A w_t                        — same chain on the mass, w_0 = 1
    z_{t+1}   = num_{t+1} / w_{t+1}          — the de-biased estimate

Because columns of A sum to 1, Σ_i num_i and Σ_i w_i = N are conserved by
every mix, so mean(num_t) tracks the exact average trajectory and
z_i → mean(num) for every node (A primitive via self-loops). Gradients are
evaluated at the de-biased z (SGP), not the raw numerator.

State layout: ``x`` holds z — the per-worker ESTIMATES — so every metric,
checkpoint, and ``final_models`` consumer sees the quantity that means
"model" here, uniformly with the other algorithms; ``num``/``w`` carry the
push-sum recursion. On a doubly stochastic W (undirected topologies) w
stays exactly 1 and the rule reduces to adapt-then-combine D-SGD — a
degenerate case the tests pin.

Comms: one gossip round transmits the numerator (d floats) plus the scalar
mass (1 float) per directed edge, i.e. ``comm_payload = d + 1`` — the +1 is
push-sum's entire bandwidth overhead over plain gossip.

``supports_edge_faults=True`` (round 5): the failure-injection machinery
(``parallel/faults.py``) realizes the faithful model for BOTH link
orientations. On directed topologies each directed edge drops
independently and every node re-splits its mass column-stochastically over
its SURVIVING out-links (``column_stochastic_weights``) — exactly the
time-varying directed setting of Nedić-Olshevsky 2016, whose analysis is
push-sum's convergence guarantee here; mass conservation Σ_i w_i = N holds
for every realization because every realized matrix is column-stochastic
(pinned through the real backend fault paths by
tests/test_push_sum.py::test_push_sum_mass_conserved_under_directed_faults).
On undirected topologies the realized MH matrices are doubly stochastic,
so w stays exactly 1 and faulty push-sum degenerates to faulty D-SGD.
Stragglers compose: an inactive node's column collapses to identity (it
keeps its mass) and the backend freezes all three state leaves.
"""

from __future__ import annotations

import jax.numpy as jnp

from distributed_optimization_tpu.algorithms.base import (
    Algorithm,
    State,
    StepContext,
    register_algorithm,
)


def _init(x0, config, *, neighbor_sum=None) -> State:
    # ones_like of a column slice inherits x0's worker-axis sharding, so the
    # mass vector lives where its worker's rows live on a mesh.
    # One scalar mass per worker, with a unit axis for every parameter axis
    # ([N, 1] for an [N, d] stack) so num / w broadcasts at any rank.
    w0 = jnp.ones_like(x0[(slice(None),) + (slice(1),) * (x0.ndim - 1)])
    return {"x": x0, "num": x0, "w": w0}


def _step(state: State, ctx: StepContext) -> State:
    z, num, w = state["x"], state["num"], state["w"]
    g = ctx.grad(z, 0)  # SGP: gradient at the de-biased estimate
    num_new = ctx.mix(num - ctx.eta * g)
    w_new = ctx.mix(w)
    return {"x": num_new / w_new, "num": num_new, "w": w_new}


PUSH_SUM = register_algorithm(
    Algorithm(
        name="push_sum",
        init=_init,
        step=_step,
        gossip_rounds=1,
        supports_edge_faults=True,
        # d model floats + the scalar push-sum mass per edge per round.
        comm_payload=lambda config, d: float(d + 1),
    )
)
