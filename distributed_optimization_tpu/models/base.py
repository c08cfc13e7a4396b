"""Problem abstraction: one convex objective family, as pure functions.

The reference dispatches on a ``problem_type`` string in four separate places
(reference ``worker.py:35-44``, ``trainer.py:21-28``, ``trainer.py:142-149``,
``simulator.py:36``). Here the dispatch happens once: a :class:`Problem`
bundles the jittable objective/gradient kernels and is threaded through the
backends as a static argument, so XLA specializes the compiled step per
problem.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

import jax


@dataclasses.dataclass(frozen=True)
class Problem:
    """A convex objective family f(w) = data_term(w; X, y) + (reg/2)‖w‖².

    All callables are pure and jittable:

    - ``objective(w, X, y, reg)`` — full/mini-batch mean objective
      (reference parity: obj_problems.py:3-11, 39-44).
    - ``gradient(w, X, y, reg)`` — mean gradient over the given rows
      (reference parity: obj_problems.py:13-20, 46-53).
    - ``objective_weighted(w, X, y, weights, reg)`` / ``gradient_weighted`` —
      per-sample-weight forms used on the TPU path (static shapes; weights
      encode masking / effective batch size).
    - ``link`` — the scalar-output GLMs' per-row pair on the margin
      ``z = X @ w`` (``ops.losses.MarginLink``: ``loss(z, y)``,
      ``coeff(z, y)``, and ``gradient_at(z, ...)`` for a caller that
      already holds the margins); the four kernels above are that pair's
      (``glm_problem``). None for a family with no scalar margin (softmax).
    - ``param_shape(n_features)`` — the shape of ONE worker's parameter
      for a d-feature dataset: ``(d,)`` for the scalar-output GLMs,
      ``(d, K)`` for the softmax family. The jax scan carries every
      model-shaped leaf as ``[N, *param_shape]`` (gossip, update and
      consensus act on the worker axis and are elementwise over the rest,
      so no layer needs the matrix flattened) and flattens once, on the
      host, at harvest. The kernels take a parameter of either form: the
      problem's own shape in, the same shape out; a flat vector in, a flat
      vector out (what the numpy/C++ tiers, ``run_batch`` and the event
      scan pass).
    - ``param_dim(n_features)`` — the product of ``param_shape``: the
      flat length every boundary speaks (``final_models``, checkpoints,
      gossip payload accounting, the other backends).
    """

    name: str
    objective: Callable[..., jax.Array]
    gradient: Callable[..., jax.Array]
    objective_weighted: Callable[..., jax.Array]
    gradient_weighted: Callable[..., jax.Array]
    param_shape: Callable[[int], tuple] = lambda d: (d,)
    link: Optional[Any] = None

    def param_dim(self, n_features: int) -> int:
        return math.prod(self.param_shape(n_features))


def glm_problem(name: str, link) -> Problem:
    """The Problem of a scalar-output GLM family stated as its margin pair."""
    return Problem(
        name=name,
        objective=link.objective,
        gradient=link.gradient,
        objective_weighted=link.objective_weighted,
        gradient_weighted=link.gradient_weighted,
        link=link,
    )


_REGISTRY: dict[str, Problem] = {}


def register_problem(problem: Problem) -> Problem:
    _REGISTRY[problem.name] = problem
    return problem


def get_problem(
    name: str,
    *,
    huber_delta: float | None = None,
    n_classes: int | None = None,
) -> Problem:
    """Look up a problem family by name ('logistic', 'quadratic', ...).

    ``huber_delta`` binds the Huber transition point (ignored for other
    families); ``None`` means the registered default
    (config.DEFAULT_HUBER_DELTA). ``n_classes`` binds the softmax family's
    class count (ignored elsewhere; ``None`` means the registered default).
    Per-parameter Problems are cached so jit static arguments stay
    identical across calls.
    """
    # Import here so registration happens on first use without import cycles.
    from distributed_optimization_tpu.models import (  # noqa: F401
        huber,
        logistic,
        quadratic,
        softmax,
    )

    if name not in _REGISTRY:
        raise ValueError(f"Unknown problem type: {name!r}; known: {sorted(_REGISTRY)}")
    if name == "huber" and huber_delta is not None:
        return huber.make_huber_problem(float(huber_delta))
    if name == "softmax" and n_classes is not None:
        return softmax.make_softmax_problem(int(n_classes))
    return _REGISTRY[name]
