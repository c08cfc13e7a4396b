"""Backend dispatch and the shared run-result container.

Mirrors the reference's trainer contract — ``run(...) -> (history, final
model)`` plus a ``total_floats_transmitted`` attribute (reference
``trainer.py:33,74,154,197``, read at ``simulator.py:81``) — as one dataclass
returned by every backend, so the simulator layer is backend-agnostic (the
``--backend`` selection named in BASELINE.json's north star).
"""

from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

from distributed_optimization_tpu.metrics import RunHistory


def x64_scope(config):
    """Scoped ``enable_x64`` for float64 configs.

    Without it jax silently truncates every array to float32, defeating
    the fidelity dtype — the single definition of that stance, shared by
    every jax execution path (jax_backend, tensor_parallel).
    """
    import jax

    return (
        jax.enable_x64()
        if config.dtype == "float64" and not jax.config.jax_enable_x64
        else contextlib.nullcontext()
    )


@dataclasses.dataclass
class BackendRunResult:
    history: RunHistory
    final_models: np.ndarray  # [N, d] per-worker models after T iterations
    final_avg_model: np.ndarray  # [d] network average (the reported model)
    # Full final algorithm state (every leaf, e.g. gradient tracking's
    # y/g_prev), host-fetched. Populated only on request
    # (jax_backend.run(return_state=True)) — used by invariant-level tests
    # (e.g. GT's tracking invariant under failure injection).
    final_state: dict | None = None

    @property
    def total_floats_transmitted(self) -> float:
        return self.history.total_floats_transmitted


def run_algorithm(config, dataset, f_opt, **kwargs) -> BackendRunResult:
    """Run ``config.algorithm`` on ``config.backend`` over ``dataset``.

    ``dataset`` is a HostDataset; backends derive their preferred layout.
    Extra kwargs are backend-specific (mesh=..., batch_schedule=..., ...).
    """
    if config.backend == "jax":
        if config.tp_degree > 1:
            # Tensor parallelism (round-5 capability, product-surfaced in
            # round 6): the config validated the supported combination
            # (softmax + dsgd + ring); the TP module validates the
            # dataset-dependent full-batch requirement and the mesh fit.
            from distributed_optimization_tpu.parallel import tensor_parallel

            return tensor_parallel.run_tp_backend(
                config, dataset, f_opt, **kwargs
            )
        from distributed_optimization_tpu.backends import jax_backend

        return jax_backend.run(config, dataset, f_opt, **kwargs)
    if config.backend == "numpy":
        from distributed_optimization_tpu.backends import numpy_backend

        return numpy_backend.run(config, dataset, f_opt, **kwargs)
    if config.backend == "cpp":
        from distributed_optimization_tpu.backends import cpp_backend

        return cpp_backend.run(config, dataset, f_opt, **kwargs)
    raise ValueError(f"Unknown backend: {config.backend!r}")


def run_algorithm_batch(config, dataset, f_opt, **kwargs):
    """Run R seed replicates of ``config`` as ONE vmapped program.

    Returns a ``jax_backend.BatchRunResult`` (per-replica trajectories +
    aggregate sweep throughput). Only the jax backend compiles a batched
    program; the config validation already rejects ``replicas > 1``
    elsewhere, and a direct call with another backend gets the same
    explanation.
    """
    if config.backend != "jax":
        raise ValueError(
            "replica-batched execution vmaps the jax scan; backend="
            f"{config.backend!r} runs one trajectory at a time — use "
            "backend='jax' or loop single runs"
        )
    from distributed_optimization_tpu.backends import jax_backend

    return jax_backend.run_batch(config, dataset, f_opt, **kwargs)
