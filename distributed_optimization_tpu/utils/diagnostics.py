"""Runtime correctness diagnostics (SURVEY.md §5.2 build target).

The reference's only invariant checking is two asserts on the mixing matrix
(reference ``trainer.py:130-131``). The single-threaded simulator has nothing
to race; on a real collective backend the equivalent hazards are non-finite
propagation, nondeterministic compilation, and mis-wired collectives. Three
checks, all usable as preflight guards or in tests:

- ``nan_debugging`` — scoped ``jax_debug_nans`` so the first NaN-producing
  primitive raises with a traceback instead of silently poisoning a 10k-step
  scan;
- ``check_determinism`` — run a function twice and require bitwise-identical
  outputs (XLA compilations are deterministic given fixed inputs; divergence
  means stray host RNG or nondeterministic collective ordering);
- ``check_collectives`` — ppermute round-trip and psum identities on an
  actual mesh: shifting +1 then −1 along the worker axis must reproduce the
  input exactly, and psum of a one-hot must equal the all-ones vector.
"""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Iterator

import numpy as np


@contextlib.contextmanager
def nan_debugging(enable: bool = True) -> Iterator[None]:
    """Scoped jax_debug_nans: raise at the first NaN-producing op."""
    import jax

    if not enable:
        yield
        return
    prev = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", True)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", prev)


def check_determinism(fn, *args, repeats: int = 2) -> None:
    """Require ``fn(*args)`` to be bitwise reproducible across calls.

    Raises AssertionError naming the first differing output leaf.
    """
    import jax

    baseline = jax.tree.map(np.asarray, fn(*args))
    base_leaves, treedef = jax.tree.flatten(baseline)
    for r in range(1, repeats):
        again = jax.tree.map(np.asarray, fn(*args))
        again_leaves, treedef2 = jax.tree.flatten(again)
        if treedef2 != treedef:
            raise AssertionError(
                f"run {r}: output structure changed: {treedef} vs {treedef2}"
            )
        for i, (a, b) in enumerate(zip(base_leaves, again_leaves)):
            if not np.array_equal(a, b, equal_nan=True):
                raise AssertionError(
                    f"run {r}: output leaf {i} is not bitwise reproducible "
                    f"(max abs diff {np.max(np.abs(a - b))})"
                )


def _mesh_and_probe(mesh):
    import jax

    from distributed_optimization_tpu.parallel.mesh import (
        WORKER_AXIS,
        make_worker_mesh,
    )

    if mesh is None:
        mesh = make_worker_mesh(len(jax.devices()))
    k = mesh.devices.size
    axis = mesh.axis_names[0] if mesh.axis_names else WORKER_AXIS
    x = np.arange(k * 3, dtype=np.float32).reshape(k, 3)
    return mesh, k, axis, x


def check_ppermute_roundtrip(mesh=None) -> None:
    """ppermute identity: shifting +1 then −1 along the worker axis must
    reproduce the input exactly. Raises AssertionError on mismatch."""
    import jax
    from jax.sharding import PartitionSpec as P

    mesh, k, axis, x = _mesh_and_probe(mesh)

    @partial(
        jax.shard_map, mesh=mesh, in_specs=P(axis, None), out_specs=P(axis, None)
    )
    def roundtrip(block):
        fwd = [(i, (i + 1) % k) for i in range(k)]
        back = [(i, (i - 1) % k) for i in range(k)]
        out = jax.lax.ppermute(block, axis, fwd)
        return jax.lax.ppermute(out, axis, back)

    got = np.asarray(jax.jit(roundtrip)(x))
    if not np.array_equal(got, x):
        raise AssertionError("ppermute +1/-1 round-trip is not the identity")


def check_psum_identity(mesh=None) -> None:
    """psum identity: the collective sum over the worker axis must equal the
    host-side sum. Raises AssertionError on mismatch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    mesh, k, axis, x = _mesh_and_probe(mesh)

    @partial(
        jax.shard_map, mesh=mesh, in_specs=P(axis, None), out_specs=P(axis, None)
    )
    def total(block):
        return jnp.broadcast_to(
            jax.lax.psum(jnp.sum(block, axis=0, keepdims=True), axis), block.shape
        )

    got = np.asarray(jax.jit(total)(x))
    expect = np.broadcast_to(x.sum(axis=0, keepdims=True), x.shape)
    if not np.allclose(got, expect, rtol=1e-6):
        raise AssertionError("psum over the worker axis disagrees with host sum")


def check_collectives(mesh=None) -> None:
    """Verify ppermute round-trip and psum identities on a device mesh.

    Raises AssertionError on any mismatch. Builds an all-device 1-D mesh when
    none is given; a 1-device mesh degenerates gracefully (self-permutes).
    """
    check_ppermute_roundtrip(mesh)
    check_psum_identity(mesh)


class PreflightError(RuntimeError):
    """One named preflight identity failed; ``check`` is its identity name,
    ``cause`` the underlying assertion/exception."""

    def __init__(self, check: str, cause: BaseException):
        super().__init__(f"preflight check {check!r} failed: {cause}")
        self.check = check
        self.cause = cause


def _determinism_probe() -> None:
    """Bitwise reproducibility of a jit'd program mixing counter-based RNG
    with an MXU matmul and a sort — the op classes whose nondeterministic
    compilation or stray host RNG ``check_determinism`` exists to catch."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe(key):
        x = jax.random.normal(key, (16, 16), dtype=jnp.float32)
        return jnp.sum(x @ x.T), jnp.sort(x.ravel())[:4]

    check_determinism(probe, jax.random.key(0))


# The CLI preflight's named identities (--preflight): run in order, fail
# loudly at the FIRST broken one with its identity named (PreflightError).
PREFLIGHT_CHECKS = (
    ("collectives.ppermute_roundtrip", check_ppermute_roundtrip),
    ("collectives.psum_identity", check_psum_identity),
    ("determinism.jit_rng_matmul_sort", lambda mesh=None: _determinism_probe()),
)


def run_preflight(mesh=None) -> list[str]:
    """Run every preflight identity; return the names that passed.

    Raises ``PreflightError`` naming the first failing identity — the CLI
    surfaces it verbatim so a broken runtime is diagnosed before any
    compile/run time is spent on the main experiment.
    """
    passed: list[str] = []
    for name, check in PREFLIGHT_CHECKS:
        try:
            check(mesh)
        except Exception as e:
            raise PreflightError(name, e) from e
        passed.append(name)
    return passed
