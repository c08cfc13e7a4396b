"""Profiling and phase timing (SURVEY.md §5.1 build target).

The reference's only observability is coarse per-iteration wall-clock deltas
(reference ``trainer.py:35,63,71``). Here:

- ``PhaseTimer`` — named phase accounting (data gen, oracle, compile,
  steady-state run), so compile time never pollutes the iters/sec headline
  (the jax backend already separates AOT compile from execution). The
  ``Simulator`` owns one (``phase_timer``): data-gen and oracle are timed
  at construction, each run splits into compile/run, and the phases land
  in the text report, ``--json``, and the telemetry manifests
  (docs/OBSERVABILITY.md). Since ISSUE-10 it IS the hierarchical span
  tracer (``observability/spans.Tracer``): the flat ``{name: seconds}``
  surface is unchanged, and every timed phase is also recorded as a span
  with nesting and timestamps, exportable as a Chrome trace;
- ``trace`` — context manager around ``jax.profiler`` trace collection for
  TensorBoard/XProf; raises if the profiler cannot start.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, Optional

from distributed_optimization_tpu.observability.spans import Tracer

# The flat phase accounting grew into hierarchical span tracing
# (ISSUE-10); PhaseTimer remains the name the rest of the repo
# constructs. Tracer is a strict superset: ``.phase(name)`` context
# manager, writable ``.phases`` dict, ``.report()`` — plus ``.span()``
# nesting, ``.add_span()`` post-hoc intervals, and Chrome trace export.
PhaseTimer = Tracer


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """Collect a jax.profiler trace into ``log_dir`` (no-op if None).

    View with TensorBoard's profile plugin / XProf, or read the
    ``.xplane.pb`` with ``jax.profiler.ProfileData``. A profiler that will
    not start raises: a requested trace that silently is not there would
    leave every metric read from it unmeasured.

    The Python tracer is off (on, a T=30 run held 2.3 M host events, 130
    MB — PERF.md): the host planes then hold the program's own spans
    (``dopt.run.*`` and whatever else runs under a ``Tracer``) over the
    device's operations, in a trace of a few MB.
    """
    if log_dir is None:
        yield
        return
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
