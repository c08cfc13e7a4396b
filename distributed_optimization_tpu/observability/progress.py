"""In-flight progress streaming: heartbeats from the executing backends.

A fused run is one opaque XLA call: between submit and result there is
nothing to look at, which is exactly wrong for a serving daemon under
load and for the long async/federated runs this repo now executes. This
module defines the heartbeat contract the backends emit at CHUNK
boundaries (``jax_backend.run(..., progress_cb=...)``: the sequential
scan's segments, batched segments, async eval-chunk loop) and the
bounded pub/sub stream the daemon's ``/v1/progress/<request_id>`` channel
reads.

Discipline (the ``config.telemetry`` convention, asserted in tests):
progress OFF changes nothing — same code path, same compiled program,
bitwise-identical trajectories. Progress ON executes the SAME flat scan
in segments through the already-tested continuation machinery, so
trajectories stay bitwise-identical too; the only cost is one host sync
per heartbeat (measured ≤3% steady-state in
``docs/perf/observatory.json``).

The heartbeat payload is the live form of the post-hoc health block:
iteration/event index and wall seconds always; current gap/consensus when
metrics are collected; the realized windowed-connectivity B̂ over the
executed prefix when a synchronous fault process is active (Koloskova et
al. '20 — the quantity time-varying-gossip convergence depends on); and
realized staleness quantiles for async runs (Assran et al. '19's
straggler accounting, live).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Iterator, Optional

import numpy as np

# Default cap on buffered heartbeats per stream: late subscribers replay
# at most this many events. A run emits one per progress_every evals, so
# 4096 covers every realistic cadence; beyond it the oldest drop (the
# stream is a live channel, not an archive — the RunTrace manifest is).
DEFAULT_STREAM_CAPACITY = 4096


@dataclasses.dataclass
class ProgressEvent:
    """One heartbeat. ``kind``: 'chunk' (round-based paths), 'async'
    (event path), or 'lifecycle' (serving queued/running/done markers)."""

    kind: str
    iteration: int                    # global iteration/round index reached
    n_iterations: int                 # the run's horizon
    wall_seconds: float               # since the run (not the queue) started
    gap: Optional[float] = None      # current suboptimality (metrics on)
    consensus: Optional[float] = None
    # Live realized windowed-connectivity over the executed prefix
    # (synchronous fault processes only; None when n/a or over budget).
    bhat: Optional[int] = None
    # Async extras: executed event index and realized staleness quantiles
    # over the executed window.
    event_index: Optional[int] = None
    n_events: Optional[int] = None
    staleness_p50: Optional[float] = None
    staleness_p90: Optional[float] = None
    staleness_max: Optional[float] = None
    # Replica-batched extras: per-replica gaps at this boundary (small R).
    gap_per_replica: Optional[list] = None
    # Lifecycle / free-form annotations (status strings, cohort facts).
    status: Optional[str] = None
    extra: Optional[dict] = None

    def to_dict(self) -> dict:
        out: dict[str, Any] = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is None:
                continue
            if isinstance(v, (np.floating, np.integer)):
                v = v.item()
            out[f.name] = v
        return out


def format_progress_line(ev: ProgressEvent, label: str = "") -> str:
    """One human-readable heartbeat line (the CLI ``--progress`` output)."""
    head = f"[progress{':' + label if label else ''}]"
    pct = 100.0 * ev.iteration / max(ev.n_iterations, 1)
    parts = [
        f"{head} iter {ev.iteration}/{ev.n_iterations} ({pct:.0f}%)",
        f"t={ev.wall_seconds:.2f}s",
    ]
    if ev.gap is not None and np.isfinite(ev.gap):
        parts.append(f"gap={ev.gap:.3e}")
    if ev.consensus is not None and np.isfinite(ev.consensus):
        parts.append(f"cons={ev.consensus:.3e}")
    if ev.bhat is not None:
        parts.append(f"B̂={ev.bhat}")
    if ev.event_index is not None:
        parts.append(f"events={ev.event_index}/{ev.n_events}")
    if ev.staleness_p90 is not None:
        parts.append(
            f"staleness p50/p90={ev.staleness_p50:.0f}/"
            f"{ev.staleness_p90:.0f}"
        )
    if ev.status is not None:
        parts.append(ev.status)
    return " ".join(parts)


class ProgressStream:
    """Bounded, thread-safe heartbeat channel (one per served request).

    Producers (``SimulationService._execute``'s backend callback) call
    ``publish``; consumers (the daemon's ``/v1/progress`` handler) call
    ``follow`` and receive every event exactly once in order, blocking
    for new ones until the stream is closed. Events carry a monotone
    ``seq`` so a reconnecting client can resume with ``after_seq``.
    """

    def __init__(self, capacity: int = DEFAULT_STREAM_CAPACITY):
        self._cond = threading.Condition()
        self._events: list[tuple[int, dict]] = []  # (seq, payload)
        self._capacity = max(int(capacity), 1)
        self._next_seq = 0
        self._closed = False

    def publish(self, event) -> int:
        payload = event.to_dict() if hasattr(event, "to_dict") else dict(event)
        with self._cond:
            if self._closed:
                return self._next_seq  # late heartbeat after close: drop
            seq = self._next_seq
            self._next_seq += 1
            payload = {"seq": seq, **payload}
            self._events.append((seq, payload))
            if len(self._events) > self._capacity:
                del self._events[0]
            self._cond.notify_all()
            return seq

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()

    @property
    def closed(self) -> bool:
        with self._cond:
            return self._closed

    def events(self, after_seq: int = -1) -> list[dict]:
        """Buffered events with seq > after_seq (non-blocking snapshot)."""
        with self._cond:
            return [p for s, p in self._events if s > after_seq]

    def follow(
        self, after_seq: int = -1, timeout: Optional[float] = None,
        poll_s: float = 0.2,
    ) -> Iterator[dict]:
        """Yield events in order, blocking for new ones; stops when the
        stream is closed and drained, or when ``timeout`` seconds elapse
        without the stream closing (bounded wait for the HTTP handler)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        last = after_seq
        while True:
            with self._cond:
                fresh = [p for s, p in self._events if s > last]
                if not fresh:
                    if self._closed:
                        return
                    if deadline is not None and time.monotonic() >= deadline:
                        return
                    self._cond.wait(timeout=poll_s)
                    continue
            for payload in fresh:
                last = payload["seq"]
                yield payload


# -------------------------------------------------- live B̂ over the prefix


def make_live_bhat(config, max_cells: int = 200_000):
    """``fn(t) -> Optional[int]``: realized windowed-connectivity B̂ over
    the first ``t`` rounds of this config's fault timeline — the live form
    of ``telemetry.realized_bhat`` — or None when the notion does not
    apply (no synchronous fault process / matching schedule / centralized)
    or the per-heartbeat rebuild would exceed ``max_cells`` timeline
    cells (honesty over silent cost: heartbeats must stay cheap).

    The timeline is built ONCE host-side (bitwise the realization the
    backend consumes — the ``parallel/faults.py`` purity contract) and
    each call measures B̂ on a prefix view.
    """
    from distributed_optimization_tpu.algorithms import get_algorithm

    if not get_algorithm(config.algorithm).is_decentralized:
        return None
    if getattr(config, "execution", "sync") == "async":
        return None
    if config.gossip_schedule != "synchronous":
        return None
    from distributed_optimization_tpu.parallel import build_topology
    from distributed_optimization_tpu.parallel.faults import (
        _edge_list,
        config_faults_active,
        timeline_for_config,
        windowed_connectivity,
    )

    if not config_faults_active(config):
        return None
    topo = build_topology(
        config.topology, config.n_workers,
        erdos_renyi_p=config.erdos_renyi_p,
        seed=config.resolved_topology_seed(),
        impl=config.resolved_topology_impl(),
        sampler=config.resolved_topology_sampler(),
    )
    n_edges = max(len(_edge_list(topo)), 1)
    if config.n_iterations * n_edges > max_cells:
        return None
    tl = timeline_for_config(config, topo, config.n_iterations)

    def prefix(arr, t):
        return None if arr is None else arr[:t]

    def live_bhat(t: int) -> Optional[int]:
        t = int(min(max(t, 1), tl.horizon))
        tl_t = dataclasses.replace(
            tl,
            horizon=t,
            edge_up=prefix(tl.edge_up, t),
            node_up=prefix(tl.node_up, t),
            rejoin=prefix(tl.rejoin, t),
            part_up=prefix(tl.part_up, t),
        )
        return windowed_connectivity(tl_t, topo)

    return live_bhat


def progress_heartbeat_counter():
    """The registry counter every emitted heartbeat increments."""
    from distributed_optimization_tpu.observability.metrics_registry import (
        metrics_registry,
    )

    return metrics_registry().counter(
        "dopt_progress_heartbeats_total",
        "Progress heartbeats emitted by executing backends",
    )
