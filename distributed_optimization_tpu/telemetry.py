"""Flight recorder: trace buffers, cost accounting, versioned run manifests.

The backends compile whole runs into fused scans where everything between
eval points is invisible; this module is the structured-observability layer
on top of them (ISSUE-5 tentpole):

- **trace buffers** (``TRACE_FIELDS``): opt-in per-eval-row health series —
  per-worker gradient/parameter norms, non-finite sentinel counts, realized
  fault-layer liveness (node-up masks, live-edge counts), and robust-
  aggregation activity — recorded INSIDE the compiled scan through the
  scan's stacked outputs (never the carry, so telemetry off or on leaves
  the optimization dataflow untouched; tests assert bitwise trajectory
  parity). Both backends emit the same schema: jax fills the rows from the
  scan ``ys``, the numpy oracle from its per-iteration loop.
- **cost & phase accounting**: XLA ``Lowered.cost_analysis()`` FLOPs/bytes
  per compiled program (``cost_from_lowered``) and wall-clock phase timings
  (``utils.profiling.PhaseTimer``, wired by the Simulator) collected into
  one structure instead of scattered locals.
- **versioned run manifests** (``RunTrace``): one schema-versioned artifact
  per run — config + hash, backend/platform, phase timings, cost analysis,
  trace buffers, and a derived run-health summary including the realized
  windowed-connectivity B̂ over the run (the quantity time-varying-gossip
  convergence actually depends on — Koloskova et al. '20; see
  ``parallel/faults.py::windowed_connectivity``). Serialized as JSON/JSONL
  by the Simulator (``write_telemetry``), the CLI (``--telemetry OUT``),
  and the bench scripts (``write_bench_manifest`` sidecars).

This module is jax-free at import time (like ``config.py``); anything that
needs the topology/fault machinery imports it lazily.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
from pathlib import Path
from typing import Any, Optional

import numpy as np

# Manifest / trace schema version. Bump when a field is added, removed, or
# changes meaning; ``RunTrace.from_dict`` rejects versions it does not know.
# v2 (ISSUE-10): every manifest carries a ``provenance`` block (git SHA +
# dirty flag, jax version, device kind — before it, only the platform
# string was captured) and an optional ``spans`` list (Chrome trace
# events from the span tracer).
SCHEMA_VERSION = 2

# The trace-buffer schema: field name -> row shape kind. 'per_worker'
# fields are [n_evals, N] float32, 'scalar' fields are [n_evals] float32
# (one row per eval point; the replica-batched path adds a leading [R]).
# Both backends emit EXACTLY these keys when telemetry is on — the
# jax-vs-numpy schema-parity test pins it.
TRACE_FIELDS: dict[str, str] = {
    # L2 norm of each worker's minibatch gradient at the eval boundary,
    # evaluated at the post-step state with the SAME batch realization the
    # eval iteration's step consumed (counter-based keys on jax; the cached
    # last-drawn indices on the numpy oracle).
    "grad_norm": "per_worker",
    # L2 norm of each worker's model row.
    "param_norm": "per_worker",
    # Fault-layer node availability at the eval iteration (1.0 = up);
    # all-ones when no node-fault process is active.
    "nodes_up": "per_worker",
    # Count of non-finite entries across ALL algorithm state leaves — the
    # NaN/Inf sentinel that otherwise stays invisible until the final fetch.
    "nonfinite": "scalar",
    # Realized directed-degree sum Σ_i deg_i(t) at the eval iteration (the
    # fault layer's live-edge accounting; the static topology's degree sum
    # when fault-free, 0.0 for centralized runs).
    "live_edges": "scalar",
    # Robust-aggregation activity: fraction of received closed-neighborhood
    # messages screened out (trimmed / clipped) this round; 0.0 when no
    # robust rule is active. See ops/robust_aggregation.py activity twins.
    "clip_frac": "scalar",
}

_RUN_TRACE_KEYS = (
    "schema_version", "kind", "label", "backend", "platform", "config",
    "config_hash", "phases", "compile_seconds", "iters_per_second",
    "eval_iterations", "cost", "trace", "health", "provenance", "spans",
)

# Top-level keys of a bench manifest sidecar (``write_bench_manifest``);
# the drift-guard schema test validates committed ``*.manifest.json``
# artifacts against exactly this set.
BENCH_MANIFEST_KEYS = (
    "schema_version", "kind", "artifact", "backend", "platform", "config",
    "config_hash", "phases", "provenance", "spans",
)


def _encode_nonfinite(obj):
    """NaN/±Inf → the sentinel strings "NaN"/"Infinity"/"-Infinity".

    A flight recorder exists precisely for divergent runs, whose
    grad-norm/gap rows ARE non-finite — and bare NaN/Infinity tokens are
    invalid JSON (jq / JSON.parse reject them). Sentinel strings keep the
    manifests strict-JSON and round-trip exactly through
    ``_decode_nonfinite``.
    """
    if isinstance(obj, float):
        if math.isnan(obj):
            return "NaN"
        if math.isinf(obj):
            return "Infinity" if obj > 0 else "-Infinity"
        return obj
    if isinstance(obj, dict):
        return {k: _encode_nonfinite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_encode_nonfinite(v) for v in obj]
    return obj


_NONFINITE = {"NaN": float("nan"), "Infinity": float("inf"),
              "-Infinity": float("-inf")}


def _decode_nonfinite(obj):
    if isinstance(obj, str) and obj in _NONFINITE:
        return _NONFINITE[obj]
    if isinstance(obj, dict):
        return {k: _decode_nonfinite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_decode_nonfinite(v) for v in obj]
    return obj


def config_hash(config_dict: dict) -> str:
    """Stable content hash of a config dict (sorted-key JSON, sha256)."""
    blob = json.dumps(config_dict, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def cost_from_lowered(lowered) -> Optional[dict]:
    """Extract the XLA cost analysis of a ``jax.stages.Lowered`` program.

    Returns a small float dict (flops, bytes accessed, ...) or None when
    the platform/version provides no analysis — never raises: cost numbers
    are telemetry, not control flow.
    """
    try:
        ca = lowered.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    keep = ("flops", "bytes accessed", "transcendentals", "optimal_seconds")
    out = {k.replace(" ", "_"): float(ca[k]) for k in keep if k in ca}
    return out or None


def _platform() -> str:
    try:
        import jax

        return jax.devices()[0].platform
    except Exception:
        return "unknown"


def _git_state() -> tuple:
    """(sha, dirty) of the checkout this package runs from, or (None,
    None) outside a git worktree — provenance is telemetry, never
    control flow worth raising for."""
    import subprocess

    root = str(Path(__file__).resolve().parent.parent)
    try:
        sha = subprocess.run(
            ["git", "-C", root, "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
        )
        if sha.returncode != 0:
            return None, None
        status = subprocess.run(
            ["git", "-C", root, "status", "--porcelain"],
            capture_output=True, text=True, timeout=10,
        )
        dirty = bool(status.stdout.strip()) if status.returncode == 0 else None
        return sha.stdout.strip(), dirty
    except Exception:
        return None, None


_PROVENANCE_CACHE: Optional[dict] = None


def provenance(refresh: bool = False) -> dict:
    """The run-environment facts every schema-v2 manifest records
    (ISSUE-10 satellite): git SHA + dirty flag of the producing checkout,
    ``jax.__version__``, and the device kind — before v2 only the
    platform string was captured, which cannot distinguish two TPU
    generations or tie a number to a commit. Cached per process (the
    git subprocess is not free); ``refresh=True`` re-reads."""
    global _PROVENANCE_CACHE
    if _PROVENANCE_CACHE is not None and not refresh:
        return dict(_PROVENANCE_CACHE)
    sha, dirty = _git_state()
    jax_version = None
    device_kind = None
    try:
        import jax

        jax_version = jax.__version__
        device_kind = jax.devices()[0].device_kind
    except Exception:
        pass
    _PROVENANCE_CACHE = {
        "git_sha": sha,
        "git_dirty": dirty,
        "jax_version": jax_version,
        "device_kind": device_kind,
    }
    return dict(_PROVENANCE_CACHE)


@dataclasses.dataclass
class RunTrace:
    """One run's flight-recorder manifest (see the module docstring).

    ``trace`` holds the per-eval-row buffers as plain lists keyed by
    ``TRACE_FIELDS`` (None when telemetry was off or the backend emits
    none); ``health`` the derived summary from ``health_summary``.
    """

    label: str
    backend: str
    platform: str
    config: dict
    config_hash: str
    phases: dict
    compile_seconds: float
    iters_per_second: float
    eval_iterations: list
    cost: Optional[dict] = None
    trace: Optional[dict] = None
    health: Optional[dict] = None
    # Schema v2: the producing environment (git sha/dirty, jax version,
    # device kind — see ``provenance()``) and the span tracer's Chrome
    # trace events (None when the producer recorded no spans).
    provenance: Optional[dict] = None
    spans: Optional[list] = None
    schema_version: int = SCHEMA_VERSION
    kind: str = "run_trace"

    def to_dict(self) -> dict:
        return {k: getattr(self, k) for k in _RUN_TRACE_KEYS}

    def to_json(self) -> str:
        # allow_nan=False + sentinel-string encoding: strict JSON even for
        # the divergent runs whose trace rows are non-finite.
        return json.dumps(
            _encode_nonfinite(self.to_dict()), sort_keys=True,
            allow_nan=False,
        )

    @classmethod
    def from_dict(cls, d: dict) -> "RunTrace":
        unknown = set(d) - set(_RUN_TRACE_KEYS)
        if unknown:
            raise ValueError(
                f"RunTrace carries unknown keys {sorted(unknown)}; "
                f"schema v{SCHEMA_VERSION} defines {_RUN_TRACE_KEYS}"
            )
        missing = set(_RUN_TRACE_KEYS) - set(d)
        if missing:
            raise ValueError(f"RunTrace is missing keys {sorted(missing)}")
        if d["schema_version"] != SCHEMA_VERSION:
            raise ValueError(
                f"unsupported RunTrace schema_version {d['schema_version']} "
                f"(this build reads v{SCHEMA_VERSION})"
            )
        if d["kind"] != "run_trace":
            raise ValueError(f"not a run_trace manifest: kind={d['kind']!r}")
        return cls(**d)

    @classmethod
    def from_json(cls, blob: str) -> "RunTrace":
        return cls.from_dict(_decode_nonfinite(json.loads(blob)))


def build_run_trace(
    label: str,
    config,
    history,
    *,
    phases: Optional[dict] = None,
    health: Optional[dict] = None,
    platform: Optional[str] = None,
    spans: Optional[list] = None,
) -> RunTrace:
    """Assemble a ``RunTrace`` from an ``ExperimentConfig`` + ``RunHistory``.

    ``phases`` may be a plain dict or a span ``Tracer`` (its aggregated
    ``.phases`` dict is recorded, and — unless ``spans`` is passed
    explicitly — its Chrome trace events land in the ``spans`` field).
    """
    cd = config.to_dict()
    trace = None
    if history.trace is not None:
        trace = {
            k: np.asarray(v, dtype=np.float64).tolist()
            for k, v in history.trace.items()
        }
    if spans is None and hasattr(phases, "chrome_events"):
        spans = phases.chrome_events()
    phase_dict = dict(getattr(phases, "phases", phases) or {})
    return RunTrace(
        label=label,
        backend=config.backend,
        platform=platform if platform is not None else _platform(),
        config=cd,
        config_hash=config_hash(cd),
        phases=phase_dict,
        compile_seconds=float(history.compile_seconds),
        iters_per_second=float(history.iters_per_second),
        eval_iterations=np.asarray(history.eval_iterations).tolist(),
        cost=history.cost,
        trace=trace,
        health=health,
        provenance=provenance(),
        spans=spans,
    )


def write_jsonl(path, traces: list[RunTrace]) -> None:
    """One manifest per line (JSONL) — the CLI/Simulator emission format."""
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    with open(p, "w") as f:
        for tr in traces:
            f.write(tr.to_json() + "\n")


def read_jsonl(path) -> list[RunTrace]:
    return [
        RunTrace.from_json(line)
        for line in Path(path).read_text().splitlines()
        if line.strip()
    ]


# --------------------------------------------------------------- run health


def _config_topology(config):
    """The run's communication graph, built once per health derivation
    (None for centralized configs). ``health_summary`` threads one build
    through both consumers — at the matrix-free scales the ER constructor
    walks an O(N²) draw stream, so rebuilding per helper is real seconds
    of redundant host work per request."""
    from distributed_optimization_tpu.algorithms import get_algorithm
    from distributed_optimization_tpu.parallel import build_topology

    if not get_algorithm(config.algorithm).is_decentralized:
        return None
    return build_topology(
        config.topology, config.n_workers,
        erdos_renyi_p=config.erdos_renyi_p,
        seed=config.resolved_topology_seed(),
        impl=config.resolved_topology_impl(),
        sampler=config.resolved_topology_sampler(),
    )


def realized_bhat(
    config, max_cells: int = 2_000_000, *, topo=None
) -> Optional[dict]:
    """Realized windowed-connectivity B̂ of this config's fault process.

    Rebuilds the run's fault timeline host-side — bitwise the realization
    the backends consume (memoryless modes are the burst_len=1 /
    iid-equivalent points of the persistent chains, see
    ``parallel/faults.py``) — and measures the smallest B such that every
    length-B window's union graph is connected. Returns ``{"bhat",
    "horizon"}`` (bhat None when even the full-horizon union is
    disconnected), or None when the notion does not apply (centralized,
    matching schedules, no peer graph). The horizon is truncated so the
    [horizon, E] unroll stays under ``max_cells`` — recorded honestly in
    the result.
    """
    from distributed_optimization_tpu.algorithms import get_algorithm
    from distributed_optimization_tpu.parallel.faults import (
        _edge_list,
        _union_connected,
        config_faults_active,
        timeline_for_config,
        windowed_connectivity,
    )

    if not get_algorithm(config.algorithm).is_decentralized:
        return None
    if config.gossip_schedule != "synchronous":
        # Matching schedules realize per-round matchings, not edge-drop
        # processes — the timeline rebuild below would not be the realized
        # graph sequence.
        return None
    if topo is None:
        topo = _config_topology(config)
    edges = _edge_list(topo)
    n_edges = max(len(edges), 1)
    if not config_faults_active(config):
        connected = _union_connected(
            np.ones(len(edges), dtype=bool), edges, config.n_workers
        )
        return {"bhat": 1 if connected else None,
                "horizon": config.n_iterations}
    horizon = min(config.n_iterations, max(1, max_cells // n_edges))
    tl = timeline_for_config(config, topo, horizon)
    return {"bhat": windowed_connectivity(tl, topo),
            "horizon": horizon}


def health_summary(
    config, history, *, serving: Optional[dict] = None,
    d_features: Optional[int] = None,
) -> dict:
    """Derive the run-health block from a finished run's history.

    Always includes the final gap, the realized/nominal connectivity
    diagnostics, and the comms block (bytes moved per round — the
    production currency compressed gossip trades on); trace-derived
    statistics (worst-worker grad norm, non-finite totals, liveness)
    appear when the run recorded trace buffers.

    ``serving``: the per-request serving facts (executable-cache hit,
    compile seconds saved, cohort size/coalescing, queue wait — see
    ``serving.service.Request.serving_block``) recorded verbatim under
    ``"serving"`` when the run was served rather than invoked directly;
    ``format_report`` summarizes them in its one-line serving section.
    """
    h: dict[str, Any] = {}
    if serving is not None:
        h["serving"] = dict(serving)
    obj = np.asarray(history.objective, dtype=np.float64)
    finite = obj[np.isfinite(obj)]
    h["final_gap"] = float(obj[-1]) if obj.size else None
    h["n_nonfinite_evals"] = int(obj.size - finite.size)
    topo = _config_topology(config)  # one build serves every block below
    tr = history.trace
    if tr:
        gn = np.asarray(tr["grad_norm"], dtype=np.float64)
        per_worker_peak = gn.max(axis=tuple(range(gn.ndim - 1)))
        h["worst_worker_grad_norm"] = float(per_worker_peak.max())
        h["worst_worker"] = int(per_worker_peak.argmax())
        h["final_max_param_norm"] = float(
            np.asarray(tr["param_norm"])[..., -1, :].max()
        )
        h["nonfinite_total"] = float(np.sum(tr["nonfinite"]))
        nodes = np.asarray(tr["nodes_up"], dtype=np.float64)
        h["min_nodes_up_frac"] = float(nodes.mean(axis=-1).min())
        if config.participation_rate < 1.0:
            # Realized participation per eval round (the satellite: the
            # recorded series IS the nodes_up trace — availability under
            # client sampling is churn-up AND sampled-in); the summary
            # quotes its mean against the configured target rate.
            h["participation"] = {
                "rate": float(config.participation_rate),
                "realized_frac_mean": float(nodes.mean()),
            }
        h["clip_frac_mean"] = float(np.mean(tr["clip_frac"]))
        live = np.asarray(tr["live_edges"], dtype=np.float64)
        nominal = (
            float(np.asarray(topo.degrees).sum()) if topo is not None
            else None
        )
        h["realized_edge_frac"] = (
            float(live.mean() / nominal) if nominal else None
        )
    h["comms"] = comms_summary(
        config, history, topo=topo, d_features=d_features
    )
    h["windowed_connectivity"] = realized_bhat(config, topo=topo)
    # Async block scoped to the rounds THIS history executed (a
    # continuation slice's eval axis carries its global round window, so
    # its health never mixes slice floats with full-schedule durations).
    rounds = None
    ev = np.asarray(getattr(history, "eval_iterations", []))
    if ev.size:
        rounds = (int(ev[0]) - config.eval_every, int(ev[-1]))
    a = async_summary(config, rounds=rounds)
    if a is not None:
        # Floats per VIRTUAL second from the run's OWN realized
        # accounting (the comms_summary convention) over the executed
        # window's simulated duration — events have no shared round, so
        # per-round accounting has the wrong denominator (docs/ASYNC.md).
        total = getattr(history, "total_floats_transmitted", None)
        a["floats_per_virtual_second"] = (
            float(total) / a["virtual_duration"]
            if total is not None and a["virtual_duration"] > 0 else 0.0
        )
        h["async"] = a
    return h


def async_summary(config, *, rounds=None) -> Optional[dict]:
    """Event-schedule health block for asynchronous runs (docs/ASYNC.md).

    Reads the run's event timeline host-side — bitwise the schedule the
    backends executed (``parallel/events.py`` is (seed, horizon)-pure, the
    ``realized_bhat`` convention) — and derives what the execution mode is
    ABOUT: the realized staleness histogram, the per-worker virtual-clock
    skew a barrier would have flattened, and the schedule facts behind
    the floats-per-VIRTUAL-second figure ``health_summary`` completes
    from the run's own realized comms accounting (events have no shared
    round, so per-round accounting is the wrong denominator).
    ``sync_virtual_duration`` prices the bulk-synchronous twin on the
    same latency draws — the ratio is the realized straggler tax.
    ``rounds``: an optional (start, stop) global ROUND window — a
    continuation slice describes only the events it executed. None for
    synchronous configs.
    """
    if getattr(config, "execution", "sync") != "async":
        return None
    from distributed_optimization_tpu.backends.async_scan import timeline_for
    from distributed_optimization_tpu.parallel.events import (
        clock_skew,
        realize_event_faults,
        staleness_histogram,
        sync_round_times,
    )
    from distributed_optimization_tpu.parallel.faults import (
        config_faults_active,
        timeline_for_config,
    )

    # Shares the backend's own cached build (timeline_for's LRU): the
    # O(E) host unroll runs once per config, not once per consumer.
    _, tl = timeline_for(config)
    n = tl.n_workers
    start_r, stop_r = (0, tl.n_rounds) if rounds is None else rounds
    ev_window = (start_r * n, stop_r * n)
    sl = slice(*ev_window)
    # Virtual duration of the executed window: event times are global, so
    # a slice's duration is the time between its boundary events.
    t_start = float(tl.t_virtual[ev_window[0] - 1]) if ev_window[0] else 0.0
    t_stop = (
        float(tl.t_virtual[ev_window[1] - 1])
        if ev_window[1] > ev_window[0] else t_start
    )
    svt = sync_round_times(tl)
    s_start = float(svt[start_r - 1]) if start_r else 0.0
    faults: Optional[dict] = None
    if config_faults_active(config):
        # Event-realized fault diagnostics (ISSUE-17): the SAME
        # (seed, horizon)-pure realization the backends executed —
        # availability is the fired-event fraction, in-flight losses are
        # crashed firing workers (their stale gradient evaporates),
        # thinned events are participation draws, degraded exchanges are
        # live firings whose partner (or edge) was down and fell back to
        # the self-loop.
        from distributed_optimization_tpu.parallel import build_topology
        topo = build_topology(
            config.topology, config.n_workers,
            erdos_renyi_p=config.erdos_renyi_p,
            seed=config.resolved_topology_seed(),
        )
        ft = timeline_for_config(config, topo, tl.n_rounds)
        real = realize_event_faults(tl, ft)
        fire_w = real.fire[sl]
        kk = tl.local_step.astype(np.int64)
        ww = tl.worker.astype(np.int64)
        ones = np.ones(len(ww), dtype=bool)
        worker_up = ft.node_up[kk, ww] if ft.node_up is not None else ones
        worker_in = ft.part_up[kk, ww] if ft.part_up is not None else ones
        faults = {
            "availability": (
                float(fire_w.mean()) if fire_w.size else 1.0
            ),
            # Crash no-ops (the in-flight gradient evaporated) vs
            # participation skips — the EventFaultRealization split,
            # windowed to the executed slice.
            "n_inflight_lost": int((~worker_up[sl]).sum()),
            "n_thinned": int((worker_up & ~worker_in)[sl].sum()),
            "n_degraded_exchanges": int(real.n_degraded),
            "n_rejoin_events": int(real.rejoin[sl].sum()),
            "matched_fired": int(real.matched_fired[sl].sum()),
        }
    return {
        "latency_model": config.latency_model,
        "latency_mean": float(config.latency_mean),
        "latency_tail": float(config.latency_tail),
        "events": int(ev_window[1] - ev_window[0]),
        # One pairwise exchange (2·d floats) per matched event; the
        # absolute floats-per-virtual-second figure is completed by
        # health_summary from the run's realized accounting — the
        # trained dimension is the DATASET's (bias column included), not
        # a config-derived guess.
        "matched_events": int(tl.matched()[sl].sum()),
        "staleness": staleness_histogram(tl, events=ev_window),
        "virtual_clock": clock_skew(tl, rounds=(start_r, stop_r)),
        "virtual_duration": t_stop - t_start,
        "sync_virtual_duration": (
            float(svt[stop_r - 1]) - s_start if stop_r > start_r else 0.0
        ),
        "faults": faults,
    }


def comms_summary(
    config, history, *, topo=None, d_features: Optional[int] = None
) -> Optional[dict]:
    """Bytes-moved accounting block (ISSUE-6 satellite).

    Derived from the run's OWN float accounting so it is exact on every
    path: the backends record ``total_floats_transmitted`` as per-edge
    payload (``Compressor.floats_per_edge`` × the algorithm's gossip
    rounds) × realized live edges — summed over the fault timeline when
    one is active — so dividing by the horizon gives the realized mean
    floats moved per ITERATION, and dividing further by the mean
    realized live-edge count recovers the per-edge per-iteration
    payload: the compressor's floats_per_edge times the algorithm's
    gossip rounds (2× for gradient tracking, which compresses both its
    x and y exchanges). This is what makes a compression win visible in
    the report/manifest without opening bench JSON. None for
    centralized runs (no peer edges to account).
    """
    from distributed_optimization_tpu.algorithms import get_algorithm

    algo = get_algorithm(config.algorithm)
    if not algo.is_decentralized:
        return None
    total = getattr(history, "total_floats_transmitted", None)
    if total is None:
        return None
    per_iter = float(total) / max(config.n_iterations, 1)
    out: dict[str, Any] = {
        "compression": config.compression,
        # Per ITERATION, not per gossip round: gradient tracking's two
        # exchanges per iteration are both included (its per-round
        # payload is the same as dsgd's; the per-iteration figure is 2×).
        "floats_per_iteration_mean": per_iter,
    }
    if config.local_steps > 1:
        # τ local descents per round at unchanged per-round comms — the
        # federated communication-reduction lever (docs/PERF.md §14):
        # floats per GRADIENT STEP is the per-round figure over τ.
        out["local_steps"] = int(config.local_steps)
        out["floats_per_gradient_step"] = per_iter / config.local_steps
    tr = history.trace
    if tr and "live_edges" in tr:
        live = np.asarray(tr["live_edges"], dtype=np.float64)
        if live.size and live.mean() > 0:
            out["floats_per_edge_per_iteration"] = float(
                per_iter / live.mean()
            )
    ici = ici_summary(config, topo=topo, d_features=d_features)
    if ici is not None:
        # Sharded worker mesh (docs/PERF.md §16): real collective bytes
        # alongside the analytic floats — the halo plan is static, so the
        # per-device ppermute traffic is exact, and simulated floats and
        # ICI bytes finally sit in one report (the PAPER.md north star).
        out["ici"] = ici
    return out


def ici_summary(
    config, *, topo=None, d_features: Optional[int] = None
) -> Optional[dict]:
    """Bytes-over-ICI block for sharded worker-mesh runs (ISSUE-11).

    Rebuilds the static halo-exchange plan host-side — the identical plan
    the backend's halo gather executes — and prices the per-device
    ppermute traffic exactly: each device ships the rotation-padded WIRE
    rows per gossip round (every rotation pads to its max per-device
    count so the collective is shape-uniform; on regular rings wire ==
    useful, on irregular graphs the pad rows ride the wire too), each
    row carrying the per-config payload width. Plain gossip moves the
    d_model model row in the state dtype; node-process faults
    (stragglers/churn/participation) add the 1-float availability
    exchange (always f32 on the wire) plus the realized-degree column
    riding the model buffer in the body's accumulation dtype
    (``faults.make_halo_faulty_mixing``); robust screening adds the
    availability exchange, and clipped gossip additionally the degree
    column (``collectives.make_halo_robust_aggregator_t``). An active
    adversary executes BOTH branches of the screened mix's ``jnp.where``
    (the benign base mix AND the honest view —
    ``parallel/adversary.py``), so attack configs price two exchange
    forms per round. None when
    the run is unsharded (``worker_mesh`` off) or centralized. The same
    numbers feed the PR-10 metrics registry as ``dopt_worker_mesh_*``
    per-device gauges when the backend actually runs.

    ``topo``: the already-built topology when the caller has one
    (``health_summary`` builds it once for every block) — rebuilding a
    matrix-free Erdős–Rényi graph replays the dense sampler's O(N²)
    stream, so the one-build convention matters here.
    """
    if getattr(config, "worker_mesh", 0) < 2:
        return None
    from distributed_optimization_tpu.algorithms import get_algorithm
    from distributed_optimization_tpu.models import get_problem
    from distributed_optimization_tpu.parallel.topology import (
        build_halo_plan,
        neighbor_tables_for,
    )

    algo = get_algorithm(config.algorithm)
    if not algo.is_decentralized:
        return None
    if topo is None:
        topo = _config_topology(config)
    nbr_idx, nbr_mask = neighbor_tables_for(topo)
    plan = build_halo_plan(
        nbr_idx, nbr_mask, config.worker_mesh, sampler=topo.sampler
    )
    problem = get_problem(
        config.problem_type, huber_delta=config.huber_delta,
        n_classes=config.n_classes,
    )
    # The trained dimension — the payload width every gossip round
    # actually moves per row — plus the fault/robust side-channel floats
    # enumerated in the docstring. ``d_features`` is the DATASET's
    # realized column count (bias included) when the caller has one
    # (Simulator/backend do; the digits dataset ignores ``n_features``);
    # the config-derived ``n_features + 1`` is the synthetic-path value.
    if d_features is None:
        d_features = config.n_features + 1
    d_model = problem.param_dim(d_features)
    robust = config.aggregation != "gossip" and config.robust_b > 0
    attack = config.attack != "none"
    node_faults = (
        config.straggler_prob > 0.0
        or config.mttf > 0.0
        or config.participation_rate < 1.0
    )
    if robust:
        avail = 1
        deg_col = 1 if config.aggregation == "clipped_gossip" else 0
    elif node_faults:
        avail, deg_col = 1, 1  # availability bit + realized-degree column
    else:
        avail = deg_col = 0
    if config.compression != "none":
        from distributed_optimization_tpu.ops.compression import (
            make_compressor,
        )

        floats_per_row = make_compressor(
            config.compression, d_model, config.compression_k
        ).floats_per_edge * algo.gossip_rounds
    else:
        floats_per_row = (d_model + deg_col + avail) * algo.gossip_rounds
    itemsize = int(np.dtype(config.dtype).itemsize)
    # Per-row bytes of each exchange FORM the compiled round can run.
    # The availability bit ships as its OWN f32 halo exchange (fault
    # masks are explicit float32 on every path — 4 B/row at any model
    # dtype); the fault/robust model buffers ship in the bodies'
    # ACCUMULATION dtype (promote(f32, model) — 4 B floats even under
    # bfloat16 state); only the plain no-fault mixing op exchanges in
    # the state dtype itself.
    acc_size = max(itemsize, 4)
    if node_faults:
        base_row = 4 + (d_model + 1) * acc_size  # avail + model+degree
    elif config.compression != "none":
        # Compressed halo exchange (ISSUE-18): the wire rows carry the
        # compressor's payload instead of the dense d_model row — the
        # analytic accounting convention every comms number in this repo
        # uses (top_k/random_k: k values + k indices; qsgd: packed bits +
        # the norm). Compression composes only with the plain benign mesh
        # (config rejects it with faults/robust/attack), so this branch
        # never interacts with the side-channel pricing above.
        from distributed_optimization_tpu.ops.compression import (
            make_compressor,
        )

        base_row = make_compressor(
            config.compression, d_model, config.compression_k
        ).floats_per_edge * itemsize
    else:
        base_row = d_model * itemsize            # plain halo mix
    robust_row = 4 + (d_model + deg_col) * acc_size
    # An active adversary executes BOTH branches of the screened mix's
    # jnp.where (parallel/adversary.py::make_byzantine_mixing): the
    # benign base mix for Byzantine rows AND the honest view — the
    # robust aggregate when a rule defends, the base mix of the
    # corrupted stack otherwise. A pure defense (robust rule, no
    # attack) binds the aggregate alone.
    if attack and robust:
        round_row_bytes = base_row + robust_row
    elif attack:
        round_row_bytes = 2 * base_row
    elif robust:
        round_row_bytes = robust_row
    else:
        round_row_bytes = base_row
    row_bytes = algo.gossip_rounds * round_row_bytes
    # Wire rows, not useful rows: every rotation pads to its max
    # per-device count so the ppermute stays shape-uniform — each device
    # ships s_max rows per rotation whether or not all of them are
    # referenced by the destination (HaloStep.send_idx pad rows).
    wire_rows = int(sum(st.send_idx.shape[1] for st in plan.steps))
    sent = plan.sent_rows.astype(np.int64)
    n_dev = int(config.worker_mesh)
    return {
        "worker_mesh": n_dev,
        "shard_rows": int(plan.shard_rows),
        "halo_rows_max": int(plan.h_max),
        "halo_rows_per_device": [
            int(len(h)) for h in plan.halo_idx
        ],
        "exchange_rotations": len(plan.steps),
        "wire_rows_per_device": wire_rows,
        "useful_rows_per_device": [int(r) for r in sent],
        "bytes_per_device_per_round": [wire_rows * row_bytes] * n_dev,
        "bytes_per_device_per_round_max": wire_rows * row_bytes,
        "bytes_total_per_round": n_dev * wire_rows * row_bytes,
        "payload_floats_per_row": (
            float(floats_per_row) if config.compression != "none"
            else int(floats_per_row)
        ),
        "compression": config.compression,
        "itemsize": itemsize,
    }


def _nominal_degree_sum(config) -> Optional[float]:
    topo = _config_topology(config)
    return float(np.asarray(topo.degrees).sum()) if topo is not None else None


# ----------------------------------------------------------- bench sidecars


def write_bench_manifest(
    artifact_path, *, config=None, phases=None, artifact_name=None,
) -> Path:
    """Write the ``<artifact>.manifest.json`` sidecar for a bench artifact.

    Every ``examples/bench_*.py`` calls this after writing its JSON so regen
    runs leave a schema-versioned provenance record (platform, config hash,
    phase timings) next to each number. ``config`` is the bench's base
    ``ExperimentConfig`` (or a plain dict, or None for benches without one
    canonical config); ``phases`` a ``PhaseTimer`` or plain dict.
    """
    p = Path(artifact_path)
    out = p.with_suffix(".manifest.json")
    cd = None
    if config is not None:
        cd = config.to_dict() if hasattr(config, "to_dict") else dict(config)
    phase_dict = dict(getattr(phases, "phases", phases) or {})
    # Span tracing (schema v2): bench scripts pass their PhaseTimer —
    # now a span Tracer — so the manifest carries the perfetto-viewable
    # span tree alongside the flat phase totals, with no bench changes.
    spans = (
        phases.chrome_events() if hasattr(phases, "chrome_events") else None
    )
    payload = {
        "schema_version": SCHEMA_VERSION,
        "kind": "bench_manifest",
        "artifact": artifact_name or p.name,
        "backend": (cd or {}).get("backend"),
        "platform": _platform(),
        "config": cd,
        "config_hash": config_hash(cd) if cd else None,
        "phases": {k: float(v) for k, v in phase_dict.items()},
        "provenance": provenance(),
        "spans": spans,
    }
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    return out
