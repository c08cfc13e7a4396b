"""Anomaly sentinel: online run-health monitors + incident forensics.

The repo can stream per-chunk heartbeats (``observability/progress.py``)
and record in-scan trace buffers (``telemetry.TRACE_FIELDS``), but until
ISSUE-13 nothing *watched* those signals: a diverging cell — an
over-budget ALIE attack (Baruch et al. '19), a partitioned realized-B̂
window violating Koloskova et al. '20's B-connectivity assumption, an
async staleness blowup past the bounded-staleness regime (Lian et al.
'17) — burned its full horizon and was only discovered in the final
report. This module closes the loop:

- **Detectors** are small stateful observers fed the SAME
  ``ProgressEvent`` heartbeats the progress streams carry (and, for the
  trace-derived signals, the flight-recorder buffers after the run).
  Each fires AT MOST ONCE per run (a latch — the incident records the
  onset; re-firing every subsequent heartbeat would be noise) and emits
  a structured ``Anomaly`` carrying the detector name, severity, onset
  iteration, and the evidence window it fired on.
- **MonitorBank** owns a run's detector set, collects anomalies,
  increments the ``dopt_anomaly_*`` families in the process metrics
  registry, and answers the early-halt policy question
  (``halt_on={'fatal','never'}``) the backends consult at chunk
  boundaries. Observation NEVER perturbs the run: monitors ride the
  segmented-scan progress machinery, whose off==on bitwise contract is
  already pinned (tests/test_observatory.py), and a monitor that raises
  is contained like any progress callback.
- **Incident forensics**: ``build_incident`` assembles a
  schema-versioned bundle per anomaly — config + structural hash, the
  evidence window, and the fault/attack context around the onset (which
  nodes were down, which Byzantine workers were active and whether the
  attack exceeded the robust budget, the realized B̂ over the onset
  window — all rebuilt host-side from the (seed, horizon)-pure timeline,
  the ``realized_bhat`` convention). Bundles serialize as JSONL next to
  RunTrace manifests (``observatory incidents`` lists them;
  ``observatory list --with-incidents`` joins them onto the run index).

Detection thresholds are heuristics, not theorems — they are constructor
knobs with conservative defaults, and every anomaly carries its evidence
window so a consumer can re-judge the call. The one hard rule: halting
is opt-in (``halt_on='fatal'``), stops only at a chunk boundary the
progress machinery already syncs at, and the executed prefix stays
bitwise the full run's prefix (the continuation contract).
"""

from __future__ import annotations

import dataclasses
import json
import math
from collections import deque
from pathlib import Path
from typing import Any, Iterable, Optional

import numpy as np

# Incident-bundle schema version (independent of the RunTrace schema:
# incidents are their own artifact kind). Bump on field changes;
# ``read_incidents`` rejects versions it does not know.
INCIDENT_SCHEMA_VERSION = 1

INCIDENT_KEYS = (
    "schema_version", "kind", "label", "detector", "severity",
    "onset_iteration", "message", "config", "config_hash",
    "structural_hash", "evidence", "context", "provenance",
)

# Severity scale, least to most severe. ``halt_on='fatal'`` halts only on
# the top tier; 'warn' anomalies are recorded and surfaced but never stop
# a run.
SEVERITIES = ("info", "warn", "fatal")

HALT_POLICIES = ("never", "fatal")


def severity_rank(severity: str) -> int:
    """Total order over severities (tests pin fatal > warn > info)."""
    try:
        return SEVERITIES.index(severity)
    except ValueError:
        raise ValueError(
            f"unknown severity {severity!r}; expected one of {SEVERITIES}"
        )


@dataclasses.dataclass
class Anomaly:
    """One detector firing: what, how bad, when, and on what evidence."""

    detector: str
    severity: str
    onset_iteration: int
    message: str
    # The observation window the detector fired on: small JSON-safe
    # arrays keyed by signal name, each paired with its iterations.
    evidence: dict
    # False for advisory firings that must NOT latch their detector:
    # connectivity_loss's B̂-ceiling warn keeps watching for the fatal
    # disconnection it exists to catch (a latched warn would mask it).
    latches: bool = True

    def to_dict(self) -> dict:
        return {
            "detector": self.detector,
            "severity": self.severity,
            "onset_iteration": int(self.onset_iteration),
            "message": self.message,
            "evidence": self.evidence,
        }


def _event_gap(ev) -> Optional[float]:
    """The gap a detector should judge: the worst replica's when the
    heartbeat carries per-replica gaps (a cohort heartbeat's mean would
    hide one diverging replica behind R-1 healthy ones)."""
    gaps = [float(ev.gap)] if ev.gap is not None else []
    per_replica = getattr(ev, "gap_per_replica", None)
    if per_replica:
        gaps.extend(float(g) for g in per_replica)
    if not gaps:
        return None
    finite = [g for g in gaps if math.isfinite(g)]
    return max(finite) if len(finite) == len(gaps) else float("nan")


class Detector:
    """Base class: a named, severity-tagged, fire-once observer.

    ``observe(ev)`` consumes one ``ProgressEvent`` heartbeat;
    ``scan_trace(trace, eval_iterations)`` consumes the flight recorder's
    post-run buffers (both optional per subclass). Both return the
    ``Anomaly`` on the firing call and None otherwise; after firing the
    detector latches and ignores further input.
    """

    name = "detector"
    severity = "warn"

    def __init__(self):
        self.fired: Optional[Anomaly] = None

    # -- subclass hooks ------------------------------------------------
    def _observe(self, ev) -> Optional[Anomaly]:
        return None

    def _scan_trace(self, trace, eval_iterations) -> Optional[Anomaly]:
        return None

    # -- public API ----------------------------------------------------
    def observe(self, ev) -> Optional[Anomaly]:
        if self.fired is not None:
            return None
        anomaly = self._observe(ev)
        if anomaly is not None and anomaly.latches:
            self.fired = anomaly
        return anomaly

    def scan_trace(self, trace, eval_iterations) -> Optional[Anomaly]:
        if self.fired is not None or trace is None:
            return None
        anomaly = self._scan_trace(trace, eval_iterations)
        if anomaly is not None and anomaly.latches:
            self.fired = anomaly
        return anomaly

    def _anomaly(self, onset: int, message: str, evidence: dict) -> Anomaly:
        return Anomaly(
            detector=self.name, severity=self.severity,
            onset_iteration=int(onset), message=message, evidence=evidence,
        )


class DivergenceDetector(Detector):
    """Suboptimality gap rising over ``window`` consecutive heartbeats,
    or breaching ``rel_ceiling`` × the best gap seen (or an absolute
    ``ceiling``). Both arms additionally require the gap to be WORSE
    than the first heartbeat's — a converged run's floating-point noise
    around a ~0 gap can satisfy any relative ratio, but only a genuinely
    degrading run climbs back above where it started. The onset is the
    FIRST heartbeat of the rising streak / the breaching heartbeat — the
    moment degradation began, not the moment the evidence became
    conclusive."""

    name = "divergence"
    severity = "fatal"

    def __init__(self, window: int = 3, rel_ceiling: float = 1e3,
                 ceiling: float = float("inf")):
        super().__init__()
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.window = int(window)
        self.rel_ceiling = float(rel_ceiling)
        self.ceiling = float(ceiling)
        self._obs: deque = deque(maxlen=self.window + 1)
        self._best: Optional[float] = None
        self._first: Optional[float] = None

    def _evidence(self) -> dict:
        return {
            "iterations": [int(t) for t, _ in self._obs],
            "gap": [float(g) for _, g in self._obs],
            "best_gap": self._best,
            "first_gap": self._first,
        }

    def _observe(self, ev):
        gap = _event_gap(ev)
        if gap is None or not math.isfinite(gap):
            return None  # the non-finite sentinel owns that case
        if self._first is None:
            self._first = gap
        self._obs.append((ev.iteration, gap))
        if self._best is None or gap < self._best:
            self._best = gap
        degrading = gap > self._first
        if gap > self.ceiling or (
            degrading and self._best > 0
            and gap > self.rel_ceiling * self._best
        ):
            return self._anomaly(
                ev.iteration,
                f"gap {gap:.3e} breached the divergence ceiling (abs "
                f"{self.ceiling:.3g} / {self.rel_ceiling:.3g}x best "
                f"{self._best:.3e})",
                self._evidence(),
            )
        if degrading and len(self._obs) == self.window + 1:
            pairs = list(self._obs)
            rising = all(
                pairs[i + 1][1] > pairs[i][1] for i in range(self.window)
            )
            if rising:
                return self._anomaly(
                    pairs[1][0],
                    f"gap rose over {self.window} consecutive heartbeats "
                    f"({pairs[0][1]:.3e} -> {pairs[-1][1]:.3e})",
                    self._evidence(),
                )
        return None


class ConsensusStallDetector(Detector):
    """Consensus error failing to decrease for ``window`` consecutive
    heartbeats while still above ``floor`` — the gossip averaging has
    stopped making progress but the network is not yet in consensus
    (disconnection, screening pathologies, a too-weak mixing rate).
    A converged run's flat consensus sits below the floor and never
    fires."""

    name = "consensus_stall"
    severity = "warn"

    def __init__(self, window: int = 4, floor: float = 1e-6):
        super().__init__()
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.window = int(window)
        self.floor = float(floor)
        self._obs: deque = deque(maxlen=self.window + 1)

    def _observe(self, ev):
        cons = ev.consensus
        if cons is None or not math.isfinite(float(cons)):
            return None
        self._obs.append((ev.iteration, float(cons)))
        if len(self._obs) < self.window + 1:
            return None
        pairs = list(self._obs)
        stalled = all(
            pairs[i + 1][1] >= pairs[i][1] and pairs[i + 1][1] > self.floor
            for i in range(self.window)
        )
        if stalled:
            return self._anomaly(
                pairs[1][0],
                f"consensus error stalled above {self.floor:.1e} for "
                f"{self.window} heartbeats ({pairs[0][1]:.3e} -> "
                f"{pairs[-1][1]:.3e})",
                {
                    "iterations": [int(t) for t, _ in pairs],
                    "consensus": [c for _, c in pairs],
                    "floor": self.floor,
                },
            )
        return None


class NonFiniteDetector(Detector):
    """NaN/Inf sentinels: a non-finite gap/consensus in a heartbeat, or a
    positive non-finite state-leaf count in the flight-recorder trace.
    Always fatal — nothing downstream of a NaN is meaningful."""

    name = "non_finite"
    severity = "fatal"

    def _observe(self, ev):
        bad = {}
        gap = _event_gap(ev)
        if gap is not None and not math.isfinite(gap):
            bad["gap"] = float(gap)
        if ev.consensus is not None and not math.isfinite(
            float(ev.consensus)
        ):
            bad["consensus"] = float(ev.consensus)
        if not bad:
            return None
        return self._anomaly(
            ev.iteration,
            f"non-finite metric(s) at iteration {ev.iteration}: "
            f"{sorted(bad)}",
            {"iteration": int(ev.iteration), **bad},
        )

    def _scan_trace(self, trace, eval_iterations):
        counts = np.asarray(trace.get("nonfinite", []), dtype=np.float64)
        if counts.size == 0:
            return None
        bad = np.flatnonzero(counts > 0)
        if bad.size == 0:
            return None
        onset_row = int(bad[0])
        iters = np.asarray(eval_iterations)
        onset = int(iters[onset_row]) if iters.size > onset_row else onset_row
        return self._anomaly(
            onset,
            f"{counts[onset_row]:.0f} non-finite state entries at "
            f"iteration {onset} (trace sentinel)",
            {
                "iterations": iters[bad][:8].astype(int).tolist(),
                "nonfinite_counts": counts[bad][:8].tolist(),
            },
        )


class ConnectivityLossDetector(Detector):
    """Realized windowed-connectivity B̂ violations: the live-B̂ heartbeat
    reporting a DISCONNECTED prefix union (no finite B exists — the
    Koloskova '20 B-connectivity assumption is void, fatal), or B̂
    exceeding ``bhat_ceiling`` (connectivity still exists but is weaker
    than the run budgeted for, warn)."""

    name = "connectivity_loss"
    severity = "fatal"  # disconnection; a ceiling breach downgrades to warn

    def __init__(self, bhat_ceiling: Optional[float] = None):
        super().__init__()
        self.bhat_ceiling = (
            float(bhat_ceiling) if bhat_ceiling is not None else None
        )
        self._seen: list = []  # (iteration, bhat) history, bounded below
        self._warned = False   # the ceiling warn fires once, non-latching

    def _observe(self, ev):
        disconnected = bool((ev.extra or {}).get("bhat_disconnected"))
        if ev.bhat is None and not disconnected:
            return None  # live B̂ not applicable on this path
        if len(self._seen) >= 64:
            del self._seen[0]
        self._seen.append(
            (int(ev.iteration), None if disconnected else int(ev.bhat))
        )
        evidence = {
            "iterations": [t for t, _ in self._seen],
            "bhat": [b for _, b in self._seen],
        }
        if disconnected:
            return self._anomaly(
                ev.iteration,
                f"realized graph union over [0, {ev.iteration}) is "
                "disconnected: no finite B-connectivity window exists",
                evidence,
            )
        if (
            self.bhat_ceiling is not None and ev.bhat > self.bhat_ceiling
            and not self._warned
        ):
            self._warned = True
            anomaly = self._anomaly(
                ev.iteration,
                f"realized B-hat {ev.bhat} exceeded the ceiling "
                f"{self.bhat_ceiling:.0f}",
                {**evidence, "ceiling": self.bhat_ceiling},
            )
            anomaly.severity = "warn"
            # Non-latching: a ceiling breach must not blind the detector
            # to a later genuine disconnection (the fatal case the
            # halt policy exists for).
            anomaly.latches = False
            return anomaly
        return None


class StalenessBlowupDetector(Detector):
    """Asynchronous staleness escaping the bounded regime: the realized
    p90 staleness over the executed window exceeding ``ceiling`` writes.
    AD-PSGD's convergence story assumes bounded staleness (Lian et al.
    '17); a blowup means the schedule's tail is starving rows."""

    name = "staleness_blowup"
    severity = "warn"

    def __init__(self, ceiling: float = 64.0):
        super().__init__()
        self.ceiling = float(ceiling)

    def _observe(self, ev):
        p90 = ev.staleness_p90
        if p90 is None or not math.isfinite(float(p90)):
            return None
        if float(p90) <= self.ceiling:
            return None
        return self._anomaly(
            ev.iteration,
            f"async staleness p90 {float(p90):.0f} exceeded the ceiling "
            f"{self.ceiling:.0f} writes (p50 {float(ev.staleness_p50):.0f}"
            f", max {float(ev.staleness_max):.0f})",
            {
                "iteration": int(ev.iteration),
                "staleness_p50": float(ev.staleness_p50),
                "staleness_p90": float(p90),
                "staleness_max": float(ev.staleness_max),
                "ceiling": self.ceiling,
            },
        )


class ScreeningSaturationDetector(Detector):
    """Robust screening trimming ~everything: the flight recorder's
    ``clip_frac`` activity (fraction of received closed-neighborhood
    messages screened out) at or above ``threshold`` for ``window``
    consecutive eval rows. A healthy trimmed-mean run screens a fixed
    2b/(deg+1) slice; near-total screening means the rule is rejecting
    honest traffic wholesale (an over-budget attack, or a radius/budget
    misconfiguration) and the 'aggregate' is mostly self-loops."""

    name = "screening_saturation"
    severity = "warn"

    def __init__(self, threshold: float = 0.95, window: int = 2):
        super().__init__()
        if not 0.0 < threshold <= 1.0:
            raise ValueError(f"threshold must be in (0, 1], got {threshold}")
        self.threshold = float(threshold)
        self.window = max(int(window), 1)

    def _scan_trace(self, trace, eval_iterations):
        frac = np.asarray(trace.get("clip_frac", []), dtype=np.float64)
        if frac.size < self.window:
            return None
        saturated = frac >= self.threshold
        run = 0
        for row, sat in enumerate(saturated):
            run = run + 1 if sat else 0
            if run == self.window:
                onset_row = row - self.window + 1
                iters = np.asarray(eval_iterations)
                onset = (
                    int(iters[onset_row]) if iters.size > onset_row
                    else onset_row
                )
                lo = max(onset_row - 1, 0)
                hi = min(row + 2, frac.size)
                return self._anomaly(
                    onset,
                    f"robust screening trimmed >= {self.threshold:.0%} of "
                    f"received messages for {self.window} consecutive "
                    f"eval windows from iteration {onset}",
                    {
                        "iterations": iters[lo:hi].astype(int).tolist(),
                        "clip_frac": frac[lo:hi].tolist(),
                        "threshold": self.threshold,
                    },
                )
        return None


def default_detectors(config, **overrides) -> list:
    """The detector set a config's run should watch — every signal the
    config can actually emit (an async run gets the staleness watcher, a
    robust-aggregation run the saturation watcher, ...), so a bank never
    carries detectors that can only stay silent. ``overrides`` replace a
    detector's constructor kwargs by detector name, e.g.
    ``divergence={'window': 2}``."""

    def kw(name):
        return dict(overrides.get(name, {}))

    dets: list = [
        DivergenceDetector(**kw("divergence")),
        NonFiniteDetector(**kw("non_finite")),
        ConsensusStallDetector(**kw("consensus_stall")),
    ]
    faults_active = (
        config.edge_drop_prob > 0.0
        or config.straggler_prob > 0.0
        or config.mttf > 0.0
        or config.participation_rate < 1.0
    )
    if faults_active and config.gossip_schedule == "synchronous":
        dets.append(ConnectivityLossDetector(**kw("connectivity_loss")))
    if getattr(config, "execution", "sync") == "async":
        dets.append(StalenessBlowupDetector(**kw("staleness_blowup")))
    if config.aggregation != "gossip" and config.robust_b > 0:
        dets.append(
            ScreeningSaturationDetector(**kw("screening_saturation"))
        )
    return dets


def _anomaly_metrics():
    from distributed_optimization_tpu.observability.metrics_registry import (
        metrics_registry,
    )

    reg = metrics_registry()
    return (
        reg.counter(
            "dopt_anomaly_firings_total",
            "Anomaly-detector firings by detector and severity",
        ),
        reg.counter(
            "dopt_anomaly_halts_total",
            "Runs halted early by the halt_on=fatal policy",
        ),
        reg.gauge(
            "dopt_anomaly_last_onset_iteration",
            "Onset iteration of the most recent firing per detector",
        ),
    )


class MonitorBank:
    """One run's detector set + the early-halt policy (module docstring).

    Feed it heartbeats via ``observe`` (the backends compose it into the
    progress callback chain) and, for trace-derived detectors, the
    flight-recorder buffers via ``scan_trace`` after the run. The
    backends consult ``should_halt()`` at chunk boundaries and call
    ``note_halt(iteration)`` when they actually stop.
    """

    def __init__(self, config, detectors: Optional[list] = None,
                 halt_on: str = "never", label: str = ""):
        if halt_on not in HALT_POLICIES:
            raise ValueError(
                f"halt_on must be one of {HALT_POLICIES}, got {halt_on!r}"
            )
        self.config = config
        self.detectors = (
            list(detectors) if detectors is not None
            else default_detectors(config)
        )
        self.halt_on = halt_on
        self.label = label
        self.anomalies: list[Anomaly] = []
        self.halted_at: Optional[int] = None
        self._firings, self._halts, self._last_onset = _anomaly_metrics()

    # ------------------------------------------------------------ feeding
    def observe(self, ev) -> list[Anomaly]:
        """Feed one heartbeat to every detector; returns the NEWLY fired
        anomalies (empty on a healthy beat). Never raises: a broken
        detector is contained like a broken progress callback."""
        fired: list[Anomaly] = []
        for det in self.detectors:
            try:
                anomaly = det.observe(ev)
            except Exception:
                from distributed_optimization_tpu.log import get_logger

                get_logger("monitors").exception(
                    "detector %s failed on a heartbeat; continuing", det.name
                )
                continue
            if anomaly is not None:
                fired.append(anomaly)
        self._record(fired)
        return fired

    def scan_trace(self, trace, eval_iterations) -> list[Anomaly]:
        """Feed the post-run flight-recorder buffers (telemetry runs
        only) to the trace-capable detectors."""
        fired: list[Anomaly] = []
        for det in self.detectors:
            try:
                anomaly = det.scan_trace(trace, eval_iterations)
            except Exception:
                from distributed_optimization_tpu.log import get_logger

                get_logger("monitors").exception(
                    "detector %s failed on the trace scan; continuing",
                    det.name,
                )
                continue
            if anomaly is not None:
                fired.append(anomaly)
        self._record(fired)
        return fired

    def _record(self, fired: Iterable[Anomaly]) -> None:
        for anomaly in fired:
            self.anomalies.append(anomaly)
            self._firings.inc(
                detector=anomaly.detector, severity=anomaly.severity,
            )
            self._last_onset.set(
                float(anomaly.onset_iteration), detector=anomaly.detector,
            )

    # ------------------------------------------------------------ policy
    def has_fatal(self) -> bool:
        return any(a.severity == "fatal" for a in self.anomalies)

    def should_halt(self) -> bool:
        """The backends' chunk-boundary question: stop now?"""
        return self.halt_on == "fatal" and self.has_fatal()

    def note_halt(self, iteration: int) -> None:
        """Called by the backend when it actually stops the run."""
        if self.halted_at is None:
            self.halted_at = int(iteration)
            self._halts.inc()

    # ----------------------------------------------------------- surfaces
    def summary(self) -> dict:
        """JSON-safe digest for health blocks / status polls, anomalies
        most-severe first."""
        ordered = sorted(
            self.anomalies,
            key=lambda a: (-severity_rank(a.severity), a.onset_iteration),
        )
        return {
            "count": len(self.anomalies),
            "fatal": sum(
                1 for a in self.anomalies if a.severity == "fatal"
            ),
            "halted_at": self.halted_at,
            "halt_on": self.halt_on,
            "anomalies": [a.to_dict() for a in ordered],
        }

    def incidents(self, label: Optional[str] = None) -> list[dict]:
        """One forensic bundle per recorded anomaly (``build_incident``)."""
        return [
            build_incident(
                self.config, a,
                label=label if label is not None else self.label,
            )
            for a in self.anomalies
        ]


# ------------------------------------------------------ incident forensics


def fault_context(config, onset: int, *, window: Optional[int] = None,
                  max_cells: int = 200_000) -> dict:
    """The operational facts around an anomaly's onset, rebuilt host-side
    from the config's (seed, horizon)-pure processes — bitwise what the
    backend executed (the ``parallel/faults.py`` purity contract):

    - attack block: the Byzantine set (seed-deterministic node indices),
      payload, and whether the attack exceeds the robust budget
      (``n_byzantine > robust_b`` is exactly the f > b breakdown regime);
    - fault block: which nodes were down at the onset round, the mean
      realized edge-up fraction over the onset window, and the realized
      B̂ of that window (None when even its union is disconnected);
    - async block: the onset-window staleness facts for event schedules.

    ``window`` is the half-width in iterations (default: 4 eval windows).
    Cost-capped like ``realized_bhat``: past ``max_cells`` timeline cells
    the fault block records ``{"skipped": ...}`` instead of stalling the
    incident path on a giant rebuild.
    """
    from distributed_optimization_tpu.algorithms import get_algorithm

    onset = int(onset)
    if window is None:
        window = 4 * config.eval_every
    lo = max(onset - window, 0)
    hi = min(onset + window, config.n_iterations)
    context: dict[str, Any] = {"window": [int(lo), int(hi)]}

    if config.attack != "none":
        from distributed_optimization_tpu.parallel.adversary import (
            byzantine_set,
        )

        mask = byzantine_set(config)
        block = {
            "attack": config.attack,
            "attack_scale": float(config.attack_scale),
            "n_byzantine": int(config.n_byzantine),
            "byzantine_nodes": np.flatnonzero(mask).astype(int).tolist(),
            "aggregation": config.aggregation,
            "robust_b": int(config.robust_b),
        }
        if config.aggregation != "gossip":
            # The f > b regime: more attackers than the per-neighborhood
            # budget the screening rule defends — the sharp breakdown
            # docs/perf/byzantine.json measures.
            block["over_budget"] = config.n_byzantine > config.robust_b
        context["attack"] = block

    from distributed_optimization_tpu.parallel.faults import (
        config_faults_active,
    )

    if (
        config_faults_active(config)
        and config.gossip_schedule == "synchronous"
        and getattr(config, "execution", "sync") != "async"
        and get_algorithm(config.algorithm).is_decentralized
    ):
        from distributed_optimization_tpu.parallel import build_topology
        from distributed_optimization_tpu.parallel.faults import (
            _edge_list,
            timeline_for_config,
            windowed_connectivity,
        )

        topo = build_topology(
            config.topology, config.n_workers,
            erdos_renyi_p=config.erdos_renyi_p,
            seed=config.resolved_topology_seed(),
            impl=config.resolved_topology_impl(),
            sampler=config.resolved_topology_sampler(),
        )
        n_edges = max(len(_edge_list(topo)), 1)
        if hi * n_edges > max_cells:
            context["faults"] = {
                "skipped": (
                    f"timeline rebuild to t={hi} over {n_edges} edges "
                    f"exceeds the {max_cells}-cell incident budget"
                ),
            }
        else:
            tl = timeline_for_config(config, topo, max(hi, 1))

            def view(arr):
                return None if arr is None else arr[lo:hi]

            tl_win = dataclasses.replace(
                tl, horizon=max(hi - lo, 1),
                edge_up=view(tl.edge_up), node_up=view(tl.node_up),
                rejoin=view(tl.rejoin), part_up=view(tl.part_up),
            )
            block = {
                "window_bhat": windowed_connectivity(tl_win, topo),
            }
            onset_row = min(onset, max(hi - 1, 0))
            up = np.ones(config.n_workers, dtype=np.float32)
            if tl.node_up is not None:
                up = up * tl.node_up[onset_row]
            if tl.part_up is not None:
                up = up * tl.part_up[onset_row]
            down = np.flatnonzero(up < 0.5)
            block["nodes_down_at_onset"] = down.astype(int).tolist()[:64]
            block["n_nodes_down_at_onset"] = int(down.size)
            if tl.edge_up is not None:
                block["edge_up_frac_window"] = float(
                    np.asarray(tl.edge_up[lo:hi], dtype=np.float64).mean()
                )
            context["faults"] = block

    if getattr(config, "execution", "sync") == "async":
        from distributed_optimization_tpu.backends.async_scan import (
            timeline_for,
        )

        _, tl = timeline_for(config)
        n = config.n_workers
        ev_lo, ev_hi = lo * n, max(hi * n, lo * n + 1)
        stale = np.asarray(
            tl.staleness[ev_lo:ev_hi], dtype=np.float64
        )
        if stale.size:
            block = {
                "latency_model": config.latency_model,
                "latency_tail": float(config.latency_tail),
                # Event-axis coordinates of the onset (ISSUE-17): one
                # round is N events, so the onset round's first event
                # index anchors the incident on the clock the backend
                # actually scanned.
                "onset_event": int(onset * n),
                "event_window": [int(ev_lo), int(min(ev_hi, len(tl.worker)))],
                "window_staleness_p50": float(np.percentile(stale, 50)),
                "window_staleness_p90": float(np.percentile(stale, 90)),
                "window_staleness_max": float(stale.max()),
            }
            if config_faults_active(config):
                # Event-realized fault forensics: which firings in the
                # onset window were in-flight losses (the stale gradient
                # evaporated with the crash) and which workers were down
                # at the onset round — host-rebuilt, bitwise the
                # realization the backend executed.
                from distributed_optimization_tpu.parallel import (
                    build_topology,
                )
                from distributed_optimization_tpu.parallel.events import (
                    realize_event_faults,
                )
                from distributed_optimization_tpu.parallel.faults import (
                    timeline_for_config,
                )

                topo = build_topology(
                    config.topology, config.n_workers,
                    erdos_renyi_p=config.erdos_renyi_p,
                    seed=config.resolved_topology_seed(),
                )
                ft = timeline_for_config(config, topo, tl.n_rounds)
                real = realize_event_faults(tl, ft)
                win_fire = real.fire[ev_lo:ev_hi]
                kk = tl.local_step.astype(np.int64)[ev_lo:ev_hi]
                win_worker = tl.worker[ev_lo:ev_hi].astype(np.int64)
                # Crash no-ops only (the EventFaultRealization
                # ``n_inflight_lost`` split): thinned events never had a
                # gradient in flight.
                win_up = (
                    ft.node_up[kk, win_worker]
                    if ft.node_up is not None
                    else np.ones(len(win_worker), dtype=bool)
                )
                lost = win_worker[~win_up]
                onset_row = min(onset, tl.n_rounds - 1)
                up = np.ones(n, dtype=bool)
                if ft.node_up is not None:
                    up &= ft.node_up[onset_row]
                if ft.part_up is not None:
                    up &= ft.part_up[onset_row]
                crashed = np.flatnonzero(~up)
                block["n_inflight_lost_window"] = int((~win_up).sum())
                block["inflight_lost_workers"] = sorted(
                    set(lost.tolist())
                )[:64]
                block["crashed_workers_at_onset"] = (
                    crashed.astype(int).tolist()[:64]
                )
                block["window_availability"] = (
                    float(win_fire.mean()) if win_fire.size else 1.0
                )
            context["async"] = block
    return context


def build_incident(config, anomaly: Anomaly, *, label: str = "",
                   remediation: Optional[dict] = None) -> dict:
    """One schema-versioned forensic bundle for a fired anomaly (module
    docstring): the anomaly facts, the producing config (+ content and
    serving-cohort structural hashes), the evidence window, the
    fault/attack context around the onset, and the environment
    provenance. Serialized as JSONL next to RunTrace manifests via
    ``write_incidents``.

    ``remediation``: optional structured block recording what the fleet's
    policy engine (``serving/fleet.py``) DID about this incident —
    ``{"policy", "outcome", "actions", ...}`` — so the forensic record
    carries detection AND response in one bundle. Readers that predate
    the fleet ignore the extra key (``read_incidents`` validates only
    kind + schema_version)."""
    from distributed_optimization_tpu.telemetry import (
        config_hash,
        provenance,
    )

    cd = config.to_dict()
    out = {
        "schema_version": INCIDENT_SCHEMA_VERSION,
        "kind": "incident",
        "label": label,
        "detector": anomaly.detector,
        "severity": anomaly.severity,
        "onset_iteration": int(anomaly.onset_iteration),
        "message": anomaly.message,
        "config": cd,
        "config_hash": config_hash(cd),
        "structural_hash": config.structural_hash(),
        "evidence": anomaly.evidence,
        "context": fault_context(config, anomaly.onset_iteration),
        "provenance": provenance(),
    }
    if remediation is not None:
        out["remediation"] = dict(remediation)
    return out


def incidents_path_for(manifest_path) -> Path:
    """The incident JSONL that rides next to a RunTrace manifest file:
    ``runs.jsonl`` → ``runs.incidents.jsonl``."""
    p = Path(manifest_path)
    stem = p.name[:-len(p.suffix)] if p.suffix else p.name
    return p.with_name(f"{stem}.incidents.jsonl")


def write_incidents(path, incidents: list[dict], *, append: bool = False,
                    ) -> Path:
    """Serialize incident bundles as strict-JSON JSONL (the telemetry
    non-finite sentinel convention: divergence evidence IS non-finite)."""
    from distributed_optimization_tpu.telemetry import _encode_nonfinite

    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    mode = "a" if append else "w"
    with open(p, mode) as f:
        for inc in incidents:
            f.write(
                json.dumps(
                    _encode_nonfinite(inc), sort_keys=True, allow_nan=False,
                )
                + "\n"
            )
    return p


def read_incidents(path) -> list[dict]:
    """Parse an incident JSONL file, validating the schema version."""
    from distributed_optimization_tpu.telemetry import _decode_nonfinite

    out = []
    for line in Path(path).read_text().splitlines():
        if not line.strip():
            continue
        blob = _decode_nonfinite(json.loads(line))
        if blob.get("kind") != "incident":
            raise ValueError(
                f"not an incident record: kind={blob.get('kind')!r}"
            )
        if blob.get("schema_version") != INCIDENT_SCHEMA_VERSION:
            raise ValueError(
                f"unsupported incident schema_version "
                f"{blob.get('schema_version')} (this build reads "
                f"v{INCIDENT_SCHEMA_VERSION})"
            )
        out.append(blob)
    return out
