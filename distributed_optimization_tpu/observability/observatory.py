"""The run registry + perf-regression checker (ISSUE-10 tentpole layer 4).

The repo now emits schema-versioned run evidence everywhere — RunTrace
JSONL from the CLI/Simulator/daemon, ``*.manifest.json`` provenance
sidecars from every bench — but nothing could READ that corpus: finding
"the runs of this config on this machine" meant grepping JSON by hand,
and a regenerated bench artifact was only ever compared to its committed
ancestor by eyeball. This module is the query side:

- ``index``/``list``: walk a directory for RunTrace manifests (``.jsonl``
  lines and bare ``.json`` objects) and bench sidecars, normalize each
  into a flat record (kind, label, config/structural hash, platform,
  provenance, final gap, iters/sec), filter by any of them, and emit a
  table or JSON. The structural hash is recomputed from the embedded
  config via ``ExperimentConfig.structural_hash`` — the SERVING cohort
  identity, so "which runs would have coalesced" is a one-flag query.
- ``compare A B``: field-level diff of two manifests — config fields
  that differ, provenance drift (different commit? dirty tree? other
  chip?), and the headline numbers side by side with ratios.
- ``perf-diff``: the regression checker. Re-checks a directory of
  freshly regenerated bench JSON against the committed ``docs/perf/*``
  within PER-ARTIFACT tolerances (``PERF_TOLERANCES``): structural keys
  must match exactly (the drift-guard contract), flagged booleans must
  not regress, and the named numeric series must agree within each
  entry's relative tolerance. Wall-clock-dependent numbers are NOT
  checked by default — they depend on the machine and the session; the
  specs name the quantities that are supposed to be stable (ratios,
  convergence envelopes, gate booleans). Exit code 1 on any regression — ``make perf-diff`` wires it
  into CI, turning the bench corpus into a guarded time series.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import json
import sys
from pathlib import Path
from typing import Any, Iterator, Optional

# ---------------------------------------------------------------- indexing


@dataclasses.dataclass
class RunRecord:
    """One indexed manifest (RunTrace or bench sidecar), flattened."""

    path: str
    line: Optional[int]  # JSONL line number (None for whole-file manifests)
    kind: str
    schema_version: int
    label: str
    backend: Optional[str]
    platform: Optional[str]
    config_hash: Optional[str]
    structural_hash: Optional[str]
    algorithm: Optional[str]
    n_workers: Optional[int]
    final_gap: Optional[float]
    iters_per_second: Optional[float]
    git_sha: Optional[str]
    device_kind: Optional[str]

    def row(self) -> str:
        gap = (
            f"{self.final_gap:.3e}" if self.final_gap is not None else "—"
        )
        ips = (
            f"{self.iters_per_second:.1f}"
            if self.iters_per_second is not None else "—"
        )
        sha = (self.git_sha or "—")[:8]
        return (
            f"{self.label[:32]:<34}{self.kind:<16}"
            f"{(self.structural_hash or '—')[:12]:<14}"
            f"{(self.algorithm or '—'):<18}{gap:>11}{ips:>9}  "
            f"{(self.platform or '—'):<5} {sha}"
        )


_HEADER = (
    f"{'label':<34}{'kind':<16}{'struct_hash':<14}{'algorithm':<18}"
    f"{'final_gap':>11}{'iters/s':>9}  {'plat':<5} git"
)


def _structural_hash_of(config_dict) -> Optional[str]:
    if not isinstance(config_dict, dict):
        return None
    try:
        from distributed_optimization_tpu.config import ExperimentConfig

        return ExperimentConfig.from_dict(config_dict).structural_hash()
    except Exception:
        # Configs from older schema versions may no longer validate;
        # an indexer must degrade to "unknown", not crash the listing.
        return None


def _record_from_manifest(
    blob: dict, path: Path, line: Optional[int]
) -> Optional[RunRecord]:
    kind = blob.get("kind")
    if kind not in ("run_trace", "bench_manifest"):
        return None
    cfg = blob.get("config") or {}
    health = blob.get("health") or {}
    prov = blob.get("provenance") or {}
    return RunRecord(
        path=str(path),
        line=line,
        kind=kind,
        schema_version=int(blob.get("schema_version", 0)),
        label=str(blob.get("label") or blob.get("artifact") or path.stem),
        backend=blob.get("backend"),
        platform=blob.get("platform"),
        config_hash=blob.get("config_hash"),
        structural_hash=_structural_hash_of(cfg),
        algorithm=cfg.get("algorithm") if isinstance(cfg, dict) else None,
        n_workers=cfg.get("n_workers") if isinstance(cfg, dict) else None,
        final_gap=_as_float(health.get("final_gap")),
        iters_per_second=_as_float(blob.get("iters_per_second")),
        git_sha=prov.get("git_sha"),
        device_kind=prov.get("device_kind"),
    )


def _as_float(v) -> Optional[float]:
    try:
        return float(v) if v is not None and not isinstance(v, str) else None
    except (TypeError, ValueError):
        return None


def iter_manifests(root) -> Iterator[tuple[dict, Path, Optional[int]]]:
    """Yield (manifest dict, path, jsonl-line-or-None) for every readable
    RunTrace/bench manifest under ``root`` (a file or a directory).
    Unreadable or foreign JSON is skipped — an index walks what it can."""
    from distributed_optimization_tpu.telemetry import _decode_nonfinite

    root = Path(root)
    paths = (
        [root] if root.is_file()
        else sorted(
            p for pattern in ("*.json", "*.jsonl") for p in root.rglob(pattern)
        )
    )
    for path in paths:
        try:
            text = path.read_text()
        except OSError:
            continue
        if path.suffix == ".jsonl":
            for i, line in enumerate(text.splitlines()):
                if not line.strip():
                    continue
                try:
                    yield _decode_nonfinite(json.loads(line)), path, i
                except json.JSONDecodeError:
                    continue
        else:
            try:
                yield _decode_nonfinite(json.loads(text)), path, None
            except json.JSONDecodeError:
                continue


def build_index(root, **filters) -> list[RunRecord]:
    """Index every manifest under ``root`` into ``RunRecord`` rows.

    ``filters``: config_hash=, structural_hash=, backend=, platform=,
    kind=, label= (substring, case-insensitive) — all ANDed.
    """
    records = []
    for blob, path, line in iter_manifests(root):
        if not isinstance(blob, dict):
            continue
        rec = _record_from_manifest(blob, path, line)
        if rec is None:
            continue
        if _matches(rec, filters):
            records.append(rec)
    return records


# --------------------------------------------------------------- incidents


@dataclasses.dataclass
class IncidentRecord:
    """One indexed anomaly-sentinel incident bundle (ISSUE-13;
    ``observability/monitors.py::build_incident``), flattened for the
    ``incidents`` subcommand and the ``list --with-incidents`` join."""

    path: str
    line: Optional[int]
    label: str
    detector: str
    severity: str
    onset_iteration: Optional[int]
    message: str
    config_hash: Optional[str]
    structural_hash: Optional[str]
    algorithm: Optional[str]
    # Fleet remediation attribution (ISSUE-16; ``serving/fleet.py``):
    # what the policy engine DID about this incident. None when the
    # bundle predates the fleet or nothing acted on it.
    remediation_policy: Optional[str] = None
    remediation_outcome: Optional[str] = None
    # Event-clock forensics (ISSUE-17; async fault context): the onset
    # round's first event index and the onset window's in-flight gradient
    # losses. None for synchronous or fault-free bundles.
    onset_event: Optional[int] = None
    n_inflight_lost: Optional[int] = None

    def row(self) -> str:
        onset = (
            str(self.onset_iteration)
            if self.onset_iteration is not None else "—"
        )
        ev = str(self.onset_event) if self.onset_event is not None else "—"
        lost = (
            str(self.n_inflight_lost)
            if self.n_inflight_lost is not None else "—"
        )
        return (
            f"{self.label[:28]:<30}{self.detector:<22}{self.severity:<8}"
            f"{onset:>8}{ev:>9}{lost:>6}  {(self.config_hash or '—')[:12]:<14}"
            f"{(self.algorithm or '—'):<18}"
            f"{(self.remediation_outcome or '—'):<12}{self.message[:48]}"
        )


_INCIDENT_HEADER = (
    f"{'label':<30}{'detector':<22}{'sev':<8}{'onset':>8}{'event':>9}"
    f"{'lost':>6}  {'config_hash':<14}{'algorithm':<18}"
    f"{'remediation':<12}message"
)


def build_incident_index(root, **filters) -> list[IncidentRecord]:
    """Index every ``kind='incident'`` JSONL record under ``root``
    (the bundles ``monitors.write_incidents`` leaves next to RunTrace
    manifests). ``filters``: detector=, severity=, config_hash=,
    structural_hash=, label= (substring) — all ANDed, the
    ``build_index`` convention."""
    records = []
    for blob, path, line in iter_manifests(root):
        if not isinstance(blob, dict) or blob.get("kind") != "incident":
            continue
        cfg = blob.get("config") or {}
        rem = blob.get("remediation")
        rem = rem if isinstance(rem, dict) else {}
        actx = (blob.get("context") or {}).get("async")
        actx = actx if isinstance(actx, dict) else {}
        rec = IncidentRecord(
            path=str(path),
            line=line,
            label=str(blob.get("label") or path.stem),
            detector=str(blob.get("detector") or "—"),
            severity=str(blob.get("severity") or "—"),
            onset_iteration=blob.get("onset_iteration"),
            message=str(blob.get("message") or ""),
            config_hash=blob.get("config_hash"),
            structural_hash=blob.get("structural_hash"),
            algorithm=cfg.get("algorithm") if isinstance(cfg, dict) else None,
            remediation_policy=rem.get("policy"),
            remediation_outcome=rem.get("outcome"),
            onset_event=actx.get("onset_event"),
            n_inflight_lost=actx.get("n_inflight_lost_window"),
        )
        if _matches(rec, filters):
            records.append(rec)
    return records


def incident_counts(root) -> dict[str, int]:
    """config_hash → incident count under ``root`` — the join key the
    ``list --with-incidents`` column uses (an incident bundle records
    the full config, so its content hash matches its run's manifest)."""
    counts: dict[str, int] = {}
    for rec in build_incident_index(root):
        if rec.config_hash:
            counts[rec.config_hash] = counts.get(rec.config_hash, 0) + 1
    return counts


def index_with_incident_counts(
    root, **filters
) -> tuple[list[RunRecord], dict[str, int]]:
    """``(build_index(root, **filters), incident_counts(root))`` in ONE
    directory walk — ``list --with-incidents`` reads both from the same
    corpus, and a scenario-engine-sized runs/ directory should not pay
    the JSON decode twice."""
    records: list[RunRecord] = []
    counts: dict[str, int] = {}
    for blob, path, line in iter_manifests(root):
        if not isinstance(blob, dict):
            continue
        if blob.get("kind") == "incident":
            ch = blob.get("config_hash")
            if ch:
                counts[ch] = counts.get(ch, 0) + 1
            continue
        rec = _record_from_manifest(blob, path, line)
        if rec is not None and _matches(rec, filters):
            records.append(rec)
    return records, counts


def _matches(rec: RunRecord, filters: dict) -> bool:
    for key, want in filters.items():
        if want is None:
            continue
        have = getattr(rec, key, None)
        if key == "label":
            if have is None or want.lower() not in have.lower():
                return False
        elif have != want:
            return False
    return True


# ---------------------------------------------------------------- compare


def load_manifest(spec: str) -> dict:
    """Load one manifest: ``path.json``, or ``path.jsonl[:line]`` (line 0
    when omitted)."""
    path, line = spec, 0
    if ":" in spec and not Path(spec).exists():
        path, _, line_s = spec.rpartition(":")
        try:
            line = int(line_s)
        except ValueError:
            path, line = spec, 0
    from distributed_optimization_tpu.telemetry import _decode_nonfinite

    p = Path(path)
    text = p.read_text()
    if p.suffix == ".jsonl":
        lines = [ln for ln in text.splitlines() if ln.strip()]
        return _decode_nonfinite(json.loads(lines[line]))
    return _decode_nonfinite(json.loads(text))


def compare_manifests(a: dict, b: dict) -> dict:
    """Field-level diff of two manifests (the ``compare`` subcommand)."""
    cfg_a, cfg_b = a.get("config") or {}, b.get("config") or {}
    config_diff = {
        k: [cfg_a.get(k), cfg_b.get(k)]
        for k in sorted(set(cfg_a) | set(cfg_b))
        if cfg_a.get(k) != cfg_b.get(k)
    }
    prov_a, prov_b = a.get("provenance") or {}, b.get("provenance") or {}
    prov_diff = {
        k: [prov_a.get(k), prov_b.get(k)]
        for k in sorted(set(prov_a) | set(prov_b))
        if prov_a.get(k) != prov_b.get(k)
    }
    ha, hb = a.get("health") or {}, b.get("health") or {}

    def ratio(x, y):
        x, y = _as_float(x), _as_float(y)
        if x is None or y is None or x == 0:
            return None
        return y / x

    headline = {}
    for key, va, vb in (
        ("final_gap", ha.get("final_gap"), hb.get("final_gap")),
        ("iters_per_second", a.get("iters_per_second"),
         b.get("iters_per_second")),
        ("compile_seconds", a.get("compile_seconds"),
         b.get("compile_seconds")),
    ):
        headline[key] = {"a": va, "b": vb, "b_over_a": ratio(va, vb)}

    def inc_block(h):
        inc = (h or {}).get("incidents") or {}
        return {
            "count": int(inc.get("count", 0)),
            "fatal": int(inc.get("fatal", 0)),
            "detectors": sorted({
                an.get("detector") for an in inc.get("anomalies", [])
                if an.get("detector")
            }),
        }

    def rem_outcomes(blob, h):
        # Remediation outcomes visible on this side (ISSUE-16): a
        # top-level block (comparing incident-bundle JSONL lines
        # directly — the fleet-on vs fleet-off workflow), plus any
        # carried by the health block's anomaly digests.
        outs = []
        rem = blob.get("remediation")
        if isinstance(rem, dict) and rem.get("outcome"):
            outs.append(str(rem["outcome"]))
        inc = (h or {}).get("incidents") or {}
        for an in inc.get("anomalies", []):
            r = an.get("remediation") if isinstance(an, dict) else None
            if isinstance(r, dict) and r.get("outcome"):
                outs.append(str(r["outcome"]))
        return sorted(outs)

    inc_a, inc_b = inc_block(ha), inc_block(hb)
    rem_a, rem_b = rem_outcomes(a, ha), rem_outcomes(b, hb)

    def async_ctx(blob):
        # Event-clock fault context (ISSUE-17): present when comparing
        # incident-bundle JSONL lines for async faulty runs.
        ctx = blob.get("context")
        actx = ctx.get("async") if isinstance(ctx, dict) else None
        if not isinstance(actx, dict):
            return None
        return {
            k: actx.get(k)
            for k in ("onset_event", "n_inflight_lost_window",
                      "window_availability", "crashed_workers_at_onset")
            if k in actx
        }

    actx_a, actx_b = async_ctx(a), async_ctx(b)
    async_delta = None
    if actx_a is not None or actx_b is not None:
        av_a = (actx_a or {}).get("window_availability")
        av_b = (actx_b or {}).get("window_availability")
        async_delta = {
            "a": actx_a,
            "b": actx_b,
            "availability_delta": (
                av_b - av_a if av_a is not None and av_b is not None
                else None
            ),
        }
    return {
        "a": {"label": a.get("label") or a.get("artifact"),
              "config_hash": a.get("config_hash")},
        "b": {"label": b.get("label") or b.get("artifact"),
              "config_hash": b.get("config_hash")},
        "same_config_hash": (
            a.get("config_hash") == b.get("config_hash")
            and a.get("config_hash") is not None
        ),
        "structural_match": (
            _structural_hash_of(cfg_a) == _structural_hash_of(cfg_b)
            and _structural_hash_of(cfg_a) is not None
        ),
        "config_diff": config_diff,
        "provenance_diff": prov_diff,
        "headline": headline,
        # Anomaly-sentinel delta (ISSUE-13): which run carried incidents,
        # how many, which detectors — the first thing to look at when two
        # runs of one config disagree.
        "incidents": {
            "a": inc_a,
            "b": inc_b,
            "delta": inc_b["count"] - inc_a["count"],
            "detectors_only_in_b": sorted(
                set(inc_b["detectors"]) - set(inc_a["detectors"])
            ),
            "detectors_only_in_a": sorted(
                set(inc_a["detectors"]) - set(inc_b["detectors"])
            ),
            # Fleet remediation-outcome delta (ISSUE-16): did the policy
            # engine act, and did the two sides resolve differently?
            "remediation": {
                "a": rem_a,
                "b": rem_b,
                "delta_remediated": (
                    rem_b.count("remediated") - rem_a.count("remediated")
                ),
            },
        },
        # Event-clock fault-context delta (ISSUE-17): None unless at
        # least one side is an incident bundle carrying an async block.
        "async_context": async_delta,
    }


# ------------------------------------------------------------- perf-diff


@dataclasses.dataclass(frozen=True)
class Check:
    """One tolerance rule: dotted-path pattern (fnmatch, list indices are
    path components) → how fresh may differ from committed.

    ``rtol``: numeric leaves must satisfy |fresh − committed| ≤
    rtol·max(|committed|, atol_floor). ``equal``: exact equality (gate
    booleans, flags, counts); with ``bool_only`` the pattern's non-boolean
    matches are skipped — the idiom for ``gates.*`` blocks that mix
    asserted booleans with measured floats. ``direction``: 'min' fails a
    fresh value only BELOW the envelope (throughput-style floors where
    faster is fine), 'max' the mirror (overhead/deviation ceilings).
    """

    pattern: str
    rtol: float = 0.25
    equal: bool = False
    bool_only: bool = False
    direction: Optional[str] = None  # None | 'min' | 'max'
    atol_floor: float = 1e-9


# Per-artifact checks. Deliberately NOT exhaustive: bench JSON is full of
# wall-clock numbers that depend on the machine and the session —
# checking those would make the guard cry wolf. What IS checked: the gate booleans every bench asserts
# (a regen that flips one has regressed — including platform-conditional
# flags like ``floor_applied``, which correctly fail when "fresh" came
# from different hardware: such a regen is not comparable evidence),
# deterministic convergence facts (final gaps, B̂ tables, floats-to-ε)
# inside generous envelopes, f64 parity ceilings, and the committed floor
# constants themselves. Artifacts without an entry get the top-level
# key-structure check only (the drift-guard parity).
PERF_TOLERANCES: dict[str, tuple[Check, ...]] = {
    "observatory.json": (
        Check("gates.*", equal=True, bool_only=True),
        Check("heartbeat.overhead_frac", rtol=1.0, direction="max",
              atol_floor=0.03),
        Check("scrape.p95_ms", rtol=3.0, direction="max", atol_floor=5.0),
    ),
    "telemetry.json": (
        Check("gates.*", equal=True, bool_only=True),
        Check("cells.*.overhead_ok", equal=True),
        Check("cells.*.off_on_bitwise_objective", equal=True),
    ),
    "serving.json": (
        Check("gates.applied", equal=True),
        Check("parity.max_abs_deviation_f64", rtol=1.0, atol_floor=1e-12,
              direction="max"),
        Check("latency.speedup_submit_to_start", rtol=0.9, direction="min"),
        Check("throughput.speedup", rtol=0.6, direction="min"),
        Check("throughput.coalescing_loses", equal=True),
    ),
    "serving_load.json": (
        # The sustained-load plane (ISSUE-15): the boolean gates —
        # restart replay 100% warm + bitwise over the persistent store,
        # shed observed at the tenant cap, the honest saturation/
        # fairness loses flags — must reproduce exactly; the wall-clock
        # cells (warm p99, saturation req/s, victim fairness ratio) get
        # generous envelopes because this shared CPU container's load
        # varies 2-3x between sessions.
        Check("gates.*", equal=True, bool_only=True),
        Check("gates.parity_max_abs_deviation_f64",
              rtol=1.0, atol_floor=1e-12, direction="max"),
        Check("latency.warm_p99_s", rtol=2.0, direction="max",
              atol_floor=1.0),
        Check("saturation.requests_per_s", rtol=0.7, direction="min"),
        Check("saturation.saturation_loses", equal=True),
        Check("fairness.victim_p99_ratio", rtol=2.0, direction="max",
              atol_floor=2.0),
        Check("fairness.fairness_loses", equal=True),
        Check("restart.warm_ratio", equal=True),
        Check("restart.bitwise", equal=True),
    ),
    "async.json": (
        Check("gates.*", equal=True, bool_only=True),
        Check("gates.jax_vs_numpy_per_event_parity_max_dev_f64",
              rtol=1.0, atol_floor=1e-12, direction="max"),
    ),
    "async_faults.json": (
        # Faults on the event clock (ISSUE-17): the crash-free bitwise
        # gate, the no-free-lunch and matched-availability flags must
        # reproduce exactly; the tracker residual is an f64 exactness
        # ceiling; the under-faults barrier speedup and the
        # churn-vs-thinning envelope get generous envelopes (latency
        # draws are seeded, but ε-crossing indices quantize at the eval
        # cadence).
        Check("gates.*", equal=True, bool_only=True),
        Check("gates.tracking_residual_max", rtol=1.0,
              atol_floor=1e-12, direction="max"),
        Check("gates.tracking_residual_staleness_zero", rtol=1.0,
              atol_floor=1e-12, direction="max"),
        Check("gates.wall_clock_speedup_under_faults", rtol=0.4,
              direction="min"),
        Check("runs.crash_free_gate.bitwise_*", equal=True),
        Check("runs.matched_availability.faulty_vs_faulty_envelope",
              rtol=0.7, direction="max", atol_floor=1.0),
    ),
    "federated.json": (
        Check("gates.max_n_completed_matrix_free", equal=True),
        Check("gates.best_floats_to_eps_reduction", rtol=0.5,
              direction="min"),
    ),
    "fused_robust.json": (
        Check("gates.*", equal=True, bool_only=True),
        Check("gates.compiled_floor", equal=True),
        Check("gates.bytes_ceiling", equal=True),
        Check("gates.gap_envelope", equal=True),
    ),
    "churn.json": (
        Check("gates.burst1_bitwise_iid", equal=True),
        Check("gates.bhat_by_burst.*", equal=True),
        Check("gates.monotone_gap_degradation.*", rtol=0.5),
    ),
    "sweep.json": (
        Check("floors.accelerator_speedup_at_r32", equal=True),
        Check("floors.cpu_steady_speedup_at_r32", equal=True),
    ),
    "scenarios.json": (
        # The golden corpus (ISSUE-12): every gate boolean — validity
        # agreement, per-cell invariants, warm replay, chaos
        # degradation — plus the exact cell counts must reproduce.
        Check("gates.*", equal=True, bool_only=True),
        Check("gates.agreement_cells", equal=True),
        # Composition closure (ISSUE-17): the fixed sample's valid-cell
        # count/fraction are committed numbers — a regen that shrinks
        # them has re-grown a rejection rule.
        Check("gates.agreement_valid_cells", equal=True),
        Check("gates.agreement_valid_fraction", equal=True),
        Check("gates.matrix_n_valid_cells", equal=True),
        Check("matrix.counts.valid", equal=True),
        Check("matrix.invariants.failures", equal=True),
    ),
    "worker_mesh.json": (
        Check("gates.*", equal=True, bool_only=True),
        Check("gates.parity_max_objective_rel_deviation_f64",
              rtol=1.0, atol_floor=1e-12, direction="max"),
        Check("gates.n100k_ici_bytes_per_device_per_round", equal=True),
    ),
    "mesh_scale.json": (
        Check("gates.n1m_*", equal=True),
        Check("gates.per_device_flat_at_matched_rows", equal=True),
        Check("gates.ring_ici_bytes_per_device_flat_in_n", equal=True),
        Check("gates.er_1m_sparse_plan_built", equal=True),
        Check("gates.topk_wire_bytes_halved", equal=True),
        Check("gates.topk_gap_within_envelope", equal=True),
        Check("gates.compressed_models_match_unsharded", equal=True),
        # deterministic pricing off the static plan: exact
        Check("gates.topk_wire_bytes_ratio", equal=True),
    ),
    "monitors.json": (
        # The anomaly sentinel (ISSUE-13): every gate boolean — monitor
        # overhead within the ≤5% ceiling on the sequential AND async
        # paths, monitors-on bitwise == off, the planted f>b divergence
        # firing with onset inside the 2-eval-window envelope, the
        # early halt actually saving work, and the incident bundle
        # naming the attacker context — must reproduce exactly; the
        # measured overhead fractions get a generous ceiling envelope.
        Check("gates.*", equal=True, bool_only=True),
        Check("overhead.overhead_frac", rtol=1.0, direction="max",
              atol_floor=0.05),
        Check("async.overhead_frac", rtol=1.0, direction="max",
              atol_floor=0.05),
        Check("divergence.onset_error_eval_windows", rtol=0.0,
              direction="max", atol_floor=2.0),
    ),
    "fleet.json": (
        # The self-healing fleet soak (ISSUE-16): the boolean gates —
        # every injected incident remediated (divergence halt +
        # quarantine, dead-worker respawn, store-corruption quarantine
        # + cold recompile), zero stuck requests, a full scale-up/
        # scale-down cycle — must reproduce exactly; the warm-p99 SLO
        # cell gets a generous ceiling envelope (shared CPU container,
        # 2-3x session-to-session wall-clock variance).
        Check("gates.*", equal=True, bool_only=True),
        Check("latency.warm_p99_s", rtol=2.0, direction="max",
              atol_floor=2.0),
        Check("stuck_requests", equal=True),
    ),
}


def _iter_leaves(obj, prefix=()) -> Iterator[tuple[tuple, Any]]:
    if isinstance(obj, dict):
        for k, v in obj.items():
            yield from _iter_leaves(v, prefix + (str(k),))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            yield from _iter_leaves(v, prefix + (str(i),))
    else:
        yield prefix, obj


def _check_leaf(check: Check, path: str, committed, fresh) -> Optional[str]:
    """None when within tolerance, else the failure message."""
    if check.equal:
        if fresh != committed:
            return f"{path}: {committed!r} -> {fresh!r} (must match exactly)"
        return None
    c, f = _as_float(committed), _as_float(fresh)
    if c is None or f is None:
        if fresh != committed and (c is None) != (f is None):
            return f"{path}: {committed!r} -> {fresh!r} (type changed)"
        return None
    scale = max(abs(c), check.atol_floor)
    if check.direction == "min":
        if f < c - check.rtol * scale:
            return (
                f"{path}: {c:.6g} -> {f:.6g} (below floor envelope "
                f"rtol={check.rtol})"
            )
        return None
    if check.direction == "max":
        if f > c + check.rtol * scale:
            return (
                f"{path}: {c:.6g} -> {f:.6g} (above ceiling envelope "
                f"rtol={check.rtol})"
            )
        return None
    if abs(f - c) > check.rtol * scale:
        return f"{path}: {c:.6g} -> {f:.6g} (rtol={check.rtol})"
    return None


def perf_diff(
    fresh_dir, committed_dir, *, artifacts: Optional[list] = None,
) -> dict:
    """Compare fresh bench JSON against the committed artifacts.

    Returns {"artifacts": {name: {"status", "failures", "checked"}},
    "ok": bool}. Every committed non-manifest artifact present in
    ``fresh_dir`` is compared: top-level key sets must match exactly
    (the drift-guard contract), then the artifact's ``PERF_TOLERANCES``
    checks run over matching leaves. A fresh artifact missing a checked
    leaf fails (a silently vanished gate is a regression, not a pass).
    """
    fresh_dir, committed_dir = Path(fresh_dir), Path(committed_dir)
    out: dict[str, Any] = {"artifacts": {}, "ok": True}
    names = sorted(
        p.name for p in committed_dir.glob("*.json")
        if not p.name.endswith(".manifest.json")
    )
    if artifacts:
        names = [n for n in names if n in set(artifacts)]
    for name in names:
        fresh_path = fresh_dir / name
        entry: dict[str, Any] = {"failures": [], "checked": 0}
        out["artifacts"][name] = entry
        if not fresh_path.exists():
            entry["status"] = "missing"
            continue
        committed = json.loads((committed_dir / name).read_text())
        fresh = json.loads(fresh_path.read_text())
        if set(committed) != set(fresh):
            entry["failures"].append(
                f"top-level keys drifted: extra={set(fresh) - set(committed)}"
                f", missing={set(committed) - set(fresh)}"
            )
        checks = PERF_TOLERANCES.get(name, ())
        committed_leaves = dict(_iter_leaves(committed))
        fresh_leaves = dict(_iter_leaves(fresh))
        for check in checks:
            matched = False
            for path_t, cval in committed_leaves.items():
                dotted = ".".join(path_t)
                if not fnmatch.fnmatch(dotted, check.pattern):
                    continue
                matched = True
                if check.bool_only and not isinstance(cval, bool):
                    continue
                entry["checked"] += 1
                if path_t not in fresh_leaves:
                    entry["failures"].append(
                        f"{dotted}: present in committed, missing in fresh"
                    )
                    continue
                msg = _check_leaf(check, dotted, cval, fresh_leaves[path_t])
                if msg is not None:
                    entry["failures"].append(msg)
            if not matched:
                entry["failures"].append(
                    f"tolerance pattern {check.pattern!r} matched nothing "
                    "in the committed artifact (stale spec)"
                )
        entry["status"] = "ok" if not entry["failures"] else "regressed"
        if entry["failures"]:
            out["ok"] = False
    return out


# -------------------------------------------------------------------- CLI


def _cmd_list(args) -> int:
    filters = dict(
        config_hash=args.config_hash,
        structural_hash=args.structural_hash,
        backend=args.backend,
        platform=args.platform,
        kind=args.kind,
        label=args.label,
    )
    if args.with_incidents:
        records, counts = index_with_incident_counts(args.root, **filters)
    else:
        records, counts = build_index(args.root, **filters), None

    def n_inc(rec):
        return counts.get(rec.config_hash, 0) if counts is not None else None

    if args.json:
        rows = []
        for rec in records:
            d = dataclasses.asdict(rec)
            if counts is not None:
                d["incidents"] = n_inc(rec)
            rows.append(d)
        print(json.dumps(rows, indent=1))
        return 0
    header = _HEADER + ("  incidents" if counts is not None else "")
    print(header)
    print("-" * len(header))
    for rec in records:
        line = rec.row()
        if counts is not None:
            line += f"  {n_inc(rec):>9}"
        print(line)
    print(f"{len(records)} manifest(s) under {args.root}")
    return 0


def _cmd_incidents(args) -> int:
    records = build_incident_index(
        args.root,
        detector=args.detector,
        severity=args.severity,
        config_hash=args.config_hash,
        structural_hash=args.structural_hash,
        label=args.label,
    )
    # Remediation-outcome filters (ISSUE-16): --remediated keeps bundles
    # the fleet's policy engine resolved; --unremediated keeps the rest —
    # failed/skipped outcomes AND bundles nothing acted on (those are
    # the ones an operator still owes a response).
    if getattr(args, "remediated", False):
        records = [
            r for r in records if r.remediation_outcome == "remediated"
        ]
    if getattr(args, "unremediated", False):
        records = [
            r for r in records if r.remediation_outcome != "remediated"
        ]
    if args.json:
        print(json.dumps(
            [dataclasses.asdict(r) for r in records], indent=1,
        ))
        return 0
    print(_INCIDENT_HEADER)
    print("-" * len(_INCIDENT_HEADER))
    for rec in records:
        print(rec.row())
    print(f"{len(records)} incident(s) under {args.root}")
    return 0


def _cmd_compare(args) -> int:
    diff = compare_manifests(load_manifest(args.a), load_manifest(args.b))
    if args.json:
        print(json.dumps(diff, indent=1, default=str))
        return 0
    print(f"A: {diff['a']['label']}  ({diff['a']['config_hash']})")
    print(f"B: {diff['b']['label']}  ({diff['b']['config_hash']})")
    print(
        f"config: {'IDENTICAL' if diff['same_config_hash'] else 'differs'}"
        f"; structural (serving-cohort) match: {diff['structural_match']}"
    )
    for k, pair in diff["config_diff"].items():
        print(f"  config.{k}: {pair[0]!r} -> {pair[1]!r}")
    for k, pair in diff["provenance_diff"].items():
        print(f"  provenance.{k}: {pair[0]!r} -> {pair[1]!r}")
    for k, row in diff["headline"].items():
        r = row["b_over_a"]
        print(
            f"  {k}: {row['a']} vs {row['b']}"
            + (f"  (B/A = {r:.3f})" if r is not None else "")
        )
    inc = diff["incidents"]
    if inc["a"]["count"] or inc["b"]["count"]:
        print(
            f"  incidents: {inc['a']['count']} vs {inc['b']['count']} "
            f"(delta {inc['delta']:+d})"
        )
        if inc["detectors_only_in_b"]:
            print(
                "    fired only in B: "
                + ", ".join(inc["detectors_only_in_b"])
            )
        if inc["detectors_only_in_a"]:
            print(
                "    fired only in A: "
                + ", ".join(inc["detectors_only_in_a"])
            )
    rem = inc["remediation"]
    if rem["a"] or rem["b"]:
        print(
            f"  remediation: {rem['a'] or ['none']} vs "
            f"{rem['b'] or ['none']} "
            f"(remediated delta {rem['delta_remediated']:+d})"
        )
    actx = diff.get("async_context")
    if actx:
        sa, sb = actx["a"] or {}, actx["b"] or {}
        print(
            "  async fault context: "
            f"availability {sa.get('window_availability')} vs "
            f"{sb.get('window_availability')}"
            + (
                f" (delta {actx['availability_delta']:+.3f})"
                if actx["availability_delta"] is not None else ""
            )
        )
        print(
            f"    in-flight losses: {sa.get('n_inflight_lost_window')} vs "
            f"{sb.get('n_inflight_lost_window')}; onset event "
            f"{sa.get('onset_event')} vs {sb.get('onset_event')}"
        )
    return 0


def _cmd_perf_diff(args) -> int:
    result = perf_diff(
        args.fresh, args.committed, artifacts=args.artifact or None,
    )
    n_ok = n_checked = 0
    for name, entry in result["artifacts"].items():
        n_checked += entry["checked"]
        status = entry["status"]
        if status == "ok":
            n_ok += 1
            print(f"[perf-diff] OK        {name} ({entry['checked']} checks)")
        elif status == "missing":
            print(f"[perf-diff] MISSING   {name} (no fresh artifact)")
        else:
            print(f"[perf-diff] REGRESSED {name}")
            for msg in entry["failures"]:
                print(f"    {msg}")
    total = len(result["artifacts"])
    print(
        f"[perf-diff] {n_ok}/{total} artifacts ok, {n_checked} leaf checks, "
        f"fresh={args.fresh} committed={args.committed}"
    )
    return 0 if result["ok"] else 1


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="distributed_optimization_tpu.observatory",
        description=(
            "Run registry + perf-regression checker over RunTrace "
            "manifests and bench sidecars (docs/OBSERVABILITY.md)."
        ),
    )
    sub = p.add_subparsers(dest="command", required=True)

    pl = sub.add_parser(
        "list", help="index manifests under a directory and print a table",
    )
    pl.add_argument("root", help="directory (or single file) to index")
    pl.add_argument("--config-hash", default=None)
    pl.add_argument("--structural-hash", default=None,
                    help="filter by the serving-cohort structural hash "
                         "(recomputed from each manifest's config)")
    pl.add_argument("--backend", default=None)
    pl.add_argument("--platform", default=None)
    pl.add_argument("--kind", default=None,
                    choices=("run_trace", "bench_manifest"))
    pl.add_argument("--label", default=None,
                    help="case-insensitive substring on label/artifact")
    pl.add_argument("--with-incidents", action="store_true",
                    help="join anomaly-sentinel incident bundles under "
                         "the same root onto the listing (per-manifest "
                         "incident count column, keyed by config hash)")
    pl.add_argument("--json", action="store_true")
    pl.set_defaults(fn=_cmd_list)

    pi = sub.add_parser(
        "incidents",
        help="list anomaly-sentinel incident bundles (the JSONL the "
             "monitors write next to RunTrace manifests)",
    )
    pi.add_argument("root", help="directory (or single file) to index")
    pi.add_argument("--detector", default=None)
    pi.add_argument("--severity", default=None,
                    choices=("info", "warn", "fatal"))
    pi.add_argument("--config-hash", default=None)
    pi.add_argument("--structural-hash", default=None)
    rem_group = pi.add_mutually_exclusive_group()
    rem_group.add_argument(
        "--remediated", action="store_true",
        help="only incidents the fleet's policy engine resolved "
             "(remediation outcome 'remediated')")
    rem_group.add_argument(
        "--unremediated", action="store_true",
        help="only incidents still owed a response (no remediation "
             "block, or a failed/skipped outcome)")
    pi.add_argument("--label", default=None,
                    help="case-insensitive substring on the run label")
    pi.add_argument("--json", action="store_true")
    pi.set_defaults(fn=_cmd_incidents)

    pc = sub.add_parser(
        "compare", help="field-level diff of two manifests",
    )
    pc.add_argument("a", help="manifest path (.json, or .jsonl[:line])")
    pc.add_argument("b")
    pc.add_argument("--json", action="store_true")
    pc.set_defaults(fn=_cmd_compare)

    pd = sub.add_parser(
        "perf-diff",
        help="check regenerated bench JSON against committed docs/perf "
             "within per-artifact tolerances (exit 1 on regression)",
    )
    pd.add_argument("--fresh", default="docs/perf",
                    help="directory of freshly regenerated artifacts "
                         "(default: docs/perf — a self-check)")
    pd.add_argument("--committed", default="docs/perf")
    pd.add_argument("--artifact", action="append",
                    help="restrict to this artifact name (repeatable)")
    pd.set_defaults(fn=_cmd_perf_diff)

    args = p.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
