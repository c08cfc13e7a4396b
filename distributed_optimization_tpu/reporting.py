"""Text report and figure generation.

Capability parity with the reference's reporting layer (reference
``simulator.py:139-201``): a numerical-results table (iterations to the
suboptimality threshold, total and per-worker floats transmitted) and a
2-panel log-scale matplotlib figure (suboptimality gap, consensus error)
with the same defensive guards — skip non-finite histories, tolerate runs
that recorded no consensus error. New columns the reference prints elsewhere
or not at all: spectral gap (reference prints it at trainer construction,
``trainer.py:133-135``) and measured iterations/second (the TPU-side
observability metric).
"""

from __future__ import annotations

from typing import Optional

import numpy as np


def _fmt_sci(v: float) -> str:
    return f"{v:.3e}"


def format_report(records, config, f_opt: float, phases=None,
                  serving=None, device=None) -> str:
    """Render the numerical-results table for a list of ExperimentRecords.

    ``device``: ``runtime.device_summary()`` of the process when a jax run
    is in the table — the header then names platform, device kind and
    visible count, so a report from a CPU fallback cannot pass for a chip's.
    Runs whose state was sharded over several devices get a line saying
    over how many.

    ``phases``: optional {name: seconds} wall-clock phase accounting
    (Simulator's PhaseTimer) appended as its own section. Records carrying
    flight-recorder state (``config.telemetry``) additionally get a
    run-health section: worst-worker gradient norm, non-finite counts, and
    realized-vs-nominal connectivity (docs/OBSERVABILITY.md).

    ``serving``: optional executable-cache / coalescing counters (the
    Simulator passes the process cache's stats once it has recorded a hit;
    the serving layer passes ``SimulationService.stats()``) rendered as a
    one-line serving summary (docs/SERVING.md).
    """
    lines = [
        "=" * 78,
        f"Numerical results — problem={config.problem_type}, N={config.n_workers}, "
        f"T={config.n_iterations}, b={config.local_batch_size}, "
        f"eta0={config.learning_rate_eta0}, lambda={config.l2_regularization_lambda}",
        f"backend={config.backend}{_device_tag(device)}; "
        f"f(x*) = {f_opt:.6f}; "
        f"suboptimality threshold = {config.suboptimality_threshold}",
        "=" * 78,
    ]
    header = (
        f"{'run':<28}{'iters→ε':>9}{'sec→ε':>8}{'floats total':>14}"
        f"{'floats/worker':>15}{'1−ρ':>8}{'iters/s':>10}"
    )
    lines += [header, "-" * len(header)]
    any_interpolated = False
    for rec in records:
        if rec.skipped_reason is not None:
            lines.append(f"{rec.label:<28}{'N/A — ' + rec.skipped_reason}")
            continue
        stats = getattr(rec, "replicate_stats", None)
        if stats is not None:
            # Replica-batched row (ISSUE-4): every quoted number is a
            # mean ± std over the seed replicates, not one trajectory's.
            if stats.n_reached:
                iters = (
                    f"{stats.iterations_to_threshold_mean:.0f}"
                    f"±{stats.iterations_to_threshold_std:.0f}"
                )
                if stats.n_reached < stats.n_replicas:
                    iters += f" ({stats.n_reached}/{stats.n_replicas})"
            else:
                iters = "never"
            s = rec.summary
            gap = (
                f"{s.spectral_gap:.4f}" if s.spectral_gap is not None else "—"
            )
            lines.append(
                # The mean±std iters→ε spans the iters→ε + sec→ε columns
                # (per-eval wall-clock is batch-wide, so sec→ε has no
                # per-replica meaning).
                f"{rec.label + f' [R={stats.n_replicas}]':<28}{iters:>17}"
                f"{_fmt_sci(s.total_transmission_floats):>14}"
                f"{_fmt_sci(s.avg_worker_transmission_floats):>15}{gap:>8}"
                f"{stats.aggregate_iters_per_second:>10.1f}"
            )
            cons = (
                f", consensus {stats.consensus_mean:.3e} ± "
                f"{stats.consensus_std:.3e}"
                if stats.consensus_mean is not None else ""
            )
            # 'a..b' only for a genuinely consecutive seed vector; an
            # explicit --seeds list is printed verbatim (11..42 would
            # misreport which seeds ran).
            consecutive = stats.seeds == list(
                range(stats.seeds[0], stats.seeds[0] + len(stats.seeds))
            )
            seed_str = (
                f"{stats.seeds[0]}..{stats.seeds[-1]}"
                if consecutive and len(stats.seeds) > 1
                else ",".join(str(s) for s in stats.seeds)
            )
            lines.append(
                f"{'':<28}final gap {stats.final_gap_mean:.5f} ± "
                f"{stats.final_gap_std:.5f} over seeds "
                f"{seed_str}{cons} "
                "(iters/s = aggregate across replicas)"
            )
            continue
        s = rec.summary
        iters = str(s.iterations_to_threshold) if s.iterations_to_threshold > 0 else "never"
        if np.isfinite(s.seconds_to_threshold):
            # "~" = interpolated from the total run wall-clock, not a measured
            # per-eval timestamp (fully fused scan path).
            mark = "" if s.time_measured else "~"
            any_interpolated |= not s.time_measured
            secs = f"{mark}{s.seconds_to_threshold:.2f}"
        else:
            secs = "—"
        gap = f"{s.spectral_gap:.4f}" if s.spectral_gap is not None else "—"
        lines.append(
            f"{rec.label:<28}{iters:>9}{secs:>8}"
            f"{_fmt_sci(s.total_transmission_floats):>14}"
            f"{_fmt_sci(s.avg_worker_transmission_floats):>15}{gap:>8}"
            f"{s.iters_per_second:>10.1f}"
        )
    lines.append("=" * 78)
    if any_interpolated:
        lines.append(
            "~ sec→ε interpolated from total run wall-clock "
            "(use --measure-time for per-eval timestamps)"
        )
    sharded = [
        f"{rec.label} over {rec.result.history.mesh_devices}"
        for rec in records
        if getattr(rec, "result", None) is not None
        and rec.result.history.mesh_devices > 1
    ]
    if sharded:
        lines.append("worker rows sharded over devices: " + "; ".join(sharded))
    health_lines = _health_section(records)
    if health_lines:
        lines.append("run health (telemetry):")
        lines += health_lines
    serving_line = _serving_line(serving)
    if serving_line:
        lines.append(serving_line)
    if phases:
        total = sum(phases.values())
        lines.append("phases:")
        for name, secs in sorted(phases.items(), key=lambda kv: -kv[1]):
            share = secs / total if total > 0 else 0.0
            lines.append(f"  {name:<12}{secs:>10.3f}s{share:>8.1%}")
    return "\n".join(lines)


def _device_tag(device) -> str:
    if device is None:
        return ""
    return (
        f" on {device['platform']} ({device['kind']}, "
        f"{device['count']} visible)"
    )


def _serving_line(serving) -> Optional[str]:
    """One-line executable-cache / coalescing summary (docs/SERVING.md).

    Accepts either a bare ``ExecutableCache.stats()`` dict or a full
    ``SimulationService.stats()`` dict (cache nested under "cache" with
    cohort/queue counters alongside); returns None when there is nothing
    to report.
    """
    if not serving:
        return None
    cache = serving.get("cache", serving)
    if not cache or (cache.get("hits", 0) + cache.get("misses", 0)) == 0:
        return None
    parts = [
        f"cache {cache['hits']} hit{'s' if cache['hits'] != 1 else ''} / "
        f"{cache['misses']} miss{'es' if cache['misses'] != 1 else ''}",
        f"{cache.get('compile_seconds_saved', 0.0):.1f}s compile saved",
    ]
    cohorts = serving.get("cohorts")
    if cohorts and cohorts.get("count"):
        parts.append(
            f"{cohorts['count']} cohort{'s' if cohorts['count'] != 1 else ''}"
            f" (mean R={cohorts['mean_size']:.1f})"
        )
    qw = serving.get("queue_wait_s")
    if qw and qw.get("mean") is not None:
        parts.append(f"mean queue wait {qw['mean'] * 1e3:.0f}ms")
    return "serving: " + ", ".join(parts)


def _health_section(records) -> list[str]:
    """Run-health lines for records that recorded trace buffers."""
    lines: list[str] = []
    for rec in records:
        h = getattr(rec, "health", None)
        if h is None:
            continue
        parts = []
        if "worst_worker_grad_norm" in h:
            parts.append(
                f"worst grad-norm {h['worst_worker_grad_norm']:.3e} "
                f"(worker {h['worst_worker']})"
            )
        if "nonfinite_total" in h:
            parts.append(f"non-finite {int(h['nonfinite_total'])}")
        if h.get("realized_edge_frac") is not None:
            parts.append(
                f"realized edges {h['realized_edge_frac']:.1%} of nominal"
            )
        wc = h.get("windowed_connectivity")
        if wc is not None:
            bhat = wc.get("bhat")
            parts.append(
                f"B̂ {bhat if bhat is not None else '∞ (disconnected union)'}"
            )
        part = h.get("participation")
        if part is not None:
            # Client sampling (docs/PERF.md §14): realized participation
            # against the configured rate — a realized fraction far off
            # target is the first sign the sampling mask isn't composing.
            parts.append(
                f"participation {part['realized_frac_mean']:.1%} "
                f"(target {part['rate']:.0%})"
            )
        if h.get("clip_frac_mean"):
            parts.append(f"screened msgs {h['clip_frac_mean']:.1%}")
        a = h.get("async")
        if a is not None:
            # Event-driven execution (docs/ASYNC.md): realized staleness,
            # the virtual-clock spread a barrier would have flattened, and
            # the straggler tax the barrier would have charged (sync twin
            # priced on the same latency draws).
            tax = (
                a["sync_virtual_duration"] / a["virtual_duration"]
                if a.get("virtual_duration") else float("nan")
            )
            parts.append(
                f"async[{a['latency_model']}] staleness "
                f"{a['staleness']['mean']:.2f} mean/"
                f"{a['staleness']['max']} max, clock skew "
                f"{a['virtual_clock']['rel_spread']:.1%}, sync tax "
                f"{tax:.2f}x, {a['floats_per_virtual_second']:.4g} "
                "floats/vs"
            )
        comms = h.get("comms")
        if comms is not None:
            # Bytes moved per ITERATION (realized mean; both gossip
            # rounds for two-mix algorithms) — the number a compression
            # operator exists to shrink; tagged with the operator so a
            # 'top_k' win reads directly off the report.
            tag = (
                f" ({comms['compression']})"
                if comms.get("compression", "none") != "none" else ""
            )
            parts.append(
                f"floats/iter {comms['floats_per_iteration_mean']:.4g}{tag}"
            )
            if comms.get("local_steps"):
                # τ gradient steps per exchanged round: the federated
                # comms-reduction lever, quoted per gradient step.
                parts.append(
                    f"floats/grad-step "
                    f"{comms['floats_per_gradient_step']:.4g} "
                    f"(τ={comms['local_steps']})"
                )
            ici = comms.get("ici")
            if ici is not None:
                # Sharded worker mesh (docs/PERF.md §16): REAL collective
                # traffic next to the analytic floats — the static halo
                # plan's per-device ppermute bytes per gossip round.
                parts.append(
                    f"ICI {ici['bytes_per_device_per_round_max']:,} "
                    f"B/dev/round over P={ici['worker_mesh']} mesh "
                    f"(halo {ici['halo_rows_max']} rows)"
                )
        inc = h.get("incidents")
        if inc is not None and inc.get("count"):
            # Anomaly sentinel (ISSUE-13): the run fired detectors — the
            # report names the worst one and whether the halt policy cut
            # the run short; the full forensics live in the incident
            # bundles / manifest health block.
            worst = inc["anomalies"][0]
            line = (
                f"INCIDENTS {inc['count']} ({inc['fatal']} fatal): "
                f"{worst['detector']} [{worst['severity']}] at iter "
                f"{worst['onset_iteration']}"
            )
            if inc.get("halted_at") is not None:
                line += f"; HALTED at iter {inc['halted_at']}"
            parts.append(line)
        if parts:
            lines.append(f"  {rec.label:<26}" + ", ".join(parts))
    return lines


def _finite_curve(iters: np.ndarray, values: Optional[np.ndarray]):
    """Return (iters, values) restricted to finite, positive entries, or None.

    Mirrors the reference's pre-plot guards (``simulator.py:178-188``): a
    curve with no finite data is skipped rather than crashing the figure.
    """
    if values is None or len(values) == 0 or len(values) != len(iters):
        return None
    mask = np.isfinite(values)
    if not mask.any():
        return None
    return iters[mask], values[mask]


def plot_histories(records, config, path: Optional[str] = None, show: bool = False):
    """2-panel log-scale figure: suboptimality gap + consensus error.

    Saves to ``path`` when given (headless-friendly); returns the Figure.
    """
    import matplotlib

    if not show:
        matplotlib.use("Agg", force=False)
    import matplotlib.pyplot as plt

    fig, (ax_gap, ax_cons) = plt.subplots(1, 2, figsize=(13, 5))

    for rec in records:
        if rec.skipped_reason is not None or rec.result is None:
            continue
        hist = rec.result.history
        curve = _finite_curve(hist.eval_iterations, hist.objective)
        if curve is not None:
            ax_gap.plot(curve[0], np.maximum(curve[1], 1e-16), label=rec.label)
        curve = _finite_curve(hist.eval_iterations, hist.consensus_error)
        if curve is not None:
            ax_cons.plot(curve[0], np.maximum(curve[1], 1e-16), label=rec.label)

    ax_gap.axhline(
        config.suboptimality_threshold, color="gray", ls="--", lw=0.8,
        label=f"ε = {config.suboptimality_threshold}",
    )
    ax_gap.set_yscale("log")
    ax_gap.set_xlabel("iteration")
    ax_gap.set_ylabel("f(x̄) − f(x*)")
    ax_gap.set_title(f"Suboptimality gap ({config.problem_type})")
    ax_gap.legend(fontsize=8)
    ax_gap.grid(True, which="both", alpha=0.3)

    ax_cons.set_yscale("log")
    ax_cons.set_xlabel("iteration")
    ax_cons.set_ylabel("(1/N) Σ ‖x_i − x̄‖²")
    ax_cons.set_title("Consensus error")
    if ax_cons.lines:
        ax_cons.legend(fontsize=8)
    ax_cons.grid(True, which="both", alpha=0.3)

    fig.tight_layout()
    if path is not None:
        fig.savefig(path, dpi=130)
    if show:
        plt.show()
    return fig
