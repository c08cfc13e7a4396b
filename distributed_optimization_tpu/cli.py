"""Command-line interface.

The reference has no CLI — its entry point is hard-coded module constants
(reference ``main.py:6-41``). This is the typed-config + real-flags layer
SURVEY.md §5.6 calls for, including the ``--backend`` selection named in
BASELINE.json's north star.

Examples:

    # the reference study, end to end, on the TPU backend:
    python -m distributed_optimization_tpu --problem-type logistic --suite \
        --plot logistic.png --json logistic.json

    # one decentralized run:
    python -m distributed_optimization_tpu --algorithm gradient_tracking \
        --topology grid --n-workers 64 --n-iterations 2000

    # the numpy fidelity oracle (reference semantics):
    python -m distributed_optimization_tpu --backend numpy --suite
"""

from __future__ import annotations

import argparse
import json
from typing import Optional, Sequence

from distributed_optimization_tpu.log import configure as configure_logging
from distributed_optimization_tpu.log import get_logger
from distributed_optimization_tpu.config import (
    AGGREGATIONS,
    ALGORITHMS,
    ATTACKS,
    BYZANTINE_PLACEMENTS,
    BACKENDS,
    COMPRESSIONS,
    EXECUTIONS,
    LATENCY_MODELS,
    MATRIX_FREE_AUTO_N,
    PROBLEM_TYPES,
    REJOINS,
    TOPOLOGIES,
    ExperimentConfig,
)

_DEFAULTS = ExperimentConfig()
_log = get_logger("cli")

# The five target configurations named in BASELINE.json, as CLI presets.
# Flags given alongside --preset still override individual fields.
PRESETS: dict[str, dict] = {
    # 1. Quadratic consensus, 4 workers, fully-connected — DGD
    "quadratic-fc-4": dict(problem_type="quadratic", algorithm="dsgd",
                           topology="fully_connected", n_workers=4),
    # 2. Logistic regression, synthetic data, 8-worker ring — DGD
    "logistic-ring-8": dict(problem_type="logistic", algorithm="dsgd",
                            topology="ring", n_workers=8),
    # 3. Decentralized ADMM, logistic, 16-worker Erdős–Rényi graph
    "admm-er-16": dict(problem_type="logistic", algorithm="admm",
                       topology="erdos_renyi", n_workers=16),
    # 4. Gradient tracking / EXTRA, quadratic, 64-worker 2D torus
    "gt-torus-64": dict(problem_type="quadratic", algorithm="gradient_tracking",
                        topology="grid", n_workers=64,
                        learning_rate_eta0=0.01),
    # 5. Decentralized logistic on real image features (stretch). The only
    # offline real image dataset in this environment is sklearn's bundled
    # 8x8 digits (1,797 samples), which supports ~28 samples/worker at
    # N=64; the BASELINE "256 workers" scale is demonstrated on the
    # synthetic config (12,500 samples — bench.py's headline), because 256
    # workers over 1,797 real samples would be 7 samples/worker — runnable
    # but statistically degenerate. docs/perf/presets.json measures both.
    "digits-64": dict(problem_type="logistic", algorithm="dsgd",
                      topology="ring", n_workers=64, dataset="digits"),
    # 6. Push-sum SGP, logistic, 16-worker strongly connected DIRECTED
    # Erdős–Rényi graph (round 4; beyond BASELINE.json) — the asymmetric-
    # link setting where MH gossip is undefined and column-stochastic
    # mixing + weight debiasing is required (Nedić-Olshevsky '16, Assran
    # et al. '19). Measured in docs/perf/presets.json like the others.
    "push-sum-der-16": dict(problem_type="logistic", algorithm="push_sum",
                            topology="directed_erdos_renyi", n_workers=16),
    # 7. Multiclass softmax on the real digits images (round 5; beyond
    # BASELINE.json) — the ten digit classes ARE the labels, so this is
    # the natural multiclass form of the stretch config: a [65, 10]
    # weight matrix per worker gossiped as a flat 650-vector.
    "digits-softmax-64": dict(problem_type="softmax", n_classes=10,
                              algorithm="dsgd", topology="ring",
                              n_workers=64, dataset="digits",
                              learning_rate_eta0=0.1),
    # 8. The compute-bound tier at CLI scale (round 5): wide softmax whose
    # gradients are real MXU matmuls — a small sibling of
    # examples/bench_compute_bound.py's measured cells
    # (docs/perf/compute_bound.json: 33-36% median MFU at d in
    # {4096, 8192}, K=512, bf16).
    "softmax-mxu-8": dict(problem_type="softmax", n_classes=128,
                          algorithm="dsgd", topology="ring", n_workers=8,
                          n_features=1024, n_informative_features=64,
                          n_samples=2048, local_batch_size=256,
                          learning_rate_eta0=0.1, n_iterations=2000),
}


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="distributed_optimization_tpu",
        description=(
            "TPU-native decentralized optimization: centralized SGD, D-SGD, "
            "gradient tracking, EXTRA and decentralized ADMM over graph "
            "topologies, on a JAX/XLA collective backend or a numpy "
            "reference-semantics oracle."
        ),
    )
    run = p.add_argument_group("run selection")
    run.add_argument("--preset", choices=sorted(PRESETS), default=None,
                     help="apply one of the BASELINE.json target configs; "
                          "other flags still override individual fields")
    run.add_argument("--suite", action="store_true",
                     help="run the reference experiment matrix (centralized + "
                          "D-SGD over ring/grid/fully-connected) instead of a "
                          "single run")
    run.add_argument("--algorithm", choices=ALGORITHMS,
                     default=_DEFAULTS.algorithm)
    run.add_argument("--topology", choices=TOPOLOGIES, default=_DEFAULTS.topology)
    run.add_argument("--backend", choices=BACKENDS, default=_DEFAULTS.backend)
    run.add_argument("--platform", choices=("tpu", "cpu", "auto"), default="auto",
                     help="force the JAX platform: 'tpu' fails when no chip "
                          "is found; 'cpu' is for quick checks and virtual "
                          "multi-device runs; 'auto' takes what JAX finds — "
                          "the report header names the device it ran on")
    run.add_argument("--multihost", action="store_true",
                     help="call jax.distributed.initialize() so the worker "
                          "mesh spans all hosts of a multi-host TPU slice "
                          "(run the same command on every host; coordinator "
                          "discovery via the standard TPU env vars)")

    prob = p.add_argument_group("problem / data (reference main.py parity)")
    prob.add_argument("--problem-type", choices=PROBLEM_TYPES,
                      default=_DEFAULTS.problem_type)
    prob.add_argument("--n-workers", type=int, default=_DEFAULTS.n_workers)
    prob.add_argument("--n-samples", type=int, default=_DEFAULTS.n_samples)
    prob.add_argument("--n-features", type=int, default=_DEFAULTS.n_features)
    prob.add_argument("--n-informative-features", type=int,
                      default=_DEFAULTS.n_informative_features)
    prob.add_argument("--classification-sep", type=float,
                      default=_DEFAULTS.classification_sep)
    prob.add_argument("--n-classes", type=int, default=_DEFAULTS.n_classes,
                      help="class count K for --problem-type softmax (the "
                           "compute-bound [d,K]-matrix-parameter family)")
    prob.add_argument("--dataset", choices=("synthetic", "digits"),
                      default="synthetic",
                      help="'digits' = real image features (the MNIST-features "
                           "stretch config) instead of synthetic data")

    opt = p.add_argument_group("optimization")
    opt.add_argument("--n-iterations", type=int, default=_DEFAULTS.n_iterations)
    opt.add_argument("--local-batch-size", type=int,
                     default=_DEFAULTS.local_batch_size)
    opt.add_argument("--learning-rate-eta0", type=float,
                     default=_DEFAULTS.learning_rate_eta0)
    opt.add_argument("--l2-lambda", type=float,
                     default=_DEFAULTS.l2_regularization_lambda)
    opt.add_argument("--lr-schedule", choices=("auto", "sqrt_decay", "constant"),
                     default=_DEFAULTS.lr_schedule)
    opt.add_argument("--admm-c", type=float, default=_DEFAULTS.admm_c)
    opt.add_argument("--admm-rho", type=float, default=_DEFAULTS.admm_rho)
    opt.add_argument("--huber-delta", type=float, default=_DEFAULTS.huber_delta,
                     help="Huber transition point δ (problem huber only; "
                          "default = the synthetic data's noise scale)")
    opt.add_argument("--erdos-renyi-p", type=float,
                     default=_DEFAULTS.erdos_renyi_p)
    opt.add_argument("--compression", choices=COMPRESSIONS,
                     default=_DEFAULTS.compression,
                     help="error-feedback gossip compression operator "
                          "(choco, dsgd, gradient_tracking)")
    opt.add_argument("--compression-k", type=int,
                     default=_DEFAULTS.compression_k,
                     help="coordinates kept per transmitted vector "
                          "(top_k/random_k) or quantization bits (qsgd)")
    opt.add_argument("--choco-gamma", type=float, default=_DEFAULTS.choco_gamma,
                     help="error-feedback consensus step size gamma "
                          "(CHOCO and compressed dsgd/gradient_tracking)")
    opt.add_argument("--local-steps", type=int, default=_DEFAULTS.local_steps,
                     help="federated local updates: τ gradient steps per "
                          "gossip round, fused in the same compiled scan "
                          "(dsgd: plain local SGD; gradient_tracking: "
                          "tracker-corrected). Per-round comms is "
                          "unchanged, so τ>1 cuts floats per unit of "
                          "progress up to τ× (docs/PERF.md §14). 1 = the "
                          "classic one-step round, bitwise")
    opt.add_argument("--participation-rate", type=float,
                     default=_DEFAULTS.participation_rate,
                     help="per-round client sampling: each worker "
                          "independently participates with this "
                          "probability (presampled [horizon, N] masks on "
                          "the fault timeline; sampled-out workers freeze "
                          "and exchange nothing; composes with churn and "
                          "the Byzantine layer). 1.0 = everyone, bitwise "
                          "the no-sampling program")
    opt.add_argument("--edge-drop-prob", type=float,
                     default=_DEFAULTS.edge_drop_prob,
                     help="failure injection: per-iteration probability that "
                          "each topology edge drops (gossip reweights on the "
                          "surviving graph)")
    opt.add_argument("--gossip-schedule",
                     choices=("synchronous", "one_peer", "round_robin"),
                     default=_DEFAULTS.gossip_schedule,
                     help="'one_peer' = randomized pairwise gossip (one "
                          "random mutual neighbor/iter); 'round_robin' = "
                          "deterministic matchings covering the edge set "
                          "every P iterations")
    opt.add_argument("--straggler-prob", type=float,
                     default=_DEFAULTS.straggler_prob,
                     help="straggler injection: per-iteration probability "
                          "that a node sits the round out (no exchange, no "
                          "local step)")
    opt.add_argument("--burst-len", type=float, default=_DEFAULTS.burst_len,
                     help="bursty link failures (Gilbert-Elliott): mean "
                          "burst-length multiplier at the SAME marginal "
                          "--edge-drop-prob (mean burst = "
                          "burst_len/(1-p) rounds). 0 = memoryless iid "
                          "drops; 1 reduces bitwise to them; > 1 "
                          "correlates failures in time (docs/CHURN.md)")
    opt.add_argument("--mttf", type=float, default=_DEFAULTS.mttf,
                     help="crash-recovery churn: mean up-time (rounds) "
                          "before a node crashes; >= 1, set together with "
                          "--mttr (replaces --straggler-prob; stationary "
                          "downtime = mttr/(mttf+mttr))")
    opt.add_argument("--mttr", type=float, default=_DEFAULTS.mttr,
                     help="crash-recovery churn: mean outage length "
                          "(rounds) before a crashed node rejoins; >= 1, "
                          "set together with --mttf")
    opt.add_argument("--rejoin", choices=REJOINS, default=_DEFAULTS.rejoin,
                     help="what a node resumes with after an outage: "
                          "'frozen' = stale pre-crash state (staleness "
                          "stress test); 'neighbor_restart' = warm restart "
                          "of the model row from the realized-neighborhood "
                          "average on the rejoin round")
    opt.add_argument("--attack", choices=ATTACKS, default=_DEFAULTS.attack,
                     help="Byzantine injection: n-byzantine workers replace "
                          "their outgoing models with this payload each "
                          "gossip round (docs/BYZANTINE.md)")
    opt.add_argument("--n-byzantine", type=int,
                     default=_DEFAULTS.n_byzantine,
                     help="size of the static seed-deterministic Byzantine "
                          "worker set")
    opt.add_argument("--attack-scale", type=float,
                     default=_DEFAULTS.attack_scale,
                     help="payload magnitude: sign-flip multiplier, "
                          "large-noise sigma, or ALIE's z (honest std "
                          "devs of shift)")
    opt.add_argument("--byzantine-placement", choices=BYZANTINE_PLACEMENTS,
                     default=_DEFAULTS.byzantine_placement,
                     help="where the attackers sit: 'uniform' draws them "
                          "without looking at the graph; 'within_budget' "
                          "draws them so every honest worker keeps at most "
                          "robust-b attacking neighbours (the screening "
                          "rules' assumption; at scale a uniform draw "
                          "breaks it: docs/BYZANTINE.md 'Placement')")
    opt.add_argument("--aggregation", choices=AGGREGATIONS,
                     default=_DEFAULTS.aggregation,
                     help="robust neighbor aggregation rule honest workers "
                          "use in place of plain W@x gossip")
    opt.add_argument("--robust-b", type=int, default=_DEFAULTS.robust_b,
                     help="per-neighborhood attack budget for the robust "
                          "rule (values trimmed per tail / messages "
                          "clipped); 0 degrades to plain gossip; needs "
                          "2*b <= min node degree")
    opt.add_argument("--clip-tau", type=float, default=_DEFAULTS.clip_tau,
                     help="fixed clipping radius for clipped_gossip "
                          "(0 = adaptive per-node radius)")
    opt.add_argument("--robust-impl",
                     choices=("auto", "dense", "gather"),
                     default=_DEFAULTS.robust_impl,
                     help="execution form of the robust rule (jax "
                          "backend): 'dense' sorts the [N,N,d] closed-"
                          "neighborhood tensor (O(N^2 d log N)); 'gather' "
                          "screens over a static [N,k_max] padded "
                          "neighbor table (O(N k_max d log k_max), "
                          "~N/k_max less work on degree-bounded graphs); "
                          "'auto' = measured rule: gather unless fully "
                          "connected")
    opt.add_argument("--partition", choices=("sorted", "shuffled"),
                     default=_DEFAULTS.partition,
                     help="worker data split: 'sorted' = the study's "
                          "non-IID sort-by-target slices; 'shuffled' = "
                          "IID control (bounded heterogeneity)")
    opt.add_argument("--seed", type=int, default=_DEFAULTS.seed)
    opt.add_argument("--topology-seed", type=int,
                     default=_DEFAULTS.topology_seed,
                     help="pin the random-topology (Erdős–Rényi) edge "
                          "draws independently of --seed (-1 = follow "
                          "--seed); replicated runs pin it automatically "
                          "so every replica shares one graph instance")
    opt.add_argument("--data-seed", type=int, default=_DEFAULTS.data_seed,
                     help="pin the DATASET's random draws independently "
                          "of --seed (-1 = follow --seed); with it "
                          "pinned, runs that differ only in --seed share "
                          "one problem instance — the serving layer "
                          "coalesces such requests into one batched "
                          "program (docs/SERVING.md)")
    opt.add_argument("--replicas", type=int, default=_DEFAULTS.replicas,
                     help="run this many seed replicates (seed, seed+1, "
                          "...) as ONE vmapped jax program and report "
                          "mean ± std over the replica axis (jax backend "
                          "only; docs/PERF.md 'Replica-batched sweeps')")
    opt.add_argument("--seeds", metavar="S1,S2,...", default=None,
                     help="explicit comma-separated replica seed list "
                          "(overrides --replicas/--seed's arithmetic "
                          "progression); implies replica-batched "
                          "execution")
    opt.add_argument("--suboptimality-threshold", type=float,
                     default=_DEFAULTS.suboptimality_threshold)

    execg = p.add_argument_group("execution")
    execg.add_argument("--execution", choices=EXECUTIONS,
                       default=_DEFAULTS.execution,
                       help="'async' scans a precomputed EVENT schedule "
                            "(AD-PSGD-style bounded-staleness gossip: one "
                            "worker's stale-read local step + a pairwise "
                            "exchange per event; stragglers are latency, "
                            "not drops — docs/ASYNC.md). n_iterations "
                            "then counts per-worker gradient steps (N "
                            "events per round); dsgd only")
    execg.add_argument("--latency-model", choices=LATENCY_MODELS,
                       default=_DEFAULTS.latency_model,
                       help="per-worker compute-time distribution of the "
                            "async event schedule (all matched to mean "
                            "--latency-mean; async only)")
    execg.add_argument("--latency-mean", type=float,
                       default=_DEFAULTS.latency_mean,
                       help="mean compute time per gradient step in "
                            "virtual seconds (async only)")
    execg.add_argument("--latency-tail", type=float,
                       default=_DEFAULTS.latency_tail,
                       help="heavy-tail straggler knob: lognormal log-std "
                            "(> 0) or pareto shape alpha (> 1); 0 for "
                            "constant/exponential (async only)")
    execg.add_argument("--tp", type=int, default=_DEFAULTS.tp_degree,
                       metavar="TP_DEGREE",
                       help="tensor parallelism: shard the softmax [d, K] "
                            "classifier over TP_DEGREE devices of a 2-D "
                            "(workers, model) mesh (jax backend; supported "
                            "combination: softmax + dsgd + ring + full "
                            "local batches — anything else is rejected "
                            "with the reason). 1 = pure data parallelism")
    execg.add_argument("--worker-mesh", type=int,
                       default=_DEFAULTS.worker_mesh, metavar="P",
                       help="shard the WORKER axis over P devices "
                            "(docs/PERF.md §16): state rows [N/P, d] and "
                            "neighbor tables [N/P, k_max] live per-shard, "
                            "gossip becomes a ppermute halo exchange at "
                            "shard edges, and trajectories stay bitwise "
                            "the unsharded gather path's. P must divide "
                            "n-workers; jax backend + neighbor-table "
                            "topologies (ring/grid/chain/erdos_renyi). On "
                            "CPU hosts simulate P devices via XLA_FLAGS="
                            "'--xla_force_host_platform_device_count=P'. "
                            "0 = unsharded")
    execg.add_argument("--topology-sampler",
                       choices=("auto", "dense", "sparse"),
                       default=_DEFAULTS.topology_sampler,
                       help="Erdős–Rényi graph sampler (docs/PERF.md §17): "
                            "'dense' replays the [N, N] uniform stream "
                            "bit-for-bit (O(N²) draws), 'sparse' draws "
                            "O(N·k_max) — the million-worker path, a "
                            "DIFFERENT realization of the same G(n, p) "
                            "law (structural identity). 'auto' = dense "
                            "below N=65,536 on the matrix-free ER path, "
                            "sparse above")
    execg.add_argument("--eval-every", type=int, default=_DEFAULTS.eval_every,
                       help="full-data objective eval cadence (1 = reference "
                            "parity)")
    execg.add_argument("--mixing-impl",
                       choices=("auto", "dense", "stencil", "gather"),
                       default=_DEFAULTS.mixing_impl,
                       help="'gather' = the k_max-bounded neighbor-table "
                            "mixing operator, O(N*k_max*d) per round with "
                            "no [N,N] matrix — the matrix-free/federated-"
                            "scale route (auto picks it on matrix-free "
                            "topologies and above the measured dense "
                            "crossover; docs/PERF.md §14)")
    execg.add_argument("--topology-impl",
                       choices=("auto", "dense", "neighbor"),
                       default=_DEFAULTS.topology_impl,
                       help="topology representation: 'neighbor' builds "
                            "the matrix-free padded [N, k_max] neighbor "
                            "table (ring/grid/chain/erdos_renyi; the only "
                            "form that fits N >= 10k), 'dense' the "
                            "[N, N] matrices; 'auto' = neighbor on the "
                            "jax backend above "
                            f"{MATRIX_FREE_AUTO_N} workers when no "
                            "dense-only feature is requested")
    execg.add_argument("--sampling-impl",
                       choices=("auto", "gather", "dense"),
                       default=_DEFAULTS.sampling_impl,
                       help="mini-batch realization on the jax backend: "
                            "gathered [N,b,d] batches vs dense per-row "
                            "weights over the full shard (auto = measured "
                            "rule: dense for shards <= 64 rows on "
                            "accelerators). dense builds an [L,L] ranking "
                            "matrix per worker per iteration — O(N*L^2) — "
                            "so forcing it on large shards is quadratic "
                            "(the backend warns beyond the measured "
                            "crossover)")
    execg.add_argument("--scan-unroll", type=int, default=_DEFAULTS.scan_unroll,
                       help="XLA unroll factor for the training scan "
                            "(0 = auto: 8 on accelerators, 1 on CPU)")
    execg.add_argument("--dtype", choices=("float32", "float64", "bfloat16"),
                       default=_DEFAULTS.dtype)
    execg.add_argument("--matmul-precision",
                       choices=("default", "high", "highest"),
                       default=_DEFAULTS.matmul_precision)

    ckpt = p.add_argument_group("checkpoint / resume (jax backend)")
    ckpt.add_argument("--checkpoint-dir", metavar="DIR", default=None,
                      help="save orbax checkpoints under DIR during the run")
    ckpt.add_argument("--checkpoint-every", type=int, default=10, metavar="K",
                      help="checkpoint cadence in eval-chunks "
                           "(K × eval_every iterations)")
    ckpt.add_argument("--no-resume", action="store_true",
                      help="start fresh even if DIR holds a checkpoint")

    diag = p.add_argument_group("profiling / diagnostics")
    diag.add_argument("--measure-time", action=argparse.BooleanOptionalAction,
                      default=None,
                      help="record real per-eval wall-clock timestamps "
                           "(the scan in segments of one eval; one sync per "
                           "eval) instead of interpolating a longer "
                           "segment's total (jax backend). Default: off — "
                           "the whole run in one segment is fastest at "
                           "every eval cadence "
                           "(docs/PERF.md root-cause section); opt in when "
                           "measured per-eval wall-clock matters more than "
                           "throughput")
    diag.add_argument("--profile-dir", metavar="DIR", default=None,
                      help="collect a jax.profiler (XProf/TensorBoard) trace "
                           "of the run into DIR; the report then ends with "
                           "the device's time by scope")
    diag.add_argument("--check-nans", action="store_true",
                      help="enable jax_debug_nans: raise at the first "
                           "NaN-producing op instead of finishing with NaNs")
    diag.add_argument("--preflight", action="store_true",
                      help="run the named preflight identities before the "
                           "main experiment — collective wiring (ppermute "
                           "round-trip, psum identity) and jit determinism "
                           "— failing loudly with the broken identity "
                           "named (utils/diagnostics.PREFLIGHT_CHECKS)")
    diag.add_argument("--telemetry", metavar="OUT", default=None,
                      help="enable the flight recorder (in-scan trace "
                           "buffers + cost analysis; docs/OBSERVABILITY.md) "
                           "and write one schema-versioned RunTrace "
                           "manifest per run to OUT as JSONL")
    diag.add_argument("--progress", action="store_true",
                      help="stream live per-chunk heartbeats to stderr "
                           "(iteration, wall seconds, current gap/"
                           "consensus, live B-hat under faults, staleness "
                           "quantiles on async runs). The fused scan then "
                           "executes as segments split at eval "
                           "boundaries — trajectories stay bitwise "
                           "identical (docs/OBSERVABILITY.md); jax "
                           "backend, tp=1")
    diag.add_argument("--progress-every", type=int, default=1, metavar="K",
                      help="heartbeat cadence in eval-chunks (K x "
                           "eval_every iterations per heartbeat; "
                           "default 1)")
    diag.add_argument("--monitors", action="store_true",
                      help="watch the run with the anomaly sentinel "
                           "(docs/OBSERVABILITY.md 'Monitors & "
                           "incidents'): online detectors for "
                           "divergence, consensus stall, non-finite "
                           "state, realized-B-hat connectivity loss, "
                           "async staleness blowup, and robust-"
                           "screening saturation consume the run's "
                           "heartbeats; firings are reported and can "
                           "be written as incident bundles. Rides the "
                           "segmented progress machinery (jax backend, "
                           "tp=1); trajectories stay bitwise when "
                           "nothing fires")
    diag.add_argument("--halt-on", choices=("never", "fatal"),
                      default="never",
                      help="early-halt policy (implies --monitors): "
                           "'fatal' stops the run at the next chunk "
                           "boundary after a fatal anomaly "
                           "(divergence, non-finite state, realized "
                           "disconnection) and reports the executed "
                           "prefix as a partial result; 'never' "
                           "(default) only records")
    diag.add_argument("--incidents-out", metavar="PATH", default=None,
                      help="write anomaly incident bundles (config + "
                           "structural hash, evidence window, fault/"
                           "attack context around the onset) as JSONL "
                           "to PATH (implies --monitors; default with "
                           "--telemetry OUT: OUT's sibling "
                           "'<OUT>.incidents.jsonl' when something "
                           "fired). Browse with 'observatory "
                           "incidents'")
    diag.add_argument("--trace-out", metavar="PATH", default=None,
                      help="write the span tracer's Chrome trace-event "
                           "JSON (data_gen/oracle + per-run compile/run "
                           "spans) to PATH — open in chrome://tracing or "
                           "ui.perfetto.dev")
    diag.add_argument("--metrics-out", metavar="PATH", default=None,
                      help="dump the process metrics registry (Prometheus "
                           "text format — the daemon's /metrics "
                           "exposition) to PATH at exit")

    out = p.add_argument_group("output")
    out.add_argument("--plot", metavar="PATH", default=None,
                     help="save the 2-panel log-scale figure to PATH")
    out.add_argument("--json", metavar="PATH", default=None,
                     help="dump all run histories + summaries as JSON")
    out.add_argument("-q", "--quiet", action="store_true",
                     help="log warnings only (package log level WARNING)")
    out.add_argument("-v", "--verbose", action="store_true",
                     help="debug-level package logging")
    return p


def config_from_args(args: argparse.Namespace) -> ExperimentConfig:
    return ExperimentConfig(
        n_workers=args.n_workers,
        local_batch_size=args.local_batch_size,
        n_iterations=args.n_iterations,
        learning_rate_eta0=args.learning_rate_eta0,
        l2_regularization_lambda=args.l2_lambda,
        strong_convexity_mu=args.l2_lambda,
        problem_type=args.problem_type,
        n_samples=args.n_samples,
        n_features=args.n_features,
        n_informative_features=args.n_informative_features,
        classification_sep=args.classification_sep,
        suboptimality_threshold=args.suboptimality_threshold,
        backend=args.backend,
        algorithm=args.algorithm,
        topology=args.topology,
        lr_schedule=args.lr_schedule,
        admm_c=args.admm_c,
        admm_rho=args.admm_rho,
        huber_delta=args.huber_delta,
        n_classes=args.n_classes,
        compression=args.compression,
        compression_k=args.compression_k,
        choco_gamma=args.choco_gamma,
        local_steps=args.local_steps,
        participation_rate=args.participation_rate,
        execution=args.execution,
        latency_model=args.latency_model,
        latency_mean=args.latency_mean,
        latency_tail=args.latency_tail,
        topology_impl=args.topology_impl,
        seed=args.seed,
        topology_seed=args.topology_seed,
        data_seed=args.data_seed,
        replicas=args.replicas,
        tp_degree=args.tp,
        worker_mesh=args.worker_mesh,
        topology_sampler=args.topology_sampler,
        eval_every=args.eval_every,
        erdos_renyi_p=args.erdos_renyi_p,
        edge_drop_prob=args.edge_drop_prob,
        straggler_prob=args.straggler_prob,
        burst_len=args.burst_len,
        mttf=args.mttf,
        mttr=args.mttr,
        rejoin=args.rejoin,
        attack=args.attack,
        n_byzantine=args.n_byzantine,
        attack_scale=args.attack_scale,
        byzantine_placement=args.byzantine_placement,
        aggregation=args.aggregation,
        robust_b=args.robust_b,
        clip_tau=args.clip_tau,
        robust_impl=args.robust_impl,
        partition=args.partition,
        gossip_schedule=args.gossip_schedule,
        mixing_impl=args.mixing_impl,
        sampling_impl=args.sampling_impl,
        scan_unroll=args.scan_unroll,
        dtype=args.dtype,
        matmul_precision=args.matmul_precision,
        telemetry=getattr(args, "telemetry", None) is not None,
    )


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)

    # --verbose/-q map to package log levels (log.py; ISSUE-5 satellite):
    # WARNING under -q, DEBUG under -v, INFO otherwise.
    configure_logging(1 if args.verbose else (-1 if args.quiet else 0))

    if args.preset is not None:
        # Preset values apply only to flags the user did not pass. Detection
        # must not compare against defaults (an explicit flag set to its
        # default value still wins): re-parse with all defaults suppressed so
        # only command-line-provided dests appear.
        aux = build_parser()
        for action in aux._actions:
            action.default = argparse.SUPPRESS
        explicit = set(vars(aux.parse_args(argv)))
        for field, value in PRESETS[args.preset].items():
            if field not in explicit:
                setattr(args, field, value)

    if args.platform != "auto":
        # Must run before any jax operation ('tpu' fails fast if no TPU
        # platform can initialize, instead of falling back to the CPU).
        import jax

        jax.config.update("jax_platforms", args.platform)

    from distributed_optimization_tpu.runtime import configure_compile_cache

    configure_compile_cache()

    if args.multihost:
        # Multi-host slice: every host runs this same process; jax wires the
        # global device mesh over ICI within a slice (and DCN across slices),
        # and the worker-axis sharding + collectives need no other changes.
        import jax

        try:
            jax.distributed.initialize()
        except ValueError as e:
            raise SystemExit(
                f"--multihost: jax.distributed.initialize() failed ({e}). "
                "On Cloud TPU slices the coordinator is auto-discovered; "
                "elsewhere set JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / "
                "JAX_PROCESS_ID, or omit --multihost on a single host."
            ) from e
        _log.info(
            "multihost: process %d of %d, %d global devices",
            jax.process_index(), jax.process_count(), len(jax.devices()),
        )

    # Grid in the suite is skipped gracefully for non-square N, but a single
    # run with an invalid combination should fail fast in config validation.
    if args.suite and args.topology == "grid":
        args.topology = _DEFAULTS.topology

    seeds_list = None
    if args.seeds:
        try:
            seeds_list = [int(x) for x in args.seeds.split(",") if x.strip()]
        except ValueError:
            raise SystemExit(
                f"--seeds must be a comma-separated integer list, got "
                f"{args.seeds!r}"
            )
        if not seeds_list:
            raise SystemExit("--seeds needs at least one seed")
        # The explicit list defines the replica axis; seed[0] anchors
        # everything else that derives from the base seed (the dataset,
        # and the topology unless --topology-seed pins it).
        args.replicas = len(seeds_list)
        args.seed = seeds_list[0]

    config = config_from_args(args)

    from distributed_optimization_tpu.simulator import Simulator

    dataset = None
    if args.dataset == "digits":
        from distributed_optimization_tpu.utils.data import generate_digits_dataset

        dataset = generate_digits_dataset(config)

    run_kwargs = {}
    replicated = config.replicas > 1 or seeds_list is not None
    if replicated:
        if seeds_list is not None:
            run_kwargs["seeds"] = seeds_list
        if args.checkpoint_dir:
            raise SystemExit(
                "--checkpoint-dir does not compose with --replicas/--seeds: "
                "continue a batch programmatically via run_batch(state0=, "
                "t0=) instead"
            )
        if args.measure_time:
            raise SystemExit(
                "--measure-time does not compose with --replicas/--seeds: "
                "the batched program is one fused vmapped scan with no "
                "per-eval host sync"
            )
    if args.checkpoint_dir:
        if args.backend != "jax":
            raise SystemExit("--checkpoint-dir requires --backend jax")
        if args.telemetry:
            raise SystemExit(
                "--telemetry does not compose with --checkpoint-dir: trace "
                "buffers are not checkpointed, so a resumed run would emit "
                "a truncated manifest"
            )
        from distributed_optimization_tpu.utils.checkpoint import CheckpointOptions

        run_kwargs["checkpoint"] = CheckpointOptions(
            directory=args.checkpoint_dir,
            every_evals=args.checkpoint_every,
            resume=not args.no_resume,
        )
    if args.progress:
        if args.backend != "jax" or args.tp > 1:
            # Heartbeats ride the jax scan's segmented execution; the
            # numpy/cpp/TP paths have no chunked form to hook — warn and
            # run without, rather than failing a script that toggles
            # backends.
            _log.warning(
                "--progress streams from the jax backend's chunked "
                "execution (tp=1); backend=%s tp=%d runs without "
                "heartbeats", args.backend, args.tp,
            )
        else:
            import sys

            from distributed_optimization_tpu.observability.progress import (
                format_progress_line,
            )

            def _print_progress(ev):
                print(format_progress_line(ev), file=sys.stderr, flush=True)

            run_kwargs["progress_cb"] = _print_progress
            run_kwargs["progress_every"] = args.progress_every
    want_monitors = (
        args.monitors or args.halt_on != "never"
        or args.incidents_out is not None
    )
    if want_monitors:
        if args.backend != "jax" or args.tp > 1:
            # Like --progress: monitors consume the jax backend's
            # segmented heartbeats — warn and run unwatched rather than
            # failing a script that toggles backends.
            _log.warning(
                "--monitors/--halt-on ride the jax backend's chunked "
                "execution (tp=1); backend=%s tp=%d runs unwatched",
                args.backend, args.tp,
            )
        else:
            from distributed_optimization_tpu.observability.monitors import (
                MonitorBank,
            )

            # A factory, not a bank: detectors latch per run, so every
            # run of a suite/matrix gets a fresh bank (the Simulator
            # resolves callables per run).
            run_kwargs["monitors"] = (
                lambda cfg: MonitorBank(cfg, halt_on=args.halt_on)
            )
    if args.measure_time is not None:
        if args.backend == "jax":
            run_kwargs["measure_timestamps"] = args.measure_time
        elif not args.measure_time:
            # Warn, don't reject: scripts that toggle the flag across
            # backends shouldn't hard-fail on the always-measured ones
            # (where --measure-time is likewise an accepted no-op).
            _log.warning(
                "--no-measure-time only applies to the jax backend's fused "
                "scan; the numpy and cpp backends always record measured "
                "per-eval timestamps — ignoring"
            )

    if args.preflight:
        from distributed_optimization_tpu.utils.diagnostics import (
            PreflightError,
            run_preflight,
        )

        try:
            passed = run_preflight()
        except PreflightError as e:
            # Loud, named failure BEFORE any compile/run time is spent:
            # the broken identity is the diagnosis.
            raise SystemExit(
                f"[cli] preflight FAILED at {e.check!r}: {e.cause}"
            ) from e
        _log.info("preflight passed: %s", ", ".join(passed))

    from distributed_optimization_tpu.utils.diagnostics import nan_debugging
    from distributed_optimization_tpu.utils.profiling import trace

    sim = Simulator(config, dataset=dataset)
    if not args.quiet:
        # Generation-time per-worker distribution report (parity: reference
        # utils.py:43-48) — makes the sorted-partition non-IID skew visible.
        from distributed_optimization_tpu.utils.data import partition_summary

        _log.info("%s", partition_summary(sim.dataset))
    with trace(args.profile_dir), nan_debugging(args.check_nans):
        if args.suite:
            if "checkpoint" in run_kwargs:
                raise SystemExit(
                    "--checkpoint-dir applies to single runs, not --suite"
                )
            sim.run_all(verbose=not args.quiet, run_kwargs=run_kwargs)
        else:
            sim.run_one(verbose=not args.quiet, run_kwargs=run_kwargs)

    sim.report_numerical_results()
    if args.profile_dir and args.backend == "jax":
        # Beside the phase table: the trace just written, joined with the
        # compiled programs' own account of their instructions.
        from distributed_optimization_tpu.observability import device_scopes

        print(device_scopes.profile_report(
            args.profile_dir, sim.phase_timer.spans()
        ))
    if args.plot:
        sim.plot_results(path=args.plot)
        _log.info("figure saved to %s", args.plot)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(sim.results_dict(), f, indent=1)
        _log.info("results saved to %s", args.json)
    if args.telemetry:
        sim.write_telemetry(args.telemetry)
    if want_monitors and args.backend == "jax" and args.tp <= 1:
        fired = any(
            rec.monitors is not None and rec.monitors.anomalies
            for rec in sim.records
        )
        incidents_out = args.incidents_out
        if incidents_out is None and args.telemetry and fired:
            # Incident bundles ride next to the RunTrace manifests by
            # default (the observatory convention: one directory, one
            # story).
            from distributed_optimization_tpu.observability.monitors import (
                incidents_path_for,
            )

            incidents_out = str(incidents_path_for(args.telemetry))
        if incidents_out is not None:
            sim.write_incidents(incidents_out)
        elif fired:
            _log.warning(
                "anomalies fired but no --incidents-out/--telemetry "
                "path was given; forensic bundles were not persisted"
            )
    if args.trace_out:
        sim.write_chrome_trace(args.trace_out)
    if args.metrics_out:
        from pathlib import Path

        Path(args.metrics_out).write_text(sim.metrics_text())
        _log.info("metrics dumped to %s", args.metrics_out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
