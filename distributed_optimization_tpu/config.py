"""Typed experiment configuration.

The reference keeps configuration as module-level constants assembled into a
plain dict (reference ``main.py:6-38``) threaded through every layer. Here the
same keys become a frozen dataclass with validation, plus new framework knobs
(backend selection, algorithm, topology, mesh shape, eval cadence) that the
reference does not have. ``to_dict``/``from_dict`` keep the reference's key
names so configs round-trip with the reference's experiment setup.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
from typing import Any

# Algorithms the framework implements. The reference only has 'centralized'
# (reference trainer.py:7-74) and 'dsgd' (trainer.py:76-197); the rest are the
# planned capability extensions named in BASELINE.json, plus push_sum (SGP —
# stochastic gradient push over directed graphs, Nedić-Olshevsky 2016 /
# Assran et al. 2019), the asymmetric-link continuation of the reference's
# MH-gossip family (reference trainer.py:118-126 builds the symmetric case).
ALGORITHMS = ("centralized", "dsgd", "gradient_tracking", "extra", "admm",
              "choco", "push_sum")

TOPOLOGIES = ("ring", "grid", "fully_connected", "erdos_renyi", "chain", "star",
              "directed_ring", "directed_erdos_renyi")

# Directed topologies carry column-stochastic (not doubly stochastic) mixing:
# plain gossip algorithms would drift toward the graph's Perron weighting
# instead of the true average, so only push_sum — which debiases by the
# tracked mass — may run on them.
DIRECTED_TOPOLOGIES = ("directed_ring", "directed_erdos_renyi")

PROBLEM_TYPES = ("logistic", "quadratic", "huber", "softmax")

BACKENDS = ("jax", "numpy", "cpp")

# Gossip-compression operators (CHOCO-SGD); implemented in ops/compression.py,
# which derives from this constant (config stays jax-free).
COMPRESSIONS = ("none", "top_k", "random_k", "qsgd")

# Algorithms the shared error-feedback compressed-gossip machinery covers
# (ops/compression.py::ErrorFeedbackGossip): CHOCO is the original
# formulation; dsgd and gradient_tracking route their gossip exchanges
# through the same per-worker estimate + compressor carry when
# ``compression != 'none'`` (ISSUE-6 tentpole — the gather path's
# production currency is bytes moved per round).
COMPRESSED_ALGORITHMS = ("choco", "dsgd", "gradient_tracking")

# Byzantine attack models (parallel/adversary.py derives from this constant):
# a static, seed-deterministic set of `n_byzantine` workers replaces its
# OUTGOING model each gossip round with an adversarial payload — sign_flip
# sends −scale·x, large_noise sends x + scale·N(0, I) redrawn per (seed, t),
# alie sends the colluders' shared "a little is enough" vector
# honest_mean − scale·honest_std (Baruch et al. 2019), hiding inside the
# honest spread to evade norm/outlier filters.
ATTACKS = ("none", "sign_flip", "large_noise", "alie")
# How the static Byzantine set is placed (parallel/adversary.py,
# docs/BYZANTINE.md "Placement"): 'uniform' draws it without looking at the
# graph; 'within_budget' draws it on the graph's neighbor table so that
# every honest worker keeps at most robust_b attacking neighbours — the
# screening rules' own assumption, which a uniform draw breaks at f²/N
# honest workers of a ring in expectation.
BYZANTINE_PLACEMENTS = ("uniform", "within_budget")

# Rejoin policies after a crash-recovery outage (parallel/faults.py
# REJOIN_POLICIES mirrors this constant; config stays jax-free).
REJOINS = ("frozen", "neighbor_restart")

# Execution modes (docs/ASYNC.md): 'sync' is the bulk-synchronous scan
# over rounds (every path before ISSUE-9); 'async' scans over a
# precomputed EVENT schedule (parallel/events.py) — AD-PSGD-style
# bounded-staleness gossip where each event is one worker's local
# gradient step at its realized staleness plus a pairwise-average
# exchange, and stragglers are modeled as LATENCY, not drops.
EXECUTIONS = ("sync", "async")

# Latency models for the asynchronous event schedule's per-worker
# compute-time draws (parallel/events.py LATENCY_MODELS mirrors this
# constant; config stays numpy/jax-free). All are normalized to mean
# ``latency_mean``; ``latency_tail`` is the shape knob (lognormal
# log-std, pareto alpha) for the heavy-tailed straggler regimes.
LATENCY_MODELS = ("constant", "exponential", "lognormal", "pareto")

# Robust neighbor-aggregation rules (ops/robust_aggregation.py) replacing
# plain W @ x gossip: coordinate-wise trimmed mean / median over the closed
# neighborhood, and self-centered clipping (ClippedGossip, He-Karimireddy-
# Jaggi 2022). 'gossip' is the plain (vulnerable) MH average; a robust rule
# with robust_b == 0 degrades to exactly plain gossip.
AGGREGATIONS = ("gossip", "trimmed_mean", "median", "clipped_gossip")

# Algorithms that accept ``local_steps`` > 1 (τ gradient steps per gossip
# round — the federated local-update regime of Koloskova et al. '20's
# unified theory; docs/PERF.md §14). Only mix-based rules whose round
# structure survives extra purely-local descents qualify: D-SGD (plain
# local SGD between gossips) and gradient tracking (tracker-corrected
# local steps, K-GT style). EXTRA/ADMM/CHOCO/push-sum each pin a
# one-exchange-per-descent recursion that τ local steps would silently
# break.
LOCAL_STEP_ALGORITHMS = ("dsgd", "gradient_tracking")

# Topologies with a neighbor-table-native (matrix-free) constructor
# (parallel/topology.py): the graph is built directly as a padded
# [N, k_max] neighbor table without ever materializing the dense [N, N]
# adjacency or mixing matrix — the representation that lifts the worker
# axis to N in the tens of thousands (the dense path's [N, N] float64
# state is ~800 MB at N = 10k). fully_connected/star are deliberately
# excluded: their k_max is N−1, so the "table" would be the quadratic
# object the path exists to avoid (build_topology rejects them loudly).
NEIGHBOR_TOPOLOGIES = ("ring", "grid", "chain", "erdos_renyi")

# N at which ``topology_impl='auto'`` switches to the matrix-free neighbor
# path (and mixing_impl='auto' to the k_max-bounded gather operator on
# matrix-backed irregular graphs): the dense-mixing measurements stop at
# N = 4096 (docs/PERF.md, "Pre-ledger history") and the
# federated-scale bench (docs/perf/federated.json) measures the gather
# route winning on CPU well below it while being the only route that
# completes at N >= 10k.
MATRIX_FREE_AUTO_N = 4096

# N at which ``topology_sampler='auto'`` switches the matrix-free
# Erdős–Rényi constructor to the O(N·k_max) sparse sampler. Below it the
# O(N²)-draw dense-stream sampler stays the realization (it is the
# bitwise reference the sparse sampler's law is tested against, and at
# small N the quadratic draw cost is immaterial); above it the quadratic
# stream replay is the recorded reason ER-at-100k was skipped in
# docs/perf/worker_mesh.json, so 'auto' routes to sparse.
SPARSE_SAMPLER_AUTO_N = 65_536

# Per-replica scalar axes ``jax_backend.run_batch`` can sweep alongside the
# seed axis (each replica r behaves exactly like a sequential run of
# ``config.replace(seed=seeds[r], **{field: values[r]})``). Only scalars
# that enter the compiled program as data — the LR schedule's eta0, the
# clipping radius, the edge-drop threshold — batch this way; structural
# fields (topology, n_workers, algorithm, ...) change the traced program
# itself and are rejected with a pointer to running separate sweeps.
SWEEPABLE_FIELDS = ("learning_rate_eta0", "clip_tau", "edge_drop_prob")

# Topologies whose edge structure is a random draw from a seed; only these
# consume ``resolved_topology_seed`` when building the graph, so only they
# contribute it to the structural hash below (a ring is the same compiled
# program whatever the seed says).
RANDOM_TOPOLOGIES = ("erdos_renyi", "directed_erdos_renyi")

# Default Huber transition point δ: fixed at the synthetic data's noise scale
# (make_regression noise=10.0, utils/data.py), i.e. the kink sits at ~1σ of the
# residuals at the optimum — the classical choice. δ is data-scale-dependent,
# so it is a config field (``huber_delta``); this constant is the SINGLE
# source of the default, consumed by ops/losses.py, ops/losses_np.py, and
# (via the C ABI's huber_delta argument) native/src/gossip_core.cpp.
DEFAULT_HUBER_DELTA = 10.0


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    """All hyperparameters for one experiment.

    Field names match the reference's config-dict keys (reference
    ``main.py:25-38``) where a counterpart exists.
    """

    # --- reference-parity fields (main.py:6-21 defaults) ---
    n_workers: int = 25
    local_batch_size: int = 16
    n_iterations: int = 10_000
    learning_rate_eta0: float = 0.05
    l2_regularization_lambda: float = 1e-4
    strong_convexity_mu: float = 1e-4
    problem_type: str = "quadratic"
    n_samples: int = 12_500
    n_features: int = 80
    n_informative_features: int = 50
    classification_sep: float = 0.7
    suboptimality_threshold: float = 0.08

    # --- new framework knobs (no reference counterpart) ---
    backend: str = "jax"  # 'jax' (TPU/XLA north star) | 'numpy' (fidelity oracle)
    algorithm: str = "dsgd"
    topology: str = "ring"
    # LR schedule: 'auto' = the reference's eta0/sqrt(t+1) decay
    # (trainer.py:17-19) for SGD-family algorithms, constant eta0 for
    # gradient_tracking/extra/admm (their linear-convergence regimes).
    lr_schedule: str = "auto"  # 'auto' | 'sqrt_decay' | 'constant'
    admm_c: float = 0.5  # ADMM edge-penalty coefficient
    # DLM proximal-linearization weight; must dominate the loss gradient's
    # Lipschitz constant for stability (L ≈ 4 for the standardized quadratic
    # data here, ≈ 0.25 for logistic). 5.0 is safe for both study problems.
    admm_rho: float = 5.0
    # CHOCO-SGD (compressed gossip) knobs: the compression operator applied
    # to transmitted model differences (see COMPRESSIONS), its parameter
    # (coordinates kept for top_k/random_k; quantization BITS for qsgd), and
    # the consensus step size gamma. Stability needs roughly gamma <= the
    # operator's contraction factor delta: k/d for top_k/random_k,
    # 1/(1+min(d/s^2, sqrt(d)/s)) with s = 2^bits for qsgd (reported as
    # Compressor.delta by ops.compression.make_compressor).
    compression: str = "none"
    compression_k: int = 0
    choco_gamma: float = 0.3
    # Class count for the multinomial softmax family (problem_type='softmax'
    # only — the compute-bound objective tier, models/softmax.py). The
    # parameter is a [n_features, n_classes] matrix flattened to d·K for the
    # mixing/algorithm layers; K also scales the per-edge gossip payload.
    n_classes: int = 10
    # Huber transition point δ (problem_type='huber' only); see
    # DEFAULT_HUBER_DELTA for the default's rationale. Threaded through all
    # three tiers: jax closures (models/huber.py), numpy twins
    # (losses_np delta kwarg), and the native core (C ABI argument).
    huber_delta: float = DEFAULT_HUBER_DELTA
    # Data partition across workers: 'sorted' = the study's contiguous
    # sort-by-target split (maximal non-IID skew, reference utils.py
    # parity); 'shuffled' = seed-deterministic IID split — the bounded-
    # heterogeneity control (used by the Byzantine benches: screening
    # rules provably pay a bias ∝ attack fraction × heterogeneity, so the
    # breakdown point is only visible without the sorted skew).
    partition: str = "sorted"
    seed: int = 203  # reference seeds np.random.seed(203) at main.py:24
    # Seed for the TOPOLOGY's random structure (Erdős–Rényi edge draws)
    # when it should NOT follow ``seed``: −1 (default) derives the graph
    # from ``seed`` as always; >= 0 pins the graph independently, so a
    # seed sweep (``replicas`` / run_batch) varies run randomness —
    # sampling, faults, adversary draws — over ONE fixed graph instance.
    # The replica-batched path pins this automatically (the graph is
    # structural: a per-replica graph cannot batch), making each batched
    # replica exactly equivalent to a sequential run of its per-replica
    # config. Deterministic topologies ignore it.
    topology_seed: int = -1
    # Seed for the DATASET's random draws (sklearn generators + the
    # 'shuffled' partition) when it should NOT follow ``seed``: −1
    # (default) derives the data from ``seed`` as the reference does; >= 0
    # pins the problem instance independently, so seed variants name runs
    # over ONE shared dataset. This is the serving layer's coalescing knob
    # (docs/SERVING.md): requests that differ only in ``seed`` can share a
    # run_batch cohort — and therefore one compiled program execution —
    # only when they agree on the dataset, which a pinned data_seed makes
    # explicit (the same contract the CLI's --seeds path has always used
    # implicitly by generating the dataset from the base seed once).
    data_seed: int = -1
    eval_every: int = 1  # full-data objective eval cadence (reference: every iter)
    erdos_renyi_p: float = 0.4  # edge probability for the ER topology
    # Failure injection (SURVEY.md §5.3): per-iteration iid probability that
    # each edge of the topology drops; gossip runs over the surviving graph
    # with MH weights recomputed on realized degrees. 0 = no faults.
    edge_drop_prob: float = 0.0
    # Straggler/node-failure injection: per-iteration iid probability that a
    # node sits the round out — it exchanges nothing and takes no local
    # step (its state is frozen for that iteration). 0 = none.
    straggler_prob: float = 0.0
    # --- temporally-correlated fault processes (docs/CHURN.md) ---
    # Bursty link failures: per-edge two-state Markov chain (Gilbert-
    # Elliott) at the SAME marginal drop rate edge_drop_prob but with mean
    # burst length burst_len/(1 - edge_drop_prob) — burst_len times the iid
    # chain's. 0 = the memoryless iid sampler (default); 1 reduces BITWISE
    # to it (different code path, identical draws/thresholds); > 1
    # correlates failures in time. Requires edge_drop_prob > 0.
    burst_len: float = 0.0
    # Crash-recovery node churn replacing iid stragglers: geometric up/down
    # holding times with mean up-time `mttf` rounds and mean outage `mttr`
    # rounds (stationary downtime mttr/(mttf+mttr)); a down node exchanges
    # nothing and takes no local step for the WHOLE outage. Both 0 = off;
    # both must be >= 1 and set together, and exclude straggler_prob
    # (mttf=1/q, mttr=1/(1-q) reduces bitwise to straggler_prob=q).
    mttf: float = 0.0
    mttr: float = 0.0
    # What a node resumes with after an outage: 'frozen' = its stale
    # pre-crash state (the staleness stress test); 'neighbor_restart' =
    # warm restart of its model row from the realized-neighborhood average
    # on the rejoin round (trades exact average preservation for a
    # consensus reset after long outages). Only meaningful with churn.
    rejoin: str = "frozen"
    # Byzantine adversary injection (docs/BYZANTINE.md): `n_byzantine`
    # workers (a static seed-deterministic set) replace their OUTGOING
    # models with an `attack` payload each gossip round. attack_scale is the
    # payload magnitude: the sign-flip multiplier, the large-noise sigma, or
    # ALIE's z (how many honest standard deviations the colluders shift).
    # Composes with edge_drop_prob/straggler_prob (attacks over failing
    # links) and is decentralized-only, like the fault machinery.
    attack: str = "none"
    n_byzantine: int = 0
    attack_scale: float = 1.0
    # Where the attackers sit (BYZANTINE_PLACEMENTS): 'within_budget' needs
    # an attack, a robust rule and robust_b >= 1 (the budget it places
    # within) and raises where the graph cannot hold n_byzantine of them.
    byzantine_placement: str = "uniform"
    # --- federated execution regime (docs/PERF.md §14) ---
    # τ local SGD steps per gossip round (Koloskova et al. '20 local
    # updates): each scan iteration is one ROUND — the algorithm's normal
    # gossip-fused first descent plus τ−1 purely-local descents (tracker-
    # corrected for gradient_tracking), all fused inside the same compiled
    # scan body. Per-round comms is unchanged, so τ is the dominant
    # communication-reduction lever: τ gradient steps per exchanged model
    # ⇒ up to τ× fewer floats per unit of progress (measured in
    # docs/perf/federated.json). 1 = the existing one-step round, bitwise.
    local_steps: int = 1
    # Per-round partial participation (client sampling): each round, every
    # worker independently participates with this probability, presampled
    # into the run's fault timeline ([horizon, N] masks — the same
    # machinery as stragglers/churn, distinct key stream). A sampled-out
    # worker exchanges nothing and takes no local step that round (its
    # state is frozen); gossip reweights on the realized subgraph via the
    # realized-adjacency composition, so participation composes with
    # churn, bursty links and the Byzantine layer. 1.0 = everyone, every
    # round — bitwise the no-sampling program (no fault machinery traced).
    participation_rate: float = 1.0
    # --- event-driven asynchronous execution (docs/ASYNC.md) ---
    # 'sync' | 'async'. 'async' replaces the bulk-synchronous round scan
    # with a scan over a precomputed event schedule
    # (parallel/events.py::build_event_timeline): n_iterations then counts
    # per-worker gradient steps (N events per "round", the same total
    # gradient budget as the synchronous run), eval_every keeps its
    # round-based meaning, and wall-clock comparisons use the schedule's
    # simulated VIRTUAL clock. All four fields are structural for the
    # serving cache: the event schedule is baked into the traced program.
    execution: str = "sync"
    # Latency distribution of the per-worker compute-time draws (see
    # LATENCY_MODELS); only meaningful with execution='async'.
    latency_model: str = "constant"
    # Mean compute time per gradient step in virtual seconds (every model
    # is matched-mean, so the tail knob never changes expected compute).
    latency_mean: float = 1.0
    # Heavy-tail straggler knob: lognormal log-std (> 0) or pareto shape
    # alpha (> 1); must stay 0 for constant/exponential (no tail shape).
    latency_tail: float = 0.0
    # 'auto' | 'dense' | 'neighbor'. Topology representation: 'dense'
    # builds the [N, N] adjacency + mixing matrix (every pre-federated
    # path); 'neighbor' is the matrix-free form — a padded [N, k_max]
    # neighbor table with gather-form MH mixing, matrix-free spectral-gap
    # diagnostics, and O(N·k_max·d) per-round work/memory, the only
    # representation that fits N in the tens of thousands. 'auto' picks
    # 'neighbor' on the jax backend above MATRIX_FREE_AUTO_N workers for
    # NEIGHBOR_TOPOLOGIES when no dense-only feature (edge-fault
    # processes, Byzantine screening, matching schedules, matrix-backed
    # mixing impls) is requested; 'dense' otherwise.
    topology_impl: str = "auto"
    # Robust neighbor aggregation (defense): which rule honest workers use
    # to combine received neighbor models, and its per-neighborhood attack
    # budget b (values trimmed from each tail / messages assumed Byzantine).
    # The backend validates 2·b <= min node degree (otherwise trimming can
    # exhaust a neighborhood); robust_b == 0 degrades every rule to exactly
    # plain MH gossip. clip_tau: fixed clipping radius for clipped_gossip
    # (0 = adaptive: each node clips its b largest-norm neighbor
    # differences down to the (deg−b)-th smallest norm).
    aggregation: str = "gossip"
    robust_b: int = 0
    clip_tau: float = 0.0
    # 'auto' | 'dense' | 'gather'. Execution form of the robust
    # rule on the jax backend (the numpy oracle has one per-node form):
    # 'dense' sorts the [N, N, d] closed-neighborhood tensor over the full
    # node axis — O(N²·d·log N) regardless of topology; 'gather'
    # precomputes a static [N, k_max] padded neighbor table, gathers
    # neighbor models and per-incident-edge liveness bits, and screens
    # over the k_max axis — O(N·k_max·d·log k_max), ~N/k_max-fold less
    # work on degree-bounded graphs (measured 69-75x e2e for trimmed
    # mean/median on an N=256 ring, docs/perf/robust_scale.json). 'auto'
    # picks dense or gather from the measured crossover
    # (resolved_robust_impl).
    robust_impl: str = "auto"
    # Gossip schedule: 'synchronous' averages with all (surviving) neighbors
    # per iteration; 'one_peer' is Boyd-style randomized gossip — each node
    # exchanges with at most ONE mutually-proposing random neighbor, W_t =
    # 0.5(I + P_t), composable with edge/straggler injection; 'round_robin'
    # cycles deterministic matchings that cover the edge set every P
    # iterations (ring/chain/even-sided grid).
    gossip_schedule: str = "synchronous"
    # 'auto' | 'dense' | 'stencil' | 'gather'. 'auto' picks the measured
    # winner: stencil where the graph embeds as mesh shifts, gather for a
    # matrix-free or large degree-bounded graph that does not, else dense
    # (ops/mixing.make_mixing_op). Under worker_mesh the mesh's halo forms
    # run instead, read off the neighbor table
    # (collectives.make_halo_mixing_op).
    mixing_impl: str = "auto"
    # 'auto' | 'gather' | 'dense'. Mini-batch realization on the jax backend:
    # 'gather' materializes [N, b, d] batches (the b largest uniforms by a
    # counted threshold, no sort; one gather of whole rows), 'dense'
    # computes the weighted gradient over the full padded shard with 1/b
    # weights on the sampled rows — same sampled subsets, no selection of
    # indices and no gather.
    # 'auto' picks from measurement (see resolved_sampling_impl).
    sampling_impl: str = "auto"
    # XLA scan unrolling for the jax backend's training loop. Swept on the
    # real chip (examples/bench_breakdown.py → docs/perf/breakdown.json):
    # 1/2/4/8 measure within noise of each other, 16+ regress and cost more
    # compile time. 0 = auto: 8 on accelerators (within noise of best,
    # +0.9s compile vs unroll=1), 1 on CPU (where compile cost dwarfs the
    # tiny kernels' dispatch savings).
    scan_unroll: int = 0
    dtype: str = "float32"
    matmul_precision: str = "highest"  # jax.lax Precision for parity-sensitive math
    record_consensus: bool = True
    # Flight-recorder trace buffers (telemetry.py, docs/OBSERVABILITY.md):
    # record per-eval-row run-health series — per-worker grad/param norms,
    # non-finite sentinel counts, fault-layer liveness, robust-aggregation
    # activity — inside the compiled scan (stacked outputs only; the scan
    # carry and the optimization dataflow are untouched, so trajectories
    # are bitwise-identical with telemetry on or off). Off by default: the
    # recording costs one extra gradient per eval point (measured overhead
    # bound in docs/perf/telemetry.json).
    telemetry: bool = False
    # Replica-batched execution (jax backend): run this many independent
    # seed replicates — seeds seed, seed+1, ..., seed+replicas−1 — through
    # ONE vmapped compiled program ([R, N, d] state, [R, n_evals] metrics)
    # instead of sequential compiled runs, and report mean ± std over the
    # replica axis. 1 = the single-trajectory path (unchanged). Each
    # replica is trajectory-equivalent to a sequential run with its seed
    # (tests pin ≤ 1e-12 in f64 through the fault and Byzantine layers).
    replicas: int = 1
    # Tensor parallelism for the compute-bound softmax tier: shard the
    # [d, K] classifier over a 'model' mesh axis of this many devices
    # (parallel/tensor_parallel.py — D-SGD + ring + softmax + full local
    # batches only; every other combination is rejected below with the
    # reason). 1 = pure data parallelism (unchanged).
    tp_degree: int = 1
    # Sharded worker mesh (docs/PERF.md §16): split the WORKER axis into
    # this many contiguous row blocks, one per device — state rows
    # [N/P, d], neighbor tables [N/P, k_max] and fault-timeline columns
    # all live per-shard, and each gossip round exchanges only the
    # boundary rows a shard's neighbor table references (a ppermute halo
    # exchange; parallel/collectives.py::make_halo_mixing_op — a ring's
    # table, and a torus's that the mesh cuts by whole grid rows, needs
    # no per-shard table at all and is mixed by row shifts, read off the
    # table and the mesh's size with no option). This is
    # the representation that lifts matrix-free N past one device's RAM:
    # per-device memory is O(N/P·(d + k_max)), and the sharded-vs-
    # unsharded trajectories are BITWISE identical at matched N (the
    # halo gather computes the exact per-row op sequence of the
    # single-device gather path). 0 = unsharded (every pre-mesh
    # program, unchanged); >= 2 = the device count, which must divide
    # n_workers. jax backend + neighbor-table topologies only; on CPU
    # hosts simulate devices via
    # XLA_FLAGS=--xla_force_host_platform_device_count=P.
    worker_mesh: int = 0
    # 'auto' | 'dense' | 'sparse'. Which Erdős–Rényi constructor realizes
    # the matrix-free graph: 'dense' replays the [N, N] uniform stream
    # bit-for-bit (O(N²) draws — the historical reference, and the oracle
    # the sparse sampler is tested against below the cutoff); 'sparse'
    # draws O(N·k_max) (forward-tail binomial degrees + tail-sampled
    # partners — the million-node path). The two realize the SAME
    # G(n, p) law but DIFFERENT graphs per (seed, p), so the resolved
    # value is part of the structural identity (structural_dict). 'auto'
    # picks 'sparse' above SPARSE_SAMPLER_AUTO_N on the matrix-free ER
    # path, 'dense' otherwise. Only meaningful for topology='erdos_renyi'
    # (rejected elsewhere rather than silently ignored).
    topology_sampler: str = "auto"

    def __post_init__(self) -> None:
        if self.problem_type not in PROBLEM_TYPES:
            raise ValueError(f"Unknown problem type: {self.problem_type}")
        if self.algorithm not in ALGORITHMS:
            raise ValueError(f"Unknown algorithm: {self.algorithm}")
        if self.topology not in TOPOLOGIES:
            raise ValueError(f"Unknown topology: {self.topology}")
        if self.backend not in BACKENDS:
            raise ValueError(f"Unknown backend: {self.backend}")
        if self.mixing_impl not in ("auto", "dense", "stencil", "gather"):
            raise ValueError(f"Unknown mixing impl: {self.mixing_impl}")
        if self.sampling_impl not in ("auto", "gather", "dense"):
            raise ValueError(f"Unknown sampling impl: {self.sampling_impl}")
        if self.lr_schedule not in ("auto", "sqrt_decay", "constant"):
            raise ValueError(f"Unknown lr schedule: {self.lr_schedule}")
        if self.compression not in COMPRESSIONS:
            raise ValueError(f"Unknown compression: {self.compression}")
        if self.compression != "none":
            if self.algorithm not in COMPRESSED_ALGORITHMS:
                raise ValueError(
                    f"compression={self.compression!r} only takes effect "
                    f"with the error-feedback gossip algorithms "
                    f"{COMPRESSED_ALGORITHMS}; other algorithms exchange "
                    "full vectors and would silently ignore it"
                )
            if self.compression_k <= 0:
                raise ValueError(
                    "compression_k (coordinates kept, or qsgd bits) must be "
                    f"positive when compression={self.compression!r}"
                )
            if (
                self.edge_drop_prob > 0.0
                or self.straggler_prob > 0.0
                or self.mttf > 0.0
                or self.gossip_schedule != "synchronous"
            ):
                raise ValueError(
                    "compressed gossip does not compose with time-varying "
                    "graphs: a dropped exchange leaves the neighbor's copy "
                    "of the shared error-feedback estimate stale, which "
                    "the single shared X̂ leaf cannot represent (per-edge "
                    "[N, N, d] staleness state would be needed) — run "
                    "faults uncompressed, or compression on a static graph"
                )
            if self.attack != "none" or self.aggregation != "gossip":
                raise ValueError(
                    "compressed gossip does not compose with Byzantine "
                    "injection / robust aggregation: screening operates "
                    "on transmitted models, but error-feedback exchanges "
                    "compressed DIFFERENCES against a shared estimate — "
                    "a screened-out update still mutates every neighbor's "
                    "X̂ copy, silently breaking the defense's contract"
                )
        if self.huber_delta <= 0.0:
            raise ValueError(f"huber_delta must be positive, got {self.huber_delta}")
        if self.n_classes < 2:
            raise ValueError(
                f"n_classes must be >= 2, got {self.n_classes}"
            )
        if (
            self.algorithm == "choco" or self.compression != "none"
        ) and not 0.0 < self.choco_gamma <= 1.0:
            raise ValueError(
                f"choco_gamma must be in (0, 1], got {self.choco_gamma}"
            )
        if self.partition not in ("sorted", "shuffled"):
            raise ValueError(f"Unknown partition: {self.partition}")
        if self.attack not in ATTACKS:
            raise ValueError(f"Unknown attack: {self.attack}")
        if self.aggregation not in AGGREGATIONS:
            raise ValueError(f"Unknown aggregation: {self.aggregation}")
        if self.n_byzantine < 0:
            raise ValueError(
                f"n_byzantine must be >= 0, got {self.n_byzantine}"
            )
        if (self.attack == "none") != (self.n_byzantine == 0):
            raise ValueError(
                f"attack={self.attack!r} and n_byzantine="
                f"{self.n_byzantine} must be set together: an attack needs "
                "attackers, and Byzantine workers need a payload to send"
            )
        if self.attack != "none":
            if self.n_byzantine >= self.n_workers:
                raise ValueError(
                    f"n_byzantine ({self.n_byzantine}) must leave at least "
                    f"one honest worker out of {self.n_workers}"
                )
            if self.attack_scale <= 0.0:
                raise ValueError(
                    f"attack_scale must be positive, got {self.attack_scale}"
                )
        elif self.attack_scale != 1.0:
            raise ValueError(
                f"attack_scale={self.attack_scale} only takes effect with "
                "an attack; attack='none' would silently ignore it"
            )
        if self.robust_b < 0:
            raise ValueError(f"robust_b must be >= 0, got {self.robust_b}")
        if self.robust_b > 0 and self.aggregation == "gossip":
            raise ValueError(
                f"robust_b={self.robust_b} only takes effect with a robust "
                "aggregation rule; plain 'gossip' has no screening step and "
                "would silently ignore it"
            )
        if self.byzantine_placement not in BYZANTINE_PLACEMENTS:
            raise ValueError(
                f"Unknown byzantine placement: {self.byzantine_placement}"
            )
        if self.byzantine_placement == "within_budget" and not (
            self.attack != "none"
            and self.aggregation != "gossip" and self.robust_b > 0
        ):
            raise ValueError(
                "byzantine_placement='within_budget' places the attackers "
                "so that every honest worker keeps at most robust_b "
                "attacking neighbours: it needs attackers to place (an "
                "attack) and a budget to place them within (a robust "
                "aggregation rule with robust_b >= 1); got attack="
                f"{self.attack!r}, aggregation={self.aggregation!r}, "
                f"robust_b={self.robust_b}"
            )
        if self.robust_impl not in ("auto", "dense", "gather"):
            raise ValueError(f"Unknown robust impl: {self.robust_impl}")
        if self.robust_impl != "auto" and not (
            self.aggregation != "gossip" and self.robust_b > 0
        ):
            raise ValueError(
                f"robust_impl={self.robust_impl!r} selects the execution "
                "form of a robust aggregation rule; without one (a non-"
                "gossip aggregation and robust_b > 0) it would be silently "
                "ignored"
            )
        if self.clip_tau < 0.0:
            raise ValueError(f"clip_tau must be >= 0, got {self.clip_tau}")
        if self.clip_tau > 0.0 and self.aggregation != "clipped_gossip":
            raise ValueError(
                f"clip_tau only applies to aggregation='clipped_gossip'; "
                f"{self.aggregation!r} would silently ignore it"
            )
        if self.aggregation != "gossip" and self.gossip_schedule != "synchronous":
            raise ValueError(
                f"aggregation={self.aggregation!r} screens MULTIPLE received "
                "neighbor messages per round; matching schedules "
                f"({self.gossip_schedule!r}) deliver at most one, so no "
                "trimming/clipping budget is realizable — use 'synchronous'"
            )
        if not 0.0 <= self.edge_drop_prob < 1.0:
            raise ValueError(
                f"edge_drop_prob must be in [0, 1), got {self.edge_drop_prob}"
            )
        if not 0.0 <= self.straggler_prob < 1.0:
            raise ValueError(
                f"straggler_prob must be in [0, 1), got {self.straggler_prob}"
            )
        if self.burst_len != 0.0 and self.burst_len < 1.0:
            raise ValueError(
                f"burst_len must be 0 (iid edge drops) or >= 1 (mean burst "
                f"multiplier), got {self.burst_len}"
            )
        if self.burst_len != 0.0 and self.edge_drop_prob == 0.0:
            raise ValueError(
                f"burst_len={self.burst_len} shapes the edge-failure "
                "process and needs edge_drop_prob > 0; without a drop rate "
                "it would be silently ignored"
            )
        if (self.mttf > 0.0) != (self.mttr > 0.0):
            raise ValueError(
                f"mttf ({self.mttf}) and mttr ({self.mttr}) must be set "
                "together: crash-recovery churn needs both a mean up-time "
                "and a mean outage length"
            )
        if self.mttf < 0.0 or self.mttr < 0.0:
            raise ValueError(
                f"mttf/mttr must be >= 0, got ({self.mttf}, {self.mttr})"
            )
        if self.mttf > 0.0:
            if self.mttf < 1.0 or self.mttr < 1.0:
                raise ValueError(
                    "mttf/mttr are mean holding times in rounds and must "
                    f"be >= 1, got ({self.mttf}, {self.mttr})"
                )
            if self.straggler_prob > 0.0:
                raise ValueError(
                    "crash-recovery churn (mttf/mttr) replaces iid "
                    "stragglers; set straggler_prob=0 (the iid model is "
                    "churn at mttf=1/q, mttr=1/(1-q))"
                )
            if self.gossip_schedule != "synchronous":
                raise ValueError(
                    "crash-recovery churn requires "
                    "gossip_schedule='synchronous': rejoin policies act on "
                    "the realized neighborhood, which matching schedules "
                    f"({self.gossip_schedule!r}, at most one partner per "
                    "round) cannot supply"
                )
        if self.rejoin not in REJOINS:
            raise ValueError(f"Unknown rejoin policy: {self.rejoin}")
        if self.rejoin == "neighbor_restart" and (
            self.attack != "none"
            or (self.aggregation != "gossip" and self.robust_b > 0)
        ):
            raise ValueError(
                "rejoin='neighbor_restart' does not compose with Byzantine "
                "injection / robust aggregation: the warm restart averages "
                "neighbors' raw model rows, bypassing both the attack "
                "payloads and the screening rule — it would model an "
                "unrealistically safe rejoin at exactly the moment an "
                "adversary controls the unscreened average. Use "
                "rejoin='frozen' under attack"
            )
        if self.rejoin != "frozen" and self.mttf == 0.0:
            raise ValueError(
                f"rejoin={self.rejoin!r} only takes effect with "
                "crash-recovery churn (mttf/mttr); without outages there "
                "are no rejoin rounds and it would be silently ignored"
            )
        if self.local_steps < 1:
            raise ValueError(
                f"local_steps must be >= 1, got {self.local_steps}"
            )
        if self.local_steps > 1:
            if self.algorithm not in LOCAL_STEP_ALGORITHMS:
                raise ValueError(
                    f"local_steps={self.local_steps} is unsupported for "
                    f"{self.algorithm!r}: τ local descents between gossip "
                    "exchanges only compose with the mix-based rules "
                    f"{LOCAL_STEP_ALGORITHMS} (EXTRA/ADMM/CHOCO/push-sum "
                    "pin a one-exchange-per-descent recursion that extra "
                    "local steps would silently break)"
                )
            if self.compression != "none":
                raise ValueError(
                    "local_steps > 1 does not compose with compressed "
                    "gossip: the error-feedback estimate exchange assumes "
                    "one descent per transmitted difference — τ local "
                    "steps between exchanges would leave the shared X̂ "
                    "tracking a state it never saw"
                )
            if self.backend == "cpp":
                raise ValueError(
                    "local_steps > 1 is unsupported on the cpp backend "
                    "(its native kernel hard-codes the one-step round); "
                    "use backend='jax' or 'numpy'"
                )
            if self.tp_degree > 1:
                raise ValueError(
                    "local_steps > 1 does not compose with tp_degree > 1: "
                    "the tensor-parallel path runs its own sharded "
                    "one-step ring stencil"
                )
        if not 0.0 < self.participation_rate <= 1.0:
            raise ValueError(
                f"participation_rate must be in (0, 1], got "
                f"{self.participation_rate}"
            )
        if self.participation_rate < 1.0:
            if self.algorithm == "centralized":
                raise ValueError(
                    "participation_rate models per-round client sampling "
                    "of peer exchanges; the centralized pattern has no "
                    "peer edges — it applies to decentralized algorithms "
                    "only"
                )
            if self.gossip_schedule != "synchronous":
                raise ValueError(
                    "participation_rate < 1 requires "
                    "gossip_schedule='synchronous': the sampled subgraph "
                    "reweights the whole realized neighborhood, which "
                    f"matching schedules ({self.gossip_schedule!r}) "
                    "cannot supply"
                )
            if self.compression != "none":
                raise ValueError(
                    "participation_rate < 1 does not compose with "
                    "compressed gossip (same reason as edge faults: a "
                    "sampled-out round leaves neighbors' error-feedback "
                    "estimates stale) — sample participation uncompressed"
                )
            if self.backend == "cpp":
                raise ValueError(
                    "participation_rate < 1 is unsupported on the cpp "
                    "backend; use backend='jax' (or the numpy oracle)"
                )
            if self.tp_degree > 1:
                raise ValueError(
                    "participation_rate < 1 does not compose with "
                    "tp_degree > 1: the TP ring stencil is a fixed "
                    "boundary exchange, not a per-round realized graph"
                )
        if self.topology_impl not in ("auto", "dense", "neighbor"):
            raise ValueError(f"Unknown topology impl: {self.topology_impl}")
        if self.topology_impl == "neighbor":
            if self.topology == "fully_connected":
                raise ValueError(
                    "topology_impl='neighbor' with 'fully_connected' would "
                    "allocate an [N, N-1] neighbor table — the quadratic "
                    "object the matrix-free path exists to avoid; use "
                    "topology_impl='dense' (k_max = N−1 leaves nothing "
                    "for a degree-bounded route to win)"
                )
            if self.topology not in NEIGHBOR_TOPOLOGIES:
                raise ValueError(
                    f"topology_impl='neighbor' supports "
                    f"{NEIGHBOR_TOPOLOGIES}; {self.topology!r} has no "
                    "matrix-free constructor"
                )
            if self.backend != "jax":
                raise ValueError(
                    "topology_impl='neighbor' is a jax-backend capability "
                    "(gather-form mixing); the numpy/cpp oracles run the "
                    "dense matrix form — use topology_impl='dense'"
                )
            if self.mixing_impl not in ("auto", "gather", "stencil"):
                raise ValueError(
                    f"topology_impl='neighbor' never materializes the "
                    f"[N, N] matrices that mixing_impl="
                    f"{self.mixing_impl!r} consumes — use 'auto', "
                    "'gather', or 'stencil'. To run the gather path over "
                    "real collectives, shard the worker axis instead: "
                    "worker_mesh >= 2 lowers gather mixing to a ppermute "
                    "halo exchange (the sharded-gather path; "
                    "docs/PERF.md §16)"
                )
            if (
                self.attack != "none"
                or (self.aggregation != "gossip" and self.robust_b > 0)
            ) and self.robust_impl not in ("auto", "gather"):
                # ISSUE-9 satellite: the matrix-free path ACCEPTS Byzantine
                # screening in its gather form (the neighbor table IS the
                # gather path's input); only the [N, N]-materializing
                # execution forms stay dense-only.
                raise ValueError(
                    f"topology_impl='neighbor' runs robust aggregation in "
                    f"gather form over the [N, k_max] table; robust_impl="
                    f"{self.robust_impl!r} materializes [N, N] objects "
                    "the matrix-free path never builds — use 'auto' or "
                    "'gather'"
                )
            if self.gossip_schedule != "synchronous":
                raise ValueError(
                    "topology_impl='neighbor' requires "
                    "gossip_schedule='synchronous' (matching schedules "
                    "sample partners from the dense adjacency)"
                )
            if self.tp_degree > 1:
                raise ValueError(
                    "topology_impl='neighbor' does not compose with "
                    "tp_degree > 1 (the TP path pins its own ring "
                    "stencil over a device mesh)"
                )
        if self.worker_mesh < 0 or self.worker_mesh == 1:
            raise ValueError(
                f"worker_mesh must be 0 (unsharded) or >= 2 devices, got "
                f"{self.worker_mesh} (1 would name the unsharded program "
                "— leave it 0)"
            )
        if self.worker_mesh >= 2:
            if self.backend != "jax":
                raise ValueError(
                    "worker_mesh shards the worker axis over a jax device "
                    f"mesh; backend={self.backend!r} has no mesh — use "
                    "backend='jax'"
                )
            if self.algorithm == "centralized":
                raise ValueError(
                    "worker_mesh shards the gossip neighbor tables; the "
                    "centralized pattern has no peer graph to shard — it "
                    "applies to decentralized algorithms only"
                )
            if self.n_workers % self.worker_mesh != 0:
                raise ValueError(
                    f"worker_mesh={self.worker_mesh} must divide n_workers "
                    f"({self.n_workers}): shards are equal contiguous row "
                    "blocks (pad N or pick a divisor)"
                )
            if self.topology not in NEIGHBOR_TOPOLOGIES:
                raise ValueError(
                    f"worker_mesh runs the neighbor-table halo-exchange "
                    f"path; topology {self.topology!r} has no matrix-free "
                    f"constructor (supported: {NEIGHBOR_TOPOLOGIES})"
                )
            if self.topology_impl == "dense":
                raise ValueError(
                    "worker_mesh shards the [N, k_max] neighbor tables; "
                    "topology_impl='dense' materializes the [N, N] "
                    "matrices the sharded path never builds — use "
                    "'auto' or 'neighbor'"
                )
            if self.mixing_impl not in ("auto", "gather"):
                raise ValueError(
                    f"worker_mesh lowers gather mixing to a ppermute halo "
                    f"exchange at shard edges; mixing_impl="
                    f"{self.mixing_impl!r} has no sharded form — use "
                    "'auto' or 'gather'"
                )
            if self.execution == "async":
                raise ValueError(
                    "worker_mesh does not compose with execution='async': "
                    "the event path is a totally ordered sequential "
                    "schedule a worker mesh cannot partition"
                )
            if self.gossip_schedule != "synchronous":
                raise ValueError(
                    "worker_mesh requires gossip_schedule='synchronous' "
                    "(matching schedules sample partners from the dense "
                    "adjacency)"
                )
            if self.edge_drop_prob > 0.0:
                raise ValueError(
                    "worker_mesh does not yet compose with per-edge fault "
                    "processes (edge_drop_prob/burst_len): the missing "
                    "piece is per-shard slicing of the [horizon, E] edge "
                    "chains through shard-local (node, slot) -> edge-id "
                    "tables — node processes (stragglers, churn, "
                    "participation) compose through the halo today"
                )
            if self.attack == "alie":
                raise ValueError(
                    "worker_mesh does not compose with attack='alie': the "
                    "colluders' shared payload is a global honest-moment "
                    "reduction whose sharded accumulation order diverges "
                    "from the single-device stream, breaking the bitwise "
                    "parity contract — use sign_flip or large_noise"
                )
            if self.rejoin == "neighbor_restart":
                raise ValueError(
                    "worker_mesh does not yet compose with "
                    "rejoin='neighbor_restart': the missing piece is the "
                    "halo-averaged warm restart (the rejoin average needs "
                    "boundary rows) — use rejoin='frozen'"
                )
            if self.robust_impl not in ("auto", "gather"):
                raise ValueError(
                    f"worker_mesh screens Byzantine messages in halo-"
                    f"gather form over the sharded tables; robust_impl="
                    f"{self.robust_impl!r} materializes [N, N] "
                    "objects the sharded path never builds — use 'auto' "
                    "or 'gather'"
                )
            if self.telemetry and (
                self.aggregation != "gossip" and self.robust_b > 0
            ):
                raise ValueError(
                    "worker_mesh does not yet compose with the telemetry "
                    "robust-activity probe: the missing piece is a "
                    "shard-local screening-fraction twin (the unsharded "
                    "probe gathers the global [N, k_max, d] stack) — "
                    "record telemetry without a robust rule, or run the "
                    "robust study unsharded"
                )
            if self.tp_degree > 1:
                raise ValueError(
                    "worker_mesh and tp_degree > 1 are mutually "
                    "exclusive: the TP path pins its own 2-D (workers, "
                    "model) mesh"
                )
        if self.topology_sampler not in ("auto", "dense", "sparse"):
            raise ValueError(
                f"Unknown topology sampler: {self.topology_sampler!r} "
                "(expected 'auto', 'dense', or 'sparse')"
            )
        if self.topology_sampler != "auto" and self.topology != "erdos_renyi":
            raise ValueError(
                f"topology_sampler={self.topology_sampler!r} selects the "
                "matrix-free Erdős–Rényi constructor; topology="
                f"{self.topology!r} has exactly one realization and would "
                "silently ignore it — leave topology_sampler='auto'"
            )
        if (
            self.topology_sampler == "sparse"
            and self.topology_impl == "dense"
        ):
            raise ValueError(
                "topology_sampler='sparse' only exists on the matrix-free "
                "path: topology_impl='dense' replays the [N, N] uniform "
                "stream as its own sampler — use topology_impl='auto' or "
                "'neighbor'"
            )
        if self.execution not in EXECUTIONS:
            raise ValueError(f"Unknown execution mode: {self.execution}")
        if self.latency_model not in LATENCY_MODELS:
            raise ValueError(f"Unknown latency model: {self.latency_model}")
        if self.execution == "sync":
            if (
                self.latency_model != "constant"
                or self.latency_mean != 1.0
                or self.latency_tail != 0.0
            ):
                raise ValueError(
                    "latency_model/latency_mean/latency_tail shape the "
                    "asynchronous event schedule; execution='sync' would "
                    "silently ignore them — set execution='async'"
                )
        else:  # execution == 'async' (docs/ASYNC.md)
            if self.latency_mean <= 0.0:
                raise ValueError(
                    f"latency_mean must be positive, got {self.latency_mean}"
                )
            if self.latency_model == "lognormal" and self.latency_tail <= 0.0:
                raise ValueError(
                    "latency_model='lognormal' needs latency_tail > 0 "
                    "(the log-std tail knob)"
                )
            if self.latency_model == "pareto" and self.latency_tail <= 1.0:
                raise ValueError(
                    "latency_model='pareto' needs latency_tail > 1 (the "
                    "shape alpha; alpha <= 1 has no finite mean)"
                )
            if (
                self.latency_model in ("constant", "exponential")
                and self.latency_tail != 0.0
            ):
                raise ValueError(
                    f"latency_tail only shapes the lognormal/pareto tails; "
                    f"latency_model={self.latency_model!r} would silently "
                    "ignore it"
                )
            if self.backend == "cpp":
                raise ValueError(
                    "execution='async' is unsupported on the cpp backend "
                    "(its native kernel hard-codes the synchronous round); "
                    "use backend='jax' or the numpy oracle"
                )
            if self.algorithm not in ("dsgd", "gradient_tracking"):
                raise ValueError(
                    f"execution='async' is unsupported for "
                    f"{self.algorithm!r}: an event applies ONE worker's "
                    "update at its realized staleness — only dsgd's "
                    "pairwise-average descent and gradient tracking's "
                    "per-event tracker telescoping have an event form; "
                    "EXTRA/ADMM's static-W fixed points, CHOCO's shared "
                    "estimates and push-sum's mass pair do not — use "
                    "algorithm='dsgd' or 'gradient_tracking'"
                )
            if self.topology in DIRECTED_TOPOLOGIES:
                raise ValueError(
                    "execution='async' realizes mutual pairwise exchanges; "
                    f"directed topology {self.topology!r} has one-way links"
                )
            # gossip_schedule has an event-axis meaning (ISSUE-17):
            # 'synchronous'/'one_peer' both name the timeline's sampled
            # mutual matchings (the schedule IS one-peer per event) and
            # 'round_robin' cycles the deterministic phase partners.
            # Round-indexed fault knobs (edge_drop/straggler/mttf/
            # participation) are realized on the event axis by
            # parallel.events.realize_event_faults — a crashed worker's
            # event fires as a no-op (mid-flight gradient lost), thinning
            # skips events at the matched rate, and rejoin policies
            # re-enter per docs/CHURN.md — so they compose here.
            if self.attack != "none" or (
                self.aggregation != "gossip" and self.robust_b > 0
            ):
                raise ValueError(
                    "execution='async' does not compose with Byzantine "
                    "injection / robust aggregation: screening needs "
                    "multiple received messages per aggregation, but an "
                    "event delivers exactly one pairwise exchange — no "
                    "trimming/clipping budget is realizable"
                )
            if self.compression != "none":
                raise ValueError(
                    "execution='async' does not compose with compressed "
                    "gossip: the error-feedback estimate exchange assumes "
                    "synchronized rounds, which the event schedule removes"
                )
            if self.tp_degree > 1 or self.replicas > 1:
                raise ValueError(
                    "execution='async' is a sequential scan over a totally "
                    "ordered event schedule; the tensor-parallel mesh and "
                    "the replica vmap axis have no event form — run "
                    "tp_degree=1, replicas=1"
                )
            if self.topology_impl == "neighbor":
                raise ValueError(
                    "execution='async' scans events over the dense-"
                    "representation topology (its regime is modest N with "
                    "long horizons, not the matrix-free 10k+ axis); use "
                    "topology_impl='dense' or 'auto'"
                )
        if self.gossip_schedule not in ("synchronous", "one_peer",
                                        "round_robin"):
            raise ValueError(
                f"Unknown gossip schedule: {self.gossip_schedule}"
            )
        if self.gossip_schedule == "round_robin" and (
            self.edge_drop_prob > 0.0 or self.straggler_prob > 0.0
        ):
            raise ValueError(
                "round_robin is a deterministic schedule; combine failure "
                "injection with 'synchronous' or 'one_peer' instead"
            )
        if self.dtype not in ("float32", "float64", "bfloat16"):
            raise ValueError(f"Unknown dtype: {self.dtype}")
        if self.matmul_precision not in ("default", "high", "highest"):
            raise ValueError(f"Unknown matmul precision: {self.matmul_precision}")
        if self.n_workers <= 0:
            raise ValueError("n_workers must be positive")
        if self.n_informative_features > self.n_features:
            raise ValueError(
                f"n_informative_features ({self.n_informative_features}) cannot "
                f"exceed n_features ({self.n_features})"
            )
        if self.eval_every <= 0:
            raise ValueError("eval_every must be positive")
        if self.scan_unroll < 0:
            raise ValueError("scan_unroll must be >= 0 (0 = auto)")
        if self.n_iterations % self.eval_every != 0:
            raise ValueError(
                f"eval_every ({self.eval_every}) must divide n_iterations "
                f"({self.n_iterations})"
            )
        if self.topology == "grid":
            side = int(math.isqrt(self.n_workers))
            if side * side != self.n_workers:
                raise ValueError(
                    f"grid topology requires a perfect-square worker count, got {self.n_workers}"
                )
        if (
            self.topology in DIRECTED_TOPOLOGIES
            and self.gossip_schedule != "synchronous"
        ):
            raise ValueError(
                f"gossip_schedule={self.gossip_schedule!r} realizes mutual "
                "pairwise matchings, an undirected construction; directed "
                f"topology {self.topology!r} has one-way links — use "
                "'synchronous' (edge_drop_prob/straggler_prob compose with "
                "it via column-stochastic renormalization of surviving "
                "out-links)"
            )
        if (
            self.topology in DIRECTED_TOPOLOGIES
            and self.algorithm != "push_sum"
        ):
            raise ValueError(
                f"topology {self.topology!r} is directed: its mixing matrix "
                "is column-stochastic, not doubly stochastic, so "
                f"{self.algorithm!r} would converge to the graph's Perron "
                "weighting instead of the true average — use "
                "algorithm='push_sum', which debiases by the tracked "
                "push-sum mass"
            )
        if self.topology_seed < -1:
            raise ValueError(
                f"topology_seed must be -1 (follow seed) or >= 0, got "
                f"{self.topology_seed}"
            )
        if self.data_seed < -1:
            raise ValueError(
                f"data_seed must be -1 (follow seed) or >= 0, got "
                f"{self.data_seed}"
            )
        if self.replicas < 1:
            raise ValueError(
                f"replicas must be >= 1, got {self.replicas}"
            )
        if self.replicas > 1:
            if self.backend != "jax":
                raise ValueError(
                    f"replicas={self.replicas} batches seed replicates "
                    "through one vmapped XLA program, which only the jax "
                    "backend compiles; the numpy/cpp backends run one "
                    "trajectory at a time — use backend='jax' or loop "
                    "single runs"
                )
            if self.algorithm == "choco":
                raise ValueError(
                    "replicas > 1 is unsupported for 'choco': its step "
                    "rule derives the compressor stream from config.seed "
                    "internally, which a batched per-replica seed axis "
                    "cannot reach — replicas would silently share "
                    "compression draws; run seeds sequentially instead"
                )
            if self.compression != "none":
                raise ValueError(
                    "replicas > 1 is unsupported with compressed gossip: "
                    "the error-feedback step derives its compressor "
                    "stream from config.seed internally, which a batched "
                    "per-replica seed axis cannot reach — replicas would "
                    "silently share compression draws; run seeds "
                    "sequentially instead"
                )
        if self.tp_degree < 1:
            raise ValueError(
                f"tp_degree must be >= 1, got {self.tp_degree}"
            )
        if self.tp_degree > 1:
            if self.backend != "jax":
                raise ValueError(
                    "tp_degree > 1 shards the model over a jax device "
                    f"mesh; backend={self.backend!r} has no mesh — use "
                    "backend='jax'"
                )
            if self.problem_type != "softmax":
                raise ValueError(
                    f"tp_degree={self.tp_degree} shards the softmax "
                    "[d, K] classifier over class columns; problem_type="
                    f"{self.problem_type!r} has a flat parameter vector "
                    "with no model axis to shard — use "
                    "problem_type='softmax'"
                )
            if self.algorithm != "dsgd" or self.topology != "ring":
                raise ValueError(
                    "the tensor-parallel path implements D-SGD ring "
                    "gossip on the class-sharded slice (the compute "
                    f"tier's measured configuration); algorithm="
                    f"{self.algorithm!r} topology={self.topology!r} is "
                    "unsupported — use algorithm='dsgd', topology='ring'"
                )
            if self.n_classes % self.tp_degree != 0:
                raise ValueError(
                    f"tp_degree={self.tp_degree} must divide n_classes "
                    f"({self.n_classes}): the [d, K] matrix shards in "
                    "equal class-column blocks"
                )
            if (
                self.edge_drop_prob > 0.0
                or self.straggler_prob > 0.0
                or self.mttf > 0.0
                or self.gossip_schedule != "synchronous"
                or self.attack != "none"
                or self.aggregation != "gossip"
            ):
                raise ValueError(
                    "tp_degree > 1 does not compose with fault injection, "
                    "matching schedules, or Byzantine machinery: the TP "
                    "ring stencil is a fixed boundary ppermute over the "
                    "workers mesh axis, not a per-iteration realized "
                    "graph — run those studies on the data-parallel path"
                )
            if self.compression != "none":
                raise ValueError(
                    "tp_degree > 1 does not compose with compressed "
                    "gossip: the TP path runs its own sharded ring "
                    "stencil, which carries no error-feedback estimate — "
                    "run compression studies on the data-parallel path"
                )
            if self.replicas > 1:
                raise ValueError(
                    "tp_degree > 1 and replicas > 1 are mutually "
                    "exclusive: the TP path pins a 2-D (workers, model) "
                    "device mesh that the replica vmap axis cannot wrap"
                )
            if self.mixing_impl not in ("auto", "stencil"):
                raise ValueError(
                    f"tp_degree > 1 realizes ring gossip as its own "
                    f"boundary-exchange stencil; mixing_impl="
                    f"{self.mixing_impl!r} would be silently ignored — "
                    "use 'auto'"
                )

    def resolved_topology_seed(self) -> int:
        """The seed random topologies actually build from: ``topology_seed``
        when pinned (>= 0), else ``seed``."""
        return self.topology_seed if self.topology_seed >= 0 else self.seed

    def resolved_data_seed(self) -> int:
        """The seed the dataset actually generates from: ``data_seed`` when
        pinned (>= 0), else ``seed``."""
        return self.data_seed if self.data_seed >= 0 else self.seed

    def resolved_topology_impl(self) -> str:
        """Resolve topology_impl='auto' (docs/PERF.md §14).

        The neighbor-table-native (matrix-free) representation activates
        automatically on the jax backend above ``MATRIX_FREE_AUTO_N``
        workers for the topologies that have a matrix-free constructor,
        provided no dense-only feature is requested — exactly the
        conditions an explicit ``topology_impl='neighbor'`` validates
        loudly. Below the threshold (or off the jax backend, or with a
        dense-only feature in play) 'auto' keeps the dense form: at small
        N the [N, N] matrices are cheap and every measured fast path
        (stencil mixing, dense fault machinery)
        assumes them.
        """
        if self.topology_impl != "auto":
            return self.topology_impl
        if self.worker_mesh >= 2:
            # The sharded worker mesh is neighbor-table-native: shards
            # hold [N/P, k_max] table blocks and halo-exchange boundary
            # rows (docs/PERF.md §16). __post_init__ already rejected
            # every dense-only feature for worker_mesh >= 2, so 'auto'
            # resolves to the matrix-free form at ANY N.
            return "neighbor"
        dense_only_feature = (
            self.backend != "jax"
            or self.topology not in NEIGHBOR_TOPOLOGIES
            or self.mixing_impl not in ("auto", "gather", "stencil")
            # Byzantine screening DOES run matrix-free now (gather form,
            # ISSUE-9 satellite) but stays an explicit opt-in: auto keeps
            # defense studies on the dense path where every execution
            # form (dense/gather) is comparable. Edge-fault
            # processes are no longer dense-only — the [horizon, E]
            # chains index through the (node, slot)→edge-id table.
            or self.attack != "none"
            or (self.aggregation != "gossip" and self.robust_b > 0)
            or self.gossip_schedule != "synchronous"
            or self.execution == "async"
            or self.tp_degree > 1
        )
        if not dense_only_feature and self.n_workers >= MATRIX_FREE_AUTO_N:
            return "neighbor"
        return "dense"

    def resolved_topology_sampler(self) -> str:
        """Resolve topology_sampler='auto' (docs/PERF.md §17).

        The sparse O(N·k_max) Erdős–Rényi sampler activates automatically
        above ``SPARSE_SAMPLER_AUTO_N`` workers on the matrix-free ER
        path — the regime where the dense sampler's O(N²) stream replay
        is the recorded blocker. Below the cutoff (or off the matrix-free
        ER path entirely) 'auto' keeps the dense-stream sampler: it is
        the bitwise reference every pre-existing ER artifact realized,
        and the graph IS the structural identity, so auto must never
        silently re-realize small-N graphs. Non-ER topologies resolve to
        'dense' (the only realization; __post_init__ rejects explicit
        non-auto values for them).
        """
        if self.topology_sampler != "auto":
            return self.topology_sampler
        if (
            self.topology == "erdos_renyi"
            and self.resolved_topology_impl() == "neighbor"
            and self.n_workers > SPARSE_SAMPLER_AUTO_N
        ):
            return "sparse"
        return "dense"

    def structural_dict(self) -> dict[str, Any]:
        """The canonical view of everything that changes the TRACED program.

        Two configs with equal structural dicts compile to the same XLA
        program shape on the replica-batched path, where the per-replica
        scalars are data: ``seed`` feeds PRNG keys / fault timelines /
        Byzantine sets (all traced inputs), ``data_seed`` only picks the
        dataset VALUES (also traced inputs), and the ``SWEEPABLE_FIELDS``
        (eta0, clip_tau, edge_drop_prob) enter as swept per-replica scalars.
        Everything else — and the structural BOUNDARIES inside the
        sweepables — stays: ``edge_drop_prob == 0`` means no fault
        machinery is traced at all, and ``clip_tau == 0`` selects the
        adaptive-radius clipping program, so those zero/nonzero indicators
        are recorded even though the values are not. Random topologies
        contribute their resolved seed (the realized graph is baked into
        the program as mixing constants); deterministic topologies do not.

        This is the serving layer's cache/coalescing identity
        (docs/SERVING.md): the executable cache keys compiled programs on
        ``structural_hash()`` (plus call-level facts like the cohort size
        and data shapes), and the request coalescer groups pending requests
        whose structural hash AND dataset agree into one ``run_batch``
        cohort.

        The federated fields are STRUCTURAL, deliberately (tested in
        tests/test_federated.py): ``local_steps`` changes the traced scan
        body (τ unrolled/fori local descents), ``participation_rate``
        both gates the fault machinery in or out AND bakes a different
        presampled participation timeline shape decision, and
        ``topology_impl`` selects between the dense-matrix and
        gather-table programs. All three therefore stay in the dict
        verbatim (``topology_impl`` as its RESOLVED value, so
        'auto'-at-large-N and an explicit 'neighbor' of the same program
        share a cohort) — two requests differing in any of them MISS each
        other's cached executables rather than silently colliding into
        one cohort.
        """
        d = self.to_dict()
        d["seed"] = None
        d["data_seed"] = None
        for f in SWEEPABLE_FIELDS:
            d[f] = None
        d["topology_seed"] = (
            self.resolved_topology_seed()
            if self.topology in RANDOM_TOPOLOGIES
            else None
        )
        d["topology_impl"] = self.resolved_topology_impl()
        # The ER sampler realizes a DIFFERENT graph per identity (same
        # law, different draws), and the realized graph is baked into the
        # compiled program — so the RESOLVED sampler is structural, like
        # topology_seed. Deterministic topologies have one realization
        # and contribute None (a ring is the same program under any
        # sampler name).
        d["topology_sampler"] = (
            self.resolved_topology_sampler()
            if self.topology == "erdos_renyi"
            else None
        )
        d["edge_faults_traced"] = self.edge_drop_prob > 0.0
        d["clip_tau_fixed"] = self.clip_tau > 0.0
        return d

    def structural_hash(self) -> str:
        """Stable content hash of ``structural_dict`` (sorted-key JSON,
        sha256, 16 hex chars — the same convention as telemetry's
        ``config_hash``)."""
        blob = json.dumps(self.structural_dict(), sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    def replica_seeds(self) -> list[int]:
        """The per-replica seed vector a replicated run sweeps: seed,
        seed+1, ..., seed+replicas−1 (length 1 for single runs)."""
        return [self.seed + r for r in range(self.replicas)]

    def resolved_sampling_impl(self, platform: str, n_local: int) -> str:
        """Resolve sampling_impl='auto' from measured data.

        On the real chip (docs/perf/breakdown.json §sampling) the dense
        weighted-gradient form wins decisively when shards are small — the
        latency-bound regime where the gather form's selection and row
        gathers dominate the iteration (read pre-ledger against its old
        form, a ``top_k`` and two gathers a draw; since ISSUE 40 it selects
        by a counted threshold and gathers once, and the rule's lower side
        has not been read again: PERF.md section 7 row 5):
        2.5x at N=256 (L=49), 10x at N=1024 (L=13) — while the gather path
        wins for large shards (N=25, L=500: 1.8x) where the full-shard pass
        costs real FLOPs; the two tie within chip noise for L ~ 100-250.
        Rule: dense on accelerators when the padded shard length is <= 64
        rows; gather otherwise (and always on CPU, where the extra FLOPs are
        not latency-hidden).
        """
        if self.sampling_impl != "auto":
            return self.sampling_impl
        if platform != "cpu" and n_local <= 64:
            return "dense"
        return "gather"

    def resolved_robust_impl(self, k_max: int) -> str:
        """Resolve robust_impl='auto' from the topology's maximum degree.

        The gather form does (k_max+1)/N of the dense sort work but adds
        the [N, k_max, d] model gather; measured
        (docs/perf/robust_scale.json) it wins at every k_max < N−1 —
        ~70x on an N=256 ring, and still ~1.7x at N=64 Erdős–Rényi
        k_max=40 — and only stops paying at k_max = N−1 (fully
        connected), where it sorts the same closed axis as dense plus the
        gather and the two measure a tie. Rule: gather iff k_max+1 < N
        (dense keeps the fully-connected case: nothing to gain, and the
        [N, k_max+1, d] gather buffer matches dense's memory anyway). An
        explicit robust_impl is never overridden.
        """
        if self.robust_impl != "auto":
            return self.robust_impl
        return "dense" if k_max + 1 >= self.n_workers else "gather"

    def resolved_scan_unroll(self, platform: str) -> int:
        if self.scan_unroll > 0:
            return self.scan_unroll
        return 1 if platform == "cpu" else 8

    def resolved_lr_schedule(self) -> str:
        if self.lr_schedule != "auto":
            return self.lr_schedule
        # SGD-family rules (plain stochastic gossip descent, incl. SGP's
        # gradient-push) take the reference's decaying step; the
        # bias-corrected / dual methods run their constant-step regimes.
        return (
            "sqrt_decay"
            if self.algorithm in ("centralized", "dsgd", "push_sum")
            else "constant"
        )

    # The regularizer actually used for the gradient/objective: the reference
    # uses lambda for logistic and mu (== lambda by default) for quadratic
    # (reference worker.py:36-42, main.py:20-21).
    @property
    def reg_param(self) -> float:
        # Convex problems (logistic, huber) use lambda; the strongly convex
        # quadratic uses mu (== lambda by default), mirroring the reference.
        return (
            self.strong_convexity_mu
            if self.problem_type == "quadratic"
            else self.l2_regularization_lambda
        )

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "ExperimentConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    @classmethod
    def construction_error(cls, fields: dict[str, Any]) -> "str | None":
        """The validation message constructing these fields would raise, or
        None when they build a valid config.

        The scenario engine's ground truth (docs/SCENARIOS.md): the
        declarative validity table in ``scenarios/validity.py`` mirrors
        ``__post_init__``'s composition rules for structured querying, and
        its agreement with THIS function — verdict for verdict over every
        sampled cell of the composition matrix — is what keeps the two
        from silently drifting apart.
        """
        try:
            cls(**fields)
        except (TypeError, ValueError) as e:
            return str(e)
        return None

    def replace(self, **kwargs: Any) -> "ExperimentConfig":
        return dataclasses.replace(self, **kwargs)
