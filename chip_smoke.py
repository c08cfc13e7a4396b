"""Chip smoke: the main path, once, on the TPU, through the normal entry points.

    python chip_smoke.py

The quickest proof that the system still starts on the chip. It is not a
benchmark: every rate it prints is a smoke observation from one run. One
process, no children; any failed check or exception is a non-zero exit, and
without a TPU it exits before doing any work. Segments:

1. Headline GLM (bench.py's configuration at T=30,000): logistic D-SGD on a
   ring, N=256, d=81, b=16, f32, ``eval_every=1`` — ``Simulator`` runs data
   generation, the sklearn oracle, the scan and the report. Must cross
   ε ≤ 0.08 inside the horizon with finite, bounded consensus.
2. Full-width softmax (examples/bench_compute_bound.py's shape, the only
   supported model that loads the MXU): N=8, d=4096(+1), K=512, b=2048, in
   bf16/default and f32/highest, a few tens of steps on a seeded random
   dataset. Loss finite, below the zero model's ln K, and not rising.
3. Reference agreement on a small input: full-batch logistic and softmax
   runs against the numpy reference-semantics backend.
4. Where four chips are visible: the ``worker_mesh=4`` ring at N=100,000
   against the same config unsharded, with the state spread over the four
   and the shards sent block by block, each to its own chip; and, first of
   all, while the process has put nothing else on a device, a 1.1 GB stack
   placed over the four with no chip ever holding more than its quarter
   (``parallel.mesh.place_shards``: what a deployment past one chip's memory
   rests on). And last of all (ISSUE 35) one round of the mesh's two mixing
   forms on one ring of 2^18 rows a chip: the shift form, which a ring's
   neighbor table takes, against the gather form to a few units of the rows'
   scale, and no chip holding a neighbor table after the shift rounds (the
   gather form's executable keeps 0.54 GB of it on every chip).

5. The carried forward product at a size the chip notices (N = 65,536 workers
   of 53 rows, d = 81: a 1.1 GB stack): the run as the chip takes it
   (``forward`` = ``fused`` since ISSUE 41: one visit of the shards by
   ``ops.pallas_kernels.glm_shard_visit`` leaves the objective and the next
   gradient) and the same run with the margins X·x carried (ISSUE 31, the
   visit's rule switched off) against the same run recomputing them, the
   objective and consensus rows within the benchmark's GLM limits, the peak
   of device memory within 0.1 GB, and the fused run split at an eval
   boundary bitwise the unsplit run. What the CPU cannot see: where the
   carry's buffers live, and whether the chip's two compilations of the
   visit (in the loop, in front of it) round alike.

6. The same stack under the README's faults (30% of the links down, 10% of
   the workers out, every round; ISSUE 32), right after segment 5 so that the
   peak counter prices what the fault layer adds: the bits drawn inside the
   step (``fault_form`` ``drawn``), the ring's neighbours read by shifts
   (``fault_mixing`` ``shift``, ISSUE 33: no table, ``fault_bytes`` 0), the
   run split at an eval boundary bitwise the unsplit run, ``live_edge_share``
   within 0.567 ± 0.005, and the device's peak no more than the fault-free
   run's plus the ``fault_bytes`` the root states. Then one round of the
   shift and of the gather form on the same (seed, t): the same ``live`` bits
   and the mixed rows within 1e-6. What the CPU cannot see: what an argument
   takes in the device's tiles, whether the executable holds a table of its
   own, and whether the chip's two programs of one round agree.

7. The same stack, fault-free, once more under the profiler (ISSUE 34): the
   device's time by phase, joined two ways through the compiled program's
   own table from instruction to scope (``observability/device_scopes.py``).
   The exact join (every op event by instruction name) sums with its
   ``None`` to the op line's summed leaf durations and finds at least 99% of
   the busy time among the table's instructions; the benchmark's ten-row
   join (``benchmark/scope_reduce.py``) is at or under it scope by scope and
   within 3% of it in total; the root's ``temp_bytes`` is
   ``memory_analysis().temp_size_in_bytes``. What the CPU cannot see: whether
   a trace event's name is the compiled text's instruction.

8. One round of the gather mixing at the drawn-graph cell's size (ISSUE 36):
   the connected Erdős–Rényi graph of 2^18 workers at mean degree 12 that
   topology seed 7 draws, through ``make_mixing_op``'s tables (since PR 38
   the live slots' chunk list: 209 chunks of 16,384 rows) handed to a
   jitted round as ARGUMENTS, against the benchmark reference's
   edge-list form (``benchmark/reference/dsgd_er.py``: its own draw of the
   graph, edge for edge the table's, and two scatter-adds) within
   ``GATHER_ROUND_ULPS`` units of the rows' scale; and the device's bytes
   after the rounds: the rows, the result and the tables once, so no second
   copy of a table and none among the executable's constants. What the CPU
   cannot see: what a ``[209, 16384]`` table takes in the device's tiles,
   and whether the executable keeps one.

9. The tracker cell's deployment at its own size (ISSUE 39): gradient
   tracking on least squares over a 128 x 128 torus, 800 rows a worker, built
   from the benchmark's own files; 100 iterations with the state returned.
   The root says two gossip rounds, the ``gather`` sampler and the
   ``stencil`` mixing on a ``128x128`` grid; the tracker's mean is the last
   gradients' mean within ``TRACKER_MEAN_UNITS`` units of the gradients'
   scale; one round of the program's grid stencil on a random ``[16384, 81]``
   stack against the reference's (``benchmark/reference/gt_torus.py``)
   within ``STENCIL_ROUND_ULPS`` units; and one iteration's gathered batch is
   the rows the reference's ``batch_weights`` weighs. What the CPU cannot
   see: ``sampling_impl`` auto resolving to ``gather`` above 64 rows, and
   whether the chip's ``top_k`` and the reference's pick the same rows.

10. The shard visit at the GLM cells' shapes cut to 2^14 workers (ISSUE 41):
   the kernel's gradient and loss sums against XLA's two passes within 1e-6
   of their scale, and the kernel without its objective half
   (``glm_shard_gradient``, ISSUE 51) against the visit's gradient; a run
   whose root says ``forward`` = ``fused`` and whose
   compiled scan holds the kernel's call and no ``copy`` or ``transpose`` of
   the shard stack (the kernel's ``[d, L, N]`` view is a bitcast of what the
   runtime keeps); the same run with four gradient steps a round, whose root
   says ``local_forward`` = ``visited`` and ``shard_reads`` 4, against that
   round with the visit's rule switched off (``carried``, ``recomputed``, 8)
   within the federated cell's limits; where four chips are visible the run under
   ``worker_mesh=4``, ``fused`` too, within ``MESH_ATOL`` of the unsharded
   one. What the CPU cannot see: Mosaic's compile, the runtime's layout of
   the stack, and whether GSPMD leaves the ``shard_map`` round the call alone.

11. The Byzantine cell's deployment cut to 2^16 workers (ISSUE 43): the
   README's sign-flipping ring (6 attackers in 64, placed within the
   per-neighbourhood budget, screened by the trimmed mean at b = 1), built
   from the benchmark's own files; 100 iterations against the benchmark's
   plain reference (``benchmark/reference/dsgd_ring_byzantine.py``) by the
   cell's own limits. The root says ``forward`` = ``fused`` (the shard visit
   with the HONEST mean as the eval's x-bar), ``robust_impl`` = ``gather``,
   ``screen_order`` = ``network:3`` (ISSUE 44: three slot planes ordered by
   compare-and-select, no sort in the compiled scan), ``screen_fetch`` =
   ``shift`` (ISSUE 45: the two received planes are two shifts of the
   transmitted stack, no gather under ``dopt.robust``), ``budget_max`` = 1;
   the device's peak over the bytes in use at the segment's start; and the
   compiled scan's instructions under ``dopt.robust``, the largest with
   their bytes. What the CPU cannot see: the visit carrying an adversary,
   the shifted planes' layout and what the unrolled trip keeps alive.

Every ``*_impl`` selector and ``scan_unroll`` stay at their defaults, so
the choices ``auto`` makes on the chip are the ones exercised. The last
line of stdout is one JSON object naming the device as JAX reports it.
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import sys

import numpy as np

# f32 (device) against f64 (numpy reference) over tens of full-batch steps
# on models of order 0.1–1: accumulated rounding stays near 1e-6; a bf16
# data path or a wrong update is orders of magnitude above this.
REFERENCE_ATOL = 1e-4
# worker_mesh=4 and worker_mesh=0 are different programs (halo ppermute vs
# in-place gather): same arithmetic, summation order free to differ by an
# f32 ulp per step. 100 steps on models of order 0.1 stay well inside this.
MESH_ATOL = 1e-5


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: FAILED — {what}")


def _say(segment: str, device: dict, hist, **extra) -> None:
    fields = {
        "platform": device["platform"],
        "device_kind": device["kind"],
        "devices_visible": device["count"],
        "mesh_devices": hist.mesh_devices,
        "compile_s": round(hist.compile_seconds, 2),
        "steps_per_s_smoke_observation": round(hist.iters_per_second, 1),
        **extra,
    }
    print(f"[chip_smoke] {segment}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def glm_segment(device: dict, *, n_workers: int = 256,
                n_iterations: int = 30_000) -> None:
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.simulator import Simulator

    cfg = ExperimentConfig(
        problem_type="logistic", algorithm="dsgd", topology="ring",
        n_workers=n_workers, n_iterations=n_iterations,
    )
    sim = Simulator(cfg)
    rec = sim.run_one(verbose=False)
    sim.report_numerical_results()
    hist = rec.result.history
    crossed = rec.summary.iterations_to_threshold
    _say("glm", device, hist, crossed_eps_at=crossed,
         final_gap=f"{hist.objective[-1]:.4f}",
         consensus=f"{hist.consensus_error[-1]:.3e}")
    _check(hist.objective.shape == (n_iterations,), "one gap row per iteration")
    _check(bool(np.all(np.isfinite(hist.objective))), "suboptimality gap finite")
    _check(0 < crossed <= n_iterations,
           f"gap crosses eps={cfg.suboptimality_threshold} within T={n_iterations}")
    cons = hist.consensus_error
    _check(bool(np.all(np.isfinite(cons))) and cons[-1] < 1.0,
           "consensus error finite and bounded")
    _check(rec.result.final_models.shape == (n_workers, sim.dataset.n_features),
           "final model stack is [N, d]")


def softmax_segment(device: dict, *, n_workers: int = 8, d_feat: int = 4096,
                    n_classes: int = 512, batch: int = 2048,
                    n_iterations: int = 40, eval_every: int = 10) -> None:
    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.utils.data import random_softmax_dataset

    ds = random_softmax_dataset(n_workers, batch, d_feat, n_classes)
    for dtype, precision in (("bfloat16", "default"), ("float32", "highest")):
        cfg = ExperimentConfig(
            problem_type="softmax", n_classes=n_classes, algorithm="dsgd",
            topology="ring", n_workers=n_workers, local_batch_size=batch,
            n_samples=n_workers * batch, n_features=d_feat,
            n_informative_features=min(64, d_feat),
            n_iterations=n_iterations, eval_every=eval_every,
            dtype=dtype, matmul_precision=precision,
        )
        # f* = 0, so the recorded "gap" is the full-dataset loss itself.
        result = jax_backend.run(cfg, ds, 0.0)
        loss = result.history.objective
        _say(f"softmax {dtype}/{precision}", device, result.history,
             loss=">".join(f"{v:.4f}" for v in loss))
        _check(loss.shape == (n_iterations // eval_every,), "one loss row per eval")
        _check(bool(np.all(np.isfinite(loss))), f"{dtype} loss finite")
        _check(loss[0] < math.log(n_classes),
               f"{dtype} loss below the zero model's ln K after {eval_every} steps")
        _check(bool(np.all(np.diff(loss) <= 0.0)), f"{dtype} loss not rising")
        _check(
            result.final_models.shape == (n_workers, (d_feat + 1) * n_classes)
            and bool(np.all(np.isfinite(result.final_models))),
            f"{dtype} final models finite, [N, (d+1)K]",
        )


def reference_segment(device: dict) -> None:
    """Small full-batch runs (no sampling, so deterministic on every
    backend) against the numpy reference-semantics backend."""
    from distributed_optimization_tpu.backends import jax_backend, numpy_backend
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
    from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

    for problem, extra in (("logistic", {}), ("softmax", {"n_classes": 4})):
        cfg = ExperimentConfig(
            problem_type=problem, algorithm="dsgd", topology="ring",
            n_workers=8, n_samples=256, n_features=12,
            n_informative_features=8, local_batch_size=32,
            n_iterations=40, eval_every=10, **extra,
        )
        ds = generate_synthetic_dataset(cfg)
        _, f_opt = compute_reference_optimum(
            ds, cfg.reg_param, n_classes=cfg.n_classes
        )
        got = jax_backend.run(cfg, ds, f_opt)
        want = numpy_backend.run(cfg, ds, f_opt)
        err = float(np.max(np.abs(got.final_models - want.final_models)))
        gap_err = float(np.max(np.abs(got.history.objective - want.history.objective)))
        _say(f"reference {problem}", device, got.history,
             max_model_err=f"{err:.2e}", max_gap_err=f"{gap_err:.2e}")
        _check(err <= REFERENCE_ATOL and gap_err <= REFERENCE_ATOL,
               f"{problem} agrees with the numpy reference at {REFERENCE_ATOL}")


def placement_segment(device: dict, *, n_workers: int = 65_536,
                      rows: int = 53, d: int = 81) -> None:
    """``place_shards`` under a mesh of four, before anything else has been
    on a device, so that ``peak_bytes_in_use`` is this segment's own: each
    chip's block goes to that chip, as it is (``direct``) and in flat pieces,
    and the first chip never holds the whole stack, which is what
    ``shard_over_workers(mesh, jnp.asarray(X))`` did."""
    import jax

    from distributed_optimization_tpu.parallel.mesh import (
        make_sized_worker_mesh,
        place_shards,
    )

    mesh = make_sized_worker_mesh(4)
    X = np.arange(n_workers * rows * d, dtype=np.float32).reshape(
        n_workers, rows, d)
    # One chip's quarter as the host holds it; the runtime lays [n, 53, 81]
    # out with n minor, 1.06 of that (297,298,432 bytes here: PR 30).
    quarter = X.nbytes // 4
    for kw, label, room in (
        ({}, "mesh4:direct", 1.25),
        # pieces of 32 MiB, two in flight beside the block: read 1.19
        (dict(min_tiled_bytes=0, block_bytes=32 << 20),
         "mesh4:flat:68688x1024/8", 1.5),
    ):
        got, how, _ = place_shards(mesh, X, **kw)
        jax.block_until_ready(got)
        stats = [dev.memory_stats() for dev in mesh.devices.flat]
        peaks = [int(st["peak_bytes_in_use"]) for st in stats]
        print(f"[chip_smoke] placement: {how} stack_bytes={X.nbytes} "
              f"peak_bytes_by_device={peaks}", flush=True)
        _check(how == label, f"a {X.shape} stack over four chips goes up {label}")
        _check(all(s.device == dev and s.index[0].start == p * (n_workers // 4)
                   for p, (s, dev) in enumerate(zip(
                       sorted(got.addressable_shards,
                              key=lambda s: s.index[0].start),
                       mesh.devices.flat))),
               "each chip holds its own block of workers")
        _check(max(peaks) <= room * quarter,
               f"no chip ever held more than {room} of its quarter of the "
               f"stack ({quarter} bytes): the first chip is no staging post")
        _check(np.asarray(got).tobytes() == X.tobytes(),
               "the placed stack is the host array, bit for bit")
        del got


HALO_FORMS_ULPS = 8  # of the rows' scale: two programs of one arithmetic
HALO_SHIFT_ROOM = 300_000_000  # bytes a chip may hold after a shift round


def halo_forms_segment(device: dict, *, n_workers: int = 1 << 20,
                       d: int = 81) -> None:
    """One round of the worker mesh's two mixing forms on the same ring at
    the four-chip cell's size, 2^18 rows a chip: the shift form (what
    ``make_halo_mixing_op`` takes on a ring's table) against the gather
    form, and what each leaves on a chip after a call. The gather form's
    executable holds its per-shard neighbor table, 0.54 GB a chip in (8,
    128) tiles; the shift form has no table, and the CPU cannot see either
    (ISSUE 35). Bytes in use are read against the segment's own start, and
    the segment runs last: segments 5 and 6 read chip 0's peak as theirs."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_optimization_tpu.parallel import build_topology
    from distributed_optimization_tpu.parallel.collectives import (
        _make_halo_gather_mixing_op,
        make_halo_mixing_op,
    )
    from distributed_optimization_tpu.parallel.mesh import (
        WORKER_AXIS,
        make_sized_worker_mesh,
    )

    mesh = make_sized_worker_mesh(4)

    def in_use():
        return [int(dev.memory_stats()["bytes_in_use"])
                for dev in mesh.devices.flat]

    before = in_use()
    topo = build_topology("ring", n_workers, impl="neighbor")
    rows = NamedSharding(mesh, P(WORKER_AXIS, None))
    x = jax.jit(lambda key: jax.random.normal(key, (n_workers, d), jnp.float32),
                out_shardings=rows)(jax.random.key(35))
    shift = make_halo_mixing_op(topo, mesh, dtype=jnp.float32)
    _check(shift.impl == "halo_shift", "a ring's table is mixed by shifts")
    got, held = {}, {}
    for op in (shift, _make_halo_gather_mixing_op(topo, mesh, dtype=jnp.float32)):
        for form in ("apply", "neighbor_sum"):
            got[op.impl, form] = np.asarray(
                jax.block_until_ready(jax.jit(getattr(op, form))(x)))
        held[op.impl] = [b - a for a, b in zip(before, in_use())]
        print(f"[chip_smoke] halo forms: {op.impl} bytes in use over the "
              f"segment's start, by device: {held[op.impl]}", flush=True)
        if op is shift:
            _check(max(held[op.impl]) <= HALO_SHIFT_ROOM,
                   f"after its shift rounds no chip holds {HALO_SHIFT_ROOM} "
                   "bytes more: the rows and no neighbor table")
    for form in ("apply", "neighbor_sum"):
        want = got["halo_gather", form]
        unit = float(np.finfo(np.float32).eps) * float(np.max(np.abs(want)))
        gap = float(np.max(np.abs(got["halo_shift", form] - want))) / unit
        print(f"[chip_smoke] halo forms: {form} shift against gather, worst "
              f"gap {gap:.2f} units of the rows' scale", flush=True)
        _check(gap <= HALO_FORMS_ULPS,
               f"the two forms' {form} agree within {HALO_FORMS_ULPS} units "
               "of the rows' scale")
    print(f"[chip_smoke] halo forms: the gather form keeps "
          f"{min(held['halo_gather']) - max(held['halo_shift'])} bytes more "
          "on every chip after its calls", flush=True)


# The gather round against the edge-list form: the one sums a row's live
# slots in the table's order onto w_self·x, the other adds w_e (x_j − x_i)
# edge by edge onto x; read 1.9 units on the chip (PRs 36 and 38: the live
# list's sums are the padded table's to the bit).
GATHER_ROUND_ULPS = 16
# What the device may hold after the rounds beside the rows, the result and
# the tables once: under half of one table (14.2 MB in the device's tiles
# since PR 38; under 1 MB read).
GATHER_ROUND_ROOM = 7_000_000


def gather_round_segment(device: dict, *, n_workers: int = 1 << 18,
                         d: int = 81, mean_degree: int = 12,
                         topology_seed: int = 7) -> None:
    """One round of the program's gather mixing on the drawn-graph cell's
    graph against the reference's edge-list form, and what the rounds leave
    on the device (ISSUE 36). Bytes in use are read against the segment's
    own start."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import dsgd_er
    from distributed_optimization_tpu.ops.mixing import make_mixing_op
    from distributed_optimization_tpu.parallel.topology import cached_topology

    dev = jax.devices()[0]

    def in_use():
        return int(dev.memory_stats()["bytes_in_use"])

    before = in_use()
    p = mean_degree / n_workers
    topo, _ = cached_topology(
        "erdos_renyi", n_workers, erdos_renyi_p=p, seed=topology_seed,
        impl="neighbor", sampler="sparse")
    op = make_mixing_op(topo, impl="gather")
    table_bytes = sum(
        leaf.on_device_size_in_bytes() for leaf in jax.tree.leaves(op.tables))
    x = jax.block_until_ready(jax.random.normal(
        jax.random.key(36), (n_workers, d), jnp.float32))
    # As the scan is handed them: arguments, the operators bound inside.
    mixed = {
        form: jax.block_until_ready(jax.jit(
            lambda x, tb, form=form: getattr(op.bind(tb), form)(x)
        )(x, op.tables))
        for form in ("apply", "neighbor_sum")
    }
    rows_bytes = x.on_device_size_in_bytes()
    held = in_use() - before
    print(f"[chip_smoke] gather round: k_max={topo.nbr_idx.shape[1]} "
          f"edges={int(topo.degrees.sum()) // 2} table_bytes={table_bytes} "
          f"rows_bytes={rows_bytes} held over the segment's start={held}",
          flush=True)
    _check(held <= table_bytes + 3 * rows_bytes + GATHER_ROUND_ROOM,
           "after its rounds the device holds the rows, the two results and "
           "the tables once: no table twice, none among the constants")
    src, dst, tries = dsgd_er.draw_edges(n_workers, p, topology_seed)
    live = topo.nbr_mask
    upper = live & (topo.nbr_idx > np.arange(n_workers)[:, None])
    _check(np.array_equal(np.nonzero(upper)[0], src)
           and np.array_equal(topo.nbr_idx[upper], dst),
           "the reference draws the table's graph edge for edge")
    graph = dsgd_er.edge_blocks(
        src, dst, dsgd_er.edge_weights(src, dst, n_workers))
    from scipy import sparse

    ends = sparse.coo_matrix(
        (np.ones(src.size), (src, dst)), shape=(n_workers, n_workers)).tocsr()
    want = {
        # the reference's own round, on the chip, float32
        "apply": np.asarray(jax.jit(dsgd_er.mix)(x, *graph)),
        # the adjacency's product, on the host, float64
        "neighbor_sum": (ends + ends.T) @ np.asarray(x, np.float64),
    }
    for form, rows in want.items():
        unit = float(np.finfo(np.float32).eps) * float(np.max(np.abs(rows)))
        gap = float(np.max(np.abs(np.asarray(mixed[form]) - rows))) / unit
        print(f"[chip_smoke] gather round: {form} against the edge list "
              f"({tries} tr{'y' if tries == 1 else 'ies'}), worst gap "
              f"{gap:.2f} units of the rows' scale", flush=True)
        _check(gap <= GATHER_ROUND_ULPS,
               f"the gather's {form} is the edge list's within "
               f"{GATHER_ROUND_ULPS} units of the rows' scale")


# mean_i y and mean_i g_prev after 100 iterations, in units of float32's
# epsilon times the largest last gradient: each round adds a rounding of the
# entries, the mean over 16,384 workers keeps a small share of it (the first
# readings: 48.66 on one v5e, 37.59 on the sandbox's CPU; PERF.md section 6).
TRACKER_MEAN_UNITS = 256.0
# Five additions and a product by 1/5 a row, in one order or the other.
STENCIL_ROUND_ULPS = 4.0


def tracker_segment(device: dict, *, iterations: int = 100, seed: int = 39) -> None:
    """The tracker cell's experiment at its own size, from the benchmark's
    own files, for ``iterations`` iterations: what the root says, the
    tracking invariant, one stencil round and one gathered batch against the
    reference's (ISSUE 39)."""
    import jax
    import jax.numpy as jnp

    from benchmark import datasets, program
    from benchmark import run as harness
    from benchmark.reference import gt_torus
    from benchmark.reference.dsgd_ring import batch_weights
    from distributed_optimization_tpu.ops.mixing import make_mixing_op
    from distributed_optimization_tpu.ops.sampling import sample_worker_batches
    from distributed_optimization_tpu.parallel.topology import cached_topology

    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    _, config, traffic = harness.load_cell(
        bench, "quad81_gt_torus16k.track1k", rehearse=False)
    exp = config["experiment"]
    n, b = int(exp["n_workers"]), int(exp["local_batch_size"])
    side = math.isqrt(n)
    X, y, L = datasets.make(config, seed)
    cfg, ds = program.build(
        config, dict(traffic, n_iterations=iterations), X, y, L,
        program.seed_for(seed))
    result, root, _ = _rooted_run(
        f"gradient tracking on a {side}x{side} torus, {L} rows a worker",
        device, cfg, ds,
        ("algorithm", "gossip_rounds", "state_leaves", "state_bytes",
         "sampling", "select", "batch_gathers", "batch_rows", "mixing",
         "grid_shape"),
        return_state=True)
    _check((root["gossip_rounds"], root["sampling"], root["mixing"],
            root["grid_shape"]) == (2, "gather", "stencil", f"{side}x{side}"),
           "the root says two rounds, the gather sampler, the grid's stencil")
    _check((root["select"], root["batch_gathers"]) == ("threshold:16", 1),
           "a batch is selected by a counted threshold and fetched by one gather")
    hist = result.history
    _check(bool(np.all(np.isfinite(hist.objective)))
           and hist.objective[-1] < hist.objective[0]
           and hist.consensus_error[0] == 0.0,
           "the objective descends from f(0), finite; the first round is "
           "pure gossip from zero")
    state = result.final_state
    mean_y, mean_g = state["y"].mean(axis=0), state["g_prev"].mean(axis=0)
    eps = float(np.finfo(np.float32).eps)
    gap = float(np.max(np.abs(mean_y - mean_g))) / (eps * float(np.max(np.abs(mean_g))))
    print(f"[chip_smoke] tracker: |mean y - mean g_prev| after {iterations} "
          f"iterations {gap:.2f} units of the mean's magnitude "
          f"({float(np.max(np.abs(mean_g))):.4g}; the largest last gradient "
          f"{float(np.max(np.abs(state['g_prev']))):.4g})", flush=True)
    _check(gap <= TRACKER_MEAN_UNITS,
           f"the tracker's mean is the gradients' within {TRACKER_MEAN_UNITS} "
           f"units of its magnitude")

    topo, _ = cached_topology("grid", n, impl=cfg.resolved_topology_impl())
    op = make_mixing_op(topo, impl="auto")
    rows = jax.random.normal(jax.random.key(39), (n, X.shape[1]), jnp.float32)
    mixed = np.asarray(jax.jit(op.apply)(rows))
    want = np.asarray(jax.jit(lambda u: gt_torus.torus_mix(u, side))(rows))
    gap = float(np.max(np.abs(mixed - want))) / (eps * float(np.max(np.abs(want))))
    print(f"[chip_smoke] tracker: one {op.impl} round against the reference's, "
          f"worst gap {gap:.2f} units of the rows' scale", flush=True)
    _check(op.impl == "stencil" and gap <= STENCIL_ROUND_ULPS,
           f"the grid stencil is the reference's within {STENCIL_ROUND_ULPS} units")

    # The row a batch entry came from rides in ``y``'s place.
    t = jnp.asarray(7, jnp.int32)
    row_ids = jnp.broadcast_to(jnp.arange(L, dtype=jnp.float32), (n, L))
    Xd = jnp.asarray(X.reshape(n, L, -1))
    Xb, ids, w = jax.jit(
        lambda Xd, row_ids: sample_worker_batches(
            jax.random.fold_in(jax.random.key(cfg.seed), 0), t, Xd, row_ids,
            jnp.full((n,), L, jnp.int32), b)
    )(Xd, row_ids)
    ids = np.asarray(ids).astype(np.int64)
    want = np.asarray(jax.jit(
        lambda: batch_weights(cfg.seed, t, n, L, b))())
    hit = np.take_along_axis(want, ids, axis=1)
    _check(bool(np.all(hit == np.float32(1.0 / b)))
           and bool(np.all(np.sum(want > 0, axis=1) == b))
           and bool(np.all(np.diff(np.sort(ids, axis=1), axis=1) > 0)),
           "the gathered batch is the b distinct rows the reference weighs")
    some = np.arange(0, n, n // 64)
    _check(np.array_equal(np.asarray(Xb)[some], X.reshape(n, L, -1)[some[:, None], ids[some]])
           and bool(np.all(np.asarray(w) == np.float32(1.0 / b))),
           "the gathered features are those rows', the weights 1/b")
    print(f"[chip_smoke] tracker: iteration 7's batch is the reference's rows "
          f"({n} workers x {b} of {L})", flush=True)


def byzantine_segment(device: dict, *, n_workers: int = 1 << 16,
                      iterations: int = 100, seed: int = 43) -> None:
    """The Byzantine cell's experiment cut to ``n_workers``, from the
    benchmark's own files, against its reference over ``iterations``
    iterations: what the root says, the rows by the cell's limits, the
    peak, and what the compiled scan holds under ``dopt.robust`` (ISSUE 43)."""
    import jax

    from benchmark import compare, datasets, program
    from benchmark import run as harness
    from benchmark.reference import dsgd_ring_byzantine
    from distributed_optimization_tpu.observability import device_scopes

    cell = "glm81_ring262k_signflip_tm1.screen1k"
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    _, config, traffic = harness.load_cell(bench, cell, rehearse=False)
    exp = dict(config["experiment"])
    share = exp["n_byzantine"] / exp["n_workers"]
    exp.update(n_workers=n_workers, n_byzantine=int(share * n_workers))
    config = dict(config, experiment=exp)
    traffic = dict(traffic, n_iterations=iterations, check_iterations=iterations)
    X, y, L = datasets.make(config, seed)
    cfg, ds = program.build(config, traffic, X, y, L, program.seed_for(seed))
    start = int(jax.devices()[0].memory_stats()["bytes_in_use"])
    result, root, peak = _rooted_run(
        f"sign-flip on a ring of {n_workers}, trimmed mean", device, cfg, ds,
        ("attack", "byzantine_placement", "budget_max", "aggregation",
         "robust_impl", "screen_order", "screen_fetch", "screened_rows",
         "robust_bytes", "forward", "mixing", "temp_bytes"))
    _check((root["forward"], root["robust_impl"], root["screen_order"],
            root["screen_fetch"], root["budget_max"])
           == ("fused", "gather", "network:3", "shift", 1),
           "the root says the shard visit, the gather form, three slots "
           "ordered by the network, the received rows by shifts, one "
           "attacker at most beside an honest worker")
    _check(root["attack"] == f"sign_flip:{exp['n_byzantine']}/{n_workers}"
           and root["byzantine_placement"] == "within_budget"
           and root["aggregation"] == "trimmed_mean:b=1"
           and root["screened_rows"] == 3 * n_workers,
           "the root says who lied and how it was screened")
    ref = dsgd_ring_byzantine.run(config, traffic, X, y, program.seed_for(seed))
    nums = compare.numbers(harness.produced_of(result), ref)
    ok = compare.judge(nums, config["limits"]["screen1k"],
                       lambda line: print("[chip_smoke] byzantine:", line, flush=True))
    _check(ok, f"the program's {iterations} rows are the reference's by the cell's limits")
    hist = result.history
    _check(bool(np.all(np.isfinite(result.final_models)))
           and hist.objective[-1] < hist.objective[0],
           "every model finite, the objective at the honest mean descending")
    print(f"[chip_smoke] byzantine: peak {peak - start} B over the "
          f"{start} B in use at the segment's start (shards "
          f"{X.nbytes} B, the scan's temporaries {int(root['temp_bytes'])} B)",
          flush=True)
    table = device_scopes.table_for(root["program"])["rows"]
    _check(not [r for r in table if r["head"].startswith("%sort")],
           "the compiled scan holds no sort")
    rows = [r for r in table if r["scope"] == "robust"]
    _check(bool(rows), "the compiled scan carries dopt.robust")
    _check(not [r for r in table if "robust" in (r["scope"], *r["also"])
                and "gather" in r["head"].split("(")[0]],
           "no gather under dopt.robust: a ring's rows come by shifts")
    sized = sorted(((device_scopes._shape_bytes(r["head"]), r["head"]) for r in rows),
                   reverse=True)
    print(f"[chip_smoke] byzantine: {len(rows)} instructions under dopt.robust; "
          "the largest: " + "; ".join(f"{h[:64]} {b} B" for b, h in sized[:6]),
          flush=True)


# The benchmark's GLM limits (benchmark/configs/glm81_ring262k.json): worst
# relative gap of the objective and of the consensus error over the rows.
CARRY_LIMITS = {"objective": 1e-6, "consensus_error": 1e-5}
CARRY_PEAK_ROOM = 100_000_000  # bytes the carried program may hold more
# The federated cell's (benchmark/configs/glm81_ring262k_local4_part50.json):
# four gradients a round round four times as often.
LOCAL_LIMITS = {"objective": 2e-6, "consensus_error": 1e-5}


def _glm_ring(seed: int, n_workers: int, rows: int, d: int, n_iterations: int,
              **config):
    """``(cfg, dataset)``: logistic D-SGD on a ring of ``n_workers`` shards of
    ``rows`` rows, d + 1 features (eight informative, a bias column), f32."""
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.utils.data import HostDataset

    rng = np.random.default_rng(seed)
    n = n_workers * rows
    y = np.where(rng.random(n) < 0.5, -1.0, 1.0).astype(np.float32)
    X = rng.standard_normal((n, d + 1), dtype=np.float32)
    X[:, :8] += 0.5 * y[:, None]
    X[:, -1] = 1.0
    ds = HostDataset(
        X_full=X, y_full=y, problem_type="logistic",
        shard_indices=[np.arange(i * rows, (i + 1) * rows) for i in range(n_workers)],
    )
    cfg = ExperimentConfig(
        problem_type="logistic", algorithm="dsgd", topology="ring",
        n_workers=n_workers, n_samples=n, n_features=d,
        n_informative_features=8, n_iterations=n_iterations, **config,
    )
    return cfg, ds


@contextlib.contextmanager
def _rule_off(name):
    """A private rule of ``jax_backend`` (``_forward_is_carried``,
    ``_visit_is_fused``) answering no inside the block; None: nothing."""
    from distributed_optimization_tpu.backends import jax_backend

    if name is None:
        yield
        return
    decide = getattr(jax_backend, name)
    setattr(jax_backend, name, lambda *a, **k: False)
    try:
        yield
    finally:
        setattr(jax_backend, name, decide)


def _check_rows(segment: str, what: str, got, want, limits: dict) -> None:
    """The objective and consensus rows of ``got`` within ``limits`` (worst
    relative gap) of ``want``'s."""
    for key, limit in limits.items():
        a, b = getattr(got.history, key), getattr(want.history, key)
        rel = float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))
        print(f"[chip_smoke] {segment}: {key} {what}, worst relative gap "
              f"{rel:.3e} (limit {limit})", flush=True)
        _check(a.shape == b.shape and rel <= limit,
               f"{key} rows {what} within {limit}")


def forward_carry_segment(device: dict, *, n_workers: int = 65_536,
                          rows: int = 53, d: int = 80,
                          n_iterations: int = 200):
    """Runs first on its chip, recomputed before carried, so that the peak
    counter (which only rises) prices what the carry adds. Returns its
    experiment and the peak it leaves, for ``faults_segment``."""
    cfg, ds = _glm_ring(31, n_workers, rows, d, n_iterations)

    def run(form, switched_off=None, how="", **kw):
        """One run that must say ``forward`` = ``form``, a private rule of
        ``jax_backend`` switched off for it."""
        with _rule_off(switched_off):
            result, root, peak = _rooted_run(
                f"forward {form}{how}", device, cfg, ds, ("forward",), **kw)
        _check(root["forward"] == form, f"the run's root says forward={form}")
        return result, peak

    want, peak_recomputed = run("recomputed", "_forward_is_carried")
    peaks = {}
    for form, rule in (("carried", "_visit_is_fused"), ("fused", None)):
        got, peaks[form] = run(form, rule)
        _check(got.history.objective.shape == (n_iterations,),
               f"the {form} run holds a row an iteration")
        _check_rows("forward", f"{form} against recomputed", got, want,
                    CARRY_LIMITS)
    print(f"[chip_smoke] forward: peak_bytes recomputed={peak_recomputed} "
          + " ".join(f"{k}={v}" for k, v in peaks.items()), flush=True)
    _check(max(peaks.values()) - peak_recomputed <= CARRY_PEAK_ROOM,
           f"the carry costs no more than {CARRY_PEAK_ROOM} bytes of device memory")
    split, _ = run("fused", how=", in segments of 80 evals",
                   progress_cb=lambda ev: None, progress_every=80)
    _check(_same_run(split, got),
           "the fused run split at eval boundaries is bitwise the unsplit run")
    return cfg, ds, _peak_bytes()


def _peak_bytes() -> int:
    import jax

    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def _rooted_run(label: str, device: dict, cfg, ds, say: tuple, **kw):
    """One unsharded, uncached ``jax_backend.run``: (result, the arguments of
    its ``dopt.run`` root, the device's peak after it); prints the ``say``
    arguments with the segment's line."""
    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.observability.spans import process_tracer

    result = jax_backend.run(cfg, ds, 0.0, use_mesh=False,
                             executable_cache=False, **kw)
    root = [e["args"] for e in process_tracer().spans()
            if e["name"] == "dopt.run"][-1]
    peak = _peak_bytes()
    _say(label, device, result.history, **{k: root[k] for k in say},
         peak_bytes=peak, final_loss=f"{result.history.objective[-1]:.6f}")
    return result, root, peak


def _same_run(a, b) -> bool:
    return (np.array_equal(a.final_models, b.final_models)
            and np.array_equal(a.history.objective, b.history.objective)
            and np.array_equal(a.history.consensus_error,
                               b.history.consensus_error))


LIVE_EDGE_SHARE = 0.7 * 0.9 ** 2  # a link is up, and both its ends are


def faults_segment(device: dict, cfg, ds, peak_fault_free: int) -> None:
    """``forward_carry_segment``'s experiment under p = 0.3, q = 0.1, directly
    after it: the peak counter only rises, so what it reads above
    ``peak_fault_free`` is the fault layer's."""
    cfg = cfg.replace(edge_drop_prob=0.3, straggler_prob=0.1)
    say = ("faults", "fault_form", "fault_mixing", "fault_bytes",
           "live_edge_share", "forward")
    got, root, peak = _rooted_run("faults p=0.3 q=0.1", device, cfg, ds, say)
    _check(root["fault_form"] == "drawn" and root["forward"] == "fused",
           "memoryless faults on the neighbor table are drawn in the step, "
           "and the next gradient stays in the carry")
    _check(root["fault_mixing"] == "shift" and root["fault_bytes"] == 0.0,
           "a ring's neighbours are read by shifts, with no table handed "
           "to the scan")
    _check(abs(root["live_edge_share"] - LIVE_EDGE_SHARE) <= 0.005,
           f"live_edge_share {root['live_edge_share']:.5f} within 0.005 of "
           f"{LIVE_EDGE_SHARE:.4f}")
    print(f"[chip_smoke] faults: peak_bytes fault-free={peak_fault_free} "
          f"faulty={peak} stated fault_bytes={root['fault_bytes']:.0f}", flush=True)
    _check(peak - peak_fault_free <= root["fault_bytes"],
           "the device's peak is no more than the fault-free run's plus the "
           "stated fault_bytes")
    split, _, _ = _rooted_run(
        "faults p=0.3 q=0.1, in segments of 80 evals", device, cfg, ds, say,
        progress_cb=lambda ev: None, progress_every=80)
    _check(_same_run(split, got),
           "the faulty run split at eval boundaries is bitwise the unsplit run")
    _one_round_both_ways(cfg)


def scopes_segment(device: dict, cfg, ds) -> None:
    """``forward_carry_segment``'s experiment under ``utils.profiling.trace``,
    then both joins of that trace with the program's scope table."""
    import tempfile
    import time

    from benchmark import scope_reduce, trace_reduce
    from distributed_optimization_tpu.observability import device_scopes
    from distributed_optimization_tpu.utils.profiling import trace

    trace_dir = tempfile.mkdtemp(prefix="chip_smoke_trace_")
    t0 = time.perf_counter()
    with trace(trace_dir):
        result, root, _ = _rooted_run(
            "scopes (traced)", device, cfg, ds, ("program", "temp_bytes"))
    wall = time.perf_counter() - t0
    compiled = device_scopes._held[-1]  # cache off: the holder kept it
    _check(root["temp_bytes"]
           == compiled.memory_analysis().temp_size_in_bytes > 0,
           "the root's temp_bytes is memory_analysis().temp_size_in_bytes")
    t0 = time.perf_counter()
    table = device_scopes.table_for(root["program"])
    print(f"[chip_smoke] scopes: table of {table['module']} "
          f"{len(table['rows'])} instructions, text {table['text_s']:.3f} s, "
          f"parse {table['parse_s']:.3f} s, asked and built in "
          f"{time.perf_counter() - t0:.3f} s; traced call {wall:.2f} s",
          flush=True)
    path = trace_reduce.find_xplane(trace_dir)
    planes = trace_reduce.load_events(path)
    summary = trace_reduce.reduce_planes(planes)
    leaves = sum(sec for _, sec in
                 trace_reduce.reduce_planes(planes, top=10 ** 9)["device_ops"])
    exact = device_scopes.device_time_by_scope(path, table)
    known = device_scopes.device_time_by_scope(path, {
        **table, "rows": [{**r, "scope": "update"} for r in table["rows"]]})
    T = cfg.n_iterations
    facts = {"iterations": T, "calls": [{
        "wall_s": wall, "iterations": T,
        "scan_s": T / result.history.iters_per_second}]}
    ten = scope_reduce.by_scope(summary, facts)
    busy = summary["busy_s"]
    for name, by in (("exact", exact), ("ten-row", ten)):
        print(f"[chip_smoke] scopes: {name} join, us an iteration: "
              + " ".join(f"{k}={v * 1e6 / T:.1f}" for k, v in by.items())
              + f" | busy {busy * 1e6 / T:.1f}", flush=True)
    _check(math.isclose(sum(exact.values()), leaves, rel_tol=1e-9),
           "the exact join's scopes and None sum to the op line's leaves")
    _check(known.get("update", 0.0) >= 0.99 * busy,
           f"the table knows the instructions of 99% of the busy time "
           f"({known.get('update', 0.0):.4f} of {busy:.4f} s)")
    scoped = sum(v for k, v in exact.items() if k is not None)
    scoped_ten = sum(v for k, v in ten.items() if k is not None)
    print(f"[chip_smoke] scopes: scoped share of busy, exact "
          f"{scoped / busy:.4f}, ten-row {scoped_ten / busy:.4f}", flush=True)
    _check(scoped >= 0.95 * busy, "the exact join bills 95% of the busy time "
           "to a scope")
    _check(all(v <= exact.get(k, 0.0) * (1 + 1e-9)
               for k, v in ten.items() if k is not None),
           "the ten-row join is at or under the exact one, scope by scope")
    _check(scoped_ten >= 0.97 * scoped,
           "the ten-row join is within 3% of the exact one in total")
    _check(math.isclose(sum(ten.values()), busy, rel_tol=1e-9),
           "the ten-row join's scopes and None sum to the busy time")


def _one_round_both_ways(cfg, rounds=(0, 7)) -> None:
    """The shift and the gather form of one round of ``cfg``'s faults on the
    chip, from the same keys: two programs of the same arithmetic."""
    import jax
    import jax.numpy as jnp

    from distributed_optimization_tpu.parallel import build_topology, faults

    topo = build_topology("ring", cfg.n_workers, impl="neighbor")
    key = jax.random.key(cfg.seed)
    gather, shift = (
        build(topo, None, drop_prob=cfg.edge_drop_prob,
              straggler_prob=cfg.straggler_prob, churn_active=False,
              participation_active=False, rejoin="frozen",
              fault_key=jax.random.fold_in(key, 0x0FA17),
              node_key=jax.random.fold_in(key, 0x57A66))
        for build in (faults._make_gather_faulty_mixing,
                      faults._make_shift_faulty_mixing))
    x = jax.random.normal(
        jax.random.key(1), (cfg.n_workers, cfg.n_features + 1), jnp.float32)
    for t in rounds:
        live, mixed = zip(*(
            (np.asarray(jax.jit(
                f.make_neighbor_liveness(topo.nbr_idx, topo.nbr_mask))(t)),
             np.asarray(jax.jit(f.mix)(t, x)))
            for f in (gather, shift)))
        gap = float(np.max(np.abs(mixed[0] - mixed[1])))
        print(f"[chip_smoke] faults: round {t} shift against gather, live "
              f"slots {int(live[0].sum())} of {live[0].size}, worst gap of "
              f"the mixed rows {gap:.3e}", flush=True)
        _check(np.array_equal(live[0], live[1]) and 0 < live[0].sum() < live[0].size,
               "the shift and the gather form realize the same live bits")
        _check(gap <= 1e-6, "the two forms' mixed rows agree within 1e-6")


VISIT_RTOL = 1e-6  # of each result's scale: sums in another order, f32


def shard_visit_segment(device: dict, *, n_workers: int = 1 << 14,
                        rows: int = 53, d: int = 80,
                        n_iterations: int = 100) -> None:
    import jax
    import jax.numpy as jnp

    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.observability import device_scopes
    from distributed_optimization_tpu.observability.spans import process_tracer
    from distributed_optimization_tpu.ops import losses, pallas_kernels

    cfg, ds = _glm_ring(41, n_workers, rows, d, n_iterations,
                        topology_impl="neighbor")
    rng = np.random.default_rng(41)
    # The kernel alone against XLA's two passes, ragged row counts.
    Xs = jnp.asarray(ds.X_full.reshape(n_workers, rows, d + 1))
    ys = jnp.asarray(ds.y_full.reshape(n_workers, rows))
    x = jnp.asarray(0.3 * rng.standard_normal((n_workers, d + 1), dtype=np.float32))
    n_valid = jnp.asarray(rng.integers(0, rows + 1, n_workers), jnp.int32)
    valid = jnp.arange(rows)[None, :] < n_valid[:, None]
    wts = valid * jnp.asarray(rng.random((n_workers, rows), dtype=np.float32))
    xbar = jnp.mean(x, axis=0)
    link = losses.LOGISTIC

    @jax.jit
    def two_passes():
        z, zbar = losses.paired_margins(Xs, x, xbar)
        g = jax.vmap(link.gradient_at, in_axes=(0, 0, 0, 0, 0, None))(
            z, x, Xs, ys, wts, 0.0)
        return g, jnp.sum(valid * link.loss(zbar, ys), axis=1)

    got = jax.jit(lambda: pallas_kernels.glm_shard_visit(
        link, Xs, ys, x, xbar, wts, n_valid))()
    for name, a, b in zip(("gradient", "loss sums"), got, two_passes()):
        rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        print(f"[chip_smoke] visit: {name} against XLA's two passes, worst gap "
              f"{rel:.3e} of the scale (limit {VISIT_RTOL})", flush=True)
        _check(rel <= VISIT_RTOL, f"the visit's {name} within {VISIT_RTOL}")
    # The kernel without its objective half (ISSUE 51): another compilation
    # of the same sums in the same order.
    alone = jax.jit(lambda: pallas_kernels.glm_shard_gradient(
        link, Xs, ys, x, wts))()
    rel = float(jnp.max(jnp.abs(alone - got[0])) / jnp.max(jnp.abs(got[0])))
    print(f"[chip_smoke] visit: the gradient alone against the visit's, worst "
          f"gap {rel:.3e} of the scale (limit {VISIT_RTOL})", flush=True)
    _check(rel <= VISIT_RTOL, f"the gradient alone within {VISIT_RTOL}")

    one, root, _ = _rooted_run("visit", device, cfg, ds, ("forward",))
    _check(root["forward"] == "fused",
           "a GLM's D-SGD over 53-row shards on a TPU visits them once")
    text = device_scopes._held[-1].as_text()  # cache off: the holder kept it
    stack = (f"f32[{n_workers},{rows},{d + 1}]", f"f32[{d + 1},{rows},{n_workers}]")
    moved = [
        ins[0] for ins in map(device_scopes._instruction, text.splitlines())
        if ins is not None and ins[2] in ("copy", "transpose")
        and ins[1].startswith(stack)
    ]
    calls = text.count("custom_call_target=\"tpu_custom_call\"")
    print(f"[chip_smoke] visit: compiled scan holds {calls} kernel calls, "
          f"copies or transposes of the stack: {moved}", flush=True)
    _check(calls >= 2 and not moved,
           "the compiled scan calls the kernel and moves no shard stack")
    # Four gradient steps a round: the three later descents visit the shards
    # once each, against the same round with the visit's rule switched off
    # (the margins carried, the later gradients two plain passes).
    local = cfg.replace(local_steps=4)
    said = ("forward", "local_forward", "shard_reads")
    got, root, _ = _rooted_run("visit, local_steps=4", device, local, ds, said)
    _check((root["forward"], root["local_forward"], root["shard_reads"])
           == ("fused", "visited", 4),
           "a round of four gradients reads the shards four times")
    with _rule_off("_visit_is_fused"):
        want, root, _ = _rooted_run(
            "carried, local_steps=4", device, local, ds, said)
    _check((root["forward"], root["local_forward"], root["shard_reads"])
           == ("carried", "recomputed", 8),
           "the same round without the kernel reads them eight times")
    _check_rows("visit", "visited round against recomputed", got, want,
                LOCAL_LIMITS)
    if device["count"] < 4:
        return
    sharded = jax_backend.run(cfg.replace(worker_mesh=4), ds, 0.0,
                              executable_cache=False)
    root = [e["args"] for e in process_tracer().spans()
            if e["name"] == "dopt.run"][-1]
    err = float(np.max(np.abs(sharded.final_models - one.final_models)))
    print(f"[chip_smoke] visit: worker_mesh=4 forward={root['forward']} "
          f"mixing={root['mixing']}, max |x_mesh4 - x_mesh0| = {err:.2e} "
          f"(tolerance {MESH_ATOL})", flush=True)
    _check(root["forward"] == "fused" and sharded.history.mesh_devices == 4,
           "under worker_mesh=4 every chip visits its own shards")
    _check(err <= MESH_ATOL, "the sharded fused run agrees with the unsharded")


def four_chip_segment(device: dict, *, n_workers: int = 100_000,
                      n_samples: int = 200_000, n_iterations: int = 100) -> None:
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.simulator import Simulator

    cfg = ExperimentConfig(
        problem_type="logistic", algorithm="dsgd", topology="ring",
        topology_impl="neighbor", mixing_impl="gather",
        n_workers=n_workers, n_samples=n_samples, local_batch_size=4,
        n_iterations=n_iterations, eval_every=n_iterations // 2,
    )
    sim = Simulator(cfg)
    sharded = sim.run_one("ring worker_mesh=4", verbose=False, worker_mesh=4)
    single = sim.run_one("ring worker_mesh=0", verbose=False)
    sim.report_numerical_results()
    for rec in (sharded, single):
        _say(rec.label, device, rec.result.history,
             final_gap=f"{rec.result.history.objective[-1]:.5f}")
    err = float(np.max(np.abs(
        sharded.result.final_models - single.result.final_models
    )))
    print(f"[chip_smoke] four-chip: max |x_mesh4 - x_mesh0| = {err:.2e} "
          f"(tolerance {MESH_ATOL})", flush=True)
    _check(sharded.result.history.mesh_devices == 4,
           "worker_mesh=4 state held as four row blocks, one per device")
    roots = [e["args"] for e in sim.phase_timer.spans()
             if e["name"] == "dopt.run"]
    print(f"[chip_smoke] four-chip: root spans {roots}", flush=True)
    _check(len(roots) == 2
           and roots[0]["placement"] == "mesh4:direct"
           and roots[0]["mesh"] == f"4x{n_workers // 4}"
           and roots[0]["mixing"] == "halo_shift"
           and roots[0]["halo_rows"] == 2,
           "worker_mesh=4 shards sent per chip, a ring mixed by halo shifts")
    _check(roots[1]["placement"] == "direct" and "mesh" not in roots[1],
           "worker_mesh=0 shards placed as before")
    _check(single.result.history.mesh_devices == 1,
           "worker_mesh=0 matrix-free run stays on one device")
    _check(bool(np.all(np.isfinite(sharded.result.final_models))),
           "sharded final models finite")
    _check(err <= MESH_ATOL, "worker_mesh=4 agrees with worker_mesh=0")


def main() -> int:
    from distributed_optimization_tpu.runtime import (
        configure_compile_cache,
        require_tpu,
    )

    cache_dir = configure_compile_cache()
    device = require_tpu("chip_smoke.py")
    import jax

    print(f"[chip_smoke] jax {jax.__version__} platform={device['platform']} "
          f"device_kind={device['kind']} devices_visible={device['count']} "
          f"compile_cache={cache_dir}", flush=True)
    if device["count"] >= 4:
        placement_segment(device)  # first: the peaks it reads are its own
    # next: its chip's peak is still its own, then the fault layer's on top
    cfg, ds, peak = forward_carry_segment(device)
    faults_segment(device, cfg, ds, peak)
    scopes_segment(device, cfg, ds)
    glm_segment(device)
    softmax_segment(device)
    reference_segment(device)
    gather_round_segment(device)
    tracker_segment(device)
    shard_visit_segment(device)
    byzantine_segment(device)
    if device["count"] >= 4:
        four_chip_segment(device)
        halo_forms_segment(device)
    else:
        print(f"[chip_smoke] four-chip segment: NOT RUN "
              f"({device['count']} device(s) visible)", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
