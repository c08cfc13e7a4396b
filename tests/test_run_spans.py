"""Spans inside the run builder (ISSUE 24): one ``dopt.run`` root a call of
``jax_backend._run`` with a child around each stretch it names, recorded in
the tracer the caller made current (else the bounded process tracer), none
of them a row of the flat phase table; ``--profile-dir`` traces with the
Python tracer off.
CPU, small N and T: what is checked is structure and counts, never a time.
"""

import functools
import subprocess
import sys

import jax
import numpy as np
import pytest
from conftest import small_backend_config

from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.observability import spans as spans_mod
from distributed_optimization_tpu.observability.spans import (
    PROCESS_TRACER_ROOTS,
    Tracer,
    current_tracer,
    process_tracer,
)
from distributed_optimization_tpu.serving.cache import ExecutableCache
from distributed_optimization_tpu.simulator import Simulator
from distributed_optimization_tpu.parallel.mesh import place_shards
from distributed_optimization_tpu.utils.data import (
    HostDataset,
    generate_synthetic_dataset,
    stack_shards,
)

# ``topology`` (ISSUE 36): the graph's making or its cache hit, a child of
# every decentralized call.
CHILDREN_COLD = [
    "prepare", "stack_shards", "prepare", "topology", "prepare", "upload",
    "prepare",
    "cache_lookup", "compile", "upload_wait", "scan", "harvest",
]
CHILDREN_WARM = [c for c in CHILDREN_COLD if c != "compile"]
# ``harvest``'s parts (ISSUE 48): grandchildren of the root, in order.
HARVEST_PARTS = ["rows", "fetch", "cast", "average"]


@pytest.fixture(scope="module")
def setup():
    cfg = small_backend_config(n_iterations=40, eval_every=10)
    return cfg, generate_synthetic_dataset(cfg)


def run_under(tracer, cfg, ds, **kw):
    """One call recorded in ``tracer``; returns (result, root, children in
    order of their start)."""
    with tracer.activate():
        result = jax_backend.run(cfg, ds, 0.0, **kw)
    events = tracer.spans()
    roots = [e for e in events if e["name"] == "dopt.run"]
    children = sorted(
        (e for e in events if e["parent"] == roots[-1]["id"]),
        key=lambda e: e["start"],
    )
    return result, roots, children


def names(children):
    return [e["name"].removeprefix("dopt.run.") for e in children]


def test_one_root_with_the_named_children_in_order(setup):
    cfg, ds = setup
    tracer = Tracer()
    _, roots, children = run_under(
        tracer, cfg, ds, executable_cache=ExecutableCache()
    )
    assert len(roots) == 1
    root = roots[0]
    assert names(children) == CHILDREN_COLD
    assert all(e["name"].startswith("dopt.run.") for e in children)
    # Disjoint, in order, inside the root; their sum is at most the root.
    end = root["start"]
    for e in children:
        assert e["start"] >= end
        end = e["start"] + e["duration"]
    assert end <= root["start"] + root["duration"]
    assert sum(e["duration"] for e in children) <= root["duration"]
    # ``carry``: the shape of the scan's model leaf, [N, *param_shape].
    # ``algorithm``, ``compress``, ``select``, ``wire_floats_per_edge``:
    # what ran and what one edge carries an iteration (plain D-SGD: the
    # whole model; nothing is compressed, so ``select`` says how the gather
    # sampler picks a batch's rows: a counted threshold over the uniforms'
    # 32 bits, ISSUE 40).
    # ``program``, ``temp_bytes`` (ISSUE 34): a key of the executable the call
    # ran and its temporaries by ``memory_analysis()`` (tests/test_device_scopes.py).
    args = dict(root["args"])
    assert isinstance(args.pop("program"), str) and args.pop("temp_bytes") >= 0
    # Whether the harvest wrote its float64 results into buffers an earlier
    # call of this process left free (ISSUE 49; tests/test_result_buffers.py).
    assert args.pop("result_buffers") in ("fresh", "reused")
    assert args == {
        "path": "fused", "cache": "miss",
        "carry": f"{cfg.n_workers}x{ds.n_features}",
        "algorithm": "dsgd", "compress": "none", "select": "threshold:16",
        "wire_floats_per_edge": float(ds.n_features),
        # How the shards were stacked and how they went up (ISSUE 29): an
        # ``argsort`` partition is gathered; so small a stack goes up as it
        # is, each device's block to that device (the 8 forced host devices
        # are a mesh: ISSUE 30).
        "stack": "gather", "placement": f"mesh{jax.device_count()}:direct",
        # Whether the eval's pass over the shards also made the next step's
        # margins (ISSUE 31): gathered batches on the CPU, so no.
        "forward": "recomputed",
        # How the static graph mixes on one device (ISSUE 36): a ring.
        "mixing": "stencil",
        # The rule's gossip rounds, the state the scan carries (D-SGD: the
        # models alone, as the device holds them) and the sampler that ran
        # with the rows its batches hold (ISSUE 39; on the CPU auto draws
        # indices and gathers: once a draw, the targets riding in the rows,
        # ISSUE 40).
        "gossip_rounds": 1, "state_leaves": 1,
        "state_bytes": float(cfg.n_workers * ds.n_features * 4),
        "sampling": "gather", "batch_gathers": 1,
        "batch_rows": cfg.n_workers * cfg.local_batch_size,
    }
    by_name = {e["name"]: e for e in children}
    stacked = stack_shards(ds, dtype=np.float32)
    assert by_name["dopt.run.stack_shards"]["args"]["bytes"] == (
        stacked.X.nbytes + stacked.y.nbytes
    )
    assert by_name["dopt.run.upload"]["args"]["bytes"] == (
        stacked.X.nbytes + stacked.y.nbytes + stacked.n_valid.nbytes
    )
    assert "args" not in by_name["dopt.run.scan"]
    assert by_name["dopt.run.harvest"]["args"]["bytes"] == (
        cfg.n_workers * stacked.X.shape[2] * 4
    )
    # None of them is a row of the flat phase table.
    assert tracer.phases == {}


@pytest.mark.parametrize("visit,sampling_impl,want", [
    (True, "dense", "fused"), (False, "dense", "carried"),
    (True, "auto", "recomputed"), (False, "auto", "recomputed"),
])
def test_the_root_says_which_forward_product_the_scan_carried(
    visit, sampling_impl, want, monkeypatch
):
    """``forward`` (ISSUEs 31, 41) is the engagement counter of both
    mechanisms: ``fused`` where the kernel's visit leaves the next gradient
    in the carry (a TPU's rule, patched on here: a CPU never takes it),
    ``carried`` where XLA's paired pass leaves the margins, ``recomputed``
    where neither fits (gathered batches), whatever the visit's rule says."""
    monkeypatch.setattr(
        jax_backend, "_visit_is_fused", lambda carried, X: visit and carried
    )
    cfg = small_backend_config(
        problem_type="logistic", n_iterations=10, sampling_impl=sampling_impl
    )
    _, roots, _ = run_under(
        Tracer(), cfg, generate_synthetic_dataset(cfg), executable_cache=False
    )
    assert roots[-1]["args"]["forward"] == want


@pytest.mark.parametrize("churn,sampling_impl,want", [
    (dict(rejoin="neighbor_restart"), "dense", ("carried", "restarted")),
    (dict(rejoin="frozen"), "dense", ("carried", None)),
    (dict(rejoin="neighbor_restart"), "auto", ("recomputed", None)),
    (dict(mttf=0.0, mttr=0.0), "dense", ("carried", None)),
], ids=["restart", "frozen", "restart_recomputed", "no_churn"])
def test_the_root_says_what_the_forward_product_is_of(churn, sampling_impl, want):
    """``forward_of`` = ``restarted`` (ISSUE 47) beside ``forward`` on a call
    whose carried product is taken at the models the NEXT round's
    ``neighbor_restart`` leaves; on every other call, a recomputed one under
    the same policy among them, the root does not hold the argument."""
    cfg = small_backend_config(
        problem_type="logistic", n_iterations=10, sampling_impl=sampling_impl,
        **{"mttf": 6.0, "mttr": 3.0, **churn},
    )
    _, roots, _ = run_under(
        Tracer(), cfg, generate_synthetic_dataset(cfg), executable_cache=False
    )
    args = roots[-1]["args"]
    assert (args["forward"], args.get("forward_of")) == want
    assert ("rejoin_rows" in args) == (churn.get("rejoin") == "neighbor_restart")


@pytest.mark.parametrize("layout,dtype,stack", [
    ("consecutive", "float32", "view"), ("consecutive", "float64", "cast"),
    ("argsort", "float64", "gather"),
])
def test_root_says_how_the_shards_were_stacked_and_placed(
    setup, monkeypatch, layout, dtype, stack
):
    """ISSUE 29: ``stack`` is ``view`` for shards laid worker after worker
    in the run dtype, ``cast`` for those in another, ``gather`` for an
    ``argsort`` partition; ``placement`` is ``direct``, or names the 2-D
    blocks a stack went up as. No new child, and ``stack_shards``' ``bytes``
    are still the stacked arrays'."""
    cfg, ds = setup
    stacked = stack_shards(ds, dtype=np.float32)
    n, L, d = stacked.X.shape
    if layout == "consecutive":
        ds = HostDataset(
            X_full=stacked.X.reshape(n * L, d).astype(dtype),
            y_full=stacked.y.reshape(n * L).astype(dtype),
            shard_indices=list(np.arange(n * L).reshape(n, L)),
            problem_type=ds.problem_type,
        )
    for flat, placement in (
        (False, "direct"), (True, f"flat:{n * L * d // 128}x128/2"),
    ):
        if flat:
            monkeypatch.setattr(
                jax_backend, "place_shards",
                functools.partial(
                    place_shards, min_tiled_bytes=0, columns=128,
                    block_bytes=n // 2 * L * d * 4),
            )
        _, roots, children = run_under(Tracer(), cfg, ds, use_mesh=False)
        assert roots[-1]["args"]["stack"] == stack
        assert roots[-1]["args"]["placement"] == placement
        assert names(children) in (CHILDREN_COLD, CHILDREN_WARM)
        by_name = {e["name"]: e for e in children}
        assert by_name["dopt.run.stack_shards"]["args"]["bytes"] == (
            stacked.X.nbytes + stacked.y.nbytes
        )


def test_root_names_the_worker_mesh_and_its_halo(setup):
    """ISSUE 30: under ``worker_mesh`` the root says over how many devices
    the workers lie and how many each holds, which form the halo mixing
    took (read off the neighbor table: shifts on a ring's, ISSUE 35), and
    the fullest device's boundary rows and bytes over ICI a
    round, the numbers of ``telemetry.ici_summary`` (and so of the
    ``dopt_worker_mesh_*`` gauges); the upload's ``bytes`` stay the total,
    and ONE child span is added, ``halo_plan`` round the making of the halo
    mixing (ISSUE 52), which says the form too."""
    from distributed_optimization_tpu.telemetry import ici_summary

    cfg, ds = setup
    cfg = cfg.replace(worker_mesh=4)
    _, roots, children = run_under(Tracer(), cfg, ds)
    args = roots[-1]["args"]
    ici = ici_summary(cfg, d_features=ds.n_features)
    assert args["mesh"] == f"4x{cfg.n_workers // 4}"
    assert args["placement"] == "mesh4:direct"
    assert args["mixing"] == "halo_shift"  # a ring's table (ISSUE 35)
    # A ring block has two boundary rows whatever its length.
    assert args["halo_rows"] == max(ici["halo_rows_per_device"]) == 2
    assert args["ici_bytes_per_round"] == max(
        ici["bytes_per_device_per_round"]) == 2 * ds.n_features * 4
    at = CHILDREN_COLD.index("topology") + 2  # after the prepare that follows it
    under_a_mesh = CHILDREN_COLD[:at] + ["halo_plan", "prepare"] + CHILDREN_COLD[at:]
    assert names(children) in (
        under_a_mesh, [c for c in under_a_mesh if c != "compile"])
    stacked = stack_shards(ds, dtype=np.float32)
    by_name = {e["name"]: e for e in children}
    assert by_name["dopt.run.halo_plan"]["args"] == {"form": "halo_shift"}
    assert by_name["dopt.run.upload"]["args"]["bytes"] == (
        stacked.X.nbytes + stacked.y.nbytes + stacked.n_valid.nbytes
    )
    # An unsharded run says none of it but how its one device mixes (ISSUE
    # 36): a ring by the stencil, which has no table to count.
    _, roots, _ = run_under(Tracer(), cfg.replace(worker_mesh=0), ds)
    args = roots[-1]["args"]
    assert args["mixing"] == "stencil"
    assert not {"mesh", "halo_rows", "ici_bytes_per_round", "k_max", "edges",
                "live_slot_share", "table_bytes"} & set(args)


@pytest.mark.parametrize("problem,classes", [("quadratic", 2), ("softmax", 3)])
def test_root_names_the_compressor_and_harvest_counts_every_leaf(problem, classes):
    """CHOCO with top-k (ISSUE 26): the root says which compressor ran over
    which row, how it selects (ISSUE 27) and what an edge carries (k values +
    k indices); the harvest's
    ``bytes`` are the leaves fetched: the models alone, and with
    ``return_state`` the state's leaves (x again, and xhat) beside them."""
    cfg = small_backend_config(
        n_iterations=20, eval_every=10, algorithm="choco", compression="top_k",
        compression_k=4, choco_gamma=0.2, problem_type=problem,
        n_classes=classes,
    )
    ds = generate_synthetic_dataset(cfg)
    row = ds.n_features * (classes if problem == "softmax" else 1)
    leaf = cfg.n_workers * row * 4
    for return_state, fetched in ((False, leaf), (True, 3 * leaf)):
        result, roots, children = run_under(
            Tracer(), cfg, ds, return_state=return_state)
        args = roots[-1]["args"]
        assert args["algorithm"] == "choco"
        assert args["compress"] == f"top_k:4/{row}"
        # By a counted threshold, two bits of a float32 magnitude a pass.
        assert args["select"] == "threshold:16"
        assert args["wire_floats_per_edge"] == 8.0
        assert children[-1]["name"] == "dopt.run.harvest"
        assert children[-1]["args"]["bytes"] == fetched
    assert sorted(result.final_state) == ["x", "xhat"]


@pytest.mark.parametrize("compression,k,select", [
    ("random_k", 4, "threshold:16"), ("qsgd", 4, "none"),
])
def test_root_select_for_the_other_compressors(compression, k, select):
    """``select`` on compressed D-SGD: the sparsifiers select by a counted
    threshold, a quantizer selects nothing."""
    cfg = small_backend_config(
        n_iterations=10, eval_every=10, algorithm="dsgd",
        compression=compression, compression_k=k, choco_gamma=0.2,
    )
    _, roots, _ = run_under(Tracer(), cfg, generate_synthetic_dataset(cfg))
    assert roots[-1]["args"]["compress"].startswith(f"{compression}:{k}/")
    assert roots[-1]["args"]["select"] == select


def test_second_identical_call_hits_the_cache_and_does_not_compile(setup):
    cfg, ds = setup
    tracer, cache = Tracer(), ExecutableCache()
    first, roots, _ = run_under(tracer, cfg, ds, executable_cache=cache)
    second, roots, children = run_under(tracer, cfg, ds, executable_cache=cache)
    assert [r["args"]["cache"] for r in roots] == ["miss", "hit"]
    assert roots[1]["id"] > roots[0]["id"]
    assert names(children) == CHILDREN_WARM
    np.testing.assert_array_equal(first.final_models, second.final_models)
    off, roots, children = run_under(tracer, cfg, ds, executable_cache=False)
    assert roots[-1]["args"]["cache"] == "off"
    assert names(children) == [c for c in CHILDREN_COLD if c != "cache_lookup"]


@pytest.mark.parametrize("problem", ["quadratic", "softmax", "logistic"])
@pytest.mark.parametrize("path", ["segmented", "chunked"])
def test_outputs_bitwise_under_another_tracer_and_path(setup, path, problem):
    cfg, ds = setup
    if problem != "quadratic":  # a rank-3 carry, and a GLM (ISSUE 48)
        cfg = small_backend_config(
            n_iterations=40, eval_every=10, problem_type=problem, n_classes=3)
        ds = generate_synthetic_dataset(cfg)
    base = jax_backend.run(cfg, ds, 0.0)  # the process tracer
    kw = (
        {"progress_cb": lambda ev: None} if path == "segmented"
        else {"measure_timestamps": True}
    )
    other, roots, children = run_under(Tracer(), cfg, ds, **kw)
    np.testing.assert_array_equal(base.final_models, other.final_models)
    np.testing.assert_array_equal(
        base.history.objective, other.history.objective
    )
    np.testing.assert_array_equal(
        base.history.consensus_error, other.history.consensus_error
    )
    assert roots[-1]["args"]["path"] == path
    got = names(children)
    assert got[:7] == CHILDREN_COLD[:7]
    assert got[-3:] == ["upload_wait", "scan", "harvest"]
    assert set(got) <= set(CHILDREN_COLD)


def parts_of(tracer, child):
    """The parts of ``child`` (an event), in order of their start."""
    return sorted(
        (e for e in tracer.spans() if e["parent"] == child["id"]),
        key=lambda e: e["start"],
    )


def test_harvest_holds_its_four_parts_and_they_are_grandchildren(setup):
    """ISSUE 48: ``rows``, ``fetch``, ``cast``, ``average`` under
    ``dopt.run.harvest``, disjoint and in order, inside it; the root's own
    children are what they were (the tests above), and no other child has a
    part."""
    cfg, ds = setup
    tracer = Tracer()
    result, roots, children = run_under(tracer, cfg, ds)
    harvest = children[-1]
    parts = parts_of(tracer, harvest)
    assert [e["name"] for e in parts] == [
        "dopt.run.harvest." + p for p in HARVEST_PARTS
    ]
    end = harvest["start"]
    for e in parts:
        assert (e["parent"], e["root"]) == (harvest["id"], roots[-1]["id"])
        assert e["start"] >= end
        end = e["start"] + e["duration"]
    assert end <= harvest["start"] + harvest["duration"]
    assert sum(e["duration"] for e in parts) <= harvest["duration"]
    ids = {e["id"] for e in children} | {roots[-1]["id"]}
    assert {e["parent"] for e in tracer.spans()} <= ids | {None}
    assert all(e["parent"] == harvest["id"] for e in tracer.spans()
               if e["id"] not in ids)
    rows, fetch, cast, average = (e["args"] for e in parts)
    n, d = result.final_models.shape
    # Four rows of objective and consensus error, float32 on the device.
    assert rows == {"bytes": 2 * 4 * 4}
    assert fetch == {"bytes": harvest["args"]["bytes"], "leaves": 1,
                     "strided": 0}
    assert fetch["bytes"] == n * d * 4
    # ISSUE 49: into a kept buffer or a new one, as the store stood.
    assert set(cast) == {"bytes", "reused_bytes"}
    assert cast["bytes"] == 8 * n * d and cast["reused_bytes"] in (0, 8 * n * d)
    assert roots[-1]["args"]["result_buffers"] == (
        "reused" if cast["reused_bytes"] else "fresh")
    assert average == {"rows": n}
    assert tracer.phases == {}


@pytest.mark.parametrize("problem,classes", [("quadratic", 2), ("softmax", 3)])
def test_fetch_counts_every_leaf_under_return_state(problem, classes):
    """CHOCO under ``return_state``: the models come down, then x again and
    xhat; ``fetch`` and ``cast`` open once a leaf, and their ``bytes`` by
    name are what ``harvest`` says came down, and eight bytes a number."""
    cfg = small_backend_config(
        n_iterations=20, eval_every=10, algorithm="choco", compression="top_k",
        compression_k=4, choco_gamma=0.2, problem_type=problem,
        n_classes=classes,
    )
    tracer = Tracer()
    result, _, children = run_under(
        tracer, cfg, generate_synthetic_dataset(cfg), return_state=True)
    parts = parts_of(tracer, children[-1])
    assert [e["name"].rsplit(".", 1)[1] for e in parts] == HARVEST_PARTS + [
        "fetch", "cast", "fetch", "cast"]
    fetched = sum(e["args"]["bytes"] for e in parts if e["name"].endswith("fetch"))
    written = sum(e["args"]["bytes"] for e in parts if e["name"].endswith("cast"))
    assert fetched == children[-1]["args"]["bytes"] == 3 * result.final_models.size * 4
    assert written == 8 * 3 * result.final_models.size
    assert sum(e["args"].get("leaves", 0) for e in parts) == 3
    np.testing.assert_array_equal(result.final_state["x"], result.final_models)
    np.testing.assert_array_equal(
        result.final_avg_model, result.final_models.mean(axis=0))


def test_average_says_the_honest_rows_and_copies_nothing():
    """An attacked call averages the honest rows, a benign call all of them,
    both in place (ISSUE 49: no indexed copy of the honest rows)."""
    cfg = small_backend_config(
        n_workers=16, n_iterations=10, eval_every=10, attack="sign_flip",
        n_byzantine=2, aggregation="trimmed_mean", robust_b=1,
    )
    for cfg, honest in ((cfg, 14), (cfg.replace(
            attack="none", n_byzantine=0, aggregation="gossip", robust_b=0), 16)):
        tracer = Tracer()
        result, _, children = run_under(
            tracer, cfg, generate_synthetic_dataset(cfg))
        average = parts_of(tracer, children[-1])[-1]
        assert average["name"] == "dopt.run.harvest.average"
        assert average["args"] == {"rows": honest}


@pytest.mark.parametrize("shape,order", [
    ((6, 5), "C"), ((6, 4, 3), "C"), ((6, 4, 3), "runtime")])
def test_fetch_then_cast_is_the_parents_one_expression(shape, order):
    """``_host_f64`` split in two (ISSUE 48): the same float64 ``[rows, D]``
    array to the bit, also for a leaf the runtime hands over in its own
    dimension order (strided on the host, PR 25); written into the buffer
    it is handed (ISSUE 49), whatever that held."""
    a = np.arange(np.prod(shape), dtype=np.float32).reshape(shape) / 7
    fetched = jax_backend._fetch_to_host(jax.numpy.asarray(a))
    if order == "runtime":  # the strides of a [4, 6, 3] buffer
        fetched = np.ascontiguousarray(fetched.transpose(1, 0, 2)).transpose(1, 0, 2)
        assert not fetched.flags.c_contiguous
    want = fetched.astype(np.float64, order="C").reshape(shape[0], -1)
    dst = np.full(shape, np.nan)
    got = jax_backend._cast_f64(fetched, dst)
    assert got.base is dst
    assert got.dtype == np.float64 and got.flags.c_contiguous
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, a.reshape(shape[0], -1).astype(np.float64))


def test_upload_counts_its_waiting_under_a_flat_placement(setup, monkeypatch):
    """ISSUE 48: ``blocks``, ``wait_s``, ``slowest_block_s`` and
    ``slowest_block`` on ``dopt.run.upload`` where the shards went up in
    pieces, three numbers and an index a call and no span a piece; none of
    them under ``direct``."""
    cfg, ds = setup
    tracer = Tracer()
    _, _, children = run_under(tracer, cfg, ds, use_mesh=False)
    upload = next(e for e in children if e["name"] == "dopt.run.upload")
    assert set(upload["args"]) == {"bytes"}
    n, L, d = stack_shards(ds, dtype=np.float32).X.shape
    monkeypatch.setattr(
        jax_backend, "place_shards",
        functools.partial(
            place_shards, min_tiled_bytes=0, columns=128,
            block_bytes=n // 4 * L * d * 4),
    )
    _, roots, children = run_under(tracer, cfg, ds, use_mesh=False)
    assert roots[-1]["args"]["placement"].endswith("/4")
    upload = next(e for e in children if e["name"] == "dopt.run.upload")
    args = upload["args"]
    assert set(args) == {
        "bytes", "blocks", "wait_s", "slowest_block_s", "slowest_block"}
    assert args["blocks"] == 4
    assert 0 <= args["slowest_block_s"] <= args["wait_s"] <= upload["duration"]
    assert args["slowest_block"] in range(3)  # the last is not waited for
    assert parts_of(tracer, upload) == []


def test_calls_table_is_one_row_a_call():
    """``Tracer.calls_table``: the children's and the parts' seconds and
    their numeric arguments by call, as rows and as text."""
    tracer = Tracer()
    for k in range(2):
        with tracer.span("dopt.run", aggregate=False, path="fused"):
            tracer.add_span("dopt.run.prepare", 0.25)
            with tracer.span("dopt.run.upload", bytes=100, how="flat") as up:
                up["args"].update(wait_s=0.5, blocks=2 + k)
            tracer.add_span("dopt.run.prepare", 0.5)
            with tracer.span("dopt.run.harvest", bytes=40):
                for _ in range(1 + k):
                    tracer.add_span(
                        "dopt.run.harvest.fetch", 0.125, bytes=20, strided=1)
                with tracer.span("dopt.run.harvest.cast"):
                    tracer.add_span("deeper", 1.0)
    with tracer.span("another.root"):
        tracer.add_span("dopt.run.prepare", 9.0)
    rows = tracer.calls_table(format="json")
    assert [r["start"] for r in rows][0] == 0.0 < rows[1]["start"]
    assert [r["id"] for r in rows] == sorted(r["id"] for r in rows)
    for k, row in enumerate(rows):
        assert list(row["seconds"]) == [
            "prepare", "upload", "harvest", "harvest.fetch", "harvest.cast"]
        assert row["seconds"]["prepare"] == 0.75
        assert row["seconds"]["harvest.fetch"] == 0.125 * (1 + k)
        assert row["seconds"]["harvest"] <= row["duration"]
        assert row["counts"] == {
            "upload.bytes": 100, "upload.wait_s": 0.5, "upload.blocks": 2 + k,
            "harvest.bytes": 40, "harvest.fetch.bytes": 20 * (1 + k),
            "harvest.fetch.strided": 1 + k,
        }
    text = tracer.calls_table().splitlines()
    assert len(text) == 3 and len({len(line) for line in text}) == 1
    assert text[0].split() == [
        "#", "start", "duration", *rows[0]["seconds"], *rows[0]["counts"]]
    assert text[2].split()[0] == "1" and text[2].split()[-1] == "2"
    assert text[1].split()[3] == "0.750000"
    assert tracer.calls_table("no.such.span") == "#  start  duration"
    assert tracer.calls_table("no.such.span", format="json") == []
    with pytest.raises(ValueError, match="format"):
        tracer.calls_table(format="csv")


def test_calls_table_of_real_calls(setup):
    cfg, ds = setup
    tracer = Tracer()
    for _ in range(2):
        run_under(tracer, cfg, ds)
    first, second = tracer.calls_table(format="json")
    assert set(CHILDREN_WARM) | {"harvest." + p for p in HARVEST_PARTS} <= set(
        second["seconds"])
    assert sum(
        second["seconds"]["harvest." + p] for p in HARVEST_PARTS
    ) <= second["seconds"]["harvest"] <= second["duration"]
    assert second["counts"]["harvest.fetch.bytes"] == second["counts"]["harvest.bytes"]
    assert second["counts"]["harvest.average.rows"] == cfg.n_workers


def test_iters_per_second_is_the_scan_spans_clock(setup):
    cfg, ds = setup
    result, _, children = run_under(Tracer(), cfg, ds)
    scan = next(e for e in children if e["name"] == "dopt.run.scan")
    assert result.history.iters_per_second == cfg.n_iterations / scan["duration"]
    assert result.history.time[-1] == scan["duration"]


def test_process_tracer_keeps_the_last_roots_only(setup):
    cfg, ds = setup
    assert current_tracer() is process_tracer()
    for _ in range(PROCESS_TRACER_ROOTS + 6):
        jax_backend.run(cfg, ds, 0.0)
    events = process_tracer().spans()
    roots = [e for e in events if e["parent"] is None]
    assert len(roots) == PROCESS_TRACER_ROOTS == 64
    assert [e["id"] for e in roots] == sorted(e["id"] for e in roots)
    # Every event that is left belongs to a root that is left.
    assert {e["root"] for e in events} == {e["id"] for e in roots}
    assert len(events) <= PROCESS_TRACER_ROOTS * (
        len(CHILDREN_COLD) + 1 + len(HARVEST_PARTS)
    )


def test_bounded_tracer_drops_a_root_with_its_children():
    tracer = Tracer(max_roots=2)
    for k in range(5):
        with tracer.span(f"root{k}"):
            with tracer.span("child"):
                tracer.add_span("leaf", 0.25)
    events = tracer.spans()
    assert sorted(e["name"] for e in events if e["parent"] is None) == [
        "root3", "root4"
    ]
    assert len(events) == 6
    assert tracer.phases["leaf"] == 1.25  # the flat table is not cut


def test_activate_nests_and_restores():
    outer, inner = Tracer(), Tracer()
    with outer.activate():
        assert current_tracer() is outer
        with inner.activate():
            assert current_tracer() is inner
        assert current_tracer() is outer
    assert current_tracer() is process_tracer()


def test_span_yields_its_event_and_takes_args_until_it_closes():
    tracer = Tracer()
    with tracer.span("work", aggregate=False, n=1) as ev:
        assert ev["start"] > 0 and "duration" not in ev
        ev["args"]["late"] = "yes"
    (recorded,) = tracer.spans()
    assert recorded["duration"] == ev["duration"] >= 0
    assert recorded["args"] == {"n": 1, "late": "yes"}


def test_simulator_run_one_owns_the_backends_spans():
    # A seed no other test uses: the process's executable cache has not
    # seen this program, so the run compiles.
    cfg = small_backend_config(n_iterations=40, eval_every=10, seed=240024)
    sim = Simulator(cfg)
    before = len(process_tracer().spans())
    sim.run_one("ring", topology="ring")
    events = {e["id"]: e for e in sim.phase_timer.spans()}
    group = next(e for e in events.values() if e["name"] == "run_one:ring")
    backend = [e for e in events.values() if e["name"].startswith("dopt.run")]
    assert {e["name"] for e in backend} >= {
        "dopt.run", "dopt.run.stack_shards", "dopt.run.scan",
        "dopt.run.harvest",
    }
    for e in backend:
        assert e["root"] == group["id"]
    root = next(e for e in backend if e["name"] == "dopt.run")
    assert root["parent"] == group["id"]
    # No event is synthesised beside the real ones...
    assert [e["name"] for e in events.values() if e["parent"] == group["id"]] == [
        "dopt.run"
    ]
    # ...and the flat table, and so the report and --json, keep their rows:
    # ``compile`` and ``run`` are the backend's own clocks, the two spans'.
    phases = sim.phase_timer.phases
    assert set(phases) == {"data_gen", "oracle", "compile", "run"}
    by_name = {e["name"]: e for e in backend}
    assert phases["run"] == by_name["dopt.run.scan"]["duration"]
    assert 0 < phases["compile"] <= by_name["dopt.run.compile"]["duration"]
    table = sim.phase_timer.report()
    assert "dopt.run" not in table and "run_one" not in table
    assert len(process_tracer().spans()) == before


def test_profile_dir_trace_holds_the_spans_and_no_python_events(setup, tmp_path):
    from distributed_optimization_tpu.utils import profiling

    cfg, ds = setup
    jax_backend.run(cfg, ds, 0.0)  # compiled before the trace starts
    tracer = Tracer()
    with profiling.trace(str(tmp_path)), tracer.activate():
        jax_backend.run(cfg, ds, 0.0)
    (xplane,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(xplane))
    host = {}
    python_events = 0
    for plane in data.planes:
        for line in plane.lines:
            for event in line.events:
                python_events += event.name.startswith("$")
                if event.name.startswith("dopt.run"):
                    host.setdefault(event.name, []).append(
                        (plane.name, event.start_ns, event.duration_ns)
                    )
    recorded = {e["name"]: e for e in tracer.spans()}
    assert set(host) == set(recorded)
    assert all(p.startswith("/host:") for v in host.values() for p, _, _ in v)
    # One clock: an annotation starts before its span's clock is read and
    # ends after it, so it is the longer of the two, by microseconds.
    _, _, scan_ns = host["dopt.run.scan"][0]
    scan_s = recorded["dopt.run.scan"]["duration"]
    assert scan_s <= scan_ns * 1e-9 <= scan_s + 0.01
    # The Python tracer's events are called "$<file>:<line> <function>".
    assert python_events == 0


def test_spans_module_imports_without_jax():
    code = (
        "import sys, importlib.util\n"
        "sys.modules['jax'] = None\n"
        f"spec = importlib.util.spec_from_file_location('spans', {spans_mod.__file__!r})\n"
        "m = importlib.util.module_from_spec(spec); spec.loader.exec_module(m)\n"
        "t = m.Tracer()\n"
        "with t.span('a'):\n    pass\n"
        "assert [e['name'] for e in t.spans()] == ['a']\n"
        "assert len(t.calls_table('a').splitlines()) == 2\n"
        "assert 'jax.profiler' not in sys.modules\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=60
    )
    assert proc.returncode == 0, proc.stderr
