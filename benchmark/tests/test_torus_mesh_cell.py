"""The four-chip torus cell (ISSUE 52): the harness rehearses it over four
devices, a halo exchange that delivers nothing is not correct by the cell's
own limits, the cell refuses another device count, and its three readers read
what they say off a summary recorded on the chip, off the run builder's root
spans and off the configuration file."""

import json
import os

import pytest

from benchmark import emit
from benchmark import run as harness
from benchmark.flops import halo_gather_mix

from .conftest import ROOT, run_harness, strict_loads
from .test_mesh_cell import NO_EXCHANGE, make_tracer

CELL = "glm81_torus1m_mesh4.grid1k"
NEW = ("halo.gather_hbm_share", "halo.rows_per_round", "halo.gathered_rows_per_round")


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as fh:
        return json.load(fh)


def test_the_traced_rehearsal_reports_the_cells_metrics(bench):
    """8 x 8 over four forced devices: ``correct``, the three new metrics
    and the halo's wire bytes beside every metric the cell was appended to."""
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "3000000041", "--seconds", "0.3", "--trace", "1",
         "--rehearse"], devices=4)
    assert rc == 0, err[-2000:]
    line = strict_loads(out.splitlines()[-1])
    emit.validate(line, bench, CELL, True)
    assert line["correct"] is True and line["device"]["count"] == 4
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got) and "mesh.mix_share" not in got
    # two grid rows of 8 a shard, 81 floats a row; 16 workers x 4 slots
    assert got["halo.rows_per_round"] == 16.0
    assert got["halo.wire_bytes_per_round"] == 16 * 81 * 4
    assert got["halo.gathered_rows_per_round"] == 64.0
    assert got["halo.gather_hbm_share"] == 0.0  # a CPU has no device plane
    assert "mesh.busy_skew" in got and "run_builder.first_compile_s" in got


def test_boundary_rows_not_exchanged_is_not_correct(bench):
    """Every ``ppermute`` hands back zeros: each chip's first and last grid
    rows mix with 0 where the neighbouring chip's grid row belongs."""
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "79", "--seconds", "0.3", "--trace", "0",
         "--rehearse"], devices=4, prelude=NO_EXCHANGE)
    assert rc == 0, err[-2000:]
    line = strict_loads(out.splitlines()[-1])
    emit.validate(line, bench, CELL, False)
    assert line["correct"] is False and line["device"]["count"] == 4
    assert "consensus_max_rel" in err and "OVER" in err


def test_the_cell_refuses_other_than_four_devices():
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "1", "--seconds", "0.3", "--trace", "0"],
        devices=4)
    assert rc != 0 and out == "" and "needs 4 TPU chip(s)" in err


def test_the_compulsory_bytes_come_from_the_file_alone():
    config = load("configs", "glm81_torus1m_mesh4.json")
    S, D, k, h = 262144, 81, 4, 2 * 1024
    assert halo_gather_mix.per_round_bytes(config) == (
        2 * S * D * 4 + S * k * 8 + 2 * h * D * 4) == 179585024
    small = {"experiment": dict(config["experiment"], n_workers=64)}
    assert halo_gather_mix.per_round_bytes(small) == 2 * 16 * D * 4 + 16 * k * 8 + 2 * 16 * D * 4


def test_readers_on_a_summary_recorded_on_the_chip(monkeypatch):
    """``testdata/torus_mesh4_grid1k.summary.json`` is the reduction of a
    traced run of the cell on the four-chip host (busy seconds a device, the
    ten largest rows, the device seconds by scope as ``scope_reduce`` billed
    them) and the root span's arguments of the traced call."""
    from distributed_optimization_tpu.observability import spans

    config = load("configs", "glm81_torus1m_mesh4.json")
    summary = load("testdata", "torus_mesh4_grid1k.summary.json")
    recorded, args = summary["recorded"], summary["root_args"]
    assert (args["mixing"], args["grid_shape"], args["mesh"]) == (
        "halo_gather", "1024x1024", "4x262144")
    assert (args["halo_rows"], args["ici_bytes_per_round"]) == (2048, 663552.0)
    assert (args["k_max"], args["gathered_rows"], args["halo_steps"]) == (4, 1048576, 2)
    assert args["halo_tables"] in ("constant", "argument")
    per = summary["busy_s_per_device"]
    assert len(per) == 4 and summary["busy_s"] == pytest.approx(sum(per) / 4)
    skew = harness.load_reader("mesh.busy_skew")(summary, {"n_devices": 4}, config)
    assert skew == pytest.approx(recorded["mesh.busy_skew"], rel=1e-9) and 0 <= skew < 10

    # the two counters read the traced call's own root
    scan_s = summary["scan_s"]
    calls = {"calls": [{"wall_s": scan_s + 1.0, "scan_s": scan_s, "iterations": 1000}],
             "iterations": 1000, "peaks": load("peaks.json")["TPU v5 lite"]}
    tracer = make_tracer([(scan_s / 2, {"halo_rows": 2, "gathered_rows": 7}),  # another call's
                          (scan_s, args)])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert harness.load_reader("halo.rows_per_round")(summary, calls, config) == 2048.0
    assert harness.load_reader("halo.gathered_rows_per_round")(summary, calls, config) == 1048576.0
    assert harness.load_reader("halo.wire_bytes_per_round")(summary, calls, config) == 663552.0
    assert recorded["halo.rows_per_round"] == 2048.0
    assert recorded["halo.gathered_rows_per_round"] == 1048576.0

    # the share: compulsory bytes over the gossip's device seconds at the peak
    from benchmark import scope_reduce

    gossip_us = recorded["scan.gossip_us_per_iter"]
    monkeypatch.setattr(scope_reduce, "us_per_iter", lambda t, f, c, scope: {
        "gossip": gossip_us}[scope])
    share = harness.load_reader("halo.gather_hbm_share")(summary, calls, config)
    assert share == pytest.approx(
        100.0 * 179585024 / (gossip_us * 1e-6 * 819e9), rel=1e-9)
    assert share == pytest.approx(recorded["halo.gather_hbm_share"], rel=1e-9)
    assert 0.0 < share < 100.0
    # the gossip's rows are among the ten the reduction hands over
    rows = dict(summary["device_ops"])
    assert set(summary["gossip_rows"]) <= set(rows)
    assert sum(rows[n] for n in summary["gossip_rows"]) * 1e6 / 1000 == pytest.approx(
        gossip_us, rel=1e-9)


def test_the_counters_read_zero_without_the_argument(monkeypatch):
    """The parent commit's roots (no ``gathered_rows`` under ``halo_gather``),
    an unsharded program's, and a program without a tracer: 0.0, a number."""
    from distributed_optimization_tpu.observability import spans

    calls = {"calls": [{"wall_s": 4.0, "scan_s": 2.0, "iterations": 10}]}
    rows = harness.load_reader("halo.rows_per_round")
    gathered = harness.load_reader("halo.gathered_rows_per_round")
    tracer = make_tracer([(2.0, {"mixing": "halo_gather", "halo_rows": 2048})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert (rows(None, calls, {}), gathered(None, calls, {})) == (2048.0, 0.0)
    tracer = make_tracer([(2.0, {"placement": "direct"})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert (rows(None, calls, {}), gathered(None, calls, {})) == (0.0, 0.0)
    monkeypatch.delattr(spans, "process_tracer")
    assert (rows(None, calls, {}), gathered(None, calls, {})) == (0.0, 0.0)


def test_the_share_reads_zero_without_device_time():
    read = harness.load_reader("halo.gather_hbm_share")
    config = load("configs", "glm81_torus1m_mesh4.json")
    facts = {"calls": [], "iterations": 1000, "peaks": load("peaks.json")["TPU v5 lite"]}
    assert read(None, facts, config) == 0.0
    assert read({"busy_s": 1.0, "device_ops": []}, dict(facts, iterations=0), config) == 0.0
    assert read({"busy_s": 1.0, "device_ops": []}, facts, {}) == 0.0
