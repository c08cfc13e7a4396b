"""The tracker cell (ISSUE 39): its rehearsal prints a valid line with every
metric the cell reports, in both modes; a tracker that is not kept is not
correct by the cell's own limits; and the cell's three readers read what
they say off a summary recorded on the chip and off the run builder's root
spans, and give a number, never nothing, where a program has no such
argument."""

import json
import os

import pytest

from benchmark import emit, scope_reduce
from benchmark import run as harness
from benchmark.flops import glm_step

from .conftest import ROOT, run_harness, strict_loads

CELL = "quad81_gt_torus16k.track1k"
NEW = ("algo.state_bytes", "sampling.batch_rows", "step.shard_hbm_share")


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as fh:
        return json.load(fh)


@pytest.mark.parametrize("traced", [0, 1])
def test_the_rehearsal_prints_every_metric_of_the_cell(bench, traced):
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "3900000999", "--seconds", "0.3",
         "--trace", str(traced), "--rehearse"])
    assert rc == 0, err[-2000:]
    line = strict_loads(out.splitlines()[-1])
    emit.validate(line, bench, CELL, bool(traced))
    assert line["correct"] is True and line["failed"] == 0
    expected = emit.expected_metrics(bench, CELL, bool(traced))
    assert set(line["metrics"]) == set(expected)
    if traced:
        assert set(NEW) <= set(expected)
        got = {name: line["metrics"][name]["value"] for name in NEW}
        # the rehearsal's 8 x 8 torus of 24 rows: three leaves of [64, 81]
        # float32, sixteen rows a worker a round
        assert got["algo.state_bytes"] == 3 * 64 * 81 * 4
        assert got["sampling.batch_rows"] == 64 * 16
        assert 0.0 < got["step.shard_hbm_share"] < 100.0
        for phase in ("sampling", "gradient", "gossip", "update", "eval", "unattributed"):
            assert f"scan.{phase}_us_per_iter" in line["metrics"]
    else:
        assert set(line["metrics"]) == {"iters_per_s", "setup_s"}


BROKEN = """
import sys
from distributed_optimization_tpu.algorithms import gradient_tracking as gt
from distributed_optimization_tpu.algorithms import base
real = gt.GRADIENT_TRACKING
def step(state, ctx):
    new = real.step(state, ctx)
    {how}
    return new
import dataclasses
base._REGISTRY["gradient_tracking"] = dataclasses.replace(real, step=step)
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
"""

BREAKS = {
    # the tracker is the last gradient: constant-step D-SGD
    "no_tracking": "new = dict(new, y=new['g_prev'])",
    # the last gradients are forgotten: y accumulates every gradient
    "g_prev_not_kept": "new = dict(new, g_prev=state['g_prev'])",
}


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_a_tracker_that_is_not_kept_is_not_correct(bench, how):
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "79", "--seconds", "0.3", "--trace", "0",
         "--rehearse"], prelude=BROKEN.format(how=BREAKS[how]))
    assert rc == 0, err[-2000:]
    line = strict_loads(out.splitlines()[-1])
    emit.validate(line, bench, CELL, False)
    assert line["correct"] is False
    assert "OVER" in err


def recorded_tracer(summary):
    from distributed_optimization_tpu.observability.spans import Tracer

    tracer = Tracer()
    with tracer.span("dopt.run", aggregate=False) as root:
        for name, seconds in summary["children"]:
            tracer.add_span(name, seconds, aggregate=False)
        root.setdefault("args", {}).update(summary["root_args"])
    return tracer


def test_readers_on_a_summary_recorded_on_the_chip(monkeypatch):
    """``testdata/gt_torus16k_track1k.summary.json`` is the reduction of a
    traced run of the cell on one v5e (busy seconds and the ten largest rows),
    the line's metrics, and the root span's arguments and children of the
    traced call."""
    from distributed_optimization_tpu.observability import spans

    config = load("configs", "quad81_gt_torus16k.json")
    summary = load("testdata", "gt_torus16k_track1k.summary.json")
    args = summary["root_args"]
    tracer = recorded_tracer(summary)
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    monkeypatch.setattr(scope_reduce, "_last", None)
    scan_s = dict(summary["children"])["dopt.run.scan"]
    peaks = load("peaks.json")["TPU v5 lite"]
    facts = {"iterations": 1000, "peaks": peaks, "n_devices": 1,
             "calls": [{"wall_s": summary["wall_s"], "scan_s": scan_s, "iterations": 1000}]}
    got = {name: harness.load_reader(name)(summary, facts, config)
           for name in NEW + ("scan.device_us_per_iter", "scan.temp_bytes")}
    for name, value in got.items():
        assert value == pytest.approx(summary["recorded"][name], rel=1e-9), name
    # what the root said on the chip: the rule, its three leaves as the
    # device holds them (the worker axis minor, 81 rows tiling to 88), the
    # sampler that ran and the rows it fetched
    assert (args["algorithm"], args["gossip_rounds"], args["state_leaves"]) == (
        "gradient_tracking", 2, 3)
    assert (args["sampling"], args["mixing"], args["grid_shape"], args["forward"]) == (
        "gather", "stencil", "128x128", "recomputed")
    assert got["algo.state_bytes"] == args["state_bytes"] == 3 * 16384 * 88 * 4
    assert got["sampling.batch_rows"] == args["batch_rows"] == 16384 * 16
    # the share is the file's bytes over the trace's busy seconds, under 100
    assert got["step.shard_hbm_share"] == pytest.approx(
        100.0 * glm_step.compulsory_bytes(config) * 1000
        / (summary["busy_s"] * peaks["hbm_bytes_per_s"]))
    assert 5.0 < got["step.shard_hbm_share"] < 100.0
    # a deployment's worth of memory, and a busy chip
    assert summary["memory_peak_bytes"] >= 0.25 * 16e9
    assert summary["busy_s"] > 0.75 * summary["window_s"]


def make_tracer(roots):
    """One ``dopt.run`` root for each (scan seconds, root arguments)."""
    from distributed_optimization_tpu.observability.spans import Tracer

    tracer = Tracer()
    for scan, args in roots:
        with tracer.span("dopt.run", aggregate=False) as root:
            tracer.add_span("dopt.run.scan", scan, aggregate=False)
            root.setdefault("args", {}).update(args)
    return tracer


def test_counters_read_the_traced_calls_own_root(monkeypatch):
    from distributed_optimization_tpu.observability import spans

    held = harness.load_reader("algo.state_bytes")
    rows = harness.load_reader("sampling.batch_rows")
    share = harness.load_reader("step.shard_hbm_share")
    config = load("configs", "quad81_gt_torus16k.json")
    calls = {"iterations": 10, "peaks": load("peaks.json")["TPU v5 lite"], "n_devices": 1,
             "calls": [{"wall_s": 40.0, "scan_s": 2.0, "iterations": 10}]}
    # the warm-up's root, the traced call's, another call's
    tracer = make_tracer([(1.0, {"state_bytes": 17301504.0, "batch_rows": 262144}),
                          (2.0, {"state_bytes": 17301504.0, "batch_rows": 262144}),
                          (7.0, {"state_bytes": 1.0e9, "batch_rows": 5})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert held(None, calls, config) == 17301504.0
    assert rows(None, calls, config) == 262144.0
    # a program from before the arguments (the parent commit, which runs
    # this cell): a number, not nothing
    tracer = make_tracer([(1.0, {}), (2.0, {"placement": "direct"})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert held(None, calls, config) == 0.0 and rows(None, calls, config) == 0.0
    assert isinstance(held(None, calls, config), float)
    # and one with no tracer at all
    monkeypatch.delattr(spans, "process_tracer")
    assert held(None, calls, config) == 0.0 and rows(None, calls, config) == 0.0
    # the share needs no root: the file's bytes over the trace's busy seconds
    trace = {"busy_s": 0.13, "device_ops": [], "idle_gaps": []}
    want = 100.0 * glm_step.compulsory_bytes(config) * 10 / (0.13 * 819e9)
    assert share(trace, calls, config) == pytest.approx(want) and 40.0 < want < 41.0
    # no trace, no busy second, no rule named: 0.0, a number
    assert share(None, calls, config) == 0.0
    assert share(dict(trace, busy_s=0.0), calls, config) == 0.0
    assert share(trace, calls, dict(config, step_bytes=None)) == 0.0
