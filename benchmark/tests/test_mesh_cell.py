"""The four-chip cell (ISSUE 30): a halo exchange that delivers nothing is
not correct by the cell's own limits (``test_rehearsal.py``'s breaks alter a
result after the fact; this one breaks the exchange itself, over four
devices), and the cell's three readers read what they say off a summary
recorded on the chip and off the run builder's root spans."""

import json
import os

import pytest

from benchmark import emit
from benchmark import run as harness

from .conftest import ROOT, run_harness, strict_loads

CELL = "glm81_ring1m_mesh4.halo1k"

NO_EXCHANGE = """
import sys
import jax
real = jax.lax.ppermute
jax.lax.ppermute = lambda x, axis_name, perm: real(x, axis_name, perm) * 0
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
"""


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as fh:
        return json.load(fh)


def test_boundary_rows_not_exchanged_is_not_correct(bench):
    """Every ``ppermute`` hands back zeros: each chip's two end workers mix
    with 0 where their neighbour's model belongs."""
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "78", "--seconds", "0.3", "--trace", "0",
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


def test_readers_on_a_summary_recorded_on_the_chip():
    """``testdata/mesh4_halo1k.summary.json`` is the reduction of a traced
    run of the cell on the four-chip host (busy seconds a device and the ten
    largest rows) and the root span's arguments of the traced call."""
    config = load("configs", "glm81_ring1m_mesh4.json")
    summary = load("testdata", "mesh4_halo1k.summary.json")
    facts = {"n_devices": 4}
    per = summary["busy_s_per_device"]
    assert len(per) == 4 and summary["busy_s"] == pytest.approx(sum(per) / 4)
    skew = harness.load_reader("mesh.busy_skew")(summary, facts, config)
    assert skew == pytest.approx(100.0 * (max(per) - min(per)) / max(per))
    assert skew == pytest.approx(summary["recorded"]["mesh.busy_skew"], rel=1e-9)
    assert 0.0 <= skew < 10.0  # four chips worked
    rows = dict(summary["device_ops"])
    names = set(config["mixing_ops"])
    assert names & set(rows), "a mixing row is among the ten the reduction hands over"
    share = harness.load_reader("mesh.mix_share")(summary, facts, config)
    assert share == pytest.approx(
        100.0 * sum(rows[n] for n in names & set(rows)) / summary["busy_s"])
    assert share == pytest.approx(summary["recorded"]["mesh.mix_share"], rel=1e-9)
    assert 0.0 < share < 100.0
    assert summary["root_args"]["ici_bytes_per_round"] == 648.0
    assert summary["root_args"]["mesh"] == "4x262144"
    assert summary["root_args"]["placement"] == "mesh4:flat:1099008x1024/32"
    assert summary["root_args"]["mixing"] == "halo_gather"


def test_busy_skew_says_how_many_devices_worked():
    read = harness.load_reader("mesh.busy_skew")
    four = {"n_devices": 4}
    assert read(None, four, {}) is None
    assert read({"busy_s": 2.0, "busy_s_per_device": [2.0, 2.0, 2.0, 2.0]}, four, {}) == 0.0
    assert read({"busy_s": 1.9, "busy_s_per_device": [2.0, 1.9, 1.8, 1.9]}, four, {}) == (
        pytest.approx(10.0))
    # the whole of the work on one chip, three planes idle or absent
    assert read({"busy_s": 0.5, "busy_s_per_device": [2.0, 0.0, 0.0, 0.0]}, four, {}) == 100.0
    assert read({"busy_s": 2.0, "busy_s_per_device": [2.0]}, four, {}) == 100.0
    assert read({"busy_s": 2.0}, four, {}) == 100.0  # a rehearsal's stand-in
    assert read({"busy_s": 0.0, "busy_s_per_device": [0.0] * 4}, four, {}) is None


def test_mix_share_without_names_or_trace_reports_nothing():
    read = harness.load_reader("mesh.mix_share")
    summary = {"busy_s": 2.0, "device_ops": [["gather f32[262144,2,81]", 0.5]]}
    assert read(None, {}, {"mixing_ops": ["gather f32[262144,2,81]"]}) is None
    assert read(summary, {}, {}) is None
    assert read(summary, {}, {"mixing_ops": ["gather f32[262144,2,81]"]}) == 25.0
    assert read(summary, {}, {"mixing_ops": ["another"]}) == 0.0


def make_tracer(roots):
    """A tracer holding one ``dopt.run`` root for each (scan seconds, root
    arguments) pair."""
    from distributed_optimization_tpu.observability.spans import Tracer

    tracer = Tracer()
    for scan, args in roots:
        with tracer.span("dopt.run", aggregate=False) as root:
            tracer.add_span("dopt.run.scan", scan, aggregate=False)
            root.setdefault("args", {}).update(args)
    return tracer


def test_wire_bytes_reads_the_traced_calls_own_roots(monkeypatch):
    from distributed_optimization_tpu.observability import spans

    read = harness.load_reader("halo.wire_bytes_per_round")
    calls = {"calls": [{"wall_s": 4.0, "scan_s": 2.0, "iterations": 10}]}
    # the warm-up's root, the traced call's, and another experiment's
    tracer = make_tracer([(1.0, {"ici_bytes_per_round": 648.0}),
                          (2.0, {"ici_bytes_per_round": 648.0}),
                          (7.0, {"ici_bytes_per_round": 1296.0})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert read(None, calls, {}) == 648.0
    # an unsharded program's roots carry no such argument: nothing to report
    tracer = make_tracer([(1.0, {}), (2.0, {"placement": "direct"})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert read(None, calls, {}) is None
    # and a program from before the spans has no tracer at all
    monkeypatch.delattr(spans, "process_tracer")
    assert read(None, calls, {}) is None
