"""The fault cell (ISSUE 32): a fault layer that does less than the
configuration states is not correct by the cell's own limits
(``test_rehearsal.py``'s breaks alter a result after the fact; these break
the layer itself: stragglers that step, links that never drop), and the
cell's three readers read what they say off a summary recorded on the chip
and off the run builder's root spans, and give a number, never nothing,
where a program has no such span, argument or row."""

import json
import os

import pytest

from benchmark import emit
from benchmark import run as harness

from .conftest import ROOT, run_harness, strict_loads

CELL = "glm81_ring262k_drop30strag10.steady1k"

BROKEN = """
import dataclasses, sys
from distributed_optimization_tpu.backends import jax_backend
real = jax_backend.make_faulty_mixing
def broken(topo, drop_prob, seed, **kw):
    {how}
jax_backend.make_faulty_mixing = broken
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
"""

BREAKS = {
    # a straggler's links drop but it takes its step: the freeze never runs
    "no_freeze": """
    fm = real(topo, drop_prob, seed, **kw)
    thaw = lambda f: dataclasses.replace(f, straggler_prob=0.0)
    return dataclasses.replace(thaw(fm), bind=lambda tb: thaw(fm.bind(tb)))""",
    # every link is up every round: only the stragglers change the graph
    "links_never_drop": "return real(topo, 0.0, seed, **kw)",
}


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as fh:
        return json.load(fh)


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_a_fault_layer_that_does_less_is_not_correct(bench, how):
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "79", "--seconds", "0.3", "--trace", "0",
         "--rehearse"], prelude=BROKEN.format(how=BREAKS[how]))
    assert rc == 0, err[-2000:]
    line = strict_loads(out.splitlines()[-1])
    emit.validate(line, bench, CELL, False)
    assert line["correct"] is False
    assert "consensus_max_rel" in err and "OVER" in err


def test_readers_on_a_summary_recorded_on_the_chip():
    """``testdata/drop30strag10_steady1k.summary.json`` is the reduction of a
    traced run of the cell on one v5e (busy seconds and the ten largest rows)
    and the root span's arguments of the traced call."""
    config = load("configs", "glm81_ring262k_drop30strag10.json")
    summary = load("testdata", "drop30strag10_steady1k.summary.json")
    rows = dict(summary["device_ops"])
    names = set(config["fault_ops"])
    assert names & set(rows), "a fault row is among the ten the reduction hands over"
    share = harness.load_reader("faults.mix_share")(summary, {}, config)
    assert share == pytest.approx(
        100.0 * sum(rows[n] for n in names & set(rows)) / summary["busy_s"])
    assert share == pytest.approx(summary["recorded"]["faults.mix_share"], rel=1e-9)
    assert 0.0 < share < 100.0
    args = summary["root_args"]
    assert args["faults"] == "edge_drop:0.3,straggler:0.1"
    assert args["fault_form"] == "drawn" and args["forward"] == "carried"
    assert args["fault_bytes"] == summary["recorded"]["faults.state_bytes"]
    assert args["live_edge_share"] == pytest.approx(0.7 * 0.9 ** 2, abs=0.005)


def test_mix_share_without_names_rows_or_trace_reads_zero():
    read = harness.load_reader("faults.mix_share")
    summary = {"busy_s": 2.0, "device_ops": [["fusion f32[524288,81]", 0.5]]}
    assert read(summary, {}, {"fault_ops": ["fusion f32[524288,81]"]}) == 25.0
    assert read(summary, {}, {"fault_ops": ["another"]}) == 0.0
    assert read(summary, {}, {}) == 0.0
    assert read(None, {}, {"fault_ops": ["fusion f32[524288,81]"]}) == 0.0
    # a rehearsal's stand-in: no rows at all
    assert read({"busy_s": 0.02, "device_ops": [], "idle_gaps": []}, {},
                {"fault_ops": ["fusion f32[524288,81]"]}) == 0.0


def make_tracer(roots):
    """A tracer holding one ``dopt.run`` root for each (scan seconds, fault
    seconds or None, root arguments)."""
    from distributed_optimization_tpu.observability.spans import Tracer

    tracer = Tracer()
    for scan, fault_s, args in roots:
        with tracer.span("dopt.run", aggregate=False) as root:
            if fault_s is not None:
                tracer.add_span("dopt.run.faults", fault_s, aggregate=False)
            tracer.add_span("dopt.run.scan", scan, aggregate=False)
            root.setdefault("args", {}).update(args)
    return tracer


def test_span_and_counter_read_the_traced_calls_own_root(monkeypatch):
    from distributed_optimization_tpu.observability import spans

    seconds = harness.load_reader("faults.timeline_s")
    held = harness.load_reader("faults.state_bytes")
    calls = {"calls": [{"wall_s": 40.0, "scan_s": 2.0, "iterations": 10}]}
    # the warm-up's root, the traced call's, and another experiment's
    tracer = make_tracer([(1.0, 0.5, {"fault_bytes": 6291456.0}),
                          (2.0, 0.25, {"fault_bytes": 6291456.0}),
                          (7.0, 3.0, {"fault_bytes": 530579456.0})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert seconds(None, calls, {}) == 0.25
    assert held(None, calls, {}) == 6291456.0
    # a program from before the span and the argument (the parent commit,
    # which runs this cell): a number, not nothing
    tracer = make_tracer([(1.0, None, {}), (2.0, None, {"placement": "direct"})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert seconds(None, calls, {}) == 0.0 and held(None, calls, {}) == 0.0
    assert isinstance(seconds(None, calls, {}), float)
    # and one with no tracer at all
    monkeypatch.delattr(spans, "process_tracer")
    assert seconds(None, calls, {}) == 0.0 and held(None, calls, {}) == 0.0
