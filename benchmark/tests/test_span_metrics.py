"""The run builder's spans as per-layer metrics: ``span_reduce`` against a
tracer built by hand, and each cell's traced rehearsal printing all six."""

import math

import pytest

from benchmark import emit, span_reduce

from .conftest import run_harness, strict_loads
from .test_rehearsal import CELLS

SECONDS = ["run_builder.stack_s", "run_builder.upload_s", "run_builder.harvest_s",
           "run_builder.prepare_s", "run_builder.unattributed_s"]
NEW = SECONDS + ["run_builder.first_compile_s"]


@pytest.mark.parametrize("cell,chips", CELLS)
def test_traced_rehearsal_prints_the_span_metrics(bench, cell, chips):
    rc, out, err = run_harness(
        ["--workload", cell, "--seed", "2147483777", "--seconds", "0.5",
         "--trace", "1", "--rehearse"], devices=chips)
    assert rc == 0, err[-2000:]
    line = emit.validate(strict_loads(out.splitlines()[-1]), bench, cell, True)
    values = {name: line["metrics"][name]["value"] for name in NEW}
    for name, value in values.items():
        assert math.isfinite(value) and value >= 0, (name, value)
        assert line["metrics"][name]["unit"] == "s"
    # In a rehearsal busy_s is the scan's own seconds (the dopt.run.scan
    # span): with it the parts make up the traced calls' wall, inside the window.
    total = sum(values[name] for name in SECONDS) + line["device"]["busy_s"]
    assert 0 < total <= line["device"]["window_s"]
    assert values["run_builder.stack_s"] > 0 and values["run_builder.harvest_s"] > 0
    # The rehearsal keeps no persistent cache: the warm-up compiled.
    assert values["run_builder.first_compile_s"] > 0


def make_tracer(scans, compile_first=0.5):
    """A tracer holding one ``dopt.run`` root for each of ``scans`` (the
    seconds of its scan span); the first is the warm-up's and compiled."""
    from distributed_optimization_tpu.observability.spans import Tracer

    tracer = Tracer()
    for k, scan in enumerate(scans):
        with tracer.span("dopt.run", aggregate=False):
            with tracer.span("dopt.run.stack_shards", aggregate=False):
                pass
            if k == 0:
                tracer.add_span("dopt.run.compile", compile_first, aggregate=False)
            tracer.add_span("dopt.run.prepare", 0.25, aggregate=False)
            tracer.add_span("dopt.run.prepare", 0.5, aggregate=False)
            tracer.add_span("dopt.run.scan", scan, aggregate=False)
        with tracer.span("elsewhere"):
            pass
    return tracer


def facts_of(*calls):
    """Call facts as ``run.run_calls`` makes them, from (wall_s, scan_s)."""
    return {"calls": [{"wall_s": w, "scan_s": 10 / (10 / s), "iterations": 10}
                      for w, s in calls]}


def use(monkeypatch, tracer):
    from distributed_optimization_tpu.observability import spans

    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)


def test_sums_by_name_the_children_of_each_calls_own_root(monkeypatch):
    # Warm-up, a traced call, a call that failed its gates (it left a root
    # and is not among the facts), a second traced call.
    use(monkeypatch, make_tracer([1.0, 2.0, 7.0, 3.0]))
    facts = facts_of((4.0, 2.0), (5.0, 3.0))
    summary = span_reduce.reduce(facts)
    assert summary["wall_s"] == 9.0
    assert summary["by_name"]["dopt.run.prepare"] == 1.5
    assert summary["by_name"]["dopt.run.scan"] == 5.0
    assert "dopt.run.compile" not in summary["by_name"]  # the warm-up's, not a traced call's
    assert summary["first_compile_s"] == 0.5
    assert span_reduce.seconds(facts, "prepare", "scan", "harvest") == 6.5
    # Two calls whose scans took the same time take a root each.
    use(monkeypatch, make_tracer([1.0, 2.0, 2.0]))
    assert span_reduce.seconds(facts_of((4.0, 2.0), (4.0, 2.0)), "scan") == 4.0


def test_raises_when_the_spans_are_not_those_of_the_calls(monkeypatch):
    from distributed_optimization_tpu.observability.spans import Tracer

    use(monkeypatch, Tracer())
    with pytest.raises(span_reduce.SpanError, match="0 'dopt.run' span"):
        span_reduce.reduce(facts_of((4.0, 2.0)))
    use(monkeypatch, make_tracer([1.0, 2.0]))
    with pytest.raises(span_reduce.SpanError, match="no 'dopt.run' span whose scan"):
        span_reduce.reduce(facts_of((4.0, 2.0), (4.0, 2.0)))
    with pytest.raises(span_reduce.SpanError, match="no 'dopt.run' span whose scan"):
        span_reduce.reduce(facts_of((4.0, 2.5)))
    with pytest.raises(span_reduce.SpanError):
        span_reduce.reduce({"calls": []})


def test_raises_when_a_root_outlasts_its_call(monkeypatch):
    tracer = make_tracer([1.0, 2.0])
    root = [e for e in tracer.spans() if e["name"] == "dopt.run"][-1]
    use(monkeypatch, tracer)
    span_reduce.reduce(facts_of((root["duration"] + 1.0, 2.0)))
    with pytest.raises(span_reduce.SpanError, match="inside a call of"):
        span_reduce.reduce(facts_of((root["duration"] / 2, 2.0)))


def test_raises_when_the_warm_ups_root_may_have_been_dropped(monkeypatch):
    from distributed_optimization_tpu.observability.spans import PROCESS_TRACER_ROOTS

    use(monkeypatch, make_tracer([1.0] + [2.0] * (PROCESS_TRACER_ROOTS // 2)))
    with pytest.raises(span_reduce.SpanError, match="tracer is full"):
        span_reduce.reduce(facts_of((4.0, 2.0)))


def test_a_program_without_the_tracer_names_nothing(monkeypatch, capfd):
    """The parent of the PR that brought the spans, under this benchmark: no
    reader raises or returns None (either refuses the parent's traced run);
    nothing is named and the whole wall is unattributed."""
    from distributed_optimization_tpu.observability import spans

    from benchmark.run import load_reader

    monkeypatch.delattr(spans, "process_tracer")
    facts = facts_of((4.0, 2.0), (5.0, 3.0))
    values = {name: load_reader(name)(None, facts, {}) for name in NEW}
    assert values.pop("run_builder.unattributed_s") == 9.0
    assert set(values.values()) == {0.0}
    assert "no process_tracer" in capfd.readouterr().err
