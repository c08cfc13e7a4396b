"""The host path's nine per-layer metrics (ISSUE 48): ``host_path_reduce``
and each reader against a tracer built by hand, on a program with the parts
and on one without (the parent commit under these files), and a traced
rehearsal of a GLM cell and of ``train2k`` printing all nine."""

import math

import pytest

from benchmark import emit, host_path_reduce, span_reduce
from benchmark.run import load_reader

from .conftest import run_harness, strict_loads
from .test_span_metrics import facts_of, use

PARTS = ["run_builder.harvest_rows_s", "run_builder.harvest_fetch_s",
         "run_builder.harvest_cast_s", "run_builder.harvest_average_s"]
NINE = PARTS + ["run_builder.harvest_self_s", "run_builder.harvest_fetch_gbps",
                "run_builder.upload_wait_s", "run_builder.upload_gbps",
                "run_builder.upload_slowest_block_s"]


def make_tracer(scans, *, parts=True, fetch_s=0.5, flat=True, leaves=1):
    """One ``dopt.run`` root for each of ``scans`` as ``jax_backend._run``
    leaves it: ``upload`` with its counters (``flat``), ``upload_wait``, the
    scan, and ``harvest`` with its parts made by ``add_span`` inside the open
    child (``parts``; ``leaves`` fetches and casts, as under
    ``return_state``)."""
    from distributed_optimization_tpu.observability.spans import Tracer

    tracer = Tracer()
    for k, scan in enumerate(scans):
        with tracer.span("dopt.run", aggregate=False):
            tracer.add_span("dopt.run.prepare", 0.25, aggregate=False)
            with tracer.span("dopt.run.upload", aggregate=False, bytes=4_000_000_000) as up:
                if parts and flat:
                    up["args"].update(blocks=32, wait_s=0.25, slowest_block_s=0.03 * (k + 1),
                                      slowest_block=7)
            up["duration"] = 0.375  # the recorded event: a live span of microseconds
            tracer.add_span("dopt.run.upload_wait", 0.125, aggregate=False)
            tracer.add_span("dopt.run.scan", scan, aggregate=False)
            with tracer.span("dopt.run.harvest", aggregate=False,
                             bytes=leaves * 10**9) as harvest:
                if parts:
                    tracer.add_span("dopt.run.harvest.rows", 0.0625, aggregate=False, bytes=8000)
                    for _ in range(leaves):
                        tracer.add_span("dopt.run.harvest.fetch", fetch_s, aggregate=False,
                                        bytes=10**9, leaves=1, strided=1)
                        tracer.add_span("dopt.run.harvest.cast", 1.0, aggregate=False,
                                        bytes=2 * 10**9)
                    tracer.add_span("dopt.run.harvest.average", 0.25, aggregate=False,
                                    rows=96, copied_bytes=0)
            # Its parts and 1/32 s of its own.
            harvest["duration"] = 0.34375 + leaves * (fetch_s + 1.0)
    return tracer


def read_all(facts):
    return {name: load_reader(name)(None, facts, {}) for name in NINE}


def test_each_reader_on_a_program_with_the_parts(monkeypatch, capfd):
    # The warm-up's root, one traced call, a failed call's root, a second.
    use(monkeypatch, make_tracer([1.0, 2.0, 7.0, 3.0]))
    facts = facts_of((9.0, 2.0), (9.0, 3.0))
    values = read_all(facts)
    assert [values[name] for name in PARTS] == [0.125, 1.0, 2.0, 0.5]
    harvest_s = span_reduce.seconds(facts, "harvest")
    assert values["run_builder.harvest_self_s"] == 2 * 0.03125
    assert abs(sum(values[n] for n in PARTS) + values["run_builder.harvest_self_s"]
               - harvest_s) < 1e-6
    assert values["run_builder.harvest_fetch_gbps"] == 2.0
    assert values["run_builder.upload_wait_s"] == 2 * (0.25 + 0.125)
    upload_s = span_reduce.seconds(facts, "upload", "upload_wait")
    assert upload_s == 1.0 and values["run_builder.upload_gbps"] == 8.0
    # The largest over the calls (the second traced root is the tracer's fourth).
    assert values["run_builder.upload_slowest_block_s"] == 0.12
    err = capfd.readouterr().err
    assert "call 1: " in err and "harvest.fetch.strided 1" in err
    # Under return_state a call opens fetch and cast once a leaf: summed.
    use(monkeypatch, make_tracer([1.0, 2.0], leaves=3))
    values = read_all(facts_of((20.0, 2.0)))
    assert values["run_builder.harvest_fetch_s"] == 1.5
    assert values["run_builder.harvest_cast_s"] == 3.0
    assert values["run_builder.harvest_fetch_gbps"] == 2.0
    # A direct placement waits for nothing inside upload.
    use(monkeypatch, make_tracer([1.0, 2.0], flat=False))
    values = read_all(facts_of((9.0, 2.0)))
    assert values["run_builder.upload_wait_s"] == 0.125
    assert values["run_builder.upload_slowest_block_s"] == 0.0
    assert values["run_builder.upload_gbps"] > 0


def test_a_fetch_of_no_seconds_is_no_division_error(monkeypatch):
    use(monkeypatch, make_tracer([1.0, 2.0], fetch_s=0.0))
    values = read_all(facts_of((9.0, 2.0)))
    assert values["run_builder.harvest_fetch_s"] == 0.0
    assert values["run_builder.harvest_fetch_gbps"] == 0.0
    assert host_path_reduce.gbps(0, 0.0) == 0.0


def test_a_program_without_the_parts_reads_zero_and_says_so(monkeypatch, capfd):
    """The parent commit under these files: roots, children and no parts.
    Every reader returns 0.0, a number (``emit.validate`` refuses a traced
    line that lacks a metric), and stderr says why, once a line."""
    use(monkeypatch, make_tracer([1.0, 2.0], parts=False))
    values = read_all(facts_of((9.0, 2.0)))
    assert set(values) == set(NINE) and set(values.values()) == {0.0}
    assert all(type(v) is float for v in values.values())
    err = capfd.readouterr().err
    assert err.count("holds a part") == 1 and "reads 0.0" in err
    # And a program from before the process tracer.
    from distributed_optimization_tpu.observability import spans

    monkeypatch.delattr(spans, "process_tracer")
    assert set(read_all(facts_of((9.0, 2.0))).values()) == {0.0}


def test_raises_when_the_spans_are_not_those_of_the_calls(monkeypatch):
    use(monkeypatch, make_tracer([1.0, 2.0]))
    with pytest.raises(span_reduce.SpanError, match="no 'dopt.run' span whose scan"):
        host_path_reduce.reduce(facts_of((9.0, 2.5)))


@pytest.mark.parametrize("cell", [
    "glm81_ring262k.steady2k", "softmax4096_ring96.train2k"])
def test_traced_rehearsal_prints_all_nine(bench, cell):
    rc, out, err = run_harness(
        ["--workload", cell, "--seed", "2147483801", "--seconds", "0.5",
         "--trace", "1", "--rehearse"])
    assert rc == 0, err[-2000:]
    line = emit.validate(strict_loads(out.splitlines()[-1]), bench, cell, True)
    values = {name: line["metrics"][name]["value"] for name in NINE}
    assert all(math.isfinite(v) and v >= 0 for v in values.values()), values
    units = {line["metrics"][name]["unit"] for name in NINE}
    assert units == {"s", "GB/s"}
    harvest_s = line["metrics"]["run_builder.harvest_s"]["value"]
    assert abs(sum(values[n] for n in PARTS) + values["run_builder.harvest_self_s"]
               - harvest_s) < 1e-6
    for name in PARTS + ["run_builder.harvest_fetch_gbps", "run_builder.upload_gbps",
                         "run_builder.upload_wait_s"]:
        assert values[name] > 0, name
    assert values["run_builder.upload_wait_s"] <= (
        line["metrics"]["run_builder.upload_s"]["value"])
    assert "[host_path_reduce] call 0: " in err
