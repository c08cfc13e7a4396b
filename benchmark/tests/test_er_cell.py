"""The drawn-graph cell (ISSUE 36): a gather that mixes less than the
configuration states is not correct by the cell's own limits
(``test_rehearsal.py``'s breaks alter a result after the fact; these break
the tables the scan is handed: a slot never read, a diagonal that does not
make up the row), and the cell's three readers read what they say off a
summary recorded on the chip and off the run builder's root spans, and give
a number, never nothing, where a program has no such span, argument or
scope."""

import json
import os

import pytest

from benchmark import emit, scope_reduce
from benchmark import run as harness
from benchmark.flops import gather_mix

from .conftest import ROOT, run_harness, strict_loads

CELL = "glm81_er262k_deg12.steady300"

BROKEN = """
import dataclasses, sys
import jax.numpy as jnp
from distributed_optimization_tpu.backends import jax_backend
real = jax_backend.make_mixing_op
def broken(topo, **kw):
    op = real(topo, **kw)
    tb = dict(op.tables)
    {how}
    return dataclasses.replace(op, tables=tb)
jax_backend.make_mixing_op = broken
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
"""

BREAKS = {
    # the table's last slot is never read: the widest rows lose a neighbour
    "slot_dropped": "tb['nbr'], tb['w_nbr'] = tb['nbr'][:-1], tb['w_nbr'][:-1]",
    # the diagonal is an edge's weight, not the row's remainder: rows do not
    # sum to one
    "diagonal_not_renormalised":
        "tb['w_self'] = 1.0 / (1.0 + jnp.asarray(topo.degrees, tb['w_self'].dtype))",
}


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as fh:
        return json.load(fh)


def test_the_rehearsal_is_correct_and_says_gather(bench):
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "2147483999", "--seconds", "0.3", "--trace", "0",
         "--rehearse"])
    assert rc == 0, err[-2000:]
    line = strict_loads(out.splitlines()[-1])
    emit.validate(line, bench, CELL, False)
    assert line["correct"] is True and line["failed"] == 0


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_a_gather_that_mixes_less_is_not_correct(bench, how):
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "79", "--seconds", "0.3", "--trace", "0",
         "--rehearse"], prelude=BROKEN.format(how=BREAKS[how]))
    assert rc == 0, err[-2000:]
    line = strict_loads(out.splitlines()[-1])
    emit.validate(line, bench, CELL, False)
    assert line["correct"] is False
    assert "OVER" in err


def test_the_compulsory_bytes_are_the_files():
    config = load("configs", "glm81_er262k_deg12.json")
    n, d, edges = 262144, 81, config["graph"]["edges"]
    assert gather_mix.per_round_bytes(config) == 2 * n * d * 4 + 2 * edges * 8
    # under a gigabyte a round: a quarter of a millisecond of the chip's memory
    peak = load("peaks.json")["TPU v5 lite"]["hbm_bytes_per_s"]
    assert 0.2e-3 < gather_mix.per_round_bytes(config) / peak < 0.3e-3


class RecordedScopes:
    """Stands where ``observability.device_scopes`` stands, holding the rows
    of the program's table that the recorded run's ten kinds were billed
    through."""

    def __init__(self, program, rows):
        self.program, self.rows = program, rows

    def table_for(self, program):
        if program != self.program:
            return None
        return {"module": "jit_seg_scan", "text_s": 0.0, "parse_s": 0.0, "rows": self.rows}


def recorded_tracer(summary):
    from distributed_optimization_tpu.observability.spans import Tracer

    tracer = Tracer()
    with tracer.span("dopt.run", aggregate=False) as root:
        for name, seconds in summary["children"]:
            tracer.add_span(name, seconds, aggregate=False)
        root.setdefault("args", {}).update(summary["root_args"])
    return tracer


def test_readers_on_a_summary_recorded_on_the_chip(monkeypatch):
    """``testdata/er262k_steady300.summary.json`` is the reduction of a traced
    run of the cell on one v5e (busy seconds and the ten largest rows), the
    root span's arguments and children of the traced call, and the rows of
    the program's scope table for those ten kinds."""
    from distributed_optimization_tpu.observability import spans

    config = load("configs", "glm81_er262k_deg12.json")
    summary = load("testdata", "er262k_steady300.summary.json")
    args = summary["root_args"]
    tracer = recorded_tracer(summary)
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    monkeypatch.setattr(scope_reduce, "_device_scopes",
                        lambda: RecordedScopes(args["program"], summary["scope_rows"]))
    monkeypatch.setattr(scope_reduce, "_last", None)
    scan_s = dict(summary["children"])["dopt.run.scan"]
    facts = {"iterations": 300, "peaks": load("peaks.json")["TPU v5 lite"],
             "calls": [{"wall_s": summary["wall_s"], "scan_s": scan_s, "iterations": 300}]}
    got = {name: harness.load_reader(name)(summary, facts, config)
           for name in ("topology.build_s", "gossip.table_bytes", "gossip.gather_hbm_share",
                        "scan.gossip_us_per_iter", "scan.device_us_per_iter")}
    for name, value in got.items():
        assert value == pytest.approx(summary["recorded"][name], rel=1e-9), name
    # a later call of the process: the graph was kept
    assert dict(summary["children"])["dopt.run.topology"] == got["topology.build_s"] < 0.01
    assert summary["topology_cache"] == "hit"
    # the tables as the chip lays them out: 30 rows tile to 32
    assert (args["mixing"], args["k_max"], args["edges"]) == ("gather", 30, 1573450)
    assert got["gossip.table_bytes"] == args["table_bytes"] == 2 * 32 * 262144 * 4 + 262144 * 4
    assert args["live_slot_share"] == pytest.approx(2 * 1573450 / (30 * 262144))
    # the mechanism does most of the work, far from its compulsory bytes
    assert got["scan.gossip_us_per_iter"] > 0.5 * got["scan.device_us_per_iter"]
    assert 0.0 < got["gossip.gather_hbm_share"] < 1.0
    assert got["gossip.gather_hbm_share"] == pytest.approx(
        100.0 * gather_mix.per_round_bytes(config)
        / (got["scan.gossip_us_per_iter"] * 1e-6 * facts["peaks"]["hbm_bytes_per_s"]))


def make_tracer(roots):
    """One ``dopt.run`` root for each (scan seconds, topology seconds or
    None, root arguments)."""
    from distributed_optimization_tpu.observability.spans import Tracer

    tracer = Tracer()
    for scan, topo_s, args in roots:
        with tracer.span("dopt.run", aggregate=False) as root:
            if topo_s is not None:
                tracer.add_span("dopt.run.topology", topo_s, aggregate=False)
            tracer.add_span("dopt.run.scan", scan, aggregate=False)
            root.setdefault("args", {}).update(args)
    return tracer


def test_span_and_counter_read_the_traced_calls_own_root(monkeypatch):
    from distributed_optimization_tpu.observability import spans

    seconds = harness.load_reader("topology.build_s")
    held = harness.load_reader("gossip.table_bytes")
    share = harness.load_reader("gossip.gather_hbm_share")
    config = load("configs", "glm81_er262k_deg12.json")
    calls = {"iterations": 10, "peaks": load("peaks.json")["TPU v5 lite"],
             "calls": [{"wall_s": 40.0, "scan_s": 2.0, "iterations": 10}]}
    # the warm-up's root (it drew the graph), the traced call's, another's
    tracer = make_tracer([(1.0, 4.5, {"table_bytes": 68157440.0}),
                          (2.0, 0.00002, {"table_bytes": 68157440.0}),
                          (7.0, 3.0, {"table_bytes": 1.0e9})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert seconds(None, calls, config) == 0.00002
    assert held(None, calls, config) == 68157440.0
    # a program from before the span and the argument (the parent commit,
    # which runs this cell): a number, not nothing
    tracer = make_tracer([(1.0, None, {}), (2.0, None, {"placement": "direct"})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert seconds(None, calls, config) == 0.0 and held(None, calls, config) == 0.0
    assert isinstance(seconds(None, calls, config), float)
    # and one with no tracer at all
    monkeypatch.delattr(spans, "process_tracer")
    assert seconds(None, calls, config) == 0.0 and held(None, calls, config) == 0.0
    # no trace, no gossip seconds under a scope, no rule named: 0.0, a number
    assert share(None, calls, config) == 0.0
    monkeypatch.setattr(scope_reduce, "_device_scopes", lambda: None)
    monkeypatch.setattr(scope_reduce, "_last", None)
    empty = {"busy_s": 0.02, "device_ops": [], "idle_gaps": []}
    assert share(empty, calls, config) == 0.0
    assert share(empty, calls, dict(config, gossip_bytes=None)) == 0.0
