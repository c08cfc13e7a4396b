"""The churn cell (ISSUE 46): a fault layer that does less than the
configuration states (a restart that never runs, chains that forget the round
before) is not correct by the cell's own limits (``test_rehearsal.py``'s
breaks alter a result after the fact; these break the layer itself), and the
cell's two new readers read what they say off a summary recorded on the chip
and off the run builder's root spans, and give a number, never nothing, where
a program has no such argument."""

import json
import os

import pytest

from benchmark import emit
from benchmark import run as harness

from . import test_scope_metrics
from .conftest import ROOT, run_harness, strict_loads
from .test_fault_cell import make_tracer

CELL = "glm81_ring262k_burst4_churn400.outage1k"
CONFIG = "glm81_ring262k_burst4_churn400"
SIBLING = "glm81_ring262k_drop30strag10.steady1k"


class EitherCell(str):
    """Stands in ``test_scope_metrics.ONLY_IN`` where one cell's name stands,
    for a scope that two cells report: equal to each of them."""

    def __new__(cls, *names):
        self = super().__new__(cls, names[0])
        self.names = names
        return self

    def __eq__(self, other):
        return other in self.names

    __hash__ = str.__hash__


# ``test_scope_metrics.ONLY_IN`` maps a scope that not every cell reports to
# the ONE cell that does, and its traced rehearsals hold every cell to it.
# ``scan.faults_us_per_iter`` is two cells' since this one. A PR that adds a
# cell may add files and edit none, so the table's entry is widened from
# here, at collection (PERF.md section 7, row 11: the next ``benchmark`` issue
# makes the table's values sets and drops this). Run alone,
# ``test_scope_metrics.py`` does not know that this cell reports the scope.
test_scope_metrics.ONLY_IN["scan.faults_us_per_iter"] = EitherCell(SIBLING, CELL)

BROKEN = """
import dataclasses, sys
import numpy as np
from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.parallel import faults
{how}
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
"""

BREAKS = {
    # a worker comes back with its stale row: the restart never runs
    "restart_never_runs": """
real = jax_backend.make_faulty_mixing
def frozen(topo, drop_prob, seed, **kw):
    return real(topo, drop_prob, seed, **dict(kw, rejoin="frozen"))
jax_backend.make_faulty_mixing = frozen
""",
    # every round's uniform against the stationary thresholds: the chains
    # forget the round before
    "chains_without_memory": """
real = faults._chain_scan
faults._chain_scan = lambda draw, t_init, t_enter, t_stay, size, horizon: real(
    draw, t_init, t_init, t_init, size, horizon)
""",
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


def test_the_rehearsed_line_carries_the_two_readers(bench):
    """The counters are the program's, so a rehearsal reads them too: 64
    chains of 40 rounds, near the stationary 150/550 down, a few rows back."""
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "3400000046", "--seconds", "0.3", "--trace", "1",
         "--rehearse"])
    assert rc == 0, err[-2000:]
    line = strict_loads(out.splitlines()[-1])
    emit.validate(line, bench, CELL, True)
    assert line["correct"] is True, err[-2000:]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert 10.0 < metrics["faults.down_share"] < 45.0
    assert 0.0 < metrics["faults.rejoin_rows_per_iter"] < 2.0
    assert metrics["faults.state_bytes"] == 3 * 40 * 64  # three leaves, a byte a bit
    assert 0.0 < metrics["faults.timeline_s"] < 5.0
    for cell in bench["workloads"]:
        expected = emit.expected_metrics(bench, cell["name"], True)
        assert ("faults.down_share" in expected) == (cell["name"] == CELL)
        assert ("faults.rejoin_rows_per_iter" in expected) == (cell["name"] == CELL)
        assert ("scan.faults_us_per_iter" in expected) == (cell["name"] in (CELL, SIBLING))


def test_readers_on_a_summary_recorded_on_the_chip(monkeypatch):
    """``testdata/burst4_churn400_outage1k.summary.json`` is the reduction of
    a traced run of the cell on one v5e (busy seconds, the ten largest rows)
    with the line's metrics and the traced call's root arguments."""
    from distributed_optimization_tpu.observability import spans

    config = load("configs", CONFIG + ".json")
    summary = load("testdata", "burst4_churn400_outage1k.summary.json")
    recorded, args = summary["recorded"], summary["root_args"]
    scan_s, T = summary["scan_s"], summary["iterations"]
    tracer = make_tracer([(scan_s - 1.0, 0.5, {"rejoin_rows": 7, "down_share": 0.5}),
                          (scan_s, recorded["faults.timeline_s"], args)])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    facts = {"calls": [{"wall_s": summary["wall_s"], "scan_s": scan_s, "iterations": T}],
             "iterations": T, "n_devices": 1, "peaks": load("peaks.json")["TPU v5 lite"]}

    def read(name):
        return harness.load_reader(name)(summary, facts, config)

    assert read("faults.rejoin_rows_per_iter") == args["rejoin_rows"] / T
    assert read("faults.rejoin_rows_per_iter") == recorded["faults.rejoin_rows_per_iter"]
    exp = config["experiment"]
    a_round = exp["n_workers"] * (exp["mttr"] / (exp["mttf"] + exp["mttr"])) / exp["mttr"]
    assert read("faults.rejoin_rows_per_iter") == pytest.approx(a_round, rel=0.02)
    assert read("faults.down_share") == 100.0 * args["down_share"]
    assert read("faults.down_share") == recorded["faults.down_share"]
    assert read("faults.down_share") == pytest.approx(100.0 * 150 / 550, abs=2.0)
    assert read("faults.timeline_s") == recorded["faults.timeline_s"]
    # the three leaves, a byte a bit, in the device's own tiles
    assert read("faults.state_bytes") == recorded["faults.state_bytes"] == args["fault_bytes"]
    assert args["fault_bytes"] >= 3 * T * exp["n_workers"]
    share = read("step.shard_hbm_share")
    assert share == pytest.approx(recorded["step.shard_hbm_share"], rel=1e-9)
    assert 0.0 < share < 100.0
    assert args["faults"] == "edge_drop:0.3,burst:4,mttf:400,mttr:150"
    assert args["fault_chains"] == "burst:0.3x4,churn:400/150"
    assert (args["fault_form"], args["fault_mixing"]) == ("timeline", "shift")
    assert (args["rejoin"], args["forward"]) == ("neighbor_restart", "recomputed")
    assert args["timeline_placement"] == "device"
    assert summary["memory_peak_bytes"] >= 0.25 * 16e9 and summary["correct"] is True


def test_readers_without_the_arguments_read_zero(monkeypatch):
    from distributed_optimization_tpu.observability import spans

    rows = harness.load_reader("faults.rejoin_rows_per_iter")
    down = harness.load_reader("faults.down_share")
    calls = {"calls": [{"wall_s": 40.0, "scan_s": 2.0, "iterations": 10}]}
    # the warm-up's root, the traced call's, and another experiment's
    tracer = make_tracer([(1.0, 0.5, {"rejoin_rows": 99, "down_share": 0.9}),
                          (2.0, 0.25, {"rejoin_rows": 4766, "down_share": 0.27}),
                          (7.0, 3.0, {"rejoin_rows": 1, "down_share": 0.01})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert rows(None, calls, {}) == 476.6 and down(None, calls, {}) == 27.0
    # the parent commit under these files, and a call without churn: roots
    # without the arguments give a number, not nothing
    tracer = make_tracer([(1.0, None, {}), (2.0, None, {"fault_form": "timeline"})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert rows(None, calls, {}) == 0.0 and down(None, calls, {}) == 0.0
    assert isinstance(rows(None, calls, {}), float) and isinstance(down(None, calls, {}), float)
    # no traced call at all, and a program with no tracer
    assert rows(None, {"calls": []}, {}) == 0.0 and down(None, {"calls": []}, {}) == 0.0
    monkeypatch.delattr(spans, "process_tracer")
    assert rows(None, calls, {}) == 0.0 and down(None, calls, {}) == 0.0


def test_the_steps_compulsory_bytes_come_from_the_file_alone():
    from benchmark.flops import glm_step

    config = load("configs", CONFIG + ".json")
    assert config["step_bytes"] == "glm_step"
    assert glm_step.compulsory_bytes(config) == (
        262144 * 53 * 82 * 4 + 2 * 262144 * 81 * 4)
