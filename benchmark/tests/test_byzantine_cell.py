"""The Byzantine cell (ISSUE 43): a round that screens less than the
configuration states, or metrics that count the attackers' rows, are not
correct by the cell's own limits (``test_rehearsal.py``'s breaks alter a
result after the fact; these break the layer itself), and the cell's four
readers read what they say off a summary recorded on the chip and off the run
builder's root spans, and give a number, never nothing, where a program has
no such scope, span or argument."""

import json
import os

import pytest

from benchmark import emit, scope_reduce
from benchmark import run as harness

from . import test_scope_metrics
from .conftest import ROOT, run_harness, strict_loads
from .test_scope_metrics import facts_of, use

CELL = "glm81_ring262k_signflip_tm1.screen1k"
CONFIG = "glm81_ring262k_signflip_tm1"

# ``test_scope_metrics.ONLY_IN`` is the table of the scopes that one cell alone
# reports, read when its traced rehearsals run. A PR that adds a cell may add
# files and edit none, so the new cell's scope is entered from here, at
# collection (PERF.md section 7, row 10: the next ``benchmark`` issue moves the
# entry into that table and drops this line). Run alone,
# ``test_scope_metrics.py`` does not know the cell's ninth scope.
test_scope_metrics.ONLY_IN.setdefault("scan.robust_us_per_iter", CELL)

BROKEN = """
import sys
import numpy as np
from distributed_optimization_tpu.backends import jax_backend
from distributed_optimization_tpu.parallel import adversary
{how}
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
"""

BREAKS = {
    # honest workers average what they receive: the rule is never applied
    "screening_skipped": """
real = jax_backend.make_byzantine_mixing
jax_backend.make_byzantine_mixing = lambda adv, base, aggregate_t=None: real(adv, base)
""",
    # the mean model and the consensus error over every row, attackers' too
    "attackers_counted": """
adversary.Adversary.honest = property(lambda self: np.ones_like(self.byzantine))
""",
}


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as fh:
        return json.load(fh)


@pytest.mark.parametrize("how", sorted(BREAKS))
def test_a_round_that_does_less_is_not_correct(bench, how):
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "79", "--seconds", "0.3", "--trace", "0",
         "--rehearse"], prelude=BROKEN.format(how=BREAKS[how]))
    assert rc == 0, err[-2000:]
    line = strict_loads(out.splitlines()[-1])
    emit.validate(line, bench, CELL, False)
    assert line["correct"] is False
    assert "OVER" in err


def test_the_rehearsed_line_carries_the_four_readers(bench):
    """No device plane on the CPU: the two trace readers give 0.0, the
    counter 0.0 (the tables are constants), the span the seconds the traced
    call spent placing the attackers."""
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "3400000043", "--seconds", "0.3", "--trace", "1",
         "--rehearse"])
    assert rc == 0, err[-2000:]
    line = strict_loads(out.splitlines()[-1])
    emit.validate(line, bench, CELL, True)
    assert line["correct"] is True, err[-2000:]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["scan.robust_us_per_iter"] == 0.0
    assert metrics["robust.screen_hbm_share"] == 0.0
    assert metrics["robust.table_bytes"] == 0.0
    assert 0.0 < metrics["adversary.build_s"] < 1.0
    assert metrics["scan.unattributed_us_per_iter"] == metrics["scan.device_us_per_iter"]
    for cell in bench["workloads"]:
        expected = emit.expected_metrics(bench, cell["name"], True)
        assert ("scan.robust_us_per_iter" in expected) == (cell["name"] == CELL)


def recorded_table(summary):
    """The compiled program's table as the recorded run's log gave it: one
    instruction a row of the ten, carrying the scope the log billed it to."""
    rows = []
    for name, scope in summary["row_scopes"].items():
        stem, _, out = name.partition(" ")
        rows.append({"head": f"%{stem}.1 = {out}{{", "scope": scope, "also": []})
    return {"module": "jit_seg_scan", "text_s": 0.0, "parse_s": 0.0, "rows": rows}


def test_readers_on_a_summary_recorded_on_the_chip(monkeypatch):
    """``testdata/signflip_tm1_screen1k.summary.json`` is the reduction of a
    traced run of the cell on one v5e (busy seconds, the ten largest rows and
    the scope the program's table gave each) and the root span's arguments
    of the traced call."""
    from .test_scope_metrics import make_tracer

    config = load("configs", CONFIG + ".json")
    summary = load("testdata", "signflip_tm1_screen1k.summary.json")
    recorded, args = summary["recorded"], summary["root_args"]
    scan_s = summary["scan_s"]
    tracer = make_tracer([(scan_s - 1.0, {"program": "warm"}),
                          (scan_s, dict(args, program="traced"))])
    use(monkeypatch, tracer, {"traced": recorded_table(summary)})
    facts = dict(facts_of(scan_s, iterations=summary["iterations"]),
                 peaks=load("peaks.json")["TPU v5 lite"], n_devices=1)

    def read(name):
        return harness.load_reader(name)(summary, facts, config)

    rows = dict(summary["device_ops"])
    robust_s = sum(rows[n] for n, s in summary["row_scopes"].items() if s == "robust")
    assert robust_s > 0
    us = read("scan.robust_us_per_iter")
    assert us == pytest.approx(robust_s * 1e6 / summary["iterations"])
    assert us == pytest.approx(recorded["scan.robust_us_per_iter"], rel=1e-9)
    share = read("robust.screen_hbm_share")
    exp = config["experiment"]
    compulsory = 2 * exp["n_workers"] * 81 * 4 + exp["n_workers"] * 2 * 8
    assert share == pytest.approx(100.0 * compulsory / (us * 1e-6 * 819e9))
    assert share == pytest.approx(recorded["robust.screen_hbm_share"], rel=1e-9)
    assert 0.0 < share < 100.0
    assert read("robust.table_bytes") == recorded["robust.table_bytes"] == args["robust_bytes"]
    # the scopes and the unattributed sum to the device's busy time
    names = [m for m in recorded if m.startswith("scan.") and m.endswith("_us_per_iter")
             and m != "scan.device_us_per_iter"]
    assert sum(read(m) for m in names) == pytest.approx(read("scan.device_us_per_iter"))
    assert read("scan.unattributed_us_per_iter") < 0.1 * read("scan.device_us_per_iter")
    assert args["attack"] == "sign_flip:24576/262144" and args["budget_max"] == 1
    assert args["byzantine_placement"] == "within_budget"
    assert (args["aggregation"], args["robust_impl"]) == ("trimmed_mean:b=1", "gather")
    assert args["screened_rows"] == 3 * 262144 and args["forward"] == "fused"


def test_readers_without_scope_span_argument_or_trace_read_zero(monkeypatch):
    from distributed_optimization_tpu.observability import spans

    from .test_fault_cell import make_tracer

    config = load("configs", CONFIG + ".json")
    share = harness.load_reader("robust.screen_hbm_share")
    us = harness.load_reader("scan.robust_us_per_iter")
    held = harness.load_reader("robust.table_bytes")
    seconds = harness.load_reader("adversary.build_s")
    calls = {"calls": [{"wall_s": 40.0, "scan_s": 2.0, "iterations": 10}], "iterations": 10,
             "peaks": {"hbm_bytes_per_s": 819e9}}
    # the parent commit under these files: roots without the argument or the span
    tracer = make_tracer([(1.0, None, {}), (2.0, None, {"placement": "direct"})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    monkeypatch.setattr(scope_reduce, "_last", None)
    stand_in = {"busy_s": 2.0, "device_ops": [], "idle_gaps": []}  # a rehearsal's
    assert us(stand_in, calls, config) == 0.0
    assert share(stand_in, calls, config) == 0.0 and share(None, calls, config) == 0.0
    assert share(stand_in, calls, {"name": CONFIG}) == 0.0  # no rule named
    assert held(None, calls, config) == 0.0 and seconds(None, calls, config) == 0.0
    assert isinstance(seconds(None, calls, config), float)
    # a program with the span and the argument
    tracer = make_tracer([(1.0, None, {"robust_bytes": 0.0}), (2.0, None, {"robust_bytes": 4096.0})])
    with tracer.span("dopt.run", aggregate=False):
        tracer.add_span("dopt.run.adversary", 0.125, aggregate=False)
        tracer.add_span("dopt.run.scan", 3.0, aggregate=False)
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert held(None, calls, config) == 4096.0
    calls["calls"][0]["scan_s"] = 3.0
    assert seconds(None, calls, config) == 0.125
    # and one with no tracer at all
    monkeypatch.delattr(spans, "process_tracer")
    assert held(None, calls, config) == 0.0 and seconds(None, calls, config) == 0.0


def test_the_rounds_compulsory_bytes_come_from_the_file_alone():
    from benchmark.flops import robust_round

    config = load("configs", CONFIG + ".json")
    assert robust_round.per_round_bytes(config) == 2 * 262144 * 81 * 4 + 262144 * 2 * 8
    small = dict(config, experiment=dict(config["experiment"], n_workers=64))
    assert robust_round.per_round_bytes(small) == 64 * (2 * 81 * 4 + 16)
