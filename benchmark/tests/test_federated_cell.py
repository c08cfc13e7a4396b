"""The federated cell (ISSUE 50): a round that does less than the
configuration states (no local descents, local descents on one batch,
sampled-out workers that step) is not correct by the cell's own limits
(``test_rehearsal.py``'s breaks alter a result after the fact; these break
the round itself), and the cell's three new readers read what they say off a
summary recorded on the chip and off the run builder's root spans, and give
a number, never nothing, where a program has no such scope or argument."""

import json
import os

import pytest

from benchmark import emit, scope_reduce
from benchmark import run as harness

from . import test_scope_metrics
from .conftest import ROOT, run_harness, strict_loads
from .test_fault_cell import make_tracer
from .test_scope_metrics import facts_of, use

CELL = "glm81_ring262k_local4_part50.rounds250"
CONFIG = "glm81_ring262k_local4_part50"
NEW = ("scan.local_us_per_iter", "local.shard_reads_per_iter", "faults.sampled_out_share")

# ``test_scope_metrics.ONLY_IN`` is the table of the scopes that one cell alone
# reports, read when its traced rehearsals run. A PR that adds a cell may add
# files and edit none, so the new cell's scope is entered from here, at
# collection, as ``test_byzantine_cell.py`` enters its own (PERF.md section 7,
# row 11). Run alone, ``test_scope_metrics.py`` does not know the tenth scope.
test_scope_metrics.ONLY_IN.setdefault("scan.local_us_per_iter", CELL)

BROKEN = """
import dataclasses, sys
from distributed_optimization_tpu.algorithms import dsgd
from distributed_optimization_tpu.backends import jax_backend
{how}
from benchmark import run
sys.exit(run.main(sys.argv[1:]))
"""

BREAKS = {
    # a round of one gradient: the local descents never run
    "local_descents_skipped": """
dsgd.local_descent_loop = lambda v, ctx, direction: v
""",
    # every local descent on slot 0's batch
    "local_batches_reused": """
real = dsgd.local_descent_loop
dsgd.local_descent_loop = lambda v, ctx, direction: real(
    v, ctx, lambda vv, s: direction(vv, 0))
""",
    # a sampled-out worker's links drop but it takes the round's four steps
    "sampled_out_workers_step": """
real = jax_backend.make_faulty_mixing
def unfrozen(fm):  # the layer itself and what it is over the scan's tables
    bind = fm.bind and (lambda tables: unfrozen(fm.bind(tables)))
    return dataclasses.replace(fm, participation_active=False, bind=bind)
jax_backend.make_faulty_mixing = lambda *a, **kw: unfrozen(real(*a, **kw))
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
    assert "consensus_max_rel" in err and "OVER" in err


def test_the_traced_rehearsal_carries_the_three_readers(bench):
    """The counters are the program's, so a rehearsal reads them too: 64
    workers over 40 rounds, half of them out; the scope's time is a device
    trace's and reads 0.0 on the CPU."""
    rc, out, err = run_harness(
        ["--workload", CELL, "--seed", "3400000050", "--seconds", "0.3", "--trace", "1",
         "--rehearse"])
    assert rc == 0, err[-2000:]
    line = strict_loads(out.splitlines()[-1])
    emit.validate(line, bench, CELL, True)
    assert line["correct"] is True, err[-2000:]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["local.shard_reads_per_iter"] == 3 + 2 * 3  # recomputed on a CPU
    assert 47.0 < metrics["faults.sampled_out_share"] < 53.0
    assert metrics["scan.local_us_per_iter"] == 0.0
    assert metrics["faults.state_bytes"] == 40 * 64  # one leaf, a byte a bit
    assert 0.0 < metrics["faults.timeline_s"] < 5.0
    assert 0.0 < metrics["step.shard_hbm_share"] < 100.0
    for cell in bench["workloads"]:
        expected = emit.expected_metrics(bench, cell["name"], True)
        assert all((name in expected) == (cell["name"] == CELL) for name in NEW)
    assert "scan.faults_us_per_iter" not in emit.expected_metrics(bench, CELL, True)


def test_readers_on_a_summary_recorded_on_the_chip(monkeypatch):
    """``testdata/local4_part50_rounds250.summary.json`` is the reduction of
    a traced run of the cell on one v5e (busy seconds, the ten largest rows)
    with the line's metrics and the traced call's root arguments."""
    from distributed_optimization_tpu.observability import spans

    config = load("configs", CONFIG + ".json")
    summary = load("testdata", "local4_part50_rounds250.summary.json")
    recorded, args = summary["recorded"], summary["root_args"]
    scan_s, T = summary["scan_s"], summary["iterations"]
    tracer = make_tracer([(scan_s - 1.0, 0.5, {"shard_reads": 99, "sampled_out_share": 0.9}),
                          (scan_s, recorded["faults.timeline_s"], args)])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    facts = {"calls": [{"wall_s": summary["wall_s"], "scan_s": scan_s, "iterations": T}],
             "iterations": T, "n_devices": 1, "peaks": load("peaks.json")["TPU v5 lite"]}

    def read(name):
        return harness.load_reader(name)(summary, facts, config)

    exp = config["experiment"]
    tau = exp["local_steps"]
    assert read("local.shard_reads_per_iter") == args["shard_reads"] == 1 + 2 * (tau - 1)
    assert read("local.shard_reads_per_iter") == recorded["local.shard_reads_per_iter"]
    assert read("faults.sampled_out_share") == 100.0 * args["sampled_out_share"]
    assert read("faults.sampled_out_share") == recorded["faults.sampled_out_share"]
    assert read("faults.sampled_out_share") == pytest.approx(
        100.0 * (1.0 - exp["participation_rate"]), abs=0.1)
    assert read("faults.timeline_s") == recorded["faults.timeline_s"]
    # the one leaf, a byte a bit, in the device's own tiles
    assert read("faults.state_bytes") == recorded["faults.state_bytes"] == args["fault_bytes"]
    assert T * exp["n_workers"] <= args["fault_bytes"] < 2 * T * exp["n_workers"]
    share = read("step.shard_hbm_share")
    assert share == pytest.approx(recorded["step.shard_hbm_share"], rel=1e-9)
    assert 0.0 < share < 100.0
    # the later descents' gradients are the largest of the cell's scopes
    scopes = {k: v for k, v in recorded.items()
              if k.startswith("scan.") and k.endswith("_us_per_iter")
              and k not in ("scan.device_us_per_iter", "scan.unattributed_us_per_iter")}
    assert max(scopes, key=scopes.get) == "scan.local_us_per_iter"
    assert recorded["scan.local_us_per_iter"] > recorded["scan.gradient_us_per_iter"] > 0.0
    assert (args["local_steps"], args["forward"], args["local_forward"]) == (
        tau, "fused", "recomputed")
    assert args["faults"] == "participation:0.5" and "fault_chains" not in args
    assert (args["fault_form"], args["fault_mixing"]) == ("timeline", "shift")
    assert args["timeline_placement"] == "device"
    assert summary["memory_peak_bytes"] >= 0.25 * 16e9 and summary["correct"] is True


def test_the_scopes_time_is_read_through_the_programs_table(monkeypatch, capfd):
    """The three plain gradients' rows carry ``local`` in the program's own
    table and are billed to it; the visit's row stays ``gradient``."""
    config = load("configs", CONFIG + ".json")
    table = {"module": "jit_seg_scan", "text_s": 0.0, "parse_s": 0.0, "rows": [
        {"head": "%glm_shard_visit.1 = (f32[81,262144]{1,0:T(8,128)}, f32[262144]{0})",
         "scope": "gradient", "also": []},
        *({"head": f"%multiply_reduce_fusion.{k} = f32[262144,53]{{1,0:T(8,128)}}",
           "scope": "local", "also": []} for k in (3, 4, 5)),
        *({"head": f"%multiply_reduce_fusion.{k} = f32[262144,81]{{0,1:T(8,128)}}",
           "scope": "local", "also": ["update"]} for k in (6, 7, 8)),
    ]}
    trace = {"busy_s": 12.0, "idle_gaps": [], "device_ops": [
        ["multiply_reduce_fusion f32[262144,53]", 5.0],
        ["multiply_reduce_fusion f32[262144,81]", 4.0],
        ["glm_shard_visit (f32[81,262144]", 2.0]]}
    facts = facts_of(10.0, iterations=250)
    use(monkeypatch, test_scope_metrics.make_tracer([(10.0, {"program": "prog"})]),
        {"prog": table})
    local = harness.load_reader("scan.local_us_per_iter")
    gradient = harness.load_reader("scan.gradient_us_per_iter")
    assert local(trace, facts, config) == pytest.approx(9.0e6 / 250)
    assert gradient(trace, facts, config) == pytest.approx(2.0e6 / 250)
    assert "->  local  (also: update)" in capfd.readouterr().err
    assert "local" in scope_reduce.reported(config)
    assert "faults" not in scope_reduce.reported(config)


def test_readers_without_the_arguments_read_zero(monkeypatch):
    from distributed_optimization_tpu.observability import spans

    reads = harness.load_reader("local.shard_reads_per_iter")
    out = harness.load_reader("faults.sampled_out_share")
    calls = {"calls": [{"wall_s": 40.0, "scan_s": 2.0, "iterations": 10}]}
    # the warm-up's root, the traced call's, and another experiment's
    tracer = make_tracer([(1.0, 0.5, {"shard_reads": 9, "sampled_out_share": 0.9}),
                          (2.0, 0.25, {"shard_reads": 7, "sampled_out_share": 0.5}),
                          (7.0, 3.0, {"shard_reads": 4, "sampled_out_share": 0.01})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert reads(None, calls, {}) == 7.0 and out(None, calls, {}) == 50.0
    # the parent commit under these files, and a call of one gradient a round
    # with everyone taking part: roots without the arguments give a number
    tracer = make_tracer([(1.0, None, {}), (2.0, None, {"fault_form": "timeline"})])
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    assert reads(None, calls, {}) == 0.0 and out(None, calls, {}) == 0.0
    assert isinstance(reads(None, calls, {}), float) and isinstance(out(None, calls, {}), float)
    # no traced call at all, and a program with no tracer
    assert reads(None, {"calls": []}, {}) == 0.0 and out(None, {"calls": []}, {}) == 0.0
    monkeypatch.delattr(spans, "process_tracer")
    assert reads(None, calls, {}) == 0.0 and out(None, calls, {}) == 0.0


def test_the_rounds_compulsory_bytes_come_from_the_file_alone():
    from benchmark.flops import glm_local_steps

    config = load("configs", CONFIG + ".json")
    assert config["step_bytes"] == "glm_local_steps"
    assert glm_local_steps.compulsory_bytes(config) == (
        4 * 262144 * 53 * 82 * 4 + 2 * 262144 * 81 * 4)
