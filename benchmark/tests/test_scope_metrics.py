"""Device time by phase as per-layer metrics (ISSUE 34): ``scope_reduce``
against a ten-row summary, a tracer and a scope table built by hand; against
a program module without ``device_scopes`` (the parent commit under these
files); and in a traced rehearsal of every cell."""

import math
import sys

import pytest

from benchmark import emit, scope_reduce
from benchmark.run import load_reader

from .conftest import run_harness, strict_loads
from .test_rehearsal import CELLS

SCOPED = ["scan.sampling_us_per_iter", "scan.gradient_us_per_iter",
          "scan.gossip_us_per_iter", "scan.update_us_per_iter",
          "scan.eval_us_per_iter"]
ONLY_IN = {"scan.compress_us_per_iter": "softmax4096_choco_ring96.top1pct",
           "scan.faults_us_per_iter": "glm81_ring262k_drop30strag10.steady1k"}
GLM = {"name": "glm81_ring262k"}
FAULTY = {"name": "glm81_ring262k_drop30strag10"}

# What the program hands over: instruction heads as compiled text prints
# them, two unrolled copies a kind.
TABLE = {"module": "jit_seg_scan", "text_s": 0.0, "parse_s": 0.0, "rows": [
    {"head": "%multiply_reduce_fusion.104 = f32[262144,81]{0,1:T(8,128)}",
     "scope": "gradient", "also": ["sampling"]},
    {"head": "%multiply_reduce_fusion.105 = f32[262144,81]{0,1:T(8,128)}",
     "scope": "gradient", "also": []},
    {"head": "%fusion.7 = (f32[81]{0:T(128)}, f32[262144,81]{0,1:T(8,128)})",
     "scope": "eval", "also": ["update", "gossip"]},
    # one kind, two scopes: the training softmax and the eval's
    {"head": "%fusion.8 = (f32[96,2048]{1,0}, f32[96,2048]{1,0})",
     "scope": "gradient", "also": []},
    {"head": "%fusion.9 = (f32[96,2048]{1,0}, f32[96,2048]{1,0})",
     "scope": "eval", "also": []},
    {"head": "%copy-done.3 = f32[262144,53]{0,1:T(8,128)}", "scope": None, "also": []},
    {"head": "%compare_convert_fusion.2 = f32[1,262144]{1,0}",
     "scope": "faults", "also": []},
]}
TRACE = {"busy_s": 20.0, "idle_gaps": [], "device_ops": [
    ["multiply_reduce_fusion f32[262144,81]", 8.0],   # pure: gradient
    ["fusion (f32[81]", 4.0],                         # pure, fused: eval, whole
    ["fusion (f32[96,2048]", 3.0],                    # two scopes: None
    ["copy-done f32[262144,53]", 1.0],                # no scope: None
    ["compare_convert_fusion f32[1,262144]", 0.5],    # faults
    ["reshape f32[262144,2]", 0.25],                  # not in the table: None
]}


class FakeScopes:
    """Stands where ``observability.device_scopes`` stands."""

    def __init__(self, tables):
        self.tables, self.asked = tables, []

    def table_for(self, program):
        self.asked.append(program)
        return self.tables.get(program)


def use(monkeypatch, tracer, tables):
    from distributed_optimization_tpu.observability import spans

    fake = FakeScopes(tables)
    monkeypatch.setattr(spans, "process_tracer", lambda: tracer)
    monkeypatch.setattr(scope_reduce, "_device_scopes", lambda: fake)
    monkeypatch.setattr(scope_reduce, "_last", None)
    return fake


def make_tracer(roots):
    """One ``dopt.run`` root for each (scan seconds, root arguments)."""
    from distributed_optimization_tpu.observability.spans import Tracer

    tracer = Tracer()
    for scan, args in roots:
        with tracer.span("dopt.run", aggregate=False, **args):
            tracer.add_span("dopt.run.scan", scan, aggregate=False)
    return tracer


def facts_of(*scans, iterations=1000):
    return {"iterations": iterations * len(scans),
            "calls": [{"wall_s": s + 1.0, "scan_s": s, "iterations": iterations}
                      for s in scans]}


def test_rows_are_billed_to_the_scope_all_their_instructions_share(monkeypatch, capfd):
    # the warm-up's root, then the traced call's
    tracer = make_tracer([(19.0, {"program": "warm", "temp_bytes": 1}),
                          (21.0, {"program": "p1", "temp_bytes": 785129472})])
    fake = use(monkeypatch, tracer, {"p1": TABLE})
    facts = facts_of(21.0)
    got = scope_reduce.by_scope(TRACE, facts)
    assert got == {"gradient": 8.0, "eval": 4.0, "faults": 0.5, None: 7.5}
    assert sum(got.values()) == TRACE["busy_s"]
    assert fake.asked == ["p1"]
    err = capfd.readouterr().err
    assert "fusion (f32[81]  ->  eval  (also: gossip, update)" in err
    assert "instructions of different scopes: eval, gradient" in err
    assert "no such instruction in the program's table" in err
    assert "no scope on its instructions" in err
    # the readers of one line share the one pass
    assert scope_reduce.by_scope(TRACE, facts) is got and fake.asked == ["p1"]

    def read(name, config):
        return load_reader(name)(TRACE, facts, config)

    # a cell that reports ``faults``: scopes and unattributed sum to the device's
    values = {name: read(name, FAULTY) for name in
              SCOPED + ["scan.faults_us_per_iter", "scan.unattributed_us_per_iter"]}
    assert values["scan.gradient_us_per_iter"] == 8000.0
    assert values["scan.eval_us_per_iter"] == 4000.0
    assert values["scan.faults_us_per_iter"] == 500.0
    assert values["scan.update_us_per_iter"] == values["scan.sampling_us_per_iter"] == 0.0
    assert values["scan.unattributed_us_per_iter"] == 7500.0
    assert sum(values.values()) == read("scan.device_us_per_iter", FAULTY) == 20000.0
    # a cell that does not: what lies under that scope is unattributed there, and said
    capfd.readouterr()
    assert read("scan.unattributed_us_per_iter", GLM) == 8000.0
    assert "'faults', which this cell does not report" in capfd.readouterr().err
    assert read("scan.temp_bytes", GLM) == 785129472.0


def test_which_scopes_a_configurations_cells_report():
    assert scope_reduce.reported(GLM) == {"sampling", "gradient", "gossip", "update", "eval"}
    assert scope_reduce.reported(FAULTY) == scope_reduce.reported(GLM) | {"faults"}
    assert scope_reduce.reported({"name": "softmax4096_choco_ring96"}) == (
        scope_reduce.reported(GLM) | {"compress"})


def test_overlapped_rows_cannot_make_the_unattributed_negative(monkeypatch, capfd):
    use(monkeypatch, make_tracer([(5.0, {"program": "p1"})]), {"p1": TABLE})
    trace = {"busy_s": 7.0, "device_ops": [["multiply_reduce_fusion f32[262144,81]", 8.0]]}
    assert scope_reduce.by_scope(trace, facts_of(5.0)) == {"gradient": 8.0, None: 0.0}
    assert "over the busy" in capfd.readouterr().err


def test_a_table_the_process_no_longer_holds_bills_nothing(monkeypatch, capfd):
    use(monkeypatch, make_tracer([(5.0, {"program": "gone"})]), {})
    assert scope_reduce.by_scope(TRACE, facts_of(5.0)) == {None: 20.0}
    assert "holds the table of 0" in capfd.readouterr().err


def all_readers(config):
    names = SCOPED + list(ONLY_IN) + ["scan.unattributed_us_per_iter", "scan.temp_bytes"]
    return lambda trace, facts: {
        name: load_reader(name)(trace, facts, config) for name in names}


def test_a_program_without_device_scopes_reads_numbers(monkeypatch, capfd):
    """The parent commit under these files: every scope 0.0, the unattributed
    the whole of the device's time, ``scan.temp_bytes`` 0.0; nothing raises
    and nothing is None (either would refuse the parent's traced line)."""
    from distributed_optimization_tpu import observability

    monkeypatch.setattr(scope_reduce, "_last", None)
    monkeypatch.delattr(observability, "device_scopes", raising=False)
    monkeypatch.setitem(
        sys.modules, "distributed_optimization_tpu.observability.device_scopes", None)
    from distributed_optimization_tpu.observability import spans

    # its roots carry neither ``program`` nor ``temp_bytes``
    monkeypatch.setattr(spans, "process_tracer", lambda: make_tracer([(21.0, {})]))
    assert scope_reduce._device_scopes() is None
    values = all_readers(FAULTY)(TRACE, facts_of(21.0))
    assert values.pop("scan.unattributed_us_per_iter") == 20000.0
    assert set(values.values()) == {0.0}
    assert "no observability.device_scopes" in capfd.readouterr().err


def test_a_rehearsal_without_a_device_plane_reads_numbers(monkeypatch):
    fake = use(monkeypatch, make_tracer([(21.0, {"program": "p1", "temp_bytes": 9})]),
               {"p1": TABLE})
    trace = {"busy_s": 21.0, "device_ops": [], "idle_gaps": []}
    values = all_readers(GLM)(trace, facts_of(21.0))
    assert values.pop("scan.unattributed_us_per_iter") == 21000.0
    assert values.pop("scan.temp_bytes") == 9.0
    assert set(values.values()) == {0.0}
    assert fake.asked == []  # no rows: no table is asked for, no text is read


@pytest.mark.parametrize("cell,chips", CELLS)
def test_traced_rehearsal_prints_the_scope_metrics(bench, cell, chips):
    rc, out, err = run_harness(
        ["--workload", cell, "--seed", "2147483555", "--seconds", "0.5",
         "--trace", "1", "--rehearse"], devices=chips)
    assert rc == 0, err[-2000:]
    line = emit.validate(strict_loads(out.splitlines()[-1]), bench, cell, True)
    values = {k: v["value"] for k, v in line["metrics"].items()}
    names = SCOPED + [n for n, only in ONLY_IN.items() if only == cell]
    assert set(names) == {n for n in values if n.startswith("scan.")} - {
        "scan.device_us_per_iter", "scan.unattributed_us_per_iter", "scan.temp_bytes"}
    # no device plane on the CPU: nothing billed, the whole unattributed
    assert all(values[n] == 0.0 for n in names)
    assert math.isclose(values["scan.unattributed_us_per_iter"],
                        values["scan.device_us_per_iter"], rel_tol=1e-12)
    assert values["scan.temp_bytes"] > 0 and line["metrics"]["scan.temp_bytes"]["unit"] == "B"
    assert "[scope_reduce]" in err
