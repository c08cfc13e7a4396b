"""The compressed-gossip cell (ISSUE 26): its two controls are not correct by
the cell's own limits, its configuration file states what the cell runs, and
``gossip.compress_share`` reads the rows the file names off a summary
recorded on the chip."""

import json
import math
import os

import pytest

from benchmark import compare, datasets
from benchmark import run as harness
from benchmark.reference import choco_ring

from .conftest import ROOT

NAME, MIX = "softmax4096_choco_ring96", "top1pct"


def load(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as fh:
        return json.load(fh)


@pytest.mark.parametrize("control", ["bfloat16", "identity"])
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_control_is_not_correct(bench, control, seed):
    """The reference with bfloat16 state and operands, and the reference with
    no compressor, in the program's place: not correct by the cell's limits."""
    _, config, traffic = harness.load_cell(bench, f"{NAME}.{MIX}", rehearse=True)
    assert control in config["precision"]["controls"]
    X, y, _ = datasets.make(config, seed)
    ref = choco_ring.run(config, traffic, X, y, seed)
    ctl = choco_ring.run(config, traffic, X, y, seed, precision=control)
    said = []
    assert not compare.judge(compare.numbers(ctl, ref), config["limits"][MIX], said.append), said


def test_the_file_states_what_the_cell_runs(bench):
    config = load("configs", NAME + ".json")
    exp = config["experiment"]
    row = (exp["n_features"] + 1) * exp["n_classes"]
    assert row == 2097664 and exp["compression_k"] == math.ceil(0.01 * row) == 20977
    assert (exp["algorithm"], exp["compression"], exp["choco_gamma"]) == ("choco", "top_k", 0.04)
    sibling = load("configs", "softmax4096_ring96.json")
    same = set(sibling["experiment"]) - {"algorithm"}
    assert {k: exp[k] for k in same} == {k: sibling["experiment"][k] for k in same}
    assert config["dataset"] == sibling["dataset"]
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["reduced"] == config["reduced"] and set(config["reduced_why"]) == set(entry["reduced"])
    traffic = load("traffic", MIX + ".json")
    T = traffic["n_iterations"]
    assert T % 10 == 0 and 20 <= T <= 2000 and traffic["eval_every"] == 10
    assert traffic["check_iterations"] == 20 and traffic["trace_calls"] == 1
    assert traffic["gates"]["objective_below"] < math.log(exp["n_classes"])


def test_compress_share_reads_the_named_rows_off_a_recorded_summary():
    """``testdata/choco_top1pct.summary.json`` is the reduction of a traced
    run of the cell on the chip (busy seconds and the ten largest rows)."""
    config = load("configs", NAME + ".json")
    summary = load("testdata", "choco_top1pct.summary.json")
    names = set(config["compressor_ops"])
    rows = dict(summary["device_ops"])
    # every row of the recorded table that works on a worker's whole flat row
    # is named, and the flatten's return beside them
    flat = {n for n in rows if "2097664]" in n or "201375744]" in n}
    assert flat and flat <= names and "reshape f32[96,4097,512]" in names
    # the D-SGD step's rows are not
    assert not names & {"fusion (f32[96,4097,512]", "fusion f32[96,2048,512]",
                        "fusion (f32[95,4097,512]", "fusion f32[96,4097,512]",
                        "add f32[96,4097,512]"}
    names &= set(rows)
    want = 100.0 * sum(rows[n] for n in names) / summary["busy_s"]
    got = harness.load_reader("gossip.compress_share")(summary, {}, config)
    assert got == pytest.approx(want) and 90.0 < got <= 100.0
    assert got == pytest.approx(summary["recorded_share"], rel=1e-9)


def test_compress_share_without_names_or_trace_reports_nothing():
    read = harness.load_reader("gossip.compress_share")
    summary = {"busy_s": 2.0, "device_ops": [["sort (f32[96,2097664]", 1.0]]}
    assert read(None, {}, {"compressor_ops": ["sort (f32[96,2097664]"]}) is None
    assert read(summary, {}, {}) is None
    assert read(summary, {}, {"compressor_ops": ["sort (f32[96,2097664]"]}) == 50.0
    # a program whose table has none of the rows: a share of 0, not an error
    assert read(summary, {}, {"compressor_ops": ["another"]}) == 0.0
