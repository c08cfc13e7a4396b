"""The reduction from trace to numbers, on hand-made planes and on a small
trace recorded on the chip."""

import gzip
import json
import os

import pytest

from benchmark import trace_reduce

from .conftest import ROOT


def test_union_and_gaps():
    busy, gaps = trace_reduce.union_seconds([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert busy == pytest.approx(35e-9)
    assert gaps == [(20, 30)]


def test_busy_is_the_mean_over_devices_and_skips_containers():
    planes = {
        "/device:TPU:0": {"XLA Ops": [("while.3", 0.0, 1000.0), ("fusion.1", 0.0, 100.0),
                                      ("fusion.2", 200.0, 100.0)],
                          "XLA Modules": [("jit_run_scan", 0.0, 1000.0)]},
        "/device:TPU:1": {"XLA Ops": [("fusion.1", 0.0, 400.0)]},
        "/host:CPU": {"python": [("bench.call.0", 0.0, 1000.0), ("other", 0.0, 5.0)]},
    }
    out = trace_reduce.reduce_planes(planes)
    assert out["n_devices"] == 2
    assert out["busy_s_per_device"] == pytest.approx([200e-9, 400e-9])
    assert out["busy_s"] == pytest.approx(300e-9)  # never the sum
    # unrolled copies (fusion.1, fusion.2) add up under one row, averaged over devices
    assert out["device_ops"] == [["fusion", pytest.approx(300e-9)]]
    # the wait after the call's last operation, then the gap between two operations
    assert out["idle_gaps"][:2] == [["bench.call.0", pytest.approx(700e-9)],
                                    ["bench.call.0", pytest.approx(100e-9)]]


def test_op_kind_drops_the_suffix_and_keeps_the_output_type():
    name = ("%multiply_reduce_fusion.104 = f32[262144,81]{0,1:T(8,128)} "
            "fusion(f32[262144,53,81]{0,1,2:T(8,128)} %get-tuple-element.655)")
    assert trace_reduce.op_kind(name) == "multiply_reduce_fusion f32[262144,81]"
    assert trace_reduce._is_container("%while.3 = (s32[]{:T(128)}) while(...)")
    assert not trace_reduce._is_container(name)


def test_no_device_plane_is_nothing_to_read():
    assert trace_reduce.reduce_planes({"/host:CPU": {"python": []}}) is None


def test_recorded_chip_trace(tmp_path):
    """A trace recorded on a v5e (the GLM cell at its rehearsal size, two
    traced calls; PR 23), reduced: the numbers are fixed."""
    path = str(tmp_path / "v5e_short.xplane.pb")
    with gzip.open(os.path.join(ROOT, "benchmark", "testdata", "v5e_short.xplane.pb.gz")) as src:
        with open(path, "wb") as dst:
            dst.write(src.read())
    want = json.load(open(os.path.join(ROOT, "benchmark", "testdata", "v5e_short.expected.json")))
    got = trace_reduce.reduce(path)
    assert got["n_devices"] == want["n_devices"]
    assert got["n_events"] == want["n_events"]
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert [r[0] for r in got["device_ops"]] == [r[0] for r in want["device_ops"]]
    for g, w in zip(got["device_ops"], want["device_ops"]):
        assert g[1] == pytest.approx(w[1], rel=1e-9)
    assert [g[0] for g in got["idle_gaps"]] == [w[0] for w in want["idle_gaps"]]
    for g, w in zip(got["idle_gaps"], want["idle_gaps"]):
        assert g[1] == pytest.approx(w[1], rel=1e-9)
