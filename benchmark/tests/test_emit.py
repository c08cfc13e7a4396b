"""The validator refuses the lines a driver would refuse."""

import copy

import pytest

from benchmark import emit

BENCH = {
    "workloads": [{"name": "c.m"}, {"name": "c.other"}],
    "end_to_end": [
        {"name": "iters_per_s", "unit": "iters/s"},
        {"name": "experiment_s", "unit": "s", "workloads": ["c.other"]},
        {"name": "setup_s", "unit": "s"},
    ],
    "per_layer": [
        {"name": "scan.device_us_per_iter", "unit": "us"},
        {"name": "step.flops_share", "unit": "%", "workloads": ["c.m"]},
    ],
}
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 5 * 10**9}


def traced_line():
    return {
        "correct": True, "attempted": 2, "failed": 0,
        "metrics": {"scan.device_us_per_iter": {"value": 11.5, "unit": "us"},
                    "step.flops_share": {"value": 55.0, "unit": "%"}},
        "device": dict(DEVICE, busy_s=1.5, window_s=2.0),
        "breakdown": {"device_ops": [["fusion.1", 1.0]], "idle_gaps": [["bench.call.0", 0.2]]},
    }


def plain_line():
    return {
        "correct": True, "attempted": 4, "failed": 0,
        "metrics": {"iters_per_s": {"value": 120.0, "unit": "iters/s"},
                    "setup_s": {"value": 50.0, "unit": "s"}},
        "device": dict(DEVICE),
    }


def test_sound_lines_pass():
    emit.validate(traced_line(), BENCH, "c.m", True)
    emit.validate(plain_line(), BENCH, "c.m", False)
    assert emit.dumps(plain_line()).count("\n") == 0


def _set(path, value):
    def change(line):
        node = line
        for key in path[:-1]:
            node = node[key]
        if value is KeyError:
            del node[path[-1]]
        else:
            node[path[-1]] = value
    return change


@pytest.mark.parametrize("traced,change", [
    (True, _set(("metrics", "step.flops_share"), KeyError)),             # a per-layer metric missing
    (True, _set(("metrics", "step.flops_share", "value"), float("nan"))),
    (True, _set(("metrics", "step.flops_share", "value"), None)),
    (True, _set(("metrics", "step.flops_share", "value"), 117.0)),        # a share above 100
    (True, _set(("metrics", "step.flops_share", "unit"), "percent")),
    (True, _set(("device", "busy_s"), 2.5)),                               # busy_s > window_s
    (True, _set(("device", "busy_s"), 0.0)),
    (True, _set(("device", "busy_s"), KeyError)),
    (True, _set(("device", "window_s"), 0.0)),
    (True, _set(("device", "memory_peak_bytes"), KeyError)),
    (True, _set(("breakdown", "device_ops"), [["x", 1.0]] * 11)),
    (False, _set(("device", "memory_peak_bytes"), KeyError)),
    (False, _set(("metrics", "setup_s"), KeyError)),
    (False, _set(("metrics", "experiment_s"), {"value": 1.0, "unit": "s"})),  # not this cell's
    (False, _set(("metrics", "iters_per_s", "value"), float("inf"))),
    (False, _set(("correct",), "true")),
    (False, _set(("failed",), 9)),
    (False, _set(("attempted",), KeyError)),
])
def test_bad_lines_are_refused(traced, change):
    line = copy.deepcopy(traced_line() if traced else plain_line())
    change(line)
    with pytest.raises(ValueError):
        emit.validate(line, BENCH, "c.m", traced)
        emit.dumps(line)


def test_emit_prints_nothing_it_refused(capsys):
    line = plain_line()
    line["metrics"]["iters_per_s"]["value"] = float("nan")
    with pytest.raises(SystemExit) as exc:
        emit.emit(line, BENCH, "c.m", False)
    assert exc.value.code != 0
    assert capsys.readouterr().out == ""
