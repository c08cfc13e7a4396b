"""Every cell, end to end at its rehearsal size, in both modes: the last line
of stdout is strict JSON and passes the validator it was printed through."""

import json
import os
import shutil

import pytest

from benchmark import emit

from .conftest import ROOT, run_harness, strict_loads

with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    CELLS = [(w["name"], w["chips"]) for w in json.load(_fh)["workloads"]]


@pytest.mark.parametrize("traced", [0, 1])
@pytest.mark.parametrize("cell,chips", CELLS)
def test_cell_rehearses(bench, cell, chips, traced):
    rc, out, err = run_harness(
        ["--workload", cell, "--seed", "2147483999", "--seconds", "0.5",
         "--trace", str(traced), "--rehearse"], devices=chips)
    assert rc == 0, err[-2000:]
    lines = out.splitlines()
    assert len(lines) == 1, "the result line is all of stdout"
    line = strict_loads(lines[-1])
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    emit.validate(line, bench, cell, bool(traced))
    assert line["correct"] is True, err[-2000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    if traced:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert "[check]" in err and "limit" in err  # each number beside its limit


def test_refuses_without_a_chip():
    rc, out, err = run_harness(
        ["--workload", CELLS[0][0], "--seed", "1", "--seconds", "0.5", "--trace", "0"])
    assert rc != 0 and out == ""
    assert "refusing to run" in err


def test_refuses_outside_the_repo(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no program, no run."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env_root = str(tmp_path)
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0][0], "--seed", "1",
         "--seconds", "0.5", "--trace", "0", "--rehearse"],
        cwd=env_root, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout == ""


BROKEN = """
import sys
from benchmark import program, run
real = program.run_experiment
def broken(cfg, dataset):
    result = real(cfg, dataset)
    {how}
    return result
program.run_experiment = broken
sys.exit(run.main(sys.argv[1:]))
"""

FAULTS = {
    # a step that returns its state unchanged: the models never leave x0 = 0,
    # so the loss stays at its first value and the workers never disagree
    "state_unchanged": ("result.history.objective[:] = result.history.objective[0]; "
                        "result.history.consensus_error[:] = 0.0"),
    # part of the batch left out: the spread of the first gradients is off
    "first_gradients": "result.history.consensus_error[0] *= 0.9",
    # an answer altered where it is produced: one loss row off by a percent
    "one_loss_row": "result.history.objective[3] *= 1.01",
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(bench, fault):
    cell = CELLS[0][0]
    rc, out, err = run_harness(
        ["--workload", cell, "--seed", "77", "--seconds", "0.3", "--trace", "0",
         "--rehearse"], prelude=BROKEN.format(how=FAULTS[fault]))
    assert rc == 0, err[-2000:]
    line = strict_loads(out.splitlines()[-1])
    emit.validate(line, bench, cell, False)
    assert line["correct"] is False
    assert "OVER" in err


def test_new_cell_needs_only_new_files(tmp_path, bench):
    """A configuration, a dataset family, a traffic mix, a cell and a per-layer
    metric are added to a copy of the benchmark by adding files and entries,
    editing none."""
    root = tmp_path
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    base = json.load(open(os.path.join(ROOT, "benchmark", "configs", "glm81_ring262k.json")))
    base["name"] = "throwaway_ring"
    base["experiment"].update(base["rehearse"]["experiment"], n_workers=32)
    base["dataset"].update(base["rehearse"]["dataset"])
    base["limits"] = {"tiny8": base["limits"]["steady2k"]}
    (root / "benchmark" / "configs" / "throwaway_ring.json").write_text(json.dumps(base))
    (root / "benchmark" / "traffic" / "tiny8.json").write_text(json.dumps(
        {"n_iterations": 8, "eval_every": 2, "check_iterations": 4, "trace_calls": 1,
         "gates": {"objective_below": 0.6931471805599453, "consensus_below": 1.0}}))
    (root / "benchmark" / "datasets" / "throwaway_data.py").write_text(
        "from benchmark.datasets import gaussian_two_class\n"
        "def generate(spec, exp, seed_seq):\n"
        "    return gaussian_two_class.generate(spec, exp, seed_seq)\n")
    base["dataset"]["generator"] = "throwaway_data"
    (root / "benchmark" / "configs" / "throwaway_ring.json").write_text(json.dumps(base))
    (root / "benchmark" / "layer_metrics" / "throwaway.calls.py").write_text(
        "def read(trace, facts, config):\n    return float(len(facts['calls']))\n")
    new = json.loads(json.dumps(bench))
    new["configs"].append({"name": "throwaway_ring", "source": "test", "reduced": [],
                           "file": "benchmark/configs/throwaway_ring.json", "why": "test"})
    new["workloads"].append({"name": "throwaway_ring.tiny8", "config": "throwaway_ring",
                             "traffic": "tiny8", "chips": 1, "why": "test"})
    new["per_layer"].append({"name": "throwaway.calls", "unit": "calls", "better": "higher",
                             "source": "program_counter", "layer": "run builder",
                             "moves": "iters_per_s", "workloads": ["throwaway_ring.tiny8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(new))
    for traced in (0, 1):
        rc, out, err = run_harness(
            ["--workload", "throwaway_ring.tiny8", "--seed", "9", "--seconds", "0.3",
             "--trace", str(traced), "--rehearse"], root=str(root))
        assert rc == 0, err[-2000:]
        line = strict_loads(out.splitlines()[-1])
        emit.validate(line, new, "throwaway_ring.tiny8", bool(traced))
        assert line["correct"] is True
    assert line["metrics"]["throwaway.calls"]["value"] == 1.0
