"""The headline bench's gates, and the device gate it shares with chip_smoke.

`bench.py` measures on a TPU or not at all: on a CPU backend it exits
non-zero before doing any work, so a CPU rate can never be published under
the headline's metric name. With a device present its convergence gates
still refuse to report throughput for a run that did not optimize. There is
no published-range gate: the ledger is the record, and a faster chip is not
an error. These tests drive every branch with stubbed backends, without
chip time.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).parent.parent
sys.path.insert(0, str(ROOT))
import bench  # noqa: E402

from distributed_optimization_tpu import runtime
from distributed_optimization_tpu.backends import jax_backend, numpy_backend
from distributed_optimization_tpu.backends.base import BackendRunResult
from distributed_optimization_tpu.metrics import RunHistory
from distributed_optimization_tpu.utils import data as data_mod
from distributed_optimization_tpu.utils import oracle as oracle_mod

FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _fake_result(config, ips: float, *, objective=None,
                 consensus=None) -> BackendRunResult:
    T = config.n_iterations
    n_rows = min(T, 64)  # decaying gap that crosses ε=0.08 within the run
    if objective is None:
        objective = np.geomspace(0.5, 0.01, n_rows)
    if consensus is None:
        consensus = np.geomspace(1e-1, 1e-2, n_rows)
    hist = RunHistory(
        objective=objective,
        consensus_error=consensus,
        time=np.linspace(0.0, T / ips, n_rows),
        eval_iterations=np.linspace(1, T, n_rows).astype(int),
        total_floats_transmitted=2.0 * config.n_workers * 81 * T,
        iters_per_second=ips,
        compile_seconds=0.1,
    )
    models = np.zeros((config.n_workers, 81))
    return BackendRunResult(hist, models, models.mean(axis=0))


@pytest.fixture
def stubbed(monkeypatch):
    """Stub the device gate and every expensive call bench.main makes;
    yield a mutable dict of knobs: 'jax_ips' is the measured rate, and
    'headline' / 'parity' override the N=256 / N=25 run's result kwargs."""
    knobs = {"jax_ips": 100_000.0, "headline": {}, "parity": {}}

    class _DS:  # bench only threads the dataset through to the backends
        pass

    def fake_jax_run(cfg, ds, f_opt, **kw):
        which = "headline" if cfg.n_workers == 256 else "parity"
        return _fake_result(cfg, knobs["jax_ips"], **knobs[which])

    monkeypatch.setattr(runtime, "require_tpu", lambda what: dict(FAKE_DEVICE))
    monkeypatch.setattr(runtime, "configure_compile_cache", lambda: "unused")
    monkeypatch.setattr(data_mod, "generate_synthetic_dataset", lambda cfg: _DS())
    monkeypatch.setattr(
        oracle_mod, "compute_reference_optimum",
        lambda ds, reg: (np.zeros(81), 0.1),
    )
    monkeypatch.setattr(jax_backend, "run", fake_jax_run)
    monkeypatch.setattr(
        numpy_backend, "run",
        lambda cfg, ds, f_opt, **kw: _fake_result(cfg, 90.0),
    )
    return knobs


def test_prints_one_json_line_naming_the_device(stubbed, capsys):
    bench.main()
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, "bench must print exactly one stdout line"
    payload = json.loads(out[0])
    assert payload["metric"] == "dsgd_ring_logistic_N256_T300k_iters_per_sec_median5"
    assert payload["value"] == 100_000.0
    assert payload["unit"] == "iters/sec"
    assert payload["vs_baseline"] == pytest.approx(100_000.0 / 90.0, rel=1e-3)
    assert payload["device"] == FAKE_DEVICE
    assert payload["mesh_devices"] == 1


@pytest.mark.parametrize("ips", [40_000.0, 2_000_000.0])
def test_no_range_gate_a_faster_or_slower_chip_still_reports(stubbed, capsys, ips):
    """The removed published-range gate exited non-zero for a chip that was
    merely faster; any finite rate from a converging run is now reported."""
    stubbed["jax_ips"] = ips
    bench.main()
    assert json.loads(capsys.readouterr().out)["value"] == ips


def test_run_that_never_crosses_epsilon_refuses(stubbed, capsys):
    stubbed["headline"] = {"objective": np.full(64, 0.5)}
    with pytest.raises(SystemExit, match="never reached"):
        bench.main()
    assert capsys.readouterr().out.strip() == "", (
        "a failed gate must not emit the stdout JSON line"
    )


def test_unbounded_consensus_refuses(stubbed, capsys):
    stubbed["headline"] = {"consensus": np.geomspace(1e-1, 1e3, 64)}
    with pytest.raises(SystemExit, match="consensus error is unbounded"):
        bench.main()
    assert capsys.readouterr().out.strip() == ""


def test_broken_parity_run_refuses_before_the_headline(stubbed, monkeypatch):
    """A broken optimizer must die at the N=25 parity check, not after the
    five T=300k cycles."""
    stubbed["parity"] = {"objective": np.full(64, 0.5)}
    monkeypatch.setattr(
        numpy_backend, "run",
        lambda *a, **kw: pytest.fail("headline cycles ran despite broken parity"),
    )
    with pytest.raises(SystemExit, match="parity config failed"):
        bench.main()


def test_bench_refuses_a_cpu_backend_before_any_work(monkeypatch):
    """The real device gate, in-process: this suite runs on CPU, so
    bench.main() must exit before touching data, oracle or a backend."""
    def boom(*a, **kw):
        raise AssertionError("bench did work without a TPU")

    monkeypatch.setattr(data_mod, "generate_synthetic_dataset", boom)
    monkeypatch.setattr(jax_backend, "run", boom)
    monkeypatch.setattr(numpy_backend, "run", boom)
    with pytest.raises(SystemExit, match="needs a TPU"):
        bench.main()


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_script_exits_nonzero_on_cpu(script):
    """As the driver runs them: a CPU-only process gets a non-zero exit and
    no result line on stdout."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / script)], cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
