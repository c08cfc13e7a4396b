"""Two-process ``jax.distributed`` smoke test for the multihost path
(VERDICT r1 item 6).

The ``--multihost`` CLI flag and the ``process_allgather`` fetch in
``jax_backend._fetch_to_host`` are the first things that would break on a
real pod slice; this script exercises them without one: it launches TWO
localhost processes (each contributing 4 virtual CPU devices, 8 global),
wires them with ``jax.distributed.initialize``, runs an identical tiny
D-SGD config through ``jax_backend.run`` on the global 8-device mesh, and
verifies both processes fetch identical final models and metric histories.

Launcher mode (no args): spawns the two workers, waits, compares outputs.
Worker mode (``--process-id I --coordinator ADDR --out FILE``): runs the
experiment and dumps results as JSON.

Used by ``tests/test_multihost.py``; also runnable standalone:
``python examples/multihost_smoke.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

N_PROCESSES = 2
DEVICES_PER_PROCESS = 4


def worker(process_id: int, coordinator: str, out_path: str) -> None:
    # Env (JAX_PLATFORMS / XLA_FLAGS) is set by the launcher BEFORE python
    # starts, so jax initializes the virtual CPU devices correctly here.
    # ``process_id == -1`` is the single-process ground-truth run: the same
    # config on one process holding all 8 devices, no jax.distributed.
    import jax

    if process_id >= 0:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=N_PROCESSES,
            process_id=process_id,
        )
        assert jax.process_count() == N_PROCESSES
    assert len(jax.devices()) == N_PROCESSES * DEVICES_PER_PROCESS

    import numpy as np

    from distributed_optimization_tpu.backends import jax_backend
    from distributed_optimization_tpu.config import ExperimentConfig
    from distributed_optimization_tpu.utils.data import generate_synthetic_dataset
    from distributed_optimization_tpu.utils.oracle import compute_reference_optimum

    cfg = ExperimentConfig(
        n_workers=8,
        n_samples=320,
        n_features=10,
        n_informative_features=6,
        n_iterations=40,
        local_batch_size=8,
        problem_type="quadratic",
        algorithm="dsgd",
        topology="ring",
        eval_every=10,
    )
    # Deterministic host-side generation: every process builds the same data.
    ds = generate_synthetic_dataset(cfg)
    _, f_opt = compute_reference_optimum(ds, cfg.reg_param)
    res = jax_backend.run(cfg, ds, f_opt)

    with open(out_path, "w") as f:
        json.dump(
            {
                "process_id": process_id,
                "process_count": jax.process_count(),
                "global_devices": len(jax.devices()),
                "final_models": np.asarray(res.final_models).tolist(),
                "objective": np.asarray(res.history.objective).tolist(),
                "consensus": np.asarray(res.history.consensus_error).tolist(),
                "total_floats": res.history.total_floats_transmitted,
            },
            f,
        )


def launch() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    coordinator = f"localhost:{port}"

    tmp = tempfile.mkdtemp(prefix="multihost_smoke_")
    outs = [os.path.join(tmp, f"proc{i}.json") for i in range(N_PROCESSES)]
    single_out = os.path.join(tmp, "proc_single.json")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={DEVICES_PER_PROCESS}"
    )
    # Scrub any inherited single-controller/TPU plugin state.
    env.pop("JAX_PLATFORM_NAME", None)

    # Single-process ground truth: all 8 devices in ONE process, same
    # config. The two distributed processes agreeing with EACH OTHER could
    # hide a correlated multi-process error; agreeing with this run cannot.
    env_single = dict(env)
    env_single["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count="
        f"{N_PROCESSES * DEVICES_PER_PROCESS}"
    )

    procs = [
        subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__),
                "--process-id", str(i),
                "--coordinator", coordinator,
                "--out", outs[i],
            ],
            env=env,
            cwd=REPO_ROOT,
        )
        for i in range(N_PROCESSES)
    ] + [
        subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__),
                "--process-id", "-1",
                "--coordinator", "unused",
                "--out", single_out,
            ],
            env=env_single,
            cwd=REPO_ROOT,
        )
    ]
    try:
        # Shorter than the pytest wrapper's 540 s timeout, so a hung worker
        # is reaped here rather than orphaned when the wrapper kills only
        # this launcher.
        rcs = [p.wait(timeout=420) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any(rc != 0 for rc in rcs):
        print(f"[multihost_smoke] worker exit codes: {rcs}", file=sys.stderr)
        return 1

    results = [json.load(open(o)) for o in outs]
    import numpy as np

    a, b = results
    assert a["process_count"] == b["process_count"] == N_PROCESSES
    assert a["global_devices"] == b["global_devices"] == 8
    np.testing.assert_array_equal(
        np.asarray(a["final_models"]), np.asarray(b["final_models"]),
        err_msg="process_allgather fetch disagrees across processes",
    )
    np.testing.assert_array_equal(
        np.asarray(a["objective"]), np.asarray(b["objective"])
    )
    np.testing.assert_array_equal(
        np.asarray(a["consensus"]), np.asarray(b["consensus"])
    )
    assert a["total_floats"] == b["total_floats"]
    assert np.all(np.isfinite(np.asarray(a["objective"])))
    # Cross-execution-topology equivalence: the 2-process run must match
    # the single-process 8-device ground truth (same global mesh/sharding,
    # different process boundaries; f32 tolerance for collective-order
    # differences).
    s = json.load(open(single_out))
    np.testing.assert_allclose(
        np.asarray(a["final_models"]), np.asarray(s["final_models"]),
        rtol=1e-5, atol=1e-6,
        err_msg="2-process run diverges from the single-process ground truth",
    )
    np.testing.assert_allclose(
        np.asarray(a["objective"]), np.asarray(s["objective"]),
        rtol=1e-4, atol=1e-6,
    )
    assert a["total_floats"] == s["total_floats"]
    print(
        "[multihost_smoke] OK: 2 processes x 4 devices, identical fetched "
        "results, matching the single-process ground truth; final gap "
        f"{a['objective'][-1]:.6f}"
    )
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--coordinator", type=str, default=None)
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()
    if args.process_id is None:
        raise SystemExit(launch())
    worker(args.process_id, args.coordinator, args.out)


if __name__ == "__main__":
    main()
