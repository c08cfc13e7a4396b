"""Rehearsals of the benchmark on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They prove plumbing and arithmetic. A number they print is never a
measurement: every line they read is marked ``"rehearsal": true``.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def strict_loads(text):
    def refuse(token):
        raise ValueError(f"{token} is not JSON")

    return json.loads(text, parse_constant=refuse)


def run_harness(args, root=ROOT, devices=1, prelude=None, timeout=600):
    """Run ``benchmark/run.py`` (or a ``prelude`` that ends up calling its
    ``main``) in a fresh process on the CPU; returns (returncode, stdout,
    stderr)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if prelude is None:
        cmd = [sys.executable, os.path.join(root, "benchmark", "run.py"), *args]
    else:
        cmd = [sys.executable, "-c", prelude, *args]
    proc = subprocess.run(cmd, cwd=root, env=env, capture_output=True, text=True,
                          timeout=timeout)
    return proc.returncode, proc.stdout, proc.stderr


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)
