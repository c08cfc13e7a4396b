"""Share of the chip's bf16 peak that the training matmuls reach while the
device is busy: operations from shapes (``benchmark/flops/<name>.py``, named
by the configuration) times the iterations traced, over device-busy seconds
times the peak from ``benchmark/peaks.json``. Says "compute-bound"."""

import importlib


def read(trace, facts, config):
    if trace is None or not config.get("flops") or not facts["iterations"]:
        return None
    flops = importlib.import_module(f"benchmark.flops.{config['flops']}")
    total = flops.per_iteration(config) * facts["iterations"]
    peak = facts["peaks"]["bf16_flops_per_s"] * facts["n_devices"]
    return 100.0 * total / (trace["busy_s"] * peak)
