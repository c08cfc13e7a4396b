"""Share of the chip's memory bandwidth that an iteration's COMPULSORY bytes
reach while the device is busy: the bytes a step has to move, from shapes
(``benchmark/flops/<name>.py``, named by the configuration's ``step_bytes``:
one read of the shards and their targets, one read and one write of a state
leaf a gossip round), times the iterations traced, over the trace's busy
device seconds times the peak from ``benchmark/peaks.json``. Says how far
the whole step is from one pass over the shards: the denominator is
everything the program does, the numerator only what any program must, so
it cannot pass 100. Computed from the configuration file and the trace
alone: it reads the same work on every program.

Where no rule is named or nothing was traced it reads 0.0, a number."""

import importlib


def read(trace, facts, config):
    if trace is None or not config.get("step_bytes") or not facts["iterations"]:
        return 0.0
    if not trace["busy_s"]:
        return 0.0
    rule = importlib.import_module(f"benchmark.flops.{config['step_bytes']}")
    total = rule.compulsory_bytes(config) * facts["iterations"]
    peak = facts["peaks"]["hbm_bytes_per_s"] * facts["n_devices"]
    return 100.0 * total / (trace["busy_s"] * peak)
