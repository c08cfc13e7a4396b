"""Share of the chip's memory bandwidth that the screened round's COMPULSORY
bytes reach while the round runs: the bytes a round has to move, from shapes
(``benchmark/flops/<name>.py``, named by the configuration's
``robust_bytes``), times the iterations traced, over the device seconds
under ``dopt.robust`` (what ``scan.robust_us_per_iter`` reads) times the
peak from ``benchmark/peaks.json``. Says how far the round is from one pass
over the models: the denominator is everything the program does to corrupt
and screen, the numerator only what any program must, so it cannot pass 100.

Where the round has no device time to read (the CPU, a program without the
scope) it reads 0.0, a number."""

import importlib

from benchmark import scope_reduce


def read(trace, facts, config):
    if trace is None or not config.get("robust_bytes") or not facts["iterations"]:
        return 0.0
    us = scope_reduce.us_per_iter(trace, facts, config, "robust")
    if not us:  # None without a trace, 0 without a scope to bill
        return 0.0
    rule = importlib.import_module(f"benchmark.flops.{config['robust_bytes']}")
    peak = facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * rule.per_round_bytes(config) / (us * 1e-6 * peak)
