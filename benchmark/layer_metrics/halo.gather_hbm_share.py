"""Share of one chip's memory bandwidth that the halo gather's COMPULSORY
bytes reach while the gossip runs: the bytes a round has to move on one chip
of the worker mesh, from shapes (``benchmark/flops/<name>.py``, named by the
configuration's ``gossip_bytes``: the block read and written once, an index
and a weight a slot, the halo rows received and written once), times the
iterations traced, over a chip's device seconds under ``dopt.gossip`` (what
``scan.gossip_us_per_iter`` reads: the op table is the mean over the mesh's
device planes, and every chip holds the same block and the same halo, so the
fullest chip's is that mean) times the peak from ``benchmark/peaks.json``.
Says how far the exchange, the halo-extended block and the gather over it
are from one pass over a chip's models: the denominator is everything the
program does to mix, the numerator only what any program must, so it cannot
pass 100.

Where the gossip has no device time to read (the CPU, a program without
scopes) it reads 0.0, a number."""

import importlib

from benchmark import scope_reduce


def read(trace, facts, config):
    if trace is None or not config.get("gossip_bytes") or not facts["iterations"]:
        return 0.0
    us = scope_reduce.us_per_iter(trace, facts, config, "gossip")
    if not us:  # None without a trace, 0 without a scope to bill
        return 0.0
    rule = importlib.import_module(f"benchmark.flops.{config['gossip_bytes']}")
    peak = facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * rule.per_round_bytes(config) / (us * 1e-6 * peak)
