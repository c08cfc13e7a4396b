"""Device microseconds per scan iteration that the op table's rows, joined
through the program's scope table (``benchmark/scope_reduce.py``), bill to
no scope: rows with no scope or with instructions of several, rows the
program's table does not know, the busy time under the ten rows, and any
scope this cell does not report. With the scopes the cell reports it sums to
``scan.device_us_per_iter``; a program without scopes reads that whole."""

from benchmark import scope_reduce


def read(trace, facts, config):
    return scope_reduce.us_per_iter(trace, facts, config, None)
