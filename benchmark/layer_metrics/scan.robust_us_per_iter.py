"""Device microseconds per scan iteration in the robust-aggregation round:
the attackers' payloads written over their transmitted rows and the
screening rule over every closed neighbourhood (``dopt.robust``: the gather
of the neighbours' rows, the stack, its sort, the kept sum); the attackers'
own benign mix stays ``gossip``. So this is the metric of "the cell less its
control". The op table's rows joined through the program's scope table
(``benchmark/scope_reduce.py``): low, never high; a program without the
scope reads 0.0."""

from benchmark import scope_reduce


def read(trace, facts, config):
    return scope_reduce.us_per_iter(trace, facts, config, "robust")
