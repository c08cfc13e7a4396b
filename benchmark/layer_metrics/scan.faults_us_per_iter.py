"""Device microseconds per scan iteration in the fault layer: what a faulty round
costs beyond a fault-free one: the draws or timeline reads, liveness, realized
degrees and weights, the straggler freeze, a warm restart (``dopt.faults``);
the weighted sum over neighbours stays ``gossip``. So this is the metric of
"the cell less its control". The op table's rows joined through the program's
scope table (``benchmark/scope_reduce.py``): low, never high."""

from benchmark import scope_reduce


def read(trace, facts, config):
    return scope_reduce.us_per_iter(trace, facts, config, "faults")
