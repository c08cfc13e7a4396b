"""Device microseconds per scan iteration in the update rule: ``algo.step`` less
the gradient, gossip and compression it calls (``dopt.update``): the step
rule's own arithmetic. The op table's rows joined through the program's scope
table (``benchmark/scope_reduce.py``): low, never high."""

from benchmark import scope_reduce


def read(trace, facts, config):
    return scope_reduce.us_per_iter(trace, facts, config, "update")
