"""Device microseconds per scan iteration in the gossip: ``mix`` and
``neighbor_sum`` however realized: stencil, gather, the halo exchange and its
``ppermute``s, the fault layer's weighted sum, a robust aggregate
(``dopt.gossip``). The op table's rows joined through the program's scope
table (``benchmark/scope_reduce.py``): low, never high."""

from benchmark import scope_reduce


def read(trace, facts, config):
    return scope_reduce.us_per_iter(trace, facts, config, "gossip")
