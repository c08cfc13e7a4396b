"""Device microseconds per scan iteration in the batch draw: the dense sampler's
ranking of a shard's rows into batch weights, a gathered batch, an injected
schedule's ``take_along_axis`` (``dopt.sampling``). A full-batch cell draws
nothing and reads 0. The op table's rows joined through the program's scope
table (``benchmark/scope_reduce.py``): low, never high."""

from benchmark import scope_reduce


def read(trace, facts, config):
    return scope_reduce.us_per_iter(trace, facts, config, "sampling")
