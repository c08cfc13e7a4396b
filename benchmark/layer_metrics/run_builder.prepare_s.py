"""Seconds of the traced calls in the run builder's other host work:
``dopt.run.prepare`` (topology, mixing operator, closures, initial state),
``dopt.run.cache_lookup`` and, on a miss, ``dopt.run.compile``."""

from benchmark import span_reduce


def read(trace, facts, config):
    return span_reduce.seconds(facts, "prepare", "cache_lookup", "compile")
