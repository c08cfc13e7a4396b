"""Seconds of the traced calls spent making the communication graph, or
finding it in the process's cache: the ``dopt.run.topology`` children of the
traced calls' ``dopt.run`` roots (``jax_backend._run`` opens one round
``cached_topology`` and the graph's spectral gap). The traced call comes
after the warm-up, so a program that keeps its graphs reads next to 0 here
and one that draws a graph a call reads the draw.

A program from before that span made its graph inside ``dopt.run.prepare``
and names none of it: it reads 0.0, a number, because ``emit.validate``
refuses a traced line that lacks a metric (PERF.md, section 7)."""

from benchmark import span_reduce


def read(trace, facts, config):
    return float(span_reduce.seconds(facts, "topology"))
