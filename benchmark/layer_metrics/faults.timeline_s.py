"""Seconds of the traced calls spent making, fetching or placing fault
realizations and their tables: the ``dopt.run.faults`` children of the
traced calls' ``dopt.run`` roots (``jax_backend._run`` opens one round
``_build_faulty`` and round the placement of what it hands the program).

A program from before that span spent those seconds inside
``dopt.run.prepare`` and names none of them: it reads 0.0, a number, because
``emit.validate`` refuses a traced line that lacks a metric (PERF.md,
section 7)."""

from benchmark import span_reduce


def read(trace, facts, config):
    return float(span_reduce.seconds(facts, "faults"))
