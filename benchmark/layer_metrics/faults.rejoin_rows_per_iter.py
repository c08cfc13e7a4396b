"""Rows of the models that a traced call's ``neighbor_restart`` policy is
asked to restart, an iteration: the ``rejoin_rows`` argument of the call's
``dopt.run`` root (the set bits of the timeline's ``rejoin`` leaf over the
horizon, counted where the leaf lives) over the call's iterations. The
restart's engagement counter: 0 rows an iteration is a restart that never
ran.

A program whose roots carry no such argument (every program before ISSUE
46, and any call that restarts nothing) reads 0.0, a number, because
``emit.validate`` refuses a traced line that lacks a metric (PERF.md,
section 7)."""

from benchmark import scope_reduce


def read(trace, facts, config):
    found = [args["rejoin_rows"] for args in scope_reduce.traced_roots(facts)
             if "rejoin_rows" in args]
    iterations = max((call["iterations"] for call in facts["calls"]), default=0)
    return float(max(found)) / iterations if found and iterations else 0.0
