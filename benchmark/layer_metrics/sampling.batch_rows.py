"""Rows of the shards one iteration's batches hold, all workers together:
the ``batch_rows`` argument of the traced call's ``dopt.run`` root (N times
the effective batch; what the gather sampler fetches a round, beside the
root's ``sampling`` = ``gather``).

A program whose roots carry no such argument (every program before ISSUE
39) reads 0.0, a number, because ``emit.validate`` refuses a traced line
that lacks a metric (PERF.md, section 7)."""

from benchmark import scope_reduce


def read(trace, facts, config):
    found = [args["batch_rows"] for args in scope_reduce.traced_roots(facts)
             if "batch_rows" in args]
    return float(max(found)) if found else 0.0
