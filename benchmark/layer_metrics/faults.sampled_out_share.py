"""Share of the (round, worker) pairs of a traced call in which the worker
was sampled out, in percent: the ``sampled_out_share`` argument of the
call's ``dopt.run`` root (the mean of ``1 - part_up`` over the timeline's
horizon, counted where the leaf lives). What the freeze holds still under
client sampling; at participation rate r it is near 100 (1 - r).

A program whose roots carry no such argument (every program before ISSUE
50, and any call without participation sampling) reads 0.0, a number,
because ``emit.validate`` refuses a traced line that lacks a metric
(PERF.md, section 7)."""

from benchmark import scope_reduce


def read(trace, facts, config):
    found = [args["sampled_out_share"] for args in scope_reduce.traced_roots(facts)
             if "sampled_out_share" in args]
    return 100.0 * float(max(found)) if found else 0.0
