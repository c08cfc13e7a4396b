"""Share of the (iteration, worker) pairs of a traced call in which the
worker was down, in percent: the ``down_share`` argument of the call's
``dopt.run`` root (the mean of ``1 - node_up`` over the timeline's horizon,
counted where the leaf lives). What the freeze holds still; under churn at
mean up-time F and mean outage R it is near 100 R / (F + R).

A program whose roots carry no such argument (every program before ISSUE
46, and any call without crash-recovery churn) reads 0.0, a number, because
``emit.validate`` refuses a traced line that lacks a metric (PERF.md,
section 7)."""

from benchmark import scope_reduce


def read(trace, facts, config):
    found = [args["down_share"] for args in scope_reduce.traced_roots(facts)
             if "down_share" in args]
    return 100.0 * float(max(found)) if found else 0.0
