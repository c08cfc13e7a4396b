"""Seconds of the traced calls spent placing the attackers on the graph and
binding the screening rule: the ``dopt.run.adversary`` children of the
traced calls' ``dopt.run`` roots (``jax_backend._run`` opens one round
``_bind_byzantine``: the seeded greedy over the neighbor table, the masks,
the rule's tables). Host work every call pays inside the timed window.

A program without that span names none of it: it reads 0.0, a number,
because ``emit.validate`` refuses a traced line that lacks a metric
(PERF.md, section 7)."""

from benchmark import span_reduce


def read(trace, facts, config):
    return float(span_reduce.seconds(facts, "adversary"))
