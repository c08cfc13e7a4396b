"""Device bytes of the screened round's tables (neighbor indices, liveness,
the attackers' and the honest masks) that the traced call's scan was handed
as arguments: the ``robust_bytes`` argument of the call's ``dopt.run`` root
(``on_device_size_in_bytes``; 0 while the program closes them into its
executable, where no counter sees them).

A program whose roots carry no such argument reads 0.0, a number, because
``emit.validate`` refuses a traced line that lacks a metric (PERF.md,
section 7)."""

from benchmark import scope_reduce


def read(trace, facts, config):
    found = [args["robust_bytes"] for args in scope_reduce.traced_roots(facts)
             if "robust_bytes" in args]
    return float(max(found)) if found else 0.0
