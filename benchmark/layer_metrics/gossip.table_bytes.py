"""Device bytes of the neighbor tables the traced call's scan was handed as
arguments: the ``table_bytes`` argument of the call's ``dopt.run`` root, which
the run builder sums over the gather mixing's tables (indices, weights, the
self weights) in the device's own layout (``on_device_size_in_bytes``).

A program whose roots carry no such argument (every program before ISSUE
36, which closed its tables into the executable where no counter sees them)
reads 0.0, a number, because ``emit.validate`` refuses a traced line that
lacks a metric (PERF.md, section 7)."""

from benchmark import scope_reduce


def read(trace, facts, config):
    found = [args["table_bytes"] for args in scope_reduce.traced_roots(facts)
             if "table_bytes" in args]
    return float(max(found)) if found else 0.0
