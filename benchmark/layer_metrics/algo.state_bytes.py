"""Device bytes of the state the traced call's scan carries from iteration
to iteration: the ``state_bytes`` argument of the call's ``dopt.run`` root,
which the run builder sums over the rule's state pytree as the scan holds it
(``on_device_size_in_bytes``: the models, and for gradient tracking the
tracker and the last gradients, in the device's own tiles).

A program whose roots carry no such argument (every program before ISSUE
39) reads 0.0, a number, because ``emit.validate`` refuses a traced line
that lacks a metric (PERF.md, section 7)."""

from benchmark import scope_reduce


def read(trace, facts, config):
    found = [args["state_bytes"] for args in scope_reduce.traced_roots(facts)
             if "state_bytes" in args]
    return float(max(found)) if found else 0.0
