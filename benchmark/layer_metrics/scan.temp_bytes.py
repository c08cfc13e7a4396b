"""Bytes of temporaries the traced call's scan program holds on a device
beside its arguments and outputs: ``memory_analysis().temp_size_in_bytes`` of
the executable, the ``temp_bytes`` argument of the call's ``dopt.run`` root
(what ``memory_peak_bytes``, set while the shards go up, does not see). A
program whose roots carry no such argument reads 0.0, a number, because
``emit.validate`` refuses a traced line that lacks a metric."""

from benchmark import scope_reduce


def read(trace, facts, config):
    found = [args["temp_bytes"] for args in scope_reduce.traced_roots(facts)
             if "temp_bytes" in args]
    return float(max(found)) if found else 0.0
