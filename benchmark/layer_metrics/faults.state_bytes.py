"""Device bytes of fault realizations and tables a traced call holds: the
``fault_bytes`` argument of the call's ``dopt.run`` root span, which the run
builder sums over what the fault layer hands the program as arguments
(neighbor table, mask, slot map, and for a persistent process the
``[T, .]`` timeline leaves), in the device's own layout where the runtime
says it.

A call is paired with its root as ``span_reduce`` pairs them, by the scan's
seconds. A program whose roots carry no such argument (every program before
ISSUE 32, which closed its tables into the executable where no counter sees
them) reads 0.0, a number, because ``emit.validate`` refuses a traced line
that lacks a metric (PERF.md, section 7)."""

import math

from benchmark import span_reduce


def read(trace, facts, config):
    from distributed_optimization_tpu.observability import spans

    if not hasattr(spans, "process_tracer"):
        return 0.0
    events = spans.process_tracer().spans()
    roots = [
        (span_reduce._named(events, e, "scan"), e["args"]["fault_bytes"])
        for e in events
        if e["name"] == span_reduce.ROOT and "fault_bytes" in e.get("args", {})
    ]
    found = [
        float(value) for call in facts["calls"] for scan_s, value in roots
        if math.isclose(scan_s, call["scan_s"], rel_tol=1e-9)
    ]
    return max(found) if found else 0.0
