"""Bytes the fullest device of a worker mesh sends over ICI in one gossip
round: the ``ici_bytes_per_round`` argument of the traced calls' ``dopt.run``
root spans, which the run builder takes from the static halo plan
(``telemetry.ici_summary``, the source of the ``dopt_worker_mesh_*`` gauges).
A ring's block has two boundary rows whatever its length: 2 * 81 * 4 = 648 B
at the study's model size.

A call is paired with its root as ``span_reduce`` pairs them, by the scan's
seconds. A program that has no process tracer, or whose roots carry no such
argument (every program before ISSUE 30, and an unsharded run), reports
nothing."""

import math

from benchmark import span_reduce


def read(trace, facts, config):
    from distributed_optimization_tpu.observability import spans

    if not hasattr(spans, "process_tracer"):
        return None
    events = spans.process_tracer().spans()
    # (the scan's seconds, the argument) of every root that carries one
    roots = [
        (span_reduce._named(events, e, "scan"), e["args"]["ici_bytes_per_round"])
        for e in events
        if e["name"] == span_reduce.ROOT and "ici_bytes_per_round" in e.get("args", {})
    ]
    found = [
        float(value) for call in facts["calls"] for scan_s, value in roots
        if math.isclose(scan_s, call["scan_s"], rel_tol=1e-9)
    ]
    return max(found) if found else None
