"""Seconds of the traced calls in ``dopt.run.harvest``: the scan's rows and
the final models fetched to the host, their float64 cast, the history."""

from benchmark import span_reduce


def read(trace, facts, config):
    return span_reduce.seconds(facts, "harvest")
