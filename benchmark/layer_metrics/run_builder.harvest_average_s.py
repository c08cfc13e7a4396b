"""Seconds of the traced calls in ``dopt.run.harvest.average``: the mean of
the final models over the workers, and under an adversary the indexed copy
of the honest rows it is taken over (the part's ``copied_bytes``). 0.0 on a
program without the part (``host_path_reduce``)."""

from benchmark import host_path_reduce


def read(trace, facts, config):
    return host_path_reduce.seconds(facts, "harvest.average")
