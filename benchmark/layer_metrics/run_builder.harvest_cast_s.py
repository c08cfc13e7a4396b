"""Seconds of the traced calls in ``dopt.run.harvest.cast``: the fetched
leaves' float64 copy in C order (which also reorders a leaf the runtime
handed over in its own dimension order) and the flatten to ``[rows, D]``.
0.0 on a program without the part (``host_path_reduce``)."""

from benchmark import host_path_reduce


def read(trace, facts, config):
    return host_path_reduce.seconds(facts, "harvest.cast")
