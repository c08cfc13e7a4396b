"""Seconds of the traced calls in ``dopt.run.harvest.fetch``: the
device-to-host copy of the final models (and of every other leaf of the
state under ``return_state``), the ``np.asarray`` alone. 0.0 on a program
without the part (``host_path_reduce``)."""

from benchmark import host_path_reduce


def read(trace, facts, config):
    return host_path_reduce.seconds(facts, "harvest.fetch")
