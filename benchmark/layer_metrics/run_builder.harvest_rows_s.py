"""Seconds of the traced calls in ``dopt.run.harvest.rows``: the history's
rows brought down after the last segment and turned into lists, the last
boundary's heartbeat and save where a caller asked for them, and the
history's arrays. 0.0 on a program without the part
(``host_path_reduce``)."""

from benchmark import host_path_reduce


def read(trace, facts, config):
    return host_path_reduce.seconds(facts, "harvest.rows")
