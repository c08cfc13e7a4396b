"""Seconds of the traced calls in ``dopt.run.upload`` (the host-to-device
copy of shards, labels, row counts and a batch schedule: the enqueue) and
``dopt.run.upload_wait`` (the wait for that copy, directly before the scan's
clock starts)."""

from benchmark import span_reduce


def read(trace, facts, config):
    return span_reduce.seconds(facts, "upload", "upload_wait")
