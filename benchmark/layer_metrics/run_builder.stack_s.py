"""Seconds of the traced calls in ``dopt.run.stack_shards``: the host loop
that stacks the dataset's shards into one padded [N, L, d] array, once a
call."""

from benchmark import span_reduce


def read(trace, facts, config):
    return span_reduce.seconds(facts, "stack_shards")
