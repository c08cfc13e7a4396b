"""Device microseconds per scan iteration in the compressed exchange:
``ErrorFeedbackGossip.exchange``: the selection (``select_top_scored``), the
estimate's update and the γ step (``dopt.compress``); the ``mix`` inside it is
``gossip``. The op table's rows joined through the program's scope table
(``benchmark/scope_reduce.py``): low, never high."""

from benchmark import scope_reduce


def read(trace, facts, config):
    return scope_reduce.us_per_iter(trace, facts, config, "compress")
