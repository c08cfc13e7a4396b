"""Device microseconds per gossip round in the gradients of the round's
LATER descents: with ``local_steps`` = tau a round holds tau gradient steps a
worker, the first fused with the gossip (``dopt.gradient``, as in every
cell) and tau - 1 purely local after it, whose gradients carry
``dopt.local``; their draws stay ``sampling``. So this is the metric of "the
cell less its tau = 1 control". The op table's rows joined through the
program's scope table (``benchmark/scope_reduce.py``): low, never high; a
program without the scope (every program before ISSUE 50, which bills those
gradients to ``gradient`` with the first) reads 0.0."""

from benchmark import scope_reduce


def read(trace, facts, config):
    return scope_reduce.us_per_iter(trace, facts, config, "local")
