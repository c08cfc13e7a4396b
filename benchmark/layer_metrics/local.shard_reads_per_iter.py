"""Reads of the whole shard stack one gossip round of a traced call was
BUILT with: the ``shard_reads`` argument of the call's ``dopt.run`` root,
which the run builder works out from the round's form and from nothing
measured (the first gradient and the objective share one read under
``forward`` = ``fused``, two under ``carried``, three under ``recomputed``;
each of the tau - 1 later gradients reads the stack twice, X.x and X^T.c,
where it is ``recomputed``). A plan, as ``halo.wire_bytes_per_round`` is; tau
reads are compulsory (``benchmark/flops/glm_local_steps.py``).

A program whose roots carry no such argument (every program before ISSUE
50, and any call with ``local_steps`` = 1) reads 0.0, a number, because
``emit.validate`` refuses a traced line that lacks a metric (PERF.md,
section 7)."""

from benchmark import scope_reduce


def read(trace, facts, config):
    found = [args["shard_reads"] for args in scope_reduce.traced_roots(facts)
             if "shard_reads" in args]
    return float(max(found)) if found else 0.0
