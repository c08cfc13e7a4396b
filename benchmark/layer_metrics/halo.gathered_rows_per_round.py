"""Rows the fullest device of a worker mesh gathers from its halo-extended
block in one gossip round: the ``gathered_rows`` argument of the traced call's
``dopt.run`` root, S * k_max for a block of S workers under a neighbor table
``k_max`` wide (padded slots fetch a row too: the chip prices a gather by its
indices). What the one-chip gather says since ISSUE 36, said under
``halo_gather`` since ISSUE 52.

A program whose roots carry no such argument (the parent commit, ``halo_shift``,
an unsharded stencil) reads 0.0, a number, because ``emit.validate`` refuses a
traced line that lacks a metric."""

from benchmark import scope_reduce


def read(trace, facts, config):
    found = [args["gathered_rows"] for args in scope_reduce.traced_roots(facts)
             if "gathered_rows" in args]
    return float(max(found)) if found else 0.0
