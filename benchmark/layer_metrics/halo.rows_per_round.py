"""Boundary rows the fullest device of a worker mesh receives in one gossip
round: the ``halo_rows`` argument of the traced call's ``dopt.run`` root, which
the run builder takes from the static halo plan (``telemetry.ici_summary``).
A torus blocked by grid rows receives the grid row before its block and the
one after it, 2 * columns rows, whatever the rows it holds; a ring's block 2.

A program whose roots carry no such argument (an unsharded run, every program
before ISSUE 30) reads 0.0, a number, because ``emit.validate`` refuses a
traced line that lacks a metric."""

from benchmark import scope_reduce


def read(trace, facts, config):
    found = [args["halo_rows"] for args in scope_reduce.traced_roots(facts)
             if "halo_rows" in args]
    return float(max(found)) if found else 0.0
