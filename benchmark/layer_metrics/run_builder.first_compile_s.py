"""Seconds in ``dopt.run.compile`` of the process's first ``dopt.run`` root,
the warm-up call: lowering and compiling the scan, or loading it from the
persistent cache. Part of ``setup_s``."""

from benchmark import span_reduce


def read(trace, facts, config):
    return span_reduce.reduce(facts)["first_compile_s"]
