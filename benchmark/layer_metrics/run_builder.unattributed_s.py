"""What the spans cannot see: the harness's wall of the traced calls less
every child of their ``dopt.run`` roots, the scan included — the roots' own
time plus what ``run()`` and the benchmark's adapter do outside ``_run``.
The children lie inside the root and the root inside the wall, so it is not
negative."""

from benchmark import span_reduce


def read(trace, facts, config):
    summary = span_reduce.reduce(facts)
    return summary["wall_s"] - sum(summary["by_name"].values())
