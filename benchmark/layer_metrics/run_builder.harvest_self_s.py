"""``dopt.run.harvest``'s self time over the traced calls: the child less
its four parts (``rows``, ``fetch``, ``cast``, ``average``), so the five
sum to ``run_builder.harvest_s``. 0.0 on a program without the parts
(``host_path_reduce``), whose ``harvest`` is all self time and is
``run_builder.harvest_s`` already."""

from benchmark import host_path_reduce


def read(trace, facts, config):
    return host_path_reduce.harvest_self_s(facts)
