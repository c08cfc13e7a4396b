"""What a byte costs going up: ``dopt.run.upload``'s ``bytes`` (shards,
labels, row counts, a batch schedule) over the seconds of ``upload`` and
``upload_wait`` (``run_builder.upload_s``), in GB/s; under a mesh the sum
over the devices' links. 0.0 on a program from before ISSUE 48
(``host_path_reduce``), like its eight siblings."""

from benchmark import host_path_reduce


def read(trace, facts, config):
    return host_path_reduce.gbps(
        host_path_reduce.count(facts, "upload.bytes"),
        host_path_reduce.seconds(facts, "upload", "upload_wait"))
