"""The longest single wait of the traced calls' flat placements:
``dopt.run.upload``'s ``slowest_block_s`` (``parallel.mesh._place_flat``;
``slowest_block`` beside it says which piece), the largest over the calls.
0.0 where the shards went up ``direct``, and on a program without the
counter (``host_path_reduce``)."""

from benchmark import host_path_reduce


def read(trace, facts, config):
    return host_path_reduce.count(facts, "upload.slowest_block_s", over=max)
