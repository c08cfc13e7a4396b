"""What a byte costs coming down: the ``bytes`` of the traced calls'
``dopt.run.harvest.fetch`` parts (the fetched leaves' bytes on the device)
over the parts' seconds, in GB/s. 0.0 on a program without the part, and
for a fetch of no seconds (``host_path_reduce``)."""

from benchmark import host_path_reduce


def read(trace, facts, config):
    return host_path_reduce.gbps(
        host_path_reduce.count(facts, "harvest.fetch.bytes"),
        host_path_reduce.seconds(facts, "harvest.fetch"))
