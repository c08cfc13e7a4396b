"""Seconds of the traced calls in which the host WAITED for the link:
``dopt.run.upload``'s ``wait_s`` (inside the ``block_until_ready`` calls of
a flat placement, ``parallel.mesh._place_flat``; a ``direct`` placement
waits for nothing there) plus the whole of ``dopt.run.upload_wait``. What
``run_builder.upload_s`` holds beyond it is the host slicing, reshaping
and enqueueing. 0.0 on a program without the counter's sibling parts
(``host_path_reduce``)."""

from benchmark import host_path_reduce


def read(trace, facts, config):
    return (host_path_reduce.count(facts, "upload.wait_s")
            + host_path_reduce.seconds(facts, "upload_wait"))
