"""Share of the traced experiments' wall time in which no operation ran on
the device: everything the run builder does around the scan (topology, shard
stacking, host-to-device, cache lookup, harvest) plus the device's wait for
it. Device-busy seconds come from the trace; the calls' wall is the harness's
clock around them, as ``window_s`` is. Busy seconds above that wall are a
fault of the trace or the clock, and the validator refuses the negative
share. (The program's own ``history.iters_per_second`` is no
scan time: its clock starts while the host-to-device copy is still in
flight.)"""


def read(trace, facts, config):
    wall = sum(c["wall_s"] for c in facts["calls"])
    if trace is None or wall <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / wall)
