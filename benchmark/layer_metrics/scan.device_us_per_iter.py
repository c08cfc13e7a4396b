"""Device-busy microseconds per scan iteration: the union of the op line's
intervals (mean over the devices used) over the iterations the traced calls
executed."""


def read(trace, facts, config):
    if trace is None or not facts["iterations"]:
        return None
    return trace["busy_s"] * 1e6 / facts["iterations"]
