"""How unevenly a worker mesh's devices were busy: 100 * (max - min) / max
of the device planes' own busy seconds (``trace["busy_s_per_device"]``, each
plane's union of op intervals). Four chips that each ran their quarter read
a few percent; a run whose work landed on one chip reads near 100. A device
the cell was given and the trace holds no plane for did nothing, and counts
as 0 busy seconds (so does every device but one of a rehearsal on the CPU,
whose stand-in summary has one figure and no planes)."""


def read(trace, facts, config):
    if trace is None:
        return None
    per_device = list(trace.get("busy_s_per_device") or [trace["busy_s"]])
    per_device += [0.0] * (int(facts["n_devices"]) - len(per_device))
    top = max(per_device)
    if not top > 0:
        return None
    return 100.0 * (top - min(per_device)) / top
