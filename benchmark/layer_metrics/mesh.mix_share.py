"""Share of the device's busy seconds that the sharded mixing's operations
take: the gather on the halo-extended buffer, the concatenate that extends
it, the boundary rows' permutes and the weighted sum.

Which rows of the op table are the mixing's is a fact of the compiled
program at the configuration's size, established on the chip and written in
the configuration file (``mixing_ops``: row names as
``trace_reduce.op_kind`` gives them), as ``gossip.compress_share`` reads its
rows. The reduction hands every reader the ten largest rows only, so a
mixing row outside them is not counted: the share can read low, never
high."""


def read(trace, facts, config):
    names = set(config.get("mixing_ops") or ())
    if trace is None or not names or not trace["busy_s"]:
        return None
    seconds = sum(sec for name, sec in trace["device_ops"] if name in names)
    return 100.0 * seconds / trace["busy_s"]
