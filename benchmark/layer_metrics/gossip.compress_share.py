"""Share of the device's busy seconds that the gossip compressor's operations
take: the selection (a sort of every worker's row, or a top-k), the scatter
of the kept entries, the flatten to one parameter axis and its way back.

Which rows of the op table are the compressor's is a fact of the compiled
program at the configuration's size, established on the chip and written in
the configuration file (``compressor_ops``: row names as
``trace_reduce.op_kind`` gives them). The reduction hands every reader the
ten largest rows only, so a compressor row outside them is not counted: the
share can read low, never high."""


def read(trace, facts, config):
    names = set(config.get("compressor_ops") or ())
    if trace is None or not names or not trace["busy_s"]:
        return None
    seconds = sum(sec for name, sec in trace["device_ops"] if name in names)
    return 100.0 * seconds / trace["busy_s"]
