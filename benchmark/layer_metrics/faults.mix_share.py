"""Share of the device's busy seconds that the fault layer's operations
take: the gather of the neighbours' models, its product with the realized
weights and the sum over the slots, the liveness gathers, the draws and the
freeze of the stragglers' rows.

Which rows of the op table are the fault layer's is a fact of the compiled
program at the configuration's size, established on the chip and written in
the configuration file (``fault_ops``: row names as ``trace_reduce.op_kind``
gives them), as ``mesh.mix_share`` reads its rows. The reduction hands every
reader the ten largest rows only, so a row outside them is not counted: the
share can read low, never high. THE TRAP (PERF.md, section 7, row 4c): the
names are those of the program that was traced when they were written; a PR
that renames a row makes this read low, down to 0.0, and says nothing. A
trace without the rows, or no trace (a rehearsal), reads 0.0, a number,
because ``emit.validate`` refuses a traced line that lacks a metric."""


def read(trace, facts, config):
    names = set(config.get("fault_ops") or ())
    if trace is None or not names or not trace.get("busy_s"):
        return 0.0
    seconds = sum(sec for name, sec in trace["device_ops"] if name in names)
    return 100.0 * seconds / trace["busy_s"]
