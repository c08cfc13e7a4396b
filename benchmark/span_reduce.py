"""The run builder's own account of the traced calls: the ``dopt.run`` spans
that ``jax_backend._run`` records in the program's process-wide tracer
(``observability.spans.process_tracer()``), summed by name.

One ``dopt.run`` root covers one call of ``_run``; its children
(``dopt.run.prepare``, ``.stack_shards``, ``.upload``, ``.cache_lookup``,
``.compile``, ``.upload_wait``, ``.scan``, ``.harvest``) are disjoint and in
order, so their durations sum to the root's less its own few microseconds.
The harness's ``wall_s`` of a call is the clock around the whole of
``program.run_experiment``; what it holds beyond the children is
``unattributed_s``.

A call is paired with its root by the one number both carry: the harness's
``scan_s`` is ``n_iterations / history.iters_per_second``, and that is the
root's ``dopt.run.scan`` span (one clock, read once). So a call that failed
its gates, or any other call of ``_run`` in the process, leaves a root that
pairs with nothing and is not counted. A traced call with no root raises.

The one case that does not raise is a program from before the spans, whose
``observability.spans`` has no ``process_tracer``: the benchmark's files are
laid over the parent commit too, a traced run that fails there refuses the
PR, and ``emit.validate`` refuses a line that leaves a metric out. Such a
program recorded no second under any name, and that is what is reported:
nothing named, the calls' whole wall unattributed (PERF.md, section 7).
"""

import math
import sys

ROOT = "dopt.run"


class SpanError(RuntimeError):
    pass


def _children(events, root):
    return [e for e in events if e["parent"] == root["id"]]


def _named(events, root, name):
    return sum(e["duration"] for e in _children(events, root)
               if e["name"] == f"{ROOT}.{name}")


def reduce(facts):
    """{"wall_s", "by_name", "first_compile_s"} of the ``dopt.run`` roots of
    ``facts["calls"]``."""
    from distributed_optimization_tpu.observability import spans

    calls = facts["calls"]
    summary = {"wall_s": sum(c["wall_s"] for c in calls), "by_name": {},
               "first_compile_s": 0.0}
    if not hasattr(spans, "process_tracer"):
        print("[span_reduce] the program has no process_tracer: it names nothing "
              "of its calls", file=sys.stderr)
        return summary
    tracer = spans.process_tracer()
    events = tracer.spans()
    roots = sorted((e for e in events if e["name"] == ROOT), key=lambda e: e["id"])
    if not calls or not roots:
        raise SpanError(
            f"span_reduce: {len(roots)} {ROOT!r} span(s) in the program's process "
            f"tracer for {len(calls)} traced call(s): it exports no such spans, or "
            f"the calls ran under another tracer")
    if len([e for e in events if e["parent"] is None]) >= spans.PROCESS_TRACER_ROOTS:
        raise SpanError(
            f"span_reduce: the process tracer is full ({spans.PROCESS_TRACER_ROOTS} "
            f"roots): the warm-up's may have been dropped")
    # The process's first root is the warm-up's: the compile, or the
    # persistent cache's load.
    summary["first_compile_s"] = _named(events, roots[0], "compile")

    free = list(roots)
    for call in calls:
        root = next((r for r in free if math.isclose(
            _named(events, r, "scan"), call["scan_s"], rel_tol=1e-9)), None)
        if root is None:
            raise SpanError(
                f"span_reduce: no {ROOT!r} span whose scan took the call's "
                f"{call['scan_s']:.9f} s: the spans are not those of these calls")
        if root["duration"] > call["wall_s"]:
            raise SpanError(
                f"span_reduce: a {ROOT!r} span of {root['duration']:.6f} s inside a "
                f"call of {call['wall_s']:.6f} s")
        free.remove(root)
        for e in _children(events, root):
            summary["by_name"][e["name"]] = (
                summary["by_name"].get(e["name"], 0.0) + e["duration"])
    return summary


def seconds(facts, *names):
    """Seconds the traced calls spent in the children called
    ``dopt.run.<name>``, for each of ``names``."""
    by_name = reduce(facts)["by_name"]
    return sum(by_name.get(f"{ROOT}.{name}", 0.0) for name in names)
