"""The host path of the traced calls, one level under ``span_reduce``: the
PARTS of a ``dopt.run`` root's children and the counters the children carry
(ISSUE 48).

``jax_backend._run`` opens named parts under two of the root's children:
``dopt.run.harvest.rows`` / ``.fetch`` / ``.cast`` / ``.average`` (the
history's rows; the models' copy to the host; their float64 C-order copy and
flatten; the mean), each a span whose ``parent`` is the ``dopt.run.harvest``
event, disjoint and in order, so that what they leave is the child's self
time. ``dopt.run.upload`` carries what the host waited inside a flat
placement as arguments: ``blocks``, ``wait_s``, ``slowest_block_s``,
``slowest_block``, beside its ``bytes``.

A call is paired with its root as ``span_reduce.reduce`` pairs them: by the
scan's seconds, the one number both carry. The walk then takes the root's
children (``parent`` = the root) and, under each, its parts (``parent`` =
the child), and keeps for every call the seconds by name, less the
``dopt.run.`` prefix (``harvest``, ``harvest.cast``), and the numeric
arguments by ``<name>.<argument>`` (``upload.wait_s``,
``harvest.fetch.bytes``), both summed where a call opened a name twice (as
``fetch`` and ``cast`` under ``return_state``, or ``upload`` with a batch
schedule).

A program whose roots carry no parts (every program before ISSUE 48: the
benchmark's files are laid over the parent commit too) says nothing of what
these metrics read, and ``emit.validate`` refuses a traced line that lacks a
metric: every reader then returns 0.0, a number, and this module says so on
stderr, once a line (PERF.md, section 7).
"""

import math
import sys

from benchmark import span_reduce

PREFIX = span_reduce.ROOT + "."
HARVEST_PARTS = ("rows", "fetch", "cast", "average")

_last = None  # (facts, result): the nine readers of one line share one walk


def _say(*parts):
    print("[host_path_reduce]", *parts, file=sys.stderr, flush=True)


def _add(call, event):
    key = event["name"].removeprefix(PREFIX)
    call["seconds"][key] = call["seconds"].get(key, 0.0) + event["duration"]
    for arg, value in event.get("args", {}).items():
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            count = f"{key}.{arg}"
            call["counts"][count] = call["counts"].get(count, 0) + value


def reduce(facts):
    """[{"seconds": {name: s}, "counts": {name.argument: number}}] of the
    traced calls' roots, a call each; [] where the program's roots hold no
    parts."""
    global _last
    if _last is not None and _last[0] is facts:
        return _last[1]
    from distributed_optimization_tpu.observability import spans

    calls, parts = [], 0
    if not hasattr(spans, "process_tracer"):
        events, facts_calls = [], []  # span_reduce says so on stderr
    else:
        events, facts_calls = spans.process_tracer().spans(), facts["calls"]
    free = sorted((e for e in events if e["name"] == span_reduce.ROOT),
                  key=lambda e: e["id"])
    for fact in facts_calls:
        root = next((r for r in free if math.isclose(
            span_reduce._named(events, r, "scan"), fact["scan_s"], rel_tol=1e-9)), None)
        if root is None:
            raise span_reduce.SpanError(
                f"host_path_reduce: no {span_reduce.ROOT!r} span whose scan took the "
                f"call's {fact['scan_s']:.9f} s: the spans are not those of these calls")
        free.remove(root)
        call = {"seconds": {}, "counts": {}}
        for child in span_reduce._children(events, root):
            _add(call, child)
            for part in span_reduce._children(events, child):
                _add(call, part)
                parts += 1
        calls.append(call)
    if not parts:
        _say(f"no child of the {len(calls)} traced root(s) holds a part: a program "
             f"from before the split of harvest and upload; every run_builder.harvest_* "
             f"and run_builder.upload_* metric of this module reads 0.0")
        calls = []
    else:
        for n, call in enumerate(calls):
            _say(f"call {n}: " + "  ".join(
                f"{k} {v:.6f} s" for k, v in call["seconds"].items()
                if k.startswith(("harvest", "upload"))))
            _say(f"call {n}: " + "  ".join(
                f"{k} {v}" for k, v in call["counts"].items()
                if k.startswith(("harvest", "upload"))))
    _last = (facts, calls)
    return calls


def seconds(facts, *names):
    """Seconds of the traced calls in the children or parts called
    ``dopt.run.<name>``, for each of ``names``."""
    return float(sum(call["seconds"].get(name, 0.0)
                     for call in reduce(facts) for name in names))


def count(facts, name, over=sum):
    """The traced calls' counter ``<child or part>.<argument>``, summed over
    the calls (or ``over=max``); 0.0 where no call carries it."""
    found = [call["counts"][name] for call in reduce(facts) if name in call["counts"]]
    return float(over(found)) if found else 0.0


def harvest_self_s(facts):
    """``dopt.run.harvest`` less its four parts: what the child does between
    them (the monitors' pass over the trace, ``RunHistory``)."""
    return seconds(facts, "harvest") - seconds(
        facts, *("harvest." + p for p in HARVEST_PARTS))


def gbps(n_bytes, secs):
    """GB/s, and 0.0 for nothing in no time (a part that did not run)."""
    return n_bytes / secs / 1e9 if secs > 0 else 0.0
