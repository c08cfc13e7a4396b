"""Device seconds of the traced calls by phase of the scan: the op table's
rows billed to the scope the PROGRAM says their instructions belong to
(``observability/device_scopes.py``: ``jax.named_scope`` round the work of
each phase, and the compiled program's table from instruction to scope),
not to row names written in a configuration file. A PR that renames a
fusion cannot silence these: the renamed fusion still carries its scope.

Each traced call is paired with its ``dopt.run`` root as ``span_reduce``
pairs them (by the scan's seconds); the root's ``program`` names the
executable the call ran, and the program hands over that executable's table:
for every instruction its head as the compiled text prints it (what a trace
event's name starts with), its ``scope`` and, for a fusion, the other
scopes found inside it (``also``). The table is keyed by
``trace_reduce.op_kind(head)``, the rule that made the rows, and each of
``trace["device_ops"]``'s rows is billed to the scope ALL its instructions
share. A row whose instructions carry different scopes, a row the table
does not know, and the busy time outside the ten rows go to ``None``:
``None`` = ``busy_s`` less everything billed to a scope, floored at 0. So
the scopes and ``None`` sum to ``busy_s``, nothing is counted twice, and
every scoped number reads low, never high. (``busy_s`` is a union of
intervals, the rows are summed durations: where asynchronous copies or
collectives overlap the compute the rows can exceed the union, and the
reduction says so on stderr.) A fused row is billed whole to its ``scope``;
``also`` is printed beside it and nothing is split. Every row's verdict goes
to stderr, so a traced run's log is the breakdown by phase.

A program without ``device_scopes`` (the parent commit, over which the
driver lays these files), a root without ``program``, and a rehearsal (no
device plane, ``device_ops`` empty) bill nothing: every scope 0.0, ``None``
the whole of ``busy_s``. Numbers, because ``emit.validate`` refuses a traced
line that lacks a metric.
"""

import json
import math
import os
import sys

from benchmark import span_reduce, trace_reduce

ROOT_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRIC = "scan.{}_us_per_iter"

_last = None  # (trace, facts, result): the readers of one line share one pass


def _say(*parts):
    print("[scope_reduce]", *parts, file=sys.stderr, flush=True)


def _device_scopes():
    try:
        from distributed_optimization_tpu.observability import device_scopes
    except ImportError:
        return None
    return device_scopes


def traced_roots(facts):
    """The ``dopt.run`` roots' arguments of ``facts["calls"]``, paired by the
    scan's seconds; [] for a program with no process tracer."""
    from distributed_optimization_tpu.observability import spans

    if not hasattr(spans, "process_tracer"):
        return []
    events = spans.process_tracer().spans()
    roots = [
        (span_reduce._named(events, e, "scan"), e.get("args", {}))
        for e in events if e["name"] == span_reduce.ROOT
    ]
    return [
        args for call in facts["calls"] for scan_s, args in roots
        if math.isclose(scan_s, call["scan_s"], rel_tol=1e-9)
    ]


def kinds_of(tables):
    """{row name: {"scopes": set, "also": set}} of the programs' tables, the
    row name being ``trace_reduce.op_kind`` of an instruction's head."""
    kinds = {}
    for table in tables:
        for row in table["rows"]:
            kind = kinds.setdefault(
                trace_reduce.op_kind(row["head"]), {"scopes": set(), "also": set()})
            kind["scopes"].add(row["scope"])
            kind["also"].update(row["also"])
    return kinds


def bill(trace, kinds):
    """{scope | None: seconds}: ``trace["device_ops"]`` through ``kinds``."""
    out = {None: 0.0}
    for name, sec in trace["device_ops"]:
        kind = kinds.get(name)
        if kind is None:
            scope, why = None, "no such instruction in the program's table"
        elif len(kind["scopes"]) > 1:
            scope, why = None, "instructions of different scopes: " + ", ".join(
                sorted(str(s) for s in kind["scopes"]))
        else:
            (scope,) = kind["scopes"]
            why = "no scope on its instructions" if scope is None else ""
        also = ", ".join(sorted(kind["also"])) if kind else ""
        _say(f"{sec:12.6f} s  {name}  ->  {scope}"
             + (f"  (also: {also})" if also else "") + (f"  [{why}]" if why else ""))
        if scope is not None:
            out[scope] = out.get(scope, 0.0) + sec
    billed = sum(out.values())
    out[None] = trace["busy_s"] - billed
    if out[None] < 0:
        _say(f"the rows billed to a scope sum to {billed:.6f} s, over the busy "
             f"{trace['busy_s']:.6f} s (overlapped operations): none unattributed")
        out[None] = 0.0
    else:
        _say(f"{out[None]:12.6f} s  of the busy {trace['busy_s']:.6f} s  ->  None "
             f"(rows without one scope, and what lies under the ten)")
    return out


def by_scope(trace, facts):
    """{scope | None: device seconds of the traced calls}; the values sum to
    ``trace["busy_s"]``."""
    global _last
    if _last is not None and _last[0] is trace and _last[1] is facts:
        return _last[2]
    program = _device_scopes()
    tables = []
    if program is None:
        _say("the program has no observability.device_scopes: nothing is billed")
    elif trace["device_ops"]:
        names = [args.get("program") for args in traced_roots(facts)]
        tables = [program.table_for(n) for n in dict.fromkeys(names) if n is not None]
        if not tables or None in tables:
            _say(f"the traced calls name the programs {names}, and the process "
                 f"holds the table of {sum(t is not None for t in tables)}")
            tables = [t for t in tables if t is not None]
        for t in tables:
            _say(f"table of {t['module']}: {len(t['rows'])} instructions, text "
                 f"{t['text_s']:.3f} s, parse {t['parse_s']:.3f} s")
            if not any(row["scope"] for row in t["rows"]):
                _say("no instruction of it carries a scope: an executable from a "
                     "compile cache or store written by a program without scopes")
    result = bill(trace, kinds_of(tables))
    _last = (trace, facts, result)
    return result


def reported(config):
    """The scopes whose ``scan.<scope>_us_per_iter`` the cells of this
    configuration report (``BENCHMARK.json``)."""
    with open(os.path.join(ROOT_DIR, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"] for w in bench["workloads"] if w["config"] == config["name"]}
    prefix, _, suffix = METRIC.partition("{}")
    return {
        m["name"][len(prefix):-len(suffix)] for m in bench["per_layer"]
        if m["name"].startswith(prefix) and m["name"].endswith(suffix)
        and ("workloads" not in m or cells & set(m["workloads"]))
    } - {"device", "unattributed"}


def us_per_iter(trace, facts, config, scope):
    """``scan.<scope>_us_per_iter``; ``scope`` None is
    ``scan.unattributed_us_per_iter``: ``None`` of ``by_scope`` plus any
    scope this configuration's cells do not report (said on stderr)."""
    if trace is None or not facts["iterations"]:
        return None
    seconds = by_scope(trace, facts)
    if scope is not None:
        value = seconds.get(scope, 0.0)
    else:
        known = reported(config)
        value = seconds[None]
        for other, sec in seconds.items():
            if other is not None and other not in known and sec:
                _say(f"{sec:.6f} s under {other!r}, which this cell does not "
                     f"report: added to the unattributed")
                value += sec
    return value * 1e6 / facts["iterations"]
