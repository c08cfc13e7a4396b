"""Hierarchical span tracing with Chrome trace-event export.

The flat ``PhaseTimer`` (PR 5) answers "how many seconds went to compile
vs run" but not "WHICH request's cohort paid that compile, and where
inside it the time went". This module replaces it with spans: named,
nested, timestamped intervals (request → cohort → compile → run → chunk)
that still aggregate to the same ``{name: seconds}`` phase dict every
existing consumer reads (reports, ``--json``, manifests), plus a Chrome
trace-event JSON export viewable in chrome://tracing or Perfetto.

``Tracer`` is a drop-in superset of ``PhaseTimer``:

- ``with tracer.phase("compile"):`` / ``with tracer.span("run", id=7):``
  time a live interval; nesting is tracked per thread (the serving
  daemon's handler threads each get their own span stack), so children
  recorded inside a parent's ``with`` body parent correctly.
- ``tracer.add_span(name, seconds)`` records a post-hoc interval whose
  duration was measured elsewhere (the backend's AOT compile seconds) —
  it lands as a child of the thread's current open span.
- ``tracer.phases`` is a real, writable dict aggregating seconds by span
  name — existing code that reads or adjusts it keeps working unchanged
  (the Simulator's compile/run split assigns into it directly).
- ``to_chrome_trace()`` / ``write_chrome_trace(path)`` export complete
  ("ph": "X") events with microsecond timestamps; ``chrome_events()``
  returns the raw event list for embedding in manifests.

``utils/profiling.PhaseTimer`` is now an alias of ``Tracer``, so every
bench script's existing ``PhaseTimer()`` transparently records spans and
its manifest sidecar gains the span tree for free.

Code that is handed no tracer (the run builder, ``jax_backend._run``)
records into ``current_tracer()``: the tracer a caller made current with
``with tracer.activate():`` and otherwise ``process_tracer()``, the
process-wide default, which keeps the spans of its last
``PROCESS_TRACER_ROOTS`` root spans only. While a ``jax.profiler`` trace is
being collected every live span is also a ``TraceAnnotation``: an event of
the same name on a host plane of the ``.xplane.pb``, on the clock the
device planes use.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import json
import os
import sys
import threading
import time
from pathlib import Path
from typing import Iterator, Optional

# Root spans whose events the process-wide tracer keeps (older ones are
# dropped whole, children included).
PROCESS_TRACER_ROOTS = 64
# A call's own arguments that ``Tracer.calls_table`` repeats in its row:
# what a gossip round of the call was made of, where it holds more than
# one gradient step or samples its workers (docs/OBSERVABILITY.md).
CALL_ARGS = (
    "local_steps", "local_forward", "shard_reads", "sampled_out_share",
    "timeline_placement",
)


def _trace_annotation(name: str):
    """``jax.profiler.TraceAnnotation(name)``, or a no-op where jax has not
    been imported (then no profiler session can be live either, and this
    module stays importable without jax). With no session the annotation
    is a flag test."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(name)


class Tracer:
    """Span recorder + phase aggregator (see module docstring).

    Thread-safe: span completion appends under a lock; the per-thread
    open-span stack lives in a ``threading.local``.

    ``max_roots`` bounds the event buffer: once more than that many root
    spans (spans with no parent) have completed, the oldest root's events
    are dropped, its children with it — what a long-lived process-wide
    tracer needs. ``None`` keeps everything.
    """

    def __init__(
        self, phases: Optional[dict] = None, max_roots: Optional[int] = None
    ):
        # Aggregate seconds by span name — the PhaseTimer-compatible
        # surface. A plain dict on purpose: callers assign into it.
        self.phases: dict[str, float] = dict(phases or {})
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._tls = threading.local()
        self._next_id = 0
        self._max_roots = max_roots
        self._roots: collections.deque = collections.deque()
        # Epoch anchor so timestamps from perf_counter are absolute-ish
        # and comparable across tracers in one process.
        self._t0_wall = time.time() - time.perf_counter()

    # ------------------------------------------------------------- spans
    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = []
            self._tls.stack = st
        return st

    def _new_event(self, name, args) -> dict:
        """An open event under the thread's open span (ids are handed out
        at entry, so a parent's id is below its children's)."""
        stack = self._stack()
        with self._lock:
            self._next_id += 1
            span_id = self._next_id
        ev = {
            "id": span_id,
            "name": name,
            "parent": stack[-1]["id"] if stack else None,
            "root": stack[0]["id"] if stack else span_id,
            "thread": threading.current_thread().name,
        }
        if args:
            ev["args"] = dict(args)
        return ev

    def _record(self, ev, aggregate):
        with self._lock:
            self._events.append(ev)
            if aggregate:
                self.phases[ev["name"]] = (
                    self.phases.get(ev["name"], 0.0) + ev["duration"]
                )
            if self._max_roots is not None and ev["parent"] is None:
                self._roots.append(ev["id"])
                if len(self._roots) > self._max_roots:
                    old = self._roots.popleft()
                    self._events = [
                        e for e in self._events if e["root"] != old
                    ]

    @contextlib.contextmanager
    def span(self, name: str, aggregate: bool = True, **args) -> Iterator[dict]:
        """Time a live interval; nests under the thread's open span.
        ``aggregate=False`` records the span without folding its duration
        into ``phases`` — for grouping spans (a request, a labeled run)
        whose children already account the same seconds.

        Yields the span's event: ``start`` is set on entry and ``duration``
        on exit (so a caller that needs the interval reads the one clock
        the span read), and ``args`` may be added to until the span
        closes."""
        ev = self._new_event(name, args)
        stack = self._stack()
        stack.append(ev)
        with _trace_annotation(name):
            ev["start"] = time.perf_counter()  # perf_counter seconds
            try:
                yield ev
            finally:
                ev["duration"] = time.perf_counter() - ev["start"]
                stack.pop()
                self._record(ev, aggregate)

    # PhaseTimer compatibility: same name, same semantics, now a span.
    phase = span

    def add_span(
        self,
        name: str,
        seconds: float,
        *,
        start: Optional[float] = None,
        aggregate: bool = True,
        **args,
    ) -> None:
        """Record an interval measured elsewhere (e.g. the backend's AOT
        compile seconds) as a child of the thread's current open span.
        ``start`` defaults to "it just ended" (now − seconds)."""
        ev = self._new_event(name, args)
        ev["duration"] = float(seconds)
        ev["start"] = (
            time.perf_counter() - ev["duration"] if start is None else start
        )
        self._record(ev, aggregate)

    @contextlib.contextmanager
    def activate(self) -> Iterator["Tracer"]:
        """Make this the tracer ``current_tracer()`` returns in the calling
        context, so code that is handed no tracer (the run builder) nests
        its spans under the caller's open span."""
        token = _CURRENT.set(self)
        try:
            yield self
        finally:
            _CURRENT.reset(token)

    # ------------------------------------------------------------ reading
    def spans(self) -> list[dict]:
        with self._lock:
            return [dict(ev) for ev in self._events]

    def report(self) -> str:
        """The PhaseTimer text table (share-of-total per phase name)."""
        total = sum(self.phases.values())
        lines = [f"{'phase':<24}{'seconds':>10}{'share':>8}"]
        for name, secs in sorted(self.phases.items(), key=lambda kv: -kv[1]):
            share = secs / total if total > 0 else 0.0
            lines.append(f"{name:<24}{secs:>10.3f}{share:>7.1%}")
        lines.append(f"{'total':<24}{total:>10.3f}")
        return "\n".join(lines)

    def calls_table(self, name: str = "dopt.run", format: str = "text"):
        """One row a span called ``name`` (a call of the run builder),
        oldest first: which call was slow, and in which child — a sweep
        read without a profiler. A row holds the span's ``id``, ``start``
        (seconds since the first row's) and ``duration``; ``seconds``, by
        name less the ``<name>.`` prefix, of its children (``harvest``) and
        of their parts (``harvest.cast``); and ``counts``, the numeric
        arguments of both (``upload.bytes``, ``upload.wait_s``,
        ``harvest.fetch.strided``). Both are summed where a call opened a
        name twice. ``said`` repeats those of the span's OWN arguments that
        ``CALL_ARGS`` names (what a round of the call was made of), where it
        has them. ``format="json"`` returns the rows, ``"text"`` one
        aligned table of them, a column for every key any row holds."""
        if format not in ("text", "json"):
            raise ValueError(
                f"calls_table: format {format!r} is not 'text' or 'json'"
            )
        with self._lock:
            events = sorted(self._events, key=lambda e: e["id"])
        calls = {}  # id of a call -> its row
        owner = {}  # id of a call's child -> the call's id
        for e in events:  # ids go up at entry: a parent before its children
            if e["name"] == name:
                said = e.get("args") or {}
                calls[e["id"]] = {
                    "id": e["id"], "start": e["start"],
                    "duration": e["duration"], "seconds": {}, "counts": {},
                    "said": {a: said[a] for a in CALL_ARGS if a in said},
                }
                continue
            if e["parent"] in calls:
                call = owner[e["id"]] = e["parent"]
            elif e["parent"] in owner:  # a part of a child; nothing deeper
                call = owner[e["parent"]]
            else:
                continue
            row = calls[call]
            key = e["name"].removeprefix(name + ".")
            row["seconds"][key] = row["seconds"].get(key, 0.0) + e["duration"]
            for arg, value in (e.get("args") or {}).items():
                if isinstance(value, bool) or not isinstance(value, (int, float)):
                    continue
                count = f"{key}.{arg}"
                row["counts"][count] = row["counts"].get(count, 0) + value
        rows = sorted(calls.values(), key=lambda r: r["start"])
        first = rows[0]["start"] if rows else 0.0
        for row in rows:
            row["start"] -= first
        if format == "json":
            return rows
        flat = [
            {"start": row["start"], "duration": row["duration"],
             **row["seconds"], **row["counts"], **row["said"]}
            for row in rows
        ]
        columns = list(dict.fromkeys(
            ["start", "duration", *(k for row in flat for k in row)]
        ))
        table = [["#", *columns]] + [
            [str(n)] + [
                f"{row[c]:.6f}" if isinstance(row.get(c), float)
                else str(row.get(c, "-"))
                for c in columns
            ]
            for n, row in enumerate(flat)
        ]
        widths = [max(map(len, column)) for column in zip(*table)]
        return "\n".join(
            "  ".join(cell.rjust(w) for cell, w in zip(line, widths))
            for line in table
        )

    # ------------------------------------------------------ chrome export
    def chrome_events(self) -> list[dict]:
        """Complete ("ph": "X") trace events, µs timestamps, one tid per
        recording thread — the list ``write_bench_manifest`` embeds."""
        tids: dict[str, int] = {}
        out = []
        with self._lock:
            events = [dict(ev) for ev in self._events]
        for ev in sorted(events, key=lambda e: e["start"]):
            tid = tids.setdefault(ev["thread"], len(tids))
            entry = {
                "name": ev["name"],
                "ph": "X",
                "ts": (self._t0_wall + ev["start"]) * 1e6,
                "dur": ev["duration"] * 1e6,
                "pid": os.getpid(),
                "tid": tid,
            }
            args = dict(ev.get("args") or {})
            if ev.get("parent") is not None:
                args["parent_span"] = ev["parent"]
            args["span"] = ev["id"]
            entry["args"] = args
            out.append(entry)
        return out

    def to_chrome_trace(self) -> dict:
        """The chrome://tracing / Perfetto JSON object (thread-name
        metadata rows + the complete events)."""
        events = self.chrome_events()
        with self._lock:
            raw = [dict(ev) for ev in self._events]
        tids: dict[str, int] = {}
        for ev in sorted(raw, key=lambda e: e["start"]):
            tids.setdefault(ev["thread"], len(tids))
        meta = [
            {
                "name": "thread_name", "ph": "M", "pid": os.getpid(),
                "tid": tid, "args": {"name": thread},
            }
            for thread, tid in sorted(tids.items(), key=lambda kv: kv[1])
        ]
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def write_chrome_trace(self, path) -> Path:
        p = Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(self.to_chrome_trace()) + "\n")
        return p


_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "dopt_current_tracer", default=None
)
_PROCESS_TRACER = Tracer(max_roots=PROCESS_TRACER_ROOTS)


def process_tracer() -> Tracer:
    """The process-wide default tracer (as ``metrics_registry()`` is the
    process-wide registry). Bounded: it holds the spans of the last
    ``PROCESS_TRACER_ROOTS`` root spans, so a serving daemon or a sweep of
    a million runs keeps a fixed few hundred events."""
    return _PROCESS_TRACER


def current_tracer() -> Tracer:
    """The tracer made current by ``Tracer.activate`` in this context, else
    the process default."""
    return _CURRENT.get() or _PROCESS_TRACER
