"""Device time by phase, said by the program.

The host side of a call has spans (``spans.py``); the device side is one
compiled program. Three parts, one mechanism:

1. **Scopes.** ``scope(name)`` is ``jax.named_scope("dopt.<name>")`` for a
   name of ``SCOPES`` and nothing else: metadata on the operations traced
   under it, no operation of its own. ``jax_backend._make_step_eval``,
   ``parallel/faults.py``, ``parallel/adversary.py`` and
   ``ops/compression.py`` open them where the
   work of a phase is built. Scopes nest; an instruction belongs to its
   INNERMOST ``dopt.*`` component.
2. **The compiled program's own account.** A scope reaches the compiled
   program's ``op_name`` metadata and nothing else (not the StableHLO text,
   not a profiler trace, whose op events are named by the instruction's
   text). ``scope_table(compiled)`` reads the compiled text, the one place
   the two stand side by side, and says for every instruction which scope
   it belongs to; ``memory(compiled)`` is XLA's ``memory_analysis()``.
   This module is the one place that reads compiled text.
3. **Lazy.** ``note_program`` (called by ``_drive_segments``) only names
   the executable a call ran and reads its temporaries: two arguments on
   the ``dopt.run`` root. The table is built the first time
   ``table_for(program)`` is asked, from the executable the process still
   holds, and kept.

``device_time_by_scope(xplane_path, table)`` is the exact join for whoever
holds a profiler trace: every op event of the device, by instruction name,
through the table. The CLI's ``--profile-dir`` prints it
(``profile_report``); the benchmark's ``scan.<scope>_us_per_iter`` read the
table through the ten-row summary they are handed.
"""

from __future__ import annotations

import bisect
import collections
import glob
import hashlib
import os
import re
import threading
import time
import weakref
from typing import Optional

# The whole vocabulary: what a scan iteration is made of, plus the flight
# recorder's rows. docs/OBSERVABILITY.md ("Device scopes") says what each
# covers; the benchmark reads ``scan.<scope>_us_per_iter`` for all but
# the recorder's.
SCOPES = (
    "sampling", "gradient", "local", "gossip", "compress", "faults", "robust",
    "update", "eval", "recorder",
)
SCOPE_PREFIX = "dopt."

# Executables kept alive here for their table when nothing else holds them
# (a run with the executable cache off): the last few, not the tracer's 64
# roots, since each holds its code on the device.
HELD_PROGRAMS = 8
# Programs whose key, weak reference and (once built) table are kept.
KNOWN_PROGRAMS = 64

_MEMORY_FIELDS = (
    "temp_size_in_bytes", "argument_size_in_bytes", "output_size_in_bytes",
    "generated_code_size_in_bytes",
)
# As ``benchmark/trace_reduce.py`` leaves them out: they span their bodies.
_CONTAINERS = ("while", "conditional", "call")


def scope(name: str):
    """``jax.named_scope("dopt.<name>")``: a context manager and, as every
    ``contextlib`` one, a decorator (``@scope("faults")`` on a closure)."""
    if name not in SCOPES:
        raise ValueError(f"unknown device scope {name!r}; known: {SCOPES}")
    import jax

    return jax.named_scope(SCOPE_PREFIX + name)


def compile_keeping_scopes(lowered):
    """``lowered.compile()`` with the scopes part of what JAX's persistent
    compilation cache keys the program by. By default that key is made of
    the module with its debug information stripped, and a scope is debug
    information: an executable cached by a program without scopes (the
    commit before them; the same steps under other names) would be handed
    to this one, its instructions saying nothing. Metadata has a reader
    now, so for this compile it is in the key. Costs a cold compile where a
    source line moved; the in-process executable cache is not concerned."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return lowered.compile()
    finally:
        jax.config.update(flag, before)


# ---------------------------------------------------------- compiled text

_SCOPE_RE = re.compile(re.escape(SCOPE_PREFIX) + r"([a-z_]+)")
_INSTR_RE = re.compile(r"^\s+(?:ROOT )?(%[^\s=]+) = ")
_CALLED_RE = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|false_computation)"
    r"=(%[^\s,})]+)"
)
_BRANCHES_RE = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_NAME_RE = re.compile(r"%[^\s,(){}]+")
_SHAPE_RE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def _scope_of(line: str) -> Optional[str]:
    """The innermost ``dopt.*`` component of the line's ``op_name``."""
    at = line.find('op_name="')
    if at < 0:
        return None
    at += len('op_name="')
    found = [
        s for s in _SCOPE_RE.findall(line[at:line.find('"', at)])
        if s in SCOPES
    ]
    return found[-1] if found else None


def _balanced(text: str, at: int) -> int:
    """Index just past the parenthesis that closes the one at ``at``."""
    depth = 0
    for i in range(at, len(text)):
        if text[i] == "(":
            depth += 1
        elif text[i] == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def _instruction(line: str):
    """(name, shape, opcode, operand names, text after the operands), or
    None for a line that is no instruction."""
    m = _INSTR_RE.match(line)
    if m is None:
        return None
    rest = line[m.end():]
    end = _balanced(rest, 0) if rest.startswith("(") else rest.find(" ")
    shape, after = rest[:end], rest[end + 1:]
    paren = after.find("(")
    if end < 0 or paren < 0:
        return None
    close = _balanced(after, paren)
    return (
        m.group(1), shape, after[:paren],
        _NAME_RE.findall(after[paren:close]), after[close:],
    )


def _shape_bytes(shape: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape):
        n = _DTYPE_BYTES.get(dtype, 1)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n
    return total


def _computations(text: str):
    """({computation name: its instruction lines}, entry name, module)."""
    comps, entry, current = {}, None, None
    module = text[:200].split(",", 1)[0].split()[-1] if text else ""
    for line in text.splitlines():
        if current is not None:
            if line.startswith("}"):
                current = None
            else:
                current.append(line)
        elif line.endswith("{") and line.startswith(("%", "ENTRY ")):
            name = line.split(" ", 2)[1 if line.startswith("ENTRY ") else 0]
            current = comps[name] = []
            if line.startswith("ENTRY "):
                entry = name
    return comps, entry, module


def _fused(lines):
    """Of a fused computation: (every scope on its instructions, the scope
    of its output with the most bytes)."""
    found, by_name, root = set(), {}, None
    for line in lines:
        parsed = _instruction(line)
        if parsed is None:
            continue
        s = _scope_of(line)
        if s is not None:
            found.add(s)
        by_name[parsed[0]] = (s, _shape_bytes(parsed[1]))
        if line.lstrip().startswith("ROOT "):
            root = (parsed, s)
    if root is None:
        return found, None
    (_, _, opcode, operands, _), root_scope = root
    if opcode != "tuple":
        return found, root_scope
    outputs = [by_name[o] for o in operands if o in by_name]
    if not outputs:
        return found, None
    return found, max(outputs, key=lambda o: o[1])[0]  # ties: the first


def table_from_text(text: str) -> dict:
    """``scope_table`` of a compiled program's text (``Compiled.as_text()``)."""
    t0 = time.perf_counter()
    comps, entry, module = _computations(text)
    rows, seen, todo = [], set(), [entry] if entry else []
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for line in comps[comp]:
            parsed = _instruction(line)
            if parsed is None:
                continue
            name, shape, opcode, _, tail = parsed
            called = _CALLED_RE.findall(tail)
            for group in _BRANCHES_RE.findall(tail):
                called += _NAME_RE.findall(group)
            if opcode in _CONTAINERS:
                todo.extend(called)
                continue
            own = _scope_of(line)
            also = set()
            if opcode == "fusion" and called and called[0] in comps:
                also, biggest = _fused(comps[called[0]])
                if own is None:
                    own = biggest
            rows.append({
                "head": f"{name} = {shape}",
                "scope": own,
                "also": [s for s in SCOPES if s in also and s != own],
            })
    return {
        "module": module, "rows": rows,
        "parse_s": time.perf_counter() - t0,
    }


def scope_table(compiled) -> dict:
    """Which scope each instruction of a compiled program belongs to:
    ``{"module", "rows": [{"head", "scope", "also"}], "text_s", "parse_s"}``.

    One row for every instruction of the entry computation and of the
    computations it reaches through ``while``, ``conditional`` and ``call``
    (the containers themselves left out, as a trace's reduction leaves
    them). ``head`` is the instruction as the compiled text begins it,
    ``%multiply_reduce_fusion.104 = f32[262144,81]{...}``: what a profiler
    trace's op event is named by, so whoever joins the two normalises both
    by one rule of their own. ``scope`` is the innermost ``dopt.*``
    component of the instruction's own ``op_name`` (for a fusion that is
    XLA's choice, its root's); where a fusion's own ``op_name`` holds none,
    as a tuple root's may not, the scope of the output with the most bytes,
    ties to the first; ``None`` where there is none (the carry's copies, the
    loop counter). ``also`` lists every other scope found on the
    instructions of a fusion's computation: a row that says ``update`` and
    ``also: gossip, eval`` is one pass over the models that XLA made of all
    three, billed whole to ``update``.
    """
    t0 = time.perf_counter()
    text = compiled.as_text()
    text_s = time.perf_counter() - t0
    return {**table_from_text(text), "text_s": text_s}


def memory(compiled) -> dict:
    """``memory_analysis()`` of a compiled program, the four sizes the
    executable cache sums (``serving/cache.estimate_executable_bytes``) and
    of which ``temp_size_in_bytes`` is the root's ``temp_bytes``: asked of
    XLA once an executable, then kept beside it. Per device under a mesh."""
    return _facts_of(compiled)[1]


# -------------------------------------------------- programs of the process

_lock = threading.Lock()
_facts = weakref.WeakKeyDictionary()  # executable -> (program key, memory)
_programs: "collections.OrderedDict[str, dict]" = collections.OrderedDict()
_held = collections.deque(maxlen=HELD_PROGRAMS)
_n_local = 0


def _program_key(compiled) -> str:
    global _n_local
    try:
        fingerprint = compiled.runtime_executable().fingerprint
    except Exception:
        fingerprint = None
    if fingerprint:
        if isinstance(fingerprint, str):
            fingerprint = fingerprint.encode()
        return hashlib.sha1(fingerprint).hexdigest()[:12]
    with _lock:
        _n_local += 1
        return f"local-{_n_local}"


def _facts_of(compiled):
    try:
        return _facts[compiled]
    except (KeyError, TypeError):
        pass
    analysis = compiled.memory_analysis()
    sizes = {
        name: int(getattr(analysis, name, 0) or 0) for name in _MEMORY_FIELDS
    }
    facts = (_program_key(compiled), sizes)
    try:
        _facts[compiled] = facts
    except TypeError:  # not weakly referenceable: asked again next time
        pass
    return facts


def note_program(compiled, *, held_elsewhere: bool) -> dict:
    """What a call writes on its ``dopt.run`` root about the executable it
    ran: ``{"program", "temp_bytes"}``. ``program`` is a short key of the
    executable (of its fingerprint where the runtime has one: the same on a
    cache hit, on the miss before it and in the next process) by which
    ``table_for`` finds it again; no text is read here. ``held_elsewhere``:
    the executable cache keeps the executable alive; where it does not, the
    last ``HELD_PROGRAMS`` are kept here."""
    program, sizes = _facts_of(compiled)
    with _lock:
        known = _programs.setdefault(
            program, {"executable": weakref.ref(compiled), "table": None}
        )
        if known["executable"]() is None:  # gone; its table, if built, stays
            known["executable"] = weakref.ref(compiled)
        _programs.move_to_end(program)
        while len(_programs) > KNOWN_PROGRAMS:
            _programs.popitem(last=False)
        if not held_elsewhere and not any(c is compiled for c in _held):
            _held.append(compiled)
    return {"program": program, "temp_bytes": sizes["temp_size_in_bytes"]}


def table_for(program: str) -> Optional[dict]:
    """``scope_table`` of the executable a root's ``program`` names: built
    on the first call, kept for the next. None where the process no longer
    holds that executable (evicted from the cache, or never seen)."""
    with _lock:
        known = _programs.get(program)
    if known is None:
        return None
    if known["table"] is None:
        compiled = known["executable"]()
        if compiled is None:
            return None
        known["table"] = scope_table(compiled)
    return known["table"]


# ------------------------------------------------------------ the exact join

def _is_container(event_name: str) -> bool:
    base = event_name.lstrip("%")
    return any(
        base == p or base.startswith((p + ".", p + "-", p + " "))
        for p in _CONTAINERS
    )


def device_time_by_scope(xplane_path: str, table: dict) -> dict:
    """Device seconds by scope from a profiler trace: ``{scope | None:
    seconds}``, the mean over the device planes (as busy time is).

    Every event of a device plane's "XLA Ops" line, containers left out, is
    billed by its instruction's name through ``table``; only events inside a
    stretch of the "XLA Modules" line that names the table's own module are
    looked up, so an upload's reshape or a ``convert_element_type`` with a
    like-named instruction goes under ``None`` with the instructions that
    carry no scope. The values sum to the summed durations of the op
    line's leaves.
    """
    from jax.profiler import ProfileData

    scope_of = {
        row["head"].partition(" = ")[0]: row["scope"] for row in table["rows"]
    }
    per_plane = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        stretches = sorted(
            (float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns))
            for ev in (lines["XLA Modules"].events
                       if "XLA Modules" in lines else ())
            if ev.name.partition("(")[0] == table["module"]
        )
        starts = [lo for lo, _ in stretches]
        seconds = collections.defaultdict(float)
        for ev in lines["XLA Ops"].events:
            if _is_container(ev.name):
                continue
            start = float(ev.start_ns)
            at = bisect.bisect_right(starts, start) - 1
            inside = at >= 0 and start <= stretches[at][1]
            found = (
                scope_of.get(ev.name.partition(" = ")[0]) if inside else None
            )
            seconds[found] += float(ev.duration_ns) / 1e9
        per_plane.append(seconds)
    out = collections.defaultdict(float)
    for seconds in per_plane:
        for found, s in seconds.items():
            out[found] += s / len(per_plane)
    return dict(out)


def profile_report(profile_dir: str, span_events) -> str:
    """The "device time by scope" section of a ``--profile-dir`` run's
    report: the exact join of the newest trace under ``profile_dir`` with
    the table of every program the ``dopt.run`` roots in ``span_events``
    name."""
    lines = ["device time by scope (docs/OBSERVABILITY.md, Device scopes):"]
    paths = glob.glob(
        os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    programs = list(dict.fromkeys(
        e["args"]["program"] for e in span_events
        if e["name"] == "dopt.run" and "program" in e.get("args", {})
    ))
    if not paths or not programs:
        lines.append("  no trace, or no run that names its program")
        return "\n".join(lines)
    path = max(paths, key=os.path.getmtime)
    for program in programs:
        table = table_for(program)
        if table is None:
            lines.append(f"  program {program}: executable no longer held")
            continue
        by_scope = device_time_by_scope(path, table)
        total = sum(by_scope.values())
        lines.append(
            f"  program {program} ({table['module']}, {len(table['rows'])} "
            f"instructions; text {table['text_s']:.3f} s, parse "
            f"{table['parse_s']:.3f} s)"
        )
        if not total:
            lines.append("    the trace holds no device plane")
            continue
        order = [s for s in SCOPES if s in by_scope] + [None]
        for s in order:
            sec = by_scope.get(s, 0.0)
            lines.append(
                f"    {s or 'no scope':<12}{sec:>12.6f} s{sec / total:>8.1%}"
            )
    return "\n".join(lines)
