"""From a profiler trace (``.xplane.pb``) to the few numbers the benchmark
reports. Read with ``jax.profiler.ProfileData`` and nothing else.

Per device plane (``/device:TPU:<n>``), on the op line ("XLA Ops"):
  busy     the union of the intervals in which an operation ran; control-flow
           containers (``while``, ``conditional``, ``call``) span their bodies
           and are left out, so the gaps between a loop's operations count
           as idle
  ops      summed duration by operation name
  gaps     the longest idle gaps, each named by the harness's host span
           (``jax.profiler.TraceAnnotation``, names starting ``bench.``) that
           covers its middle; a span's start and end bound gaps too, so the
           wait before a call's first operation and after its last are listed
Over several devices busy is the MEAN of the planes' own unions, never a sum.
"""

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
CONTAINER_PREFIXES = ("while", "conditional", "call")
HOST_SPAN_PREFIX = "bench."


def find_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _is_container(name):
    base = name.lstrip("%")
    return any(
        base == p or base.startswith((p + ".", p + "-", p + " "))
        for p in CONTAINER_PREFIXES
    )


def op_kind(name):
    """An event's name is the whole HLO instruction. Keep the opcode-like
    stem and the output type, drop the numeric suffix, so that the unrolled
    copies of one operation add up under one row:
    ``%multiply_reduce_fusion.104 = f32[262144,81]{...} fusion(...)`` becomes
    ``multiply_reduce_fusion f32[262144,81]``."""
    head, _, rest = name.partition(" = ")
    stem = head.lstrip("%").rstrip("0123456789").rstrip(".")
    out = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return (stem + " " + out).strip()[:96]


def union_seconds(intervals):
    """Total length of the union of (start_ns, end_ns) intervals, in seconds,
    and the gaps between its pieces as (start_ns, end_ns)."""
    busy, gaps = 0, []
    cur_lo = cur_hi = None
    for lo, hi in sorted(intervals):
        if cur_hi is None:
            cur_lo, cur_hi = lo, hi
        elif lo <= cur_hi:
            cur_hi = max(cur_hi, hi)
        else:
            busy += cur_hi - cur_lo
            gaps.append((cur_hi, lo))
            cur_lo, cur_hi = lo, hi
    if cur_hi is not None:
        busy += cur_hi - cur_lo
    return busy / 1e9, gaps


def load_events(path):
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns)) for ev in line.events
            )
    return planes


def host_spans(planes):
    """The harness's own spans, from every non-device plane."""
    spans = []
    for plane_name, lines in planes.items():
        if plane_name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for events in lines.values():
            spans.extend(
                (name, start, start + dur) for name, start, dur in events
                if name.startswith(HOST_SPAN_PREFIX)
            )
    return sorted(spans, key=lambda s: s[1])


def reduce_planes(planes, top=10):
    spans = host_spans(planes)
    span_edges = [(t, t) for _, lo, hi in spans for t in (lo, hi)]
    devices = []
    for plane_name in sorted(planes):
        if not plane_name.startswith(DEVICE_PLANE_PREFIX):
            continue
        events = planes[plane_name].get(OP_LINE, [])
        leaves = [(n, s, d) for n, s, d in events if not _is_container(n)]
        intervals = [(s, s + d) for _, s, d in leaves]
        busy_s, _ = union_seconds(intervals)
        _, gaps = union_seconds(intervals + span_edges)  # zero-length: they only cut gaps
        ops = {}
        for name, _, dur in leaves:
            kind = op_kind(name)
            ops[kind] = ops.get(kind, 0.0) + dur / 1e9
        devices.append({
            "plane": plane_name, "busy_s": busy_s, "n_events": len(leaves),
            "ops": ops, "gaps": gaps,
        })
    if not devices:
        return None

    def span_at(ns):
        inside = [s for s in spans if s[1] <= ns <= s[2]]
        # the innermost (shortest) span that covers the instant
        return min(inside, key=lambda s: s[2] - s[1])[0] if inside else "outside-bench-spans"

    n = len(devices)
    op_names = set().union(*(d["ops"] for d in devices))
    op_table = sorted(
        ((name, sum(d["ops"].get(name, 0.0) for d in devices) / n) for name in op_names),
        key=lambda r: -r[1],
    )
    # Gaps are read on the first device; the planes share the host's clock.
    longest = sorted(devices[0]["gaps"], key=lambda g: g[0] - g[1])[:top]
    return {
        "n_devices": n,
        "busy_s": sum(d["busy_s"] for d in devices) / n,
        "busy_s_per_device": [d["busy_s"] for d in devices],
        "n_events": sum(d["n_events"] for d in devices),
        "device_ops": [[name, sec] for name, sec in op_table[:top]],
        "idle_gaps": [[span_at((lo + hi) / 2), (hi - lo) / 1e9] for lo, hi in longest],
    }


def reduce(path, top=10):
    return reduce_planes(load_events(path), top=top)
