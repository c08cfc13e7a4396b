"""The result line: built by one function, checked against ``BENCHMARK.json``
for this cell and this mode, and only then printed — last, flushed, with
nothing after it. A line that does not validate is never printed: the run
exits non-zero with the reason on stderr.
"""

import json
import math
import sys

BREAKDOWN_MAX = 10


class InvalidLine(ValueError):
    pass


def _number(value, what):
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        raise InvalidLine(f"{what} is not a number: {value!r}")
    if not math.isfinite(value):
        raise InvalidLine(f"{what} is not finite: {value!r}")
    return value


def expected_metrics(bench, workload, traced):
    """{name: unit} of the metrics this cell reports in this mode."""
    names = {w["name"] for w in bench["workloads"]}
    if workload not in names:
        raise InvalidLine(f"unknown workload {workload!r}")
    out = {}
    for m in bench["per_layer" if traced else "end_to_end"]:
        if "workloads" not in m or workload in m["workloads"]:
            out[m["name"]] = m["unit"]
    return out


def validate(line, bench, workload, traced):
    """Raise ``InvalidLine`` unless ``line`` is what the contract asks of this
    cell in this mode. Returns the line."""
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            raise InvalidLine(f"missing key {key!r}")
    if not isinstance(line["correct"], bool):
        raise InvalidLine("correct is not a boolean")
    for key in ("attempted", "failed"):
        if isinstance(line[key], bool) or not isinstance(line[key], int) or line[key] < 0:
            raise InvalidLine(f"{key} is not a count: {line[key]!r}")
    if line["failed"] > line["attempted"]:
        raise InvalidLine("failed exceeds attempted")

    expected = expected_metrics(bench, workload, traced)
    metrics = line["metrics"]
    if set(metrics) != set(expected):
        raise InvalidLine(
            f"metrics are {sorted(metrics)}, this cell in this mode reports "
            f"{sorted(expected)}")
    for name, unit in expected.items():
        entry = metrics[name]
        if set(entry) != {"value", "unit"}:
            raise InvalidLine(f"metric {name} has keys {sorted(entry)}")
        _number(entry["value"], f"metric {name}")
        if entry["unit"] != unit:
            raise InvalidLine(f"metric {name} has unit {entry['unit']!r}, not {unit!r}")
        if unit == "%" and not 0.0 <= entry["value"] <= 100.0:
            # Every "%" here is a share of a whole or of a peak: above 100 the
            # operations are counted too high or the time leaves work out.
            raise InvalidLine(f"metric {name} = {entry['value']} is not a share")

    device = line["device"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if key not in device:
            raise InvalidLine(f"device lacks {key!r}")
    if not isinstance(device["platform"], str) or not isinstance(device["kind"], str):
        raise InvalidLine("device platform and kind are strings")
    if _number(device["count"], "device count") < 1:
        raise InvalidLine("device count below 1")
    if _number(device["memory_peak_bytes"], "memory_peak_bytes") <= 0:
        raise InvalidLine("memory_peak_bytes is not above 0")
    if traced:
        for key in ("busy_s", "window_s"):
            if key not in device:
                raise InvalidLine(f"traced device lacks {key!r}")
            _number(device[key], key)
        if not device["window_s"] > 0:
            raise InvalidLine("window_s is not above 0")
        if not 0 < device["busy_s"] <= device["window_s"]:
            raise InvalidLine(
                f"busy_s {device['busy_s']} is not in (0, window_s {device['window_s']}]")
    if "breakdown" in line:
        if not traced:
            raise InvalidLine("breakdown belongs to a traced line")
        bd = line["breakdown"]
        if set(bd) != {"device_ops", "idle_gaps"}:
            raise InvalidLine(f"breakdown has keys {sorted(bd)}")
        for key, rows in bd.items():
            if len(rows) > BREAKDOWN_MAX:
                raise InvalidLine(f"breakdown.{key} has more than {BREAKDOWN_MAX} entries")
            for row in rows:
                if len(row) != 2 or not isinstance(row[0], str):
                    raise InvalidLine(f"breakdown.{key} row {row!r} is not [name, seconds]")
                _number(row[1], f"breakdown.{key} seconds")
    return line


def dumps(line):
    """Strict JSON on one line: ``NaN`` and ``Infinity`` are errors."""
    return json.dumps(line, allow_nan=False, separators=(", ", ": "))


def build(*, correct, attempted, failed, values, units, device, breakdown=None):
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {
            name: {"value": float(values[name]), "unit": units[name]}
            for name in units if name in values and values[name] is not None
        },
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    return line


def emit(line, bench, workload, traced, out=sys.stdout):
    """Validate, then print ``line`` as the last thing on ``out``."""
    try:
        validate(line, bench, workload, traced)
        text = dumps(line)
    except ValueError as err:  # InvalidLine, or json's refusal of NaN
        print(f"benchmark: result line refused: {err}", file=sys.stderr, flush=True)
        raise SystemExit(3)
    sys.stderr.flush()
    out.write(text + "\n")
    out.flush()
