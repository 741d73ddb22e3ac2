"""The last line of a run: built in one place, checked against the
contract before it is printed, and the last thing the process writes.

`validate` states the contract's shape. `build` refuses to make a line
that would not pass it, so a fault shows as a failed run with its reason
on an earlier line, never as a malformed last line.
"""
from __future__ import annotations

import json
import math
import os

DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACE_KEYS = ("busy_s", "window_s")


class LastLineError(ValueError):
    pass


def _finite_number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def validate(line: dict, expected: dict, trace: bool) -> None:
    """`expected` is {metric name: unit} for this cell in this mode:
    its end-to-end metrics without a trace, its per-layer metrics with
    one. Raises LastLineError with every fault found."""
    faults = []
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            faults.append(f"key {key!r} is missing")
    if faults:
        raise LastLineError("; ".join(faults))
    if not isinstance(line["correct"], bool):
        faults.append("correct is not true or false")
    for key in ("attempted", "failed"):
        if not (isinstance(line[key], int) and not isinstance(line[key], bool)
                and line[key] >= 0):
            faults.append(f"{key} is not a count")
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        faults.append("metrics is not an object")
        metrics = {}
    for name, unit in expected.items():
        m = metrics.get(name)
        if m is None:
            faults.append(f"metric {name} is missing")
        elif not (isinstance(m, dict) and set(m) == {"value", "unit"}):
            faults.append(f"metric {name} is not {{value, unit}}: {m!r}")
        elif not _finite_number(m["value"]):
            faults.append(f"metric {name} has no finite value: {m['value']!r}")
        elif m["unit"] != unit:
            faults.append(f"metric {name} has unit {m['unit']!r}, "
                          f"declared {unit!r}")
    for name in metrics:
        if name not in expected:
            faults.append(f"metric {name} is not declared for this cell "
                          "in this mode")
    device = line["device"]
    if not isinstance(device, dict):
        faults.append("device is not an object")
        device = {}
    for key in DEVICE_KEYS + (TRACE_KEYS if trace else ()):
        if key not in device:
            faults.append(f"device.{key} is missing")
    for key in ("platform", "kind"):
        if key in device and not (isinstance(device[key], str)
                                  and device[key]):
            faults.append(f"device.{key} is not a name")
    for key in ("count", "memory_peak_bytes"):
        if key in device and not (isinstance(device[key], int)
                                  and not isinstance(device[key], bool)
                                  and device[key] > 0):
            faults.append(f"device.{key} is not a positive whole number")
    if trace and all(k in device for k in TRACE_KEYS):
        busy, window = device["busy_s"], device["window_s"]
        if not (_finite_number(busy) and _finite_number(window)):
            faults.append("device.busy_s or window_s is not a finite number")
        elif not 0 < busy <= window:
            faults.append(f"need 0 < busy_s <= window_s, got {busy} and "
                          f"{window}")
    if "breakdown" in line:
        if not trace:
            faults.append("breakdown belongs to a traced run")
        bd = line["breakdown"]
        if not (isinstance(bd, dict)
                and set(bd) <= {"device_ops", "idle_gaps"}):
            faults.append("breakdown has other keys than device_ops, "
                          "idle_gaps")
        else:
            for key, rows in bd.items():
                ok = (isinstance(rows, list) and len(rows) <= 10 and all(
                    isinstance(r, list) and len(r) == 2
                    and isinstance(r[0], str) and _finite_number(r[1])
                    for r in rows))
                if not ok:
                    faults.append(f"breakdown.{key} is not at most 10 "
                                  "[name, seconds] pairs")
    if faults:
        raise LastLineError("; ".join(faults))


def build(*, correct: bool, attempted: int, failed: int, values: dict,
          expected: dict, device: dict, trace: bool,
          breakdown: dict | None = None) -> str:
    """The line as text. `values` is {metric: number}; every metric in
    `expected` has to be there, and nothing else is taken."""
    missing = [n for n in expected if n not in values]
    if missing:
        raise LastLineError("no value for " + ", ".join(missing))
    line = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {n: {"value": values[n], "unit": u}
                    for n, u in expected.items()},
        "device": dict(device),
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    validate(line, expected, trace)
    try:
        text = json.dumps(line, allow_nan=False)
    except ValueError as e:
        raise LastLineError(f"not strict JSON: {e}") from e
    validate(json.loads(text), expected, trace)
    if "\n" in text:
        raise LastLineError("the line holds a line break")
    return text


def parse_last(stdout_text: str) -> dict:
    """What the driver does: the last line of standard output, as JSON
    with no NaN or Infinity."""
    lines = stdout_text.rstrip("\n").split("\n")

    def refuse(token):
        raise LastLineError(f"{token} is not JSON")

    return json.loads(lines[-1], parse_constant=refuse)


class Stdout:
    """Keeps the process's real standard output for the harness and
    points file descriptor 1 at standard error, so that no logger,
    progress bar or library can write after (or between) the harness's
    lines. `finish` writes the last line and leaves with `os._exit`, so
    that no exit hook writes after it either."""

    def __init__(self):
        import sys

        sys.stdout.flush()
        self._fd = os.dup(1)
        os.dup2(2, 1)

    def say(self, text: str) -> None:
        os.write(self._fd, (text.rstrip("\n") + "\n").encode())

    def finish(self, last_line: str | None, code: int) -> None:
        import sys

        sys.stdout.flush()
        sys.stderr.flush()
        if last_line is not None:
            os.write(self._fd, (last_line + "\n").encode())
        os.close(self._fd)
        os._exit(code)
