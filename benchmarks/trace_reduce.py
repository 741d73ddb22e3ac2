"""From a profiler trace to numbers. Pure functions over event tuples
`(name, start_ns, duration_ns)`, plus the one function that reads an
`.xplane.pb` with `jax.profiler.ProfileData`. Checked on the fixtures in
`benchmarks/fixtures/`.

A device plane has several lines that describe the same time: steps,
modules, operations. Busy time is the union of the intervals on ONE
line, the operations, of ONE device, clipped to the window; summing
lines, chips or nested operations would count time twice.
"""
from __future__ import annotations

import re
import statistics
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def newest_xplane(profile_dir) -> Path:
    found = sorted(Path(profile_dir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(
            f"no .xplane.pb under {profile_dir}/plugins/profile/*/")
    return found[-1]


def load_xplane(path) -> dict:
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
    return planes


def device_planes(planes: dict) -> dict:
    return {name: lines for name, lines in sorted(planes.items())
            if name.startswith("/device:") and OPS_LINE in lines}


def host_as_device(planes: dict) -> dict:
    """For a CPU rehearsal only: the CPU backend has no device plane, so
    its XLA worker threads stand in for the operations line and the
    Python thread's jit calls for the modules line."""
    host = planes.get("/host:CPU", {})
    ops = [e for line, events in host.items() if line.startswith("tf_XLA")
           for e in events]
    return {"/host:CPU": {OPS_LINE: ops,
                          MODULES_LINE: host.get("python", [])}}


def merged(intervals) -> list:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def clipped(events, lo, hi) -> list:
    """(start, end) of every event, cut to [lo, hi]."""
    return [(max(s, lo), min(s + d, hi)) for _, s, d in events
            if s < hi and s + d > lo]


def whole_events(events, pattern: str, lo, hi) -> list:
    """The events whose name matches `pattern` and that [lo, hi] holds
    whole: a call cut by the window's edge is no whole call."""
    out = []
    for event in events:
        if re.search(pattern, event[0]):
            inside = clipped([event], lo, hi)
            if inside and inside[0][1] - inside[0][0] >= event[2]:
                out.append(event)
    return out


def union_ns(events, lo, hi) -> float:
    return sum(b - a for a, b in merged(clipped(events, lo, hi)))


def step_window(modules, pattern: str) -> tuple:
    """The traced steps: events of the modules line whose name matches
    `pattern`. A step that was in flight when tracing began shows as a
    first event much shorter than the others and is left out. Returns
    (window start, window end, steps): first kept start to last kept end.
    """
    steps = sorted((s, d) for n, s, d in modules if re.search(pattern, n))
    if len(steps) >= 3:
        typical = statistics.median(d for _, d in steps[1:])
        if steps[0][1] < 0.8 * typical:
            steps = steps[1:]
    if not steps:
        raise ValueError(f"no module event matches {pattern!r}: "
                         f"{sorted({n for n, _, _ in modules})[:8]}")
    return steps[0][0], steps[-1][0] + steps[-1][1], len(steps)


def _sweep(cuts):
    """Yield (p, q, the cuts running throughout [p, q]) between each
    pair of neighbouring boundaries; a cut is (start, end, ...)."""
    cuts = sorted(cuts)
    points = sorted({p for c in cuts for p in c[:2]})
    active, i = [], 0
    for p, q in zip(points, points[1:]):
        while i < len(cuts) and cuts[i][0] <= p:
            active.append(cuts[i])
            i += 1
        active = [c for c in active if c[1] > p]
        if active:
            yield p, q, active


def self_times(events, lo, hi) -> dict:
    """Per name, the time inside [lo, hi] during which an event is the
    innermost one running (the one that started last): an operation that
    holds others (a loop, a call) is charged only what they leave."""
    cuts = [(max(s, lo), min(s + d, hi), s, n) for n, s, d in events
            if s < hi and s + d > lo]
    out: dict = {}
    for p, q, active in _sweep(cuts):
        name = max(active, key=lambda c: c[2])[3]
        out[name] = out.get(name, 0.0) + (q - p)
    return out


def exposed_ns(events, pattern: str, lo, hi) -> tuple:
    """(time during which an event matching `pattern` runs, the part of
    it during which nothing else does), inside [lo, hi]. An operation
    that holds a matching one from start to end (a loop, a call) is its
    container, not company."""
    cuts = [(max(s, lo), min(s + d, hi), s, s + d, bool(re.search(pattern, n)))
            for n, s, d in events if s < hi and s + d > lo]
    total = alone = 0.0
    for p, q, active in _sweep(cuts):
        mine = [c for c in active if c[4]]
        if not mine:
            continue
        total += q - p
        company = [c for c in active if not c[4] and not any(
            c[2] <= m[2] and c[3] >= m[3] for m in mine)]
        if not company:
            alone += q - p
    return total, alone


def idle_gaps(events, lo, hi) -> list:
    """[(start, end)] of the stretches of [lo, hi] with no operation."""
    gaps, at = [], lo
    for a, b in merged(clipped(events, lo, hi)):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def short_name(name: str) -> str:
    """An operation's name as the trace prints it is its whole HLO
    instruction; keep `%name opcode first-result-shape`, and say so when
    it is a Pallas kernel."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    shape = re.match(r"\(?([a-z0-9]+\[[\d,]*\])", rest)
    opcode = re.search(r"[\]})] ([a-z][\w\-]*)\(", rest)
    parts = [head.lstrip("%"), opcode.group(1) if opcode else "",
             shape.group(1) if shape else ""]
    if "tpu_custom_call" in rest:
        parts.append("tpu_custom_call")
    return " ".join(p for p in parts if p)[:120]


def name_gap(gap, ops, dispatching) -> str:
    """What the host was doing in an idle gap: of the calls on the thread
    that dispatches the step (`dispatching`, its events) that cover at
    least half of the gap, the outermost and the innermost; failing
    that, the operations on either side of it."""
    a, b = gap
    covering = sorted((s, n) for n, s, d in dispatching
                      if min(b, s + d) - max(a, s) >= 0.5 * (b - a)
                      and d < 1000 * (b - a))
    if covering:
        outer, inner = covering[0][1], covering[-1][1]
        return (outer if outer == inner else f"{outer} > {inner}")[:120]
    before = max((e for e in ops if e[1] + e[2] <= a + 1),
                 key=lambda e: e[1] + e[2], default=None)
    after = min((e for e in ops if e[1] >= b - 1),
                key=lambda e: e[1], default=None)
    return (f"after {short_name(before[0]) if before else 'window start'}, "
            f"before {short_name(after[0]) if after else 'window end'}")[:120]


class Trace:
    """One device's view of the traced steps: the device whose busy time
    in its own window is the largest (never a sum over chips)."""

    def __init__(self, planes: dict, step_pattern: str,
                 rehearse: bool = False):
        devices = host_as_device(planes) if rehearse \
            else device_planes(planes)
        if not devices:
            raise ValueError("the trace holds no device plane with an "
                             f"{OPS_LINE!r} line: {sorted(planes)}")
        best = None
        for name, lines in devices.items():
            lo, hi, steps = step_window(lines.get(MODULES_LINE, []),
                                        step_pattern)
            busy = union_ns(lines[OPS_LINE], lo, hi)
            if best is None or busy > best[0]:
                best = (busy, name, lo, hi, steps)
        self.busy_ns, self.device, self.lo, self.hi, self.steps = best
        self.ops = devices[self.device][OPS_LINE]
        self.n_devices = len(devices)
        # the host thread that dispatches the step: any host line with an
        # event that names it
        self.dispatching = [
            e for plane, lines in planes.items() if plane.startswith("/host:")
            for events in lines.values()
            if any(re.search(step_pattern, n) for n, _, _ in events)
            for e in events]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def breakdown(self, top: int = 10) -> dict:
        by_name: dict = {}
        for name, t in self_times(self.ops, self.lo, self.hi).items():
            short = short_name(name)
            by_name[short] = by_name.get(short, 0.0) + t
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(idle_gaps(self.ops, self.lo, self.hi),
                      key=lambda g: g[0] - g[1])[:top]
        return {
            "device_ops": [[n, t / 1e9] for n, t in ops],
            "idle_gaps": [[name_gap(g, self.ops, self.dispatching),
                           (g[1] - g[0]) / 1e9] for g in gaps],
        }
