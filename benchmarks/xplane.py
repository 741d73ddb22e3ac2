"""What `trace_reduce.load_xplane` drops from an `.xplane.pb`, for the
reducers that need it: each device operation's scope (the `op_name` of
its HLO instruction, as jax's name stack made it), and the host plane's
lines one thread at a time.

The scope is not on the events. The profiler keeps it once per
operation, as the stat `tf_op` of the event's metadata in the device
plane, and `jax.profiler.ProfileData` shows an event's own stats only.
So the plane's metadata is read from the file's bytes: protobuf wire
format, the few field numbers of tsl's `xplane.proto` named below.
Checked on `benchmarks/fixtures/` against a trace recorded on the chip.
"""
from __future__ import annotations

import re
from pathlib import Path

CAPTURES = Path(__file__).resolve().parent.parent / ".cache" / "bench"

# xplane.proto: XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4,
# .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name = 2,
# .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
# .str_value = 5
SCOPE_STAT = "tf_op"


def newest_capture(root=None) -> Path:
    """The newest `.xplane.pb` under the benchmark's run directories:
    `run.py` does not hand the reducers the trace's path."""
    found = sorted(Path(root or CAPTURES).glob(
        "**/plugins/profile/*/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root or CAPTURES}")
    return found[-1]


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def fields(buf):
    """(field number, wire type, value) of one message: an int for a
    varint, the bytes for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, wire, value


def one(message, number, default=None):
    return next((v for n, _, v in fields(message) if n == number), default)


def op_scopes(path, plane_name: str) -> dict:
    """{event name: scope} of the operations of one device plane that
    carry one. The event name is the HLO instruction as the trace prints
    it, the key `trace_reduce`'s event tuples have."""
    space = memoryview(Path(path).read_bytes())
    for number, _, plane in fields(space):
        if number != 1 or bytes(one(plane, 2, b"")).decode() != plane_name:
            continue
        stat_names, metadata = {}, []
        for number, _, entry in fields(plane):
            if number == 5:
                stat_names[one(entry, 1)] = bytes(
                    one(one(entry, 2), 2, b"")).decode()
            elif number == 4:
                metadata.append(one(entry, 2))
        wanted = {i for i, name in stat_names.items() if name == SCOPE_STAT}
        scopes = {}
        for meta in metadata:
            for number, _, stat in fields(meta):
                if number == 5 and one(stat, 1) in wanted:
                    scopes[bytes(one(meta, 2, b"")).decode()] = \
                        bytes(one(stat, 5, b"")).decode()
        return scopes
    raise ValueError(f"{path} holds no plane {plane_name!r}")


def thread_events(path, pattern: str) -> list:
    """The events `(name, start_ns, duration_ns)` of the host thread(s)
    with an event whose name matches `pattern`. Threads share names
    (every Python thread's line is called after the executable), so
    lines are told apart by position, never merged by name."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in line.events]
            if any(re.search(pattern, name) for name, _, _ in events):
                out.extend(events)
    return out
