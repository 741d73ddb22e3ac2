#!/usr/bin/env python3
"""Cut an `.xplane.pb` to a few steps, for a fixture small enough to
commit: the device planes' events that lie wholly inside the chosen
steps, the host events that overlap them, every plane's metadata (the
operations' scopes are there), and nothing of `/host:metadata` (the
HLO protos, half of the file).

    python3 benchmarks/tools/cut_xplane.py <in.xplane.pb> <out.xplane.pb.gz> \
        [--first 2] [--steps 4] [--module train_step]

The steps are events of the first device plane's `XLA Modules` line
whose name matches `--module`; `--first` counts from 0. Field numbers
are tsl's `xplane.proto` (see benchmarks/xplane.py): XLine.timestamp_ns
= 3, .events = 4; XEvent.offset_ps = 2, .duration_ps = 3.
"""
from __future__ import annotations

import argparse
import gzip
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.xplane import fields, one  # noqa: E402

MARGIN_NS = 8e6     # host time kept before the first step: the gap's cause


def _put_varint(value: int) -> bytes:
    out = bytearray()
    while True:
        byte = value & 0x7F
        value >>= 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def put(number: int, wire: int, value) -> bytes:
    key = _put_varint(number << 3 | wire)
    if wire == 0:
        return key + _put_varint(value)
    if wire == 2:
        return key + _put_varint(len(value)) + bytes(value)
    return key + bytes(value)


def span_of(origin_ns, event) -> tuple:
    """(start, end) of an event in ns on the capture's clock; `origin_ns`
    is its line's timestamp."""
    start = origin_ns + one(event, 2, 0) / 1e3
    return start, start + one(event, 3, 0) / 1e3


def cut_line(line, keep) -> bytes:
    origin = one(line, 3, 0)
    return b"".join(put(n, w, v) for n, w, v in fields(line)
                    if n != 4 or keep(*span_of(origin, v)))


def step_window(plane, module: str, first: int, steps: int) -> tuple:
    names = {one(e, 1): bytes(one(one(e, 2), 2, b"")).decode()
             for n, _, e in fields(plane) if n == 4}
    for n, _, line in fields(plane):
        if n == 3 and bytes(one(line, 2, b"")) == b"XLA Modules":
            origin = one(line, 3, 0)
            found = sorted(span_of(origin, e) for m, _, e in fields(line)
                           if m == 4 and re.search(module, names[one(e, 1)]))
            chosen = found[first:first + steps]
            if len(chosen) < steps:
                raise SystemExit(f"{len(found)} steps, asked for {steps} "
                                 f"from {first}")
            return chosen[0][0], chosen[-1][1]
    raise SystemExit("the plane has no XLA Modules line")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("source")
    ap.add_argument("target")
    ap.add_argument("--first", type=int, default=2)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--module", default="train_step")
    args = ap.parse_args(argv)
    space = Path(args.source).read_bytes()
    planes = [(bytes(one(p, 2, b"")).decode(), p)
              for n, _, p in fields(space) if n == 1]
    lo, hi = step_window(
        next(p for name, p in planes if name.startswith("/device:")),
        args.module, args.first, args.steps)
    out = []
    for name, plane in planes:
        if name == "/host:metadata":
            continue
        if name.startswith("/host:"):
            def keep(a, b):
                return a < hi and b > lo - MARGIN_NS
        else:
            def keep(a, b):
                return a >= lo and b <= hi
        out.append(put(1, 2, b"".join(
            put(n, w, cut_line(v, keep) if n == 3 else v)
            for n, w, v in fields(plane))))
    data = b"".join(out)
    with gzip.open(args.target, "wb", compresslevel=9) as f:
        f.write(data)
    print(f"{len(space)} bytes -> {len(data)} bytes, "
          f"{Path(args.target).stat().st_size} gzipped; steps "
          f"{args.first}..{args.first + args.steps - 1}, "
          f"{(hi - lo) / 1e6:.3f} ms")


if __name__ == "__main__":
    main()
