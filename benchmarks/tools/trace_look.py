#!/usr/bin/env python3
"""Look at one trace by hand, and cut a fixture from it.

    python3 benchmarks/tools/trace_look.py <file.xplane.pb> [--cut OUT.json.gz --steps 3]

Prints every plane and line with its event count, and for the device
planes the names that took most time on the operations line (with the
events whose names suggest a kernel or a collective). `--cut` writes the
device planes' operations and modules lines of the first `--steps` whole
steps (and the host events that overlap them) as gzipped JSON
`{plane: {line: [[name, start_ns, duration_ns], ...]}}`, times shifted
to start at 0: the form `benchmarks/fixtures/` keeps.
"""
from __future__ import annotations

import argparse
import gzip
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks import trace_reduce as tr  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("--cut")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--step-module", default="train_step")
    args = ap.parse_args()
    planes = tr.load_xplane(args.xplane)
    for pname, lines in sorted(planes.items()):
        print(pname)
        for lname, events in sorted(lines.items()):
            total = sum(d for _, _, d in events) / 1e6
            print(f"    {lname!r}: {len(events)} events, {total:.1f} ms summed")
    devices = tr.device_planes(planes)
    for pname, lines in devices.items():
        mods = lines.get(tr.MODULES_LINE, [])
        print(f"\n{pname} modules: "
              f"{sorted({n for n, _, _ in mods})[:12]}")
        lo, hi, steps = tr.step_window(mods, args.step_module)
        print(f"  window {(hi - lo) / 1e6:.1f} ms, {steps} steps, busy "
              f"{tr.union_ns(lines[tr.OPS_LINE], lo, hi) / 1e6:.1f} ms")
        top = sorted(tr.self_times(lines[tr.OPS_LINE], lo, hi).items(),
                     key=lambda kv: -kv[1])
        for name, t in top[:40]:
            print(f"  {t / 1e6 / steps:9.3f} ms/step  {name}")
        odd = [(n, t) for n, t in top if re.search(
            r"custom|flash|pallas|kernel|all-reduce|all-gather|collective",
            n, re.I)]
        print("  kernels and collectives by name:")
        for name, t in odd[:40]:
            print(f"  {t / 1e6 / steps:9.3f} ms/step  {name}")
        break
    if args.cut:
        out = {}
        first = next(iter(devices.values()))
        steps = sorted((s, d) for n, s, d in first[tr.MODULES_LINE]
                       if re.search(args.step_module, n))
        # start one step early so the cut keeps a step "in flight"
        lo = steps[0][0]
        hi = steps[min(args.steps, len(steps) - 1)][0] + \
            steps[min(args.steps, len(steps) - 1)][1]
        for pname, lines in planes.items():
            keep = (tr.OPS_LINE, tr.MODULES_LINE) \
                if pname.startswith("/device:") else tuple(lines)
            for lname in keep:
                events = [[n, s - lo, d] for n, s, d in lines.get(lname, [])
                          if s + d > lo and s < hi
                          and (pname.startswith("/device:") or d > 2e5)]
                if events:
                    out.setdefault(pname, {})[lname] = events
        with gzip.open(args.cut, "wt") as f:
            json.dump(out, f)
        print(f"cut {args.cut}: {Path(args.cut).stat().st_size} bytes")


if __name__ == "__main__":
    main()
