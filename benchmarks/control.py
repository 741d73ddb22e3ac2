#!/usr/bin/env python3
"""Readings that a cell's limits are set from, many seeds in one process.

    python3 benchmarks/control.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3] [--rehearse]

Run by hand on the chip (never by the benchmark's own runs). For every
seed: the program's first steps through the timed compiled step, as
`run.py` reads them, against the plain float32 reference: the largest of
these is what sound runs give. For every control seed: the reference
computed in the next-lower precision (fp8 weight matmuls, see
benchmarks/reference/common.py) against the float32 reference: the
smallest of these is what the control gives. A limit has to stand
between the two with room on both sides. One JSON line per reading; the
trainer is built once and reseeded, so only the first seed pays set-up;
a configuration with experts has its selection biases solved anew for
every seed (benchmarks/balance.py), and says so on standard error.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from benchmarks import run  # noqa: E402


def readings(got: dict, want: dict) -> dict:
    """The numbers `run.compare` holds against limits, the loss as the
    larger of the steps' gaps."""
    out = {}
    for name, (value, note) in run.gaps(got, want).items():
        key = name.split(".")[0]
        out[key] = max(out.get(key, 0.0), value)
        if key != "loss_rel_gap":
            out[key + "_note"] = note
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    _, cell, config = run.load_cell(args.workload, args.rehearse)
    run_dir = ROOT / ".cache" / "bench" / (cell["name"] + ".control")
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    b = run.Bench(cell, config, seeds[0], run_dir, args.rehearse,
                  say=lambda text: print(text, file=sys.stderr, flush=True))
    for seed in seeds:
        t0 = time.perf_counter()
        if seed != b.seed:
            b.reseed(seed)
        first = b.fed
        program = b.first_steps(cell["check"]["steps"])
        b.free()
        kw = dict(cell=cell, config=config, exp=b.exp, weights=b.weights,
                  tokens=b.tokens, batch_size=b.batch_size,
                  devices=b.devices, first_batch=first)
        want = run.reference_steps(**kw)
        line = {"workload": cell["name"], "seed": seed, "side": "program",
                **readings(program, want)}
        print(json.dumps(line), flush=True)
        if seed in control:
            line = {"workload": cell["name"], "seed": seed, "side": "control",
                    **readings(run.reference_steps(mode="fp8", **kw), want)}
            print(json.dumps(line), flush=True)
        print(f"seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
