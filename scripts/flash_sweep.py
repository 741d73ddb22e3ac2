#!/usr/bin/env python3
"""Time the three flash kernels alone on the chip, over block pairs.

    python3 scripts/flash_sweep.py [--shape gpt2_large.seq1k ...]
        [--blocks 128 256 512 1024 | --pairs 1024x1024 ...]
        [--mask-all] [--strips N]

For each call shape (the cells' exact calls and the 4096-token bypass
shape) and each (block_q, block_k): the forward, dkv and dq kernels, each
chained ``--calls`` times inside one jit (every call reads the one
before's result, so none is hoisted), timed on the host's clock around
``block_until_ready``, best of ``--reps``; with the kernel's share of its
roofline by ``benchmarks/flops.py`` and ``tile_counts``. One JSON line a
measurement, also under ``chiprun_out/flash_sweep.jsonl``. This is what
``ops/flash.pick_block_sizes`` is written from (PERF.md section 6).

``--mask-all`` makes every visited tile an edge tile: what the mask costs;
``--strips N`` computes a triangular tile in N strips, 0 as a whole tile.
It needs the chip: nothing here falls back to the CPU. ``--rehearse`` walks
the control flow here at a tiny size in interpret mode; its times mean
nothing and are not written.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmarks import flops  # noqa: E402
from pytorch_distributed_template_tpu.ops import flash  # noqa: E402

# [batch, tokens, heads, head size], window: what the cells' models call
SHAPES = {
    "gpt2_large.seq1k": ((8, 1024, 20, 64), 0),
    "mistral7b_l2.seq8k": ((1, 8192, 32, 128), 4096),
    "mistral7b_l2.seq4k": ((2, 4096, 32, 128), 4096),
}


def chained(fn, carry_index, calls):
    """jit of ``calls`` calls of ``fn(*args)``, each taking the one
    before's first result in place of argument ``carry_index``."""
    def many(*args):
        def step(x, _):
            full = args[:carry_index] + (x,) + args[carry_index + 1:]
            out = fn(*full)
            # the first result carries; dkv's second comes with it
            first = out[0] if isinstance(out, (tuple, list)) else out
            return first.astype(x.dtype), None
        x, _ = jax.lax.scan(step, args[carry_index], None, length=calls)
        return x
    return jax.jit(many)


def best_ms(fn, args, calls, reps):
    fn(*args).block_until_ready()           # compile, warm
    took = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        took.append(time.perf_counter() - t0)
    return 1e3 * min(took) / calls


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", nargs="*", default=list(SHAPES))
    ap.add_argument("--blocks", nargs="*", type=int,
                    default=[128, 256, 512, 1024])
    ap.add_argument("--pairs", nargs="*", default=None,
                    help="block pairs as QxK, in place of --blocks' square")
    ap.add_argument("--mask-all", action="store_true")
    ap.add_argument("--strips", type=int, default=None,
                    help="set the module's STRIPS (0: whole tiles only)")
    ap.add_argument("--calls", type=int, default=16)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--tag", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    device = jax.devices()[0]
    peaks = json.loads((ROOT / "benchmarks" / "peaks.json").read_text())
    if args.rehearse:
        shapes = {"rehearsal": ((1, 256, 2, 64), 96)}
        args.shape, args.blocks, args.calls = ["rehearsal"], [64, 128], 2
        peak = next(iter(peaks.values()))
    elif device.platform != "tpu":
        sys.exit(f"flash_sweep needs the chip, found {device.platform}")
    else:
        shapes, peak = SHAPES, peaks[device.device_kind]
    if args.mask_all:
        flash._tile_is_edge = lambda *a, **k: True
    if args.strips == 0:
        flash._tile_triangles = lambda *a, **k: (None, None)
    elif args.strips:
        flash.STRIPS = args.strips
    pairs = ([tuple(map(int, p.split("x"))) for p in args.pairs]
             if args.pairs else itertools.product(args.blocks, args.blocks))
    pairs = list(pairs)
    out_path = ROOT / "chiprun_out" / "flash_sweep.jsonl"
    out_path.parent.mkdir(exist_ok=True)

    for name in args.shape:
        (b, t, h, d), window = shapes[name]
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (jax.random.normal(kk, (b * h, t, d), jnp.bfloat16)
                      for kk in keys)
        sizes = dict(batch=b, seq_len=t, n_head=h, head_dim=d)
        for bq, bk in pairs:
            call = dict(causal=True, block_q=bq, block_k=bk, t_valid=t,
                        interpret=args.rehearse, window=window)
            fwd = lambda q, k, v: flash._flash_fwd_3d(q, k, v, **call)
            out, lse = jax.jit(fwd)(q, k, v)
            delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                            axis=-1, keepdims=True)
            stats = (q, g, lse[..., None], delta, k, v)
            runs = {
                "fwd": (chained(fwd, 0, args.calls), (q, k, v)),
                "dkv": (chained(lambda *a: flash._flash_dkv_3d(*a, **call),
                                4, args.calls), stats),
                "dq": (chained(lambda *a: flash._flash_dq_3d(*a, **call),
                               0, args.calls), stats),
            }
            counts = flash.tile_counts(t, t, bq, bk, True, window)
            for kernel, (fn, operands) in runs.items():
                line = dict(shape=name, kernel=kernel, block_q=bq,
                            block_k=bk, tag=args.tag,
                            device=device.device_kind)
                try:
                    ms = best_ms(fn, operands, args.calls, args.reps)
                except Exception as e:      # what Mosaic refuses on the chip
                    line["error"] = str(e).splitlines()[0][:200]
                else:
                    line["ms"] = round(ms, 4)
                    least, _ = flops.roofline_seconds(
                        flops.flash_call_flops(kernel, window=window,
                                               **sizes),
                        flops.flash_call_bytes(kernel, **sizes), peak)
                    line["roofline_pct"] = round(1e5 * least / ms, 2)
                    line.update(counts[kernel])
                text = json.dumps(line)
                print(text, flush=True)
                if not args.rehearse:
                    with open(out_path, "a") as f:
                        f.write(text + "\n")


if __name__ == "__main__":
    main()
