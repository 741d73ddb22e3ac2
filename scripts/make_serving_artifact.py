#!/usr/bin/env python
"""Random-init params-only serving artifact, in seconds.

The serving and fleet tests and the drive recipes need something ``serve.py -r`` can load WITHOUT a training
run: routing, admission control, SSE plumbing, and recovery mechanics
are model-quality-independent, so a randomly initialized TinyLlama is
exactly as good a traffic target as a trained one — and ~100x faster
to produce. This writes the same artifact layout as
``scripts/quantize_checkpoint.py`` / ``scripts/merge_lora.py``:

    <out>/config.json     serving config (arch args, prefix cache,
                          optional shared compile-cache dir)
    <out>/model/          params-only orbax tree + meta sidecar

    python scripts/make_serving_artifact.py -o /tmp/fleet-model
    python serve.py -r /tmp/fleet-model/model --port 0

The base config is ``configs/llama_debug.json`` (so every section the
serving entrypoints expect is present); arch args are overridden from
the CLI. Byte-vocab (256) keeps text mode working tokenizer-free.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def make_artifact(out_dir, arch: str = "TinyLlama",
                  vocab_size: int = 256, d_model: int = 64,
                  n_layer: int = 2, n_head: int = 4,
                  n_kv_head: int = 2, max_len: int = 256,
                  block_tokens: int = 16, pool_blocks: int = 96,
                  compile_cache_dir=None, seed: int = 0,
                  tensor_parallel: int = 0, long: bool = False,
                  window: int = 0, kv_quant: str = "",
                  prefill_chunk_tokens: int = 0) -> Path:
    """Build + save the artifact; returns the ``-r``-able model path.

    Imports jax lazily so ``--help`` stays instant."""
    import jax
    import jax.numpy as jnp

    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.checkpoint.manager import (
        save_serving_params,
    )
    from pytorch_distributed_template_tpu.config.registry import MODELS
    from pytorch_distributed_template_tpu.parallel.tp import (
        model_geometry, validate_tp_geometry,
    )

    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    if long:
        # --long (ISSUE 15): the long-context traffic target —
        # a bigger position budget, a sliding window (paged ring
        # layout), an int8-KV pool, and chunked streaming prefill, so
        # one artifact exercises every ISSUE 15 layer. Explicit flags
        # still win.
        max_len = int(max_len) if int(max_len) != 256 else 4096
        window = int(window) or 512
        kv_quant = kv_quant or "int8"
        prefill_chunk_tokens = int(prefill_chunk_tokens) or 256
        pool_blocks = max(int(pool_blocks), 256)
    arch_args = {
        "vocab_size": int(vocab_size), "d_model": int(d_model),
        "n_layer": int(n_layer), "n_head": int(n_head),
        "n_kv_head": int(n_kv_head), "max_len": int(max_len),
    }
    if int(window) > 0:
        arch_args["window"] = int(window)
    if kv_quant:
        arch_args["kv_quant"] = str(kv_quant)
    model = MODELS.get(arch)(**arch_args)
    if int(tensor_parallel) > 1:
        # refuse at PRODUCTION time too: baking an intended tp the
        # geometry cannot shard would only move the failure to restore
        validate_tp_geometry(model, int(tensor_parallel))
    params = model.init(jax.random.key(int(seed)),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = copy.deepcopy(json.loads(
        (REPO / "configs" / "llama_debug.json").read_text()))
    cfg["name"] = "FleetDebug"
    cfg["arch"] = {"type": arch, "args": arch_args}
    cfg["serving"] = {"prefix_cache": {
        "enabled": True, "block_tokens": int(block_tokens),
        "pool_blocks": int(pool_blocks), "eviction": "lru",
    }}
    if int(prefill_chunk_tokens) > 0:
        cfg["serving"]["prefill_chunk_tokens"] = \
            int(prefill_chunk_tokens)
        cfg["serving"]["prefix_cache"]["prefill_chunk_tokens"] = \
            int(prefill_chunk_tokens)
    if int(tensor_parallel) > 1:
        # the artifact's INTENDED mesh layout: serve.py picks it up
        # without a --tp flag, and restore validates geometry against
        # whatever tp is actually requested (ISSUE 10 satellite)
        cfg["serving"]["tensor_parallel"] = int(tensor_parallel)
    if compile_cache_dir:
        cfg["compile_cache"] = {"dir": str(compile_cache_dir)}
    (out_dir / "config.json").write_text(json.dumps(cfg, indent=2))
    # save_serving_params also writes <model>.manifest.json — the
    # per-file sha256 manifest restore_serving_params verifies before
    # serving (a corrupted artifact refuses LOUDLY; ISSUE 9). The
    # tp_geometry meta records every TP-divisibility-relevant dimension
    # so a restore at an incompatible tensor_parallel refuses loudly
    # (checkpoint/manager.check_artifact_tp_geometry) instead of
    # failing deep inside a jit.
    meta = {"arch": arch, "source": "random-init", "seed": int(seed),
            "tp_geometry": model_geometry(model)}
    if int(tensor_parallel) > 1:
        meta["tensor_parallel"] = int(tensor_parallel)
    return save_serving_params(
        out_dir / "model", jax.device_get(params), meta=meta,
    )


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="random-init params-only serving artifact "
                    "(fleet / smoke traffic target)")
    p.add_argument("-o", "--out", required=True,
                   help="artifact directory (config.json + model/)")
    p.add_argument("--arch", default="TinyLlama")
    p.add_argument("--vocab-size", type=int, default=256)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--n-layer", type=int, default=2)
    p.add_argument("--n-head", type=int, default=4)
    p.add_argument("--n-kv-head", type=int, default=2)
    p.add_argument("--max-len", type=int, default=256)
    p.add_argument("--block-tokens", type=int, default=16,
                   help="prefix-cache block size baked into the "
                        "artifact's serving config")
    p.add_argument("--pool-blocks", type=int, default=96)
    p.add_argument("--compile-cache-dir", default=None,
                   help="shared persistent XLA cache dir baked into "
                        "the config (fleet replicas warm each other)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--long", action="store_true",
                   help="long-context variant (ISSUE 15): 4k max_len, "
                        "sliding window (paged ring), int8-KV pool, "
                        "chunked streaming prefill")
    p.add_argument("--window", type=int, default=0,
                   help="sliding-window size baked into the arch "
                        "(0 = full attention; --long defaults 512)")
    p.add_argument("--kv-quant", default="",
                   help="decode-cache quantization ('int8' = the "
                        "int8-KV pool layout; --long defaults int8)")
    p.add_argument("--prefill-chunk-tokens", type=int, default=0,
                   help="serving.prefill_chunk_tokens baked into the "
                        "config (--long defaults 256)")
    p.add_argument("--tp", type=int, default=0,
                   help="intended tensor_parallel degree baked into "
                        "the serving config + manifest (ISSUE 10); "
                        "geometry is validated at production time and "
                        "again at restore")
    args = p.parse_args(argv)
    path = make_artifact(
        args.out, arch=args.arch, vocab_size=args.vocab_size,
        d_model=args.d_model, n_layer=args.n_layer,
        n_head=args.n_head, n_kv_head=args.n_kv_head,
        max_len=args.max_len, block_tokens=args.block_tokens,
        pool_blocks=args.pool_blocks,
        compile_cache_dir=args.compile_cache_dir, seed=args.seed,
        tensor_parallel=args.tp, long=args.long, window=args.window,
        kv_quant=args.kv_quant,
        prefill_chunk_tokens=args.prefill_chunk_tokens)
    print(f"ARTIFACT {path}", flush=True)
    print(f"MANIFEST {path.parent / (path.name + '.manifest.json')}",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
