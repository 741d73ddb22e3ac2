"""Bisect the dense-prefill scheduling cliff (VERDICT r3 item 1).

The decode rung's DENSE prefill (Llama 12L/d768/GQA, 32k vocab,
rolling window 1024, 8x1024 prompt) measured ~290 ms while the SAME
shapes with w8a16 weights ran ~39 ms and with int8 KV ~32 ms — the
weight/cache storage dtype flips the XLA schedule. This script times
one prefill variant per invocation (one process = one clean XLA
client; variants share nothing), using the bench rung's chained
in-jit scan so nothing can dedup or pipeline across timed calls.

Usage:  python scripts/debug_prefill_cliff.py VARIANT
Variants: baseline | bf16_params | f32_cache | donate | chunked |
          no_window | w8 | kv8 | L6 | L8 | L10 | v256 | v8k |
          xla_attn | nocache
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import pytorch_distributed_template_tpu.models  # noqa: F401
from pytorch_distributed_template_tpu.config.registry import MODELS
from pytorch_distributed_template_tpu.engine.generate import fresh_cache

BATCH, PROMPT, NEW = 8, 1024, 256
N_PF = 5


def build(variant: str):
    window = 0 if variant == "no_window" else 1024
    quant = "w8a16" if variant == "w8" else ""
    kv_quant = "int8" if variant == "kv8" else ""
    n_layer = {"L6": 6, "L8": 8, "L10": 10}.get(variant, 12)
    vocab = {"v256": 256, "v8k": 8192}.get(variant, 32000)
    model = MODELS.get("Llama")(
        vocab_size=vocab, n_layer=n_layer, n_head=12, n_kv_head=4,
        d_model=768, max_len=PROMPT + NEW, window=window,
        bfloat16=True, quant=quant, kv_quant=kv_quant,
        attn_impl="xla" if variant == "xla_attn" else "flash",
    )
    if quant:
        from pytorch_distributed_template_tpu.models.quant import (
            quantize_params_w8,
        )

        dense = model.clone(quant="", kv_quant="")
        params = quantize_params_w8(dense.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"])
    else:
        params = model.init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    if variant == "bf16_params":
        params = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x, params
        )
    cache = fresh_cache(model, params, BATCH, PROMPT + NEW)
    if variant == "f32_cache":
        cache = jax.tree.map(
            lambda x: x.astype(jnp.float32)
            if x.dtype == jnp.bfloat16 else x, cache
        )
    return model, params, cache


def main(variant: str):
    model, params, cache = build(variant)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, 32000, size=(BATCH, PROMPT)), jnp.int32
    )

    donate = (1,) if variant == "donate" else ()

    def one_prefill(params, cache, tok):
        if variant == "chunked":
            # scan over 4 x 256-token segments: same cache, same math,
            # but each segment's DUS window write is small
            def seg(c, chunk):
                logits, vs = model.apply(
                    {"params": params, "cache": c}, chunk,
                    train=False, decode=True, prefill=False,
                    mutable=["cache"],
                )
                return vs["cache"], logits[:, -1]

            chunks = tok.reshape(BATCH, 4, 256).swapaxes(0, 1)
            c, lasts = lax.scan(seg, cache, chunks)
            return lasts[-1]
        if "nocache" in variant:
            # plain training-style forward (no cache at all): isolates
            # the KV-cache write from the math
            logits = model.apply({"params": params}, tok, train=False)
            if isinstance(logits, tuple):
                hidden, w = logits
                return hidden[:, -1] @ w
            return logits[:, -1]
        logits, _ = model.apply(
            {"params": params, "cache": cache}, tok,
            train=False, decode=True, prefill=True, mutable=["cache"],
        )
        return logits[:, -1]

    n_iter = (int(variant[4:]) if variant.startswith("scan")
              and variant[4:].isdigit() else N_PF)

    @jax.jit
    def prefill_many(params, cache, tokens):
        def body(carry, _):
            tok, acc = carry
            last = one_prefill(params, cache, tok)
            bump = jnp.max(jnp.argmax(last, -1)).astype(jnp.int32)
            return ((tokens + bump[None, None]) % 32000,
                    acc + jnp.sum(last)), None

        if variant == "unroll5":
            carry = (tokens, jnp.float32(0))
            for _ in range(N_PF):
                carry, _ = body(carry, None)
            return carry[1]
        (_, acc), _ = lax.scan(
            body, (tokens, jnp.float32(0)), None, length=n_iter
        )
        return acc

    del donate  # donation handled at jit level below when asked
    if variant == "donate":
        prefill_many = jax.jit(prefill_many.__wrapped__,
                               donate_argnums=(1,))

    if variant.startswith("eager"):
        # no outer scan: one jitted prefill per dispatch, each fenced
        # by a host readback — measures the call as a server would
        # issue it (dispatch cost included)
        pf = jax.jit(lambda p, c, t: jnp.sum(one_prefill(p, c, t)))
        float(pf(params, cache, prompt))
        times = []
        for i in range(N_PF):
            tok = (prompt + i + 1) % 32000
            t0 = time.perf_counter()
            float(pf(params, cache, tok))
            times.append(time.perf_counter() - t0)
        best = min(times)
        print(f"RESULT {variant}: {best * 1e3:.1f} ms/prefill best "
              f"(all: {[round(t * 1e3) for t in times]} ms; "
              f"{BATCH * PROMPT / best:.0f} tok/s)")

        # pipelined: issue 10 perturbed calls without intermediate
        # fences — async dispatch overlaps host issue with device
        # execution, so per-call time approaches max(issue, device)
        outs = []
        t0 = time.perf_counter()
        for i in range(10):
            outs.append(pf(params, cache, (prompt + 10 + i) % 32000))
        issue_s = time.perf_counter() - t0
        for o in outs:
            float(o)
        per = (time.perf_counter() - t0) / 10
        print(f"RESULT {variant}_pipelined: {per * 1e3:.1f} ms/prefill "
              f"(host issue {issue_s / 10 * 1e3:.1f} ms/call; "
              f"{BATCH * PROMPT / per:.0f} tok/s)")
        return

    t0 = time.perf_counter()
    float(prefill_many(params, cache, prompt))
    compile_s = time.perf_counter() - t0
    if variant == "donate":
        # donated buffer consumed — rebuild for the timed call
        cache = build(variant)[2]
    totals = []
    for i in range(6):
        t0 = time.perf_counter()
        float(prefill_many(params, cache, (prompt + 1 + i) % 32000))
        totals.append(time.perf_counter() - t0)
    per = min(totals) / n_iter
    print(f"RESULT {variant}: {per * 1e3:.1f} ms/prefill best "
          f"(dispatch totals {[round(t * 1e3) for t in totals]} ms / "
          f"{n_iter} iters; {BATCH * PROMPT / per:.0f} tok/s; "
          f"compile {compile_s:.0f}s)")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "baseline")
