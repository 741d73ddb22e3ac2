"""Root-cause the decode batch-32 cliff (ROUND5_NOTES item 8).

The ``decode_batch`` rung measured: dense decode step 3.4 ms at batch 16
-> 10.7 ms at batch 32 while accounted KV+weight bytes only double, and
``total_bw_frac`` falls 0.51 -> 0.24 — the step leaves the bandwidth
roofline. Suspects, in the rolling-cache decode attention
(models/llama.py _cached_attention, rolling branch, t == 1):

  (a) ``jnp.concatenate([hist_k, k], axis=1)`` — a full-cache copy per
      layer per step if XLA materializes it;
  (b) ``jnp.repeat(k_all, groups, axis=2)`` — 3x GQA head expansion
      (n_head=12 over n_kv_head=4) if XLA materializes it;
  (c) the f32 upcast of K/V inside ops/attention.multihead_attention —
      2x bytes on top of whatever (b) produced.

This script times ONE layer's worth of decode attention (512 scanned
steps, jitted, double-warmed) at batch 8/16/32/64 for variants that
remove the suspects one at a time, and prints ms/step/layer plus the
implied HBM bandwidth against the minimum bytes (one bf16 K+V cache
read + write of one row). Run on the real chip.
"""
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

W = 1024          # window / cache length
KVH, H, D = 4, 12, 64
GROUPS = H // KVH
STEPS = 512
NEG_INF = -1e30


def timeit(fn, *args):
    # fence each rep with a host readback of the result
    float(fn(*args))
    float(fn(*args))
    reps = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(fn(*args))
        reps.append((time.perf_counter() - t0) / STEPS * 1e3)
    return float(np.median(reps))


def make_state(b, key):
    ks = jax.random.split(key, 4)
    cache_k = jax.random.normal(ks[0], (b, W, KVH, D), jnp.bfloat16)
    cache_v = jax.random.normal(ks[1], (b, W, KVH, D), jnp.bfloat16)
    q0 = jax.random.normal(ks[2], (b, 1, H, D), jnp.bfloat16)
    kv0 = jax.random.normal(ks[3], (b, 1, KVH, D), jnp.bfloat16)
    slot_pos = jnp.arange(1, W + 1, dtype=jnp.int32)
    return cache_k, cache_v, slot_pos, q0, kv0


def att_current(q, k_new, v_new, cache_k, cache_v, slot_pos, cur):
    """Mirror of the shipped rolling branch at t=1: concat + repeat +
    f32-upcast einsum (ops/attention.multihead_attention)."""
    pos = jnp.full((1,), cur, jnp.int32)
    hist_pos = slot_pos - 1
    k_all = jnp.concatenate([cache_k, k_new], axis=1)
    v_all = jnp.concatenate([cache_v, v_new], axis=1)
    k_pos = jnp.concatenate([hist_pos, pos])[None, :]
    visible = (k_pos >= 0) & (k_pos <= pos[:, None]) & (
        pos[:, None] - k_pos < W)
    k_all = jnp.repeat(k_all, GROUPS, axis=2)
    v_all = jnp.repeat(v_all, GROUPS, axis=2)
    qf = q.astype(jnp.float32) * (D ** -0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k_all.astype(jnp.float32))
    scores = jnp.where(visible[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_all.astype(jnp.float32))
    return out.astype(q.dtype)


def att_grouped(q, k_new, v_new, cache_k, cache_v, slot_pos, cur):
    """No repeat: grouped GQA einsum straight against the bf16 cache
    (f32 accumulation via preferred_element_type); still concats."""
    pos = jnp.full((1,), cur, jnp.int32)
    hist_pos = slot_pos - 1
    k_all = jnp.concatenate([cache_k, k_new], axis=1)
    v_all = jnp.concatenate([cache_v, v_new], axis=1)
    k_pos = jnp.concatenate([hist_pos, pos])[None, :]
    visible = (k_pos >= 0) & (k_pos <= pos[:, None]) & (
        pos[:, None] - k_pos < W)
    b, t = q.shape[0], q.shape[1]
    qg = q.reshape(b, t, KVH, GROUPS, D).astype(jnp.float32) * (D ** -0.5)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_all,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(visible[:, None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(jnp.bfloat16),
                     v_all, preferred_element_type=jnp.float32)
    return out.reshape(b, t, H, D).astype(q.dtype)


def att_grouped_f32(q, k_new, v_new, cache_k, cache_v, slot_pos, cur):
    """Like att_grouped but probs stay f32 in the PV einsum (numerics
    closest to the shipped path; tests whether XLA fuses the v upcast)."""
    pos = jnp.full((1,), cur, jnp.int32)
    hist_pos = slot_pos - 1
    k_all = jnp.concatenate([cache_k, k_new], axis=1)
    v_all = jnp.concatenate([cache_v, v_new], axis=1)
    k_pos = jnp.concatenate([hist_pos, pos])[None, :]
    visible = (k_pos >= 0) & (k_pos <= pos[:, None]) & (
        pos[:, None] - k_pos < W)
    b, t = q.shape[0], q.shape[1]
    qg = q.reshape(b, t, KVH, GROUPS, D).astype(jnp.float32) * (D ** -0.5)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_all,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(visible[:, None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v_all,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, H, D).astype(q.dtype)


def att_write_first(q, k_new, v_new, cache_k, cache_v, slot_pos, cur):
    """No concat AND no repeat: write the new row into its ring slot
    first, then attend over the cache alone ([B, W])."""
    start = cur % W
    cache_k = lax.dynamic_update_slice(cache_k, k_new, (0, start, 0, 0))
    cache_v = lax.dynamic_update_slice(cache_v, v_new, (0, start, 0, 0))
    slot_pos = lax.dynamic_update_slice(
        slot_pos, jnp.full((1,), cur + 1, jnp.int32), (start,))
    pos = jnp.full((1,), cur, jnp.int32)
    k_pos = (slot_pos - 1)[None, :]
    visible = (k_pos >= 0) & (k_pos <= pos[:, None]) & (
        pos[:, None] - k_pos < W)
    b, t = q.shape[0], q.shape[1]
    qg = q.reshape(b, t, KVH, GROUPS, D).astype(jnp.float32) * (D ** -0.5)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, cache_k,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(visible[:, None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(jnp.bfloat16),
                     cache_v, preferred_element_type=jnp.float32)
    return (out.reshape(b, t, H, D).astype(q.dtype),
            cache_k, cache_v, slot_pos)


def run_variant(name, b, attends_and_writes):
    cache_k, cache_v, slot_pos, q0, kv0 = make_state(
        b, jax.random.key(b))

    @jax.jit
    def many(cache_k, cache_v, slot_pos, q0, kv0):
        def body(carry, i):
            cache_k, cache_v, slot_pos, acc = carry
            cur = W + i
            out, cache_k, cache_v, slot_pos = attends_and_writes(
                q0, kv0, kv0, cache_k, cache_v, slot_pos, cur)
            return (cache_k, cache_v, slot_pos, acc + out.mean()), None

        init = (cache_k, cache_v, slot_pos, jnp.zeros((), jnp.bfloat16))
        (ck, cv, sp, acc), _ = lax.scan(
            body, init, jnp.arange(STEPS, dtype=jnp.int32))
        return acc.astype(jnp.float32)

    ms = timeit(many, cache_k, cache_v, slot_pos, q0, kv0)
    # minimum bytes: read K+V cache (bf16) once + write one K+V row
    min_bytes = 2 * b * W * KVH * D * 2
    bw = min_bytes / (ms * 1e-3) / 1e9
    print(f"  {name:14s} b={b:2d}  {ms:7.3f} ms/step/layer  "
          f"min-bytes BW {bw:6.1f} GB/s")
    return ms


def wrap_att(fn):
    """Adapt an attention-only variant (returns just out) to the
    attend+write signature by doing the shipped single-row write."""
    def stepper(q, k_new, v_new, cache_k, cache_v, slot_pos, cur):
        out = fn(q, k_new, v_new, cache_k, cache_v, slot_pos, cur)
        start = cur % W
        cache_k = lax.dynamic_update_slice(
            cache_k, k_new, (0, start, 0, 0))
        cache_v = lax.dynamic_update_slice(
            cache_v, v_new, (0, start, 0, 0))
        slot_pos = lax.dynamic_update_slice(
            slot_pos, jnp.full((1,), cur + 1, jnp.int32), (start,))
        return out, cache_k, cache_v, slot_pos
    return stepper


def main():
    print(f"device: {jax.devices()[0].device_kind}; W={W} KVH={KVH} "
          f"H={H} D={D}; {STEPS} scanned steps, median of 3")
    for b in (8, 16, 32, 64):
        run_variant("current", b, wrap_att(att_current))
        run_variant("grouped", b, wrap_att(att_grouped))
        run_variant("grouped-f32", b, wrap_att(att_grouped_f32))
        run_variant("write-first", b, att_write_first)
        print()


if __name__ == "__main__":
    main()
