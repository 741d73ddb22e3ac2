"""Attention implementations.

The reference has no attention anywhere (its model zoo is one MNIST CNN,
SURVEY.md §2.3) — but the BASELINE.json ladder (ViT, GPT-2) and the
long-context mandate require it, so attention is a first-class op family
here as a family of interchangeable implementations:

- ``multihead_attention``: plain XLA einsum-softmax-einsum. XLA:TPU fuses
  the mask+softmax chain; fine up to moderate T.
- ``ring_attention``: sequence/context parallelism over a ``seq`` mesh axis
  via ``shard_map`` + ``lax.ppermute`` — each device holds a T/s slice of
  Q/K/V and K/V blocks rotate around the ring while partial attention
  accumulates with an online (flash-style) softmax. Memory per chip is
  O(T/s · d) instead of O(T · d) and the T×T score matrix never
  materializes globally. KV transfers ride ICI concurrently with the local
  block's compute (XLA's latency-hiding scheduler overlaps the ppermute).
- ``ring_attention(..., layout="zigzag")``: causal load-balanced variant.
  With the contiguous layout, causal masking makes ring shard i skip every
  K/V block originating from shard j > i — half the ring steps are fully
  masked yet still paid for (utilization (s+1)/2s). In the zigzag layout
  each device holds sequence chunks ``(i, 2s-1-i)`` of 2s chunks, so every
  device sees the same visible-key count and each post-local ring step
  needs only two quarter-block matmuls, all fully visible (no masks at
  all): half the attention FLOPs and no stragglers. Callers permute the
  sequence with ``zigzag_perm`` once at the input and invert once at the
  output (models/transformer.py does this around the whole block stack —
  two cheap all-to-alls per step, amortized over all layers).
- ``ulysses_attention``: the all-to-all SP alternative — one tiled
  all-to-all turns the sequence shard into a head shard, full-sequence
  attention runs locally, one all-to-all converts back (two collectives
  per call vs the ring's s ppermutes).
- ``flash_attention`` (ops/flash.py): fused Pallas TPU kernel for the
  single-device block-streaming case; also the per-block kernel inside
  ``ring_attention(block_impl="flash")`` via ``flash_attention_lse``.

Sliding-window banding (``window > 0``) threads through the XLA, flash
(banded grids), Ulysses, and contiguous-ring paths — the ring adds the
banded-skip schedule (stop after ~window/Tl hops; see ``ring_attention``).
All take/return ``[B, T, H, D]`` ("BTHD") and accumulate in float32
regardless of input dtype (bf16-safe).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def multihead_attention(q, k, v, causal: bool = True,
                        mask: Optional[jax.Array] = None,
                        window: int = 0):
    """Reference XLA attention. q,k,v: [B, T, H, D] -> [B, T, H, D].

    ``window > 0``: sliding-window (Mistral-style) banding — query t sees
    keys in ``(t - window, t]`` (combined with ``causal``).
    """
    dtype = q.dtype
    depth = q.shape[-1]
    q = q.astype(jnp.float32) * (depth ** -0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k.astype(jnp.float32))
    tq, tk = scores.shape[-2], scores.shape[-1]
    if causal:
        cm = jnp.tril(jnp.ones((tq, tk), bool))
        scores = jnp.where(cm[None, None], scores, NEG_INF)
    if window > 0:
        q_pos = jnp.arange(tq)[:, None]
        k_pos = jnp.arange(tk)[None, :]
        band = q_pos - k_pos < window
        scores = jnp.where(band[None, None], scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    # the flash kernel's name for the same value (ops/flash.py): a block's
    # checkpoint policy that keeps it spares the backward this attention
    return checkpoint_name(out.astype(dtype), "attn_out")


def grouped_query_attention(q, k, v, mask=None):
    """Decode-path GQA attention that never materializes the head
    expansion. q: [B, T, H, D]; k/v: [B, L, KVH, D] with H = KVH * g.
    ``mask`` follows the :func:`multihead_attention` convention
    (broadcastable to [B, 1, T, L]); the group axis is inserted here.

    Why this exists: ``jnp.repeat(k, groups, axis=2)`` before
    ``multihead_attention`` materializes a groups-x copy of the K/V
    cache on every decode step once the batch is large enough that XLA
    stops fusing the broadcast — measured on v5e at [B, W]=[32, 1024]:
    2.2x step time, and 6x at [64, 1024] (the round-4 "batch-32 cliff";
    scripts/debug_batch32_cliff.py). Grouping the query heads instead
    ([B,T,KVH,g,D] x [B,L,KVH,D] -> [B,KVH,g,T,L]) reads the cache once
    at its stored width. Scores/probs accumulate in f32 exactly like
    ``multihead_attention``; the bf16 K/V upcasts fuse into the dots
    (measured free).
    """
    dtype = q.dtype
    b, t, h, d = q.shape
    g = _gqa_groups(q, k, v)
    if mask is not None:       # normalize to [B|1, 1, T, L] like the
        if mask.ndim == 2:     # multihead_attention contract allows
            mask = mask[None, None]
        elif mask.ndim == 3:
            mask = mask[:, None]
    if g == 1:
        return multihead_attention(q, k, v, causal=False, mask=mask)
    kvh = h // g
    # q head i attends kv head i // g — the same pairing jnp.repeat
    # (..., groups, axis=2) induces, so this is a drop-in replacement
    qg = q.reshape(b, t, kvh, g, d).astype(jnp.float32) * (d ** -0.5)
    scores = jnp.einsum("btkgd,blkd->bkgtl", qg, k,
                        preferred_element_type=jnp.float32)
    if mask is not None:
        scores = jnp.where(mask[:, :, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgtl,blkd->btkgd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, t, h, d).astype(dtype)


def paged_gqa_attention(q, k_pool, v_pool, tables, row_starts, pad_lens,
                        impl: str = "auto", mesh=None, window: int = 0,
                        k_scale=None, v_scale=None):
    """Decode attention straight from the paged KV block pool
    (ops/flash.paged_attention): row ``b``'s keys/values are gathered
    through its block table instead of a contiguous per-row cache, so a
    warm prefix admit is a block-table pointer update, not an HBM
    scatter (ISSUE 7). q: ``[B, T, Hq, D]``; pools: ``[P, bt, KVH, D]``
    with ``Hq = KVH * g`` (the kernel pairs q head ``i`` with kv head
    ``i // g``, same as :func:`grouped_query_attention`).

    ``impl="auto"`` runs the Pallas kernel on TPU and the plain-JAX
    gather oracle elsewhere (the oracle materializes the page gather —
    fine for CPU tests, the exact HBM traffic the kernel avoids on
    TPU).

    ``mesh`` with a ``tensor`` axis > 1 (ISSUE 10, TP serving): the
    call runs under ``shard_map`` with PER-SHARD HEAD RANGES — each
    tensor shard's kernel instance sees only its local ``KVH/tp`` pool
    slice and the matching ``Hq/tp`` q heads (the q-to-kv pairing
    ``i // g`` is shard-local because both counts divide by the same
    tp), while block tables / row starts / pad lens stay replicated.
    Attention is embarrassingly parallel over heads, so the body needs
    no collectives; on TPU each shard's Pallas kernel DMA-walks only
    its own head slice of the pool.

    ``window``/``k_scale``/``v_scale`` (ISSUE 15): the sliding-window
    ring-table mapping and the int8-pool dequant scales, passed through
    to :func:`ops.flash.paged_attention`; scale leaves shard on their
    own head axis (axis 2 of 3) under TP, like the pages they rescale."""
    from .flash import paged_attention

    if mesh is not None and "tensor" in mesh.axis_names \
            and mesh.shape["tensor"] > 1:
        hs = P(None, None, "tensor", None)
        ss = P(None, None, "tensor")
        rep = P(None)
        if k_scale is not None:
            def local_q(q_, k_, v_, t_, rs_, pl_, ks_, vs_):
                return paged_attention(q_, k_, v_, t_, rs_, pl_,
                                       impl=impl, window=window,
                                       k_scale=ks_, v_scale=vs_)

            return shard_map(
                local_q, mesh=mesh,
                in_specs=(hs, hs, hs, P(None, None), rep, rep, ss, ss),
                out_specs=hs, check_vma=False,
            )(q, k_pool, v_pool, tables, row_starts, pad_lens,
              k_scale, v_scale)

        def local(q_, k_, v_, t_, rs_, pl_):
            return paged_attention(q_, k_, v_, t_, rs_, pl_, impl=impl,
                                   window=window)

        return shard_map(
            local, mesh=mesh,
            in_specs=(hs, hs, hs, P(None, None), rep, rep),
            out_specs=hs, check_vma=False,
        )(q, k_pool, v_pool, tables, row_starts, pad_lens)
    return paged_attention(q, k_pool, v_pool, tables, row_starts,
                           pad_lens, impl=impl, window=window,
                           k_scale=k_scale, v_scale=v_scale)


def _online_update(m, l, o, scores, vb):
    """Flash-style online-softmax accumulator update for one key block.

    m/l/o: running max [B,H,Tq], normalizer [B,H,Tq], output [B,H,Tq,D];
    scores: [B,H,Tq,Tk] for the new block; vb: [B,Tk,H,D] values.
    Shared by both ring bodies so numerics changes stay in one place.
    """
    blk_max = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m, blk_max)
    p = jnp.exp(scores - m_new[..., None])
    scale = jnp.exp(m - m_new)
    l_new = l * scale + jnp.sum(p, axis=-1)
    o_new = o * scale[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32)
    )
    return m_new, l_new, o_new


def zigzag_perm(t: int, s: int) -> np.ndarray:
    """Natural→zigzag sequence permutation for ``s`` ring shards.

    The sequence splits into ``2s`` chunks of ``t // (2s)``; ring shard i
    holds chunks ``(i, 2s-1-i)`` concatenated. Returns ``perm`` such that
    ``x[:, perm]`` is the zigzag layout; invert with ``np.argsort(perm)``.
    """
    if t % (2 * s) != 0:
        raise ValueError(f"t={t} not divisible by 2*s={2 * s}")
    c = t // (2 * s)
    parts = []
    for i in range(s):
        parts.append(np.arange(i * c, (i + 1) * c))
        j = 2 * s - 1 - i
        parts.append(np.arange(j * c, (j + 1) * c))
    return np.concatenate(parts)


def _merge_blocks(o, lse, o_b, lse_b):
    """Merge two attention partials over disjoint key blocks.

    o/o_b: [B, T, H, D] (o in float32); lse/lse_b: [B, H, T]. Exact:
    softmax over the union of key sets = lse-weighted combination of the
    per-block softmaxes. A fully-masked partial (lse_b == NEG_INF) merges
    as a no-op (weight exp(NEG_INF - lse) == 0).
    """
    lse_new = jnp.logaddexp(lse, lse_b)
    w = jnp.moveaxis(jnp.exp(lse - lse_new), 1, 2)[..., None]
    w_b = jnp.moveaxis(jnp.exp(lse_b - lse_new), 1, 2)[..., None]
    return o * w + o_b.astype(jnp.float32) * w_b, lse_new


def _expand_kv(x, groups: int):
    """GQA: broadcast compact [B, T, Hkv, D] K/V to the query head count
    for one block's compute. The ring bodies carry the COMPACT tensors
    around the ring (groups x less ICI traffic) and expand per hop."""
    return x if groups == 1 else jnp.repeat(x, groups, axis=2)


def _gqa_groups(q, k, v) -> int:
    """Validated q-to-kv head ratio (1 when heads match)."""
    if k.shape[2] == q.shape[2]:
        return 1
    if q.shape[2] % k.shape[2] or v.shape[2] != k.shape[2]:
        raise ValueError(
            f"GQA head counts must divide: q has {q.shape[2]}, "
            f"k/v have {k.shape[2]}/{v.shape[2]}"
        )
    return q.shape[2] // k.shape[2]


def _einsum_block_lse(q, kb, vb, visible):
    """(out, lse) of one attention block with an explicit [Tq, Tk] mask.

    The band-edge fallback for the windowed flash ring: Pallas banding
    assumes same-origin positions, so the O(1) ring blocks straddling the
    window edge run as a masked einsum instead (their [Tl x Tl] scores DO
    materialize — acceptable for the one or two such blocks). Fully-masked
    rows get lse = NEG_INF, making the subsequent merge a no-op there.
    """
    d = q.shape[-1]
    qf = q.astype(jnp.float32) * (d ** -0.5)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
    scores = jnp.where(visible[None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)
    p = jnp.exp(scores - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-30)[..., None]
    lse = jnp.where(m <= NEG_INF / 2, NEG_INF,
                    m + jnp.log(jnp.maximum(l, 1e-30)))
    return jnp.transpose(o, (0, 2, 1, 3)), lse  # [B,T,H,D], [B,H,T]


def _ring_attention_local_flash(q, k, v, *, axis_name: str, axis_size: int,
                                causal: bool, window: int = 0,
                                kv_groups: int = 1):
    """Contiguous-layout ring body with the Pallas flash kernel per block.

    Same ring schedule as ``_ring_attention_local``, but each [Tl x Tl]
    block runs through ``flash_attention_lse`` (scores stream through VMEM
    — nothing Tl x Tl ever materializes in HBM, so per-device sequence
    slices can be long) and partials chain via ``_merge_blocks``. Step 0 is
    the local (diagonal) block — the only one needing causal masking;
    every later block is fully visible or fully masked (gated by
    lse = NEG_INF, which also zeroes its gradient).

    ``window > 0`` (causal): three-tier banded-skip schedule —
    1. the diagonal block runs banded INSIDE the flash kernel;
    2. ring distances fully inside the band run maskless flash exactly as
       the unwindowed path;
    3. the O(1) distances straddling the band edge run as masked einsum
       blocks (``_einsum_block_lse``);
    4. distances beyond the band don't run — the ring stops early
       (``_ring_steps_needed``), so K/V hops, compute and the scan length
       are all O(window / Tl), not O(s).
    """
    from .flash import flash_attention_lse

    dtype = q.dtype
    s = axis_size
    tl = q.shape[1]
    my = lax.axis_index(axis_name)
    out0, lse0 = flash_attention_lse(
        q, _expand_kv(k, kv_groups), _expand_kv(v, kv_groups),
        causal=causal, window=window,
    )
    carry0 = (k, v, out0.astype(jnp.float32), lse0)
    perm = [(i, (i + 1) % s) for i in range(s)]

    def step(carry, t):
        kb, vb, o, lse = carry
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        out_b, lse_b = flash_attention_lse(
            q, _expand_kv(kb, kv_groups), _expand_kv(vb, kv_groups),
            causal=False,
        )
        if causal:
            src = (my - t) % s
            lse_b = jnp.where(src < my, lse_b, NEG_INF)
        o, lse = _merge_blocks(o, lse, out_b, lse_b)
        return (kb, vb, o, lse), None

    if window <= 0 or not causal:
        (_, _, o, _), _ = lax.scan(step, carry0, jnp.arange(1, s))
        return o.astype(dtype)

    # causal sliding window: distance-t keys span offsets
    # [t*tl - (tl-1), t*tl + (tl-1)] behind the query
    n = _ring_steps_needed(tl, s, window)
    full = [t for t in range(1, n) if t * tl + tl - 1 < window]
    edge = [t for t in range(1, n) if t * tl + tl - 1 >= window]
    assert full == list(range(1, len(full) + 1)) and len(edge) <= 2

    carry = carry0
    if full:
        carry, _ = lax.scan(step, carry, jnp.arange(1, len(full) + 1))
    kb, vb, o, lse = carry
    q_pos = my * tl + jnp.arange(tl)
    for t in edge:
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        src = (my - t) % s
        k_pos = src * tl + jnp.arange(tl)
        visible = (q_pos[:, None] >= k_pos[None, :]) & (
            q_pos[:, None] - k_pos[None, :] < window
        )  # wrapped sources (src > my) mask out entirely via positions
        out_b, lse_b = _einsum_block_lse(
            q, _expand_kv(kb, kv_groups), _expand_kv(vb, kv_groups),
            visible,
        )
        o, lse = _merge_blocks(o, lse, out_b, lse_b)
    return o.astype(dtype)


def _ring_attention_zigzag_local_flash(q, k, v, *, axis_name: str,
                                       axis_size: int, kv_groups: int = 1):
    """Zigzag ring body with the Pallas flash kernel per quarter block.

    The balanced schedule of ``_ring_attention_zigzag_local`` (same chunk
    visibility proof), with each quarter block as one flash call and
    lse-merges instead of the inline online-softmax accumulator. Step 0 is
    three quarter blocks (the two intra-chunk diagonals + the always-
    visible hi×lo); later steps are exactly two maskless quarter calls.
    """
    from .flash import flash_attention_lse

    dtype = q.dtype
    b, tl, h, d = q.shape
    c = tl // 2
    s = axis_size
    my = lax.axis_index(axis_name)
    q_lo, q_hi = q[:, :c], q[:, c:]
    kx, vx = _expand_kv(k, kv_groups), _expand_kv(v, kv_groups)

    o_ll, l_ll = flash_attention_lse(q_lo, kx[:, :c], vx[:, :c],
                                     causal=True)
    o_hl, l_hl = flash_attention_lse(q_hi, kx[:, :c], vx[:, :c],
                                     causal=False)
    o_hh, l_hh = flash_attention_lse(q_hi, kx[:, c:], vx[:, c:],
                                     causal=True)
    o_lo, l_lo = o_ll.astype(jnp.float32), l_ll
    o_hi, l_hi = _merge_blocks(o_hl.astype(jnp.float32), l_hl, o_hh, l_hh)

    perm = [(i, (i + 1) % s) for i in range(s)]

    def step(carry, t):
        kb, vb, o_lo, l_lo, o_hi, l_hi = carry
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        src = (my - t) % s
        pred = src < my
        kbx = _expand_kv(kb, kv_groups)
        vbx = _expand_kv(vb, kv_groups)
        k_lo, k_hi = kbx[:, :c], kbx[:, c:]
        v_lo, v_hi = vbx[:, :c], vbx[:, c:]
        sel_q = jnp.where(pred, q_lo, q_hi)
        sel_k = jnp.where(pred, k_lo, k_hi)
        sel_v = jnp.where(pred, v_lo, v_hi)
        e1_o, e1_l = flash_attention_lse(q_hi, k_lo, v_lo, causal=False)
        e2_o, e2_l = flash_attention_lse(sel_q, sel_k, sel_v, causal=False)
        o_hi, l_hi = _merge_blocks(o_hi, l_hi, e1_o, e1_l)
        # e2 routes to the lo rows when pred, else to the (post-e1) hi rows
        o_b = jnp.where(pred, o_lo, o_hi)
        l_b = jnp.where(pred, l_lo, l_hi)
        o_b, l_b = _merge_blocks(o_b, l_b, e2_o, e2_l)
        o_lo = jnp.where(pred, o_b, o_lo)
        l_lo = jnp.where(pred, l_b, l_lo)
        o_hi = jnp.where(pred, o_hi, o_b)
        l_hi = jnp.where(pred, l_hi, l_b)
        return (kb, vb, o_lo, l_lo, o_hi, l_hi), None

    carry0 = (k, v, o_lo, l_lo, o_hi, l_hi)
    (_, _, o_lo, _, o_hi, _), _ = lax.scan(step, carry0, jnp.arange(1, s))
    return jnp.concatenate([o_lo, o_hi], axis=1).astype(dtype)


def _ring_attention_zigzag_local(q, k, v, *, axis_name: str, axis_size: int,
                                 kv_groups: int = 1):
    """Causal zigzag ring attention body (runs inside shard_map).

    Local ``[B, Tl, H, D]`` slices are in zigzag layout: the first half is
    global chunk ``my`` ("lo"), the second half chunk ``2s-1-my`` ("hi"),
    of 2s chunks of ``c = Tl/2`` tokens. Key property (for ring step
    t >= 1, K/V arriving from shard ``src = (my - t) % s != my``):

    - ``q_hi × k_lo`` is ALWAYS fully visible (chunk 2s-1-my >= s > src);
    - exactly one of ``q_lo × k_lo`` (iff src < my) or ``q_hi × k_hi``
      (iff src > my) is fully visible; the other three pairings are fully
      masked.

    So every device does two fully-visible quarter-block matmuls per step —
    balanced, maskless — instead of one full (often fully-masked) block.
    Step 0 (the local block, the only one with intra-chunk diagonals) runs
    once with an explicit position mask before the scan.
    """
    dtype = q.dtype
    b, tl, h, d = q.shape
    c = tl // 2
    s = axis_size
    my = lax.axis_index(axis_name)
    qf = q.astype(jnp.float32) * (d ** -0.5)

    lo_pos = my * c + jnp.arange(c)                # chunk my
    hi_pos = (2 * s - 1 - my) * c + jnp.arange(c)  # chunk 2s-1-my
    q_pos = jnp.concatenate([lo_pos, hi_pos])

    # ---- step 0: local block, position-masked (the only diagonals) ------
    scores0 = jnp.einsum("bqhd,bkhd->bhqk", qf,
                         _expand_kv(k, kv_groups).astype(jnp.float32))
    visible0 = q_pos[:, None] >= q_pos[None, :]
    scores0 = jnp.where(visible0[None, None], scores0, NEG_INF)
    m0 = jnp.max(scores0, axis=-1)                 # [B, H, Tl]
    p0 = jnp.exp(scores0 - m0[..., None])
    l0 = jnp.sum(p0, axis=-1)
    o0 = jnp.einsum("bhqk,bkhd->bhqd", p0,
                    _expand_kv(v, kv_groups).astype(jnp.float32))

    q_lo, q_hi = qf[:, :c], qf[:, c:]
    # Unlike the contiguous body, every carry derives from device-varying
    # data (scores from q/k, positions from axis_index), so no pcast is
    # needed to stabilize the scan carry type.
    carry0 = (k, v, m0, l0, o0)

    perm = [(i, (i + 1) % s) for i in range(s)]

    def step(carry, t):
        kb, vb, m, l, o = carry
        # rotate FIRST: at scan iteration t (1-based below) the local block
        # holds K/V originating from shard (my - t) % s
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        src = (my - t) % s
        pred = src < my
        kbx = _expand_kv(kb, kv_groups)
        vbx = _expand_kv(vb, kv_groups)
        k_lo, k_hi = kbx[:, :c], kbx[:, c:]
        v_lo, v_hi = vbx[:, :c], vbx[:, c:]
        # E2: the step's second visible quarter — lo×lo below the ring
        # diagonal, hi×hi above it. Selects are on inputs (cheap); both
        # cases are FULLY visible so no mask is ever applied.
        sel_q = jnp.where(pred, q_lo, q_hi)
        sel_k = jnp.where(pred, k_lo, k_hi)
        sel_v = jnp.where(pred, v_lo, v_hi)
        e1 = jnp.einsum("bqhd,bkhd->bhqk", q_hi, k_lo.astype(jnp.float32))
        e2 = jnp.einsum("bqhd,bkhd->bhqk", sel_q, sel_k.astype(jnp.float32))
        m_lo, l_lo, o_lo = m[..., :c], l[..., :c], o[..., :c, :]
        m_hi, l_hi, o_hi = m[..., c:], l[..., c:], o[..., c:, :]
        # update 1: hi rows absorb e1 (always visible)
        m_hi, l_hi, o_hi = _online_update(m_hi, l_hi, o_hi, e1, v_lo)
        # update 2: e2 belongs to the lo rows when pred, else to the
        # (post-e1) hi rows — select the accumulator halves in, update,
        # and scatter back. Two quarter-block updates per step, nothing
        # inert: exactly half the contiguous body's per-step FLOPs.
        m_b = jnp.where(pred, m_lo, m_hi)
        l_b = jnp.where(pred, l_lo, l_hi)
        o_b = jnp.where(pred, o_lo, o_hi)
        m_b, l_b, o_b = _online_update(m_b, l_b, o_b, e2, sel_v)
        m_lo = jnp.where(pred, m_b, m_lo)
        l_lo = jnp.where(pred, l_b, l_lo)
        o_lo = jnp.where(pred, o_b, o_lo)
        m_hi = jnp.where(pred, m_hi, m_b)
        l_hi = jnp.where(pred, l_hi, l_b)
        o_hi = jnp.where(pred, o_hi, o_b)
        m = jnp.concatenate([m_lo, m_hi], axis=-1)
        l = jnp.concatenate([l_lo, l_hi], axis=-1)
        o = jnp.concatenate([o_lo, o_hi], axis=-2)
        return (kb, vb, m, l, o), None

    (kb, vb, m, l, o), _ = lax.scan(step, carry0, jnp.arange(1, s))
    out = o / jnp.maximum(l, 1e-30)[..., None]     # [B, H, Tl, D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(dtype)


def _sp_partition(mesh: Mesh, q, seq_axis: str, data_axes, head_axis):
    """Shared sequence-parallel partition plan: which mesh axes shard the
    batch (dp) and heads (hp) for this array, and the resulting spec.
    Probe shapes that don't divide an axis simply drop that axis (the
    caller's shard_map then replicates that dimension)."""
    dp = tuple(a for a in data_axes if a in mesh.axis_names)
    dp_total = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    if dp and q.shape[0] % dp_total != 0:
        dp = ()  # batch too small to shard (init probes); replicate it
    hp = head_axis if head_axis in mesh.axis_names else None
    if hp is not None and q.shape[2] % mesh.shape[hp] != 0:
        hp = None
    return dp, hp, P(dp if dp else None, seq_axis, hp, None)


def sharded_flash_attention(q, k, v, mesh: Optional[Mesh],
                            causal: bool = True, window: int = 0):
    """The Pallas flash kernel under a multi-device mesh.

    The TPU compiler refuses to partition a Mosaic kernel on its own
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map"), so with more than one device the call
    runs inside ``shard_map``: batch over the data axes, heads over
    ``tensor``. Attention is independent per (batch row, head), so the
    body needs no collective. One device (or no mesh) calls the kernel
    directly.
    """
    from .flash import flash_attention

    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal=causal, window=window)
    _, _, spec = _sp_partition(mesh, q, None, ("data", "fsdp"), "tensor")
    fn = functools.partial(flash_attention, causal=causal, window=window)
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def _ulysses_local(q, k, v, *, axis_name: str, axis_size: int,
                   causal: bool, inner: str, window: int = 0):
    """Per-shard Ulysses body (runs inside shard_map).

    q,k,v: local [B, T/s, H, D] sequence slices. One tiled all-to-all
    re-shards each to [B, T, H/s, D] (full sequence, 1/s of the heads),
    attention runs LOCALLY over the whole sequence — heads are
    embarrassingly parallel — and a second all-to-all restores the
    sequence layout. Two collectives total per attention call (vs the
    ring's s ppermutes), and the local compute is plain full-T attention,
    so the causal 2x comes from the flash kernel's diagonal predication
    rather than a schedule. Positions stay natural — no zigzag needed.
    """
    a2a = functools.partial(lax.all_to_all, axis_name=axis_name, tiled=True)
    q = a2a(q, split_axis=2, concat_axis=1)        # [B, T, H/s, D]
    # GQA: compact K/V cross the all-to-all at n_kv heads (groups x less
    # traffic) and broadcast locally after — shard j's q heads
    # [j*Hq/s, (j+1)*Hq/s) pair with kv heads [j*Hkv/s, ...): the repeat
    # mapping i -> i // groups preserves contiguous-block alignment.
    k = a2a(k, split_axis=2, concat_axis=1)
    v = a2a(v, split_axis=2, concat_axis=1)
    k, v = (_expand_kv(k, q.shape[2] // k.shape[2]),
            _expand_kv(v, q.shape[2] // v.shape[2]))
    if inner == "flash":
        from .flash import flash_attention

        out = flash_attention(q, k, v, causal=causal, window=window)
    else:
        out = multihead_attention(q, k, v, causal=causal, window=window)
    return a2a(out, split_axis=1, concat_axis=2)   # [B, T/s, H, D]


def ulysses_attention(q, k, v, mesh: Mesh, causal: bool = True,
                      seq_axis: str = "seq", data_axes=("data", "fsdp"),
                      head_axis: str = "tensor", inner: str = "xla",
                      window: int = 0):
    """All-to-all (DeepSpeed-Ulysses-style) sequence parallelism.

    The alternative SP strategy to ``ring_attention``: instead of rotating
    K/V blocks s times around the ring, ONE all-to-all converts the
    sequence sharding into a head sharding (heads are independent in
    attention), full-sequence attention runs locally, and one all-to-all
    converts back. Cheaper in collective count for moderate T; the ring
    wins when T is so long that even [B, T, H/s, D] per device is too big.
    Local head count (after any ``tensor`` sharding) must divide by the
    seq-axis size; otherwise — and for probe shapes — falls back dense.

    GQA: ``k``/``v`` may carry FEWER heads than ``q`` — the compact K/V
    cross the all-to-alls (``groups``× less traffic) and broadcast
    locally after, provided the KV head count also splits over the
    involved axes; otherwise they pre-expand.

    ``inner`` selects the local kernel: "xla" einsum or "flash" (Pallas).
    """
    kv_groups = _gqa_groups(q, k, v)

    def dense():
        return multihead_attention(q, _expand_kv(k, kv_groups),
                                   _expand_kv(v, kv_groups),
                                   causal=causal, window=window)

    if seq_axis not in mesh.axis_names or mesh.shape[seq_axis] == 1:
        return dense()
    s = mesh.shape[seq_axis]
    if q.shape[1] % s != 0:
        return dense()

    dp, hp, spec = _sp_partition(mesh, q, seq_axis, data_axes, head_axis)
    local_heads = q.shape[2] // (mesh.shape[hp] if hp else 1)
    if local_heads % s != 0:
        # not enough heads per device to split across the seq axis
        return dense()
    if kv_groups > 1:
        # the compact KV heads must split over the SAME axes as q's
        # (tensor sharding, then the a2a's seq split); else pre-expand
        hp_size = mesh.shape[hp] if hp else 1
        if k.shape[2] % hp_size or (k.shape[2] // hp_size) % s:
            k = _expand_kv(k, kv_groups)
            v = _expand_kv(v, kv_groups)
            kv_groups = 1

    fn = functools.partial(
        _ulysses_local, axis_name=seq_axis, axis_size=s, causal=causal,
        inner=inner, window=window,
    )
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=inner != "flash",
    )(q, k, v)


def _ring_steps_needed(tl: int, axis_size: int, window: int) -> int:
    """Ring steps with any in-band key for sliding window ``window``.

    Block at ring distance ``t`` holds keys ``t*tl`` to ``t*tl - (tl-1)``
    positions behind the nearest query, so it is fully out of the band
    once ``t*tl - (tl-1) >= window``. Static — the scan just gets shorter
    (the banded-skip optimization: a narrow window stops the ring after
    ``~window/tl`` hops instead of circling all ``s`` shards).
    """
    if window <= 0:
        return axis_size
    return min(axis_size, (window + tl - 2) // tl + 1)


def _ring_attention_local(q, k, v, *, axis_name: str, axis_size: int,
                          causal: bool, vary_axes: tuple = (),
                          window: int = 0, kv_groups: int = 1):
    """Per-shard ring attention body (runs inside shard_map).

    q,k,v: local [B, Tl, H, D] slices of the global [B, T, H, D] arrays,
    sharded along T over ``axis_name``. Rotates K/V blocks around the ring
    with an online-softmax accumulator: after ``axis_size`` steps every query
    has attended to every (visible) key. ``window > 0`` adds the
    sliding-window band to the position mask and shortens the scan to the
    in-band ring distance (``_ring_steps_needed``).
    """
    dtype = q.dtype
    b, tl, h, d = q.shape
    my = lax.axis_index(axis_name)
    qf = q.astype(jnp.float32) * (d ** -0.5)
    q_pos = my * tl + jnp.arange(tl)  # global query positions

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(carry, t):
        kb, vb, m, l, o = carry
        src = (my - t) % axis_size  # origin shard of the current K/V block
        k_pos = src * tl + jnp.arange(tl)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf,
                            _expand_kv(kb, kv_groups).astype(jnp.float32))
        visible = None
        if causal:
            visible = q_pos[:, None] >= k_pos[None, :]  # [Tl_q, Tl_k]
        if window > 0:
            band = q_pos[:, None] - k_pos[None, :] < window
            visible = band if visible is None else visible & band
        if visible is not None:
            scores = jnp.where(visible[None, None], scores, NEG_INF)
        m_new, l_new, o_new = _online_update(
            m, l, o, scores, _expand_kv(vb, kv_groups)
        )
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (kb, vb, m_new, l_new, o_new), None

    m0 = jnp.full((b, h, tl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, tl), jnp.float32)
    o0 = jnp.zeros((b, h, tl, d), jnp.float32)
    # The accumulators depend on device-varying data from step 1 on; mark
    # them varying over the sharded mesh axes up front so the scan carry
    # type is stable (JAX's varying-manual-axes check under shard_map).
    if vary_axes:
        vary = lambda x: lax.pcast(x, vary_axes, to="varying")
        m0, l0, o0 = vary(m0), vary(l0), vary(o0)
    # banded-skip is only sound under causal masking: without it the band
    # q_pos - k_pos < window keeps every FUTURE block visible
    n_steps = (_ring_steps_needed(tl, axis_size, window) if causal
               else axis_size)
    (kb, vb, m, l, o), _ = lax.scan(
        step, (k, v, m0, l0, o0), jnp.arange(n_steps)
    )
    out = o / jnp.maximum(l, 1e-30)[..., None]        # [B, H, Tq, D]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(dtype)


def ring_attention(q, k, v, mesh: Mesh, causal: bool = True,
                   seq_axis: str = "seq", data_axes=("data", "fsdp"),
                   head_axis: str = "tensor", layout: str = "contig",
                   block_impl: str = "einsum", window: int = 0):
    """Sequence-parallel attention over the mesh's ``seq`` axis.

    q,k,v are global ``[B, T, H, D]`` arrays (T sharded over ``seq``); the
    TxT score matrix never exists — only [Tl x Tl] blocks per device per
    ring step. Composes with DP (batch over data axes) and TP (heads over
    ``tensor``) in one shard_map.

    ``layout="zigzag"`` (causal only, T divisible by 2s): inputs must be in
    ``zigzag_perm(T, s)`` order; the balanced maskless body cuts attention
    FLOPs 2× (module docstring). Output stays in zigzag order.

    ``block_impl="flash"`` runs each ring block through the Pallas flash
    kernel (``ops/flash.flash_attention_lse``) and merges partials by
    logsumexp — per-device score tiles stream through VMEM instead of
    materializing [Tl x Tl], so long per-device slices stay HBM-light.
    ``"einsum"`` (default) is the plain-XLA body, best for short slices.

    ``window > 0`` (with ``causal``): sliding-window banding with the
    banded-skip schedule — the ring stops after ``~window/Tl`` hops
    because farther blocks are fully out of band (``_ring_steps_needed``),
    so a narrow window makes ring cost O(T·window / s) per device.
    Contiguous layout only: zigzag exists to balance the full causal
    triangle, which a band already balances (and a banded zigzag would
    put BOTH of each device's chunks on the band edge — strictly more
    masked work than contiguous).

    GQA: ``k``/``v`` may carry FEWER heads than ``q`` (``Hq % Hkv == 0``)
    — the compact K/V rotates around the ring (``groups``× less ICI
    traffic than pre-repeating) and each hop broadcasts locally for its
    block compute. When a ``tensor`` head sharding doesn't divide the KV
    head count, K/V are pre-expanded instead (a sharded-q/replicated-kv
    split would mis-pair heads).
    """
    kv_groups = _gqa_groups(q, k, v)

    def dense():
        return multihead_attention(q, _expand_kv(k, kv_groups),
                                   _expand_kv(v, kv_groups),
                                   causal=causal, window=window)

    if seq_axis not in mesh.axis_names or mesh.shape[seq_axis] == 1:
        return dense()
    axis_size = mesh.shape[seq_axis]
    zigzag = layout == "zigzag"
    if zigzag and (not causal or q.shape[1] % (2 * axis_size) != 0):
        raise ValueError(
            "layout='zigzag' needs causal=True and T divisible by "
            f"2*seq ({2 * axis_size}); got causal={causal}, T={q.shape[1]}"
        )
    if zigzag and window > 0:
        raise ValueError(
            "layout='zigzag' does not compose with window (sliding-window "
            "attention): the band already load-balances the causal "
            "triangle, so use layout='contig', which also enables the "
            "banded-skip early ring exit"
        )
    if q.shape[1] % axis_size != 0:
        # Sequence not evenly shardable (e.g. a probe batch at init time):
        # the dense path is always correct, just not sequence-parallel.
        return dense()

    dp, hp, spec = _sp_partition(mesh, q, seq_axis, data_axes, head_axis)

    if kv_groups > 1 and hp is not None and (
        k.shape[2] % mesh.shape[hp] != 0
    ):
        # head-sharded q with a KV head count the tensor axis doesn't
        # divide would mis-pair local q heads with kv heads: pre-expand
        k, v = _expand_kv(k, kv_groups), _expand_kv(v, kv_groups)
        kv_groups = 1
    # The KV spec equals q's (same dp/seq/head axes — only the head
    # COUNT differs); each shard's local q:kv ratio stays kv_groups
    # because both shard heads over the same axis.
    spec_kv = spec

    if block_impl not in ("einsum", "flash"):
        raise ValueError(
            f"block_impl={block_impl!r}; expected 'einsum' or 'flash'"
        )
    flash_blocks = block_impl == "flash"
    if flash_blocks and window > 0 and not causal:
        # the flash body's banded-skip schedule is causal-only (a
        # non-causal band keeps every future block visible); the einsum
        # body applies the band independently of causal, so use it
        flash_blocks = False
    if zigzag:
        fn = functools.partial(
            _ring_attention_zigzag_local_flash if flash_blocks
            else _ring_attention_zigzag_local,
            axis_name=seq_axis, axis_size=axis_size, kv_groups=kv_groups,
        )
    elif flash_blocks:
        fn = functools.partial(
            _ring_attention_local_flash, axis_name=seq_axis,
            axis_size=axis_size, causal=causal, window=window,
            kv_groups=kv_groups,
        )
    else:
        vary_axes = tuple(dp) + (seq_axis,) + ((hp,) if hp else ())
        fn = functools.partial(
            _ring_attention_local, axis_name=seq_axis, axis_size=axis_size,
            causal=causal, vary_axes=vary_axes, window=window,
            kv_groups=kv_groups,
        )
    # Pallas calls don't annotate varying-mesh-axes metadata on their
    # outputs, so the flash bodies run with the vma check off (the einsum
    # bodies keep it, with explicit pcasts where carries start replicated).
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec_kv, spec_kv), out_specs=spec,
        check_vma=not flash_blocks,
    )(q, k, v)
