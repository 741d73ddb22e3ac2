"""Fused flash attention: Pallas TPU forward kernel + blockwise backward.

The reference delegates all kernels to cuDNN (SURVEY.md §2.2); here the one
op XLA doesn't fuse perfectly at long sequence length — attention — gets an
in-tree Pallas kernel (see /opt/skills/guides/pallas_guide.md):

- **forward**: grid (batch*head, q-block, kv-block) with the KV dimension
  innermost — K/V blocks STREAM through VMEM (Pallas double-buffers the
  HBM→VMEM copies against compute), and the online-softmax state (m, l,
  accumulator) lives in VMEM scratch carried across the KV grid steps. Only
  a [BQ, BK] score tile ever exists, and VMEM use is independent of T, so
  sequence length is bounded by HBM, not VMEM. Causal programs predicate
  away tiles beyond the diagonal (~2× fewer FLOPs). Outputs carry the
  logsumexp rows (trailing unit lane axis: Mosaic tiling-legal).
- **backward**: the standard two-kernel flash backward, also Pallas and
  also fully streamed. A dk/dv kernel (grid over KV blocks × q blocks, q
  innermost, dk/dv accumulated in scratch) and a dq kernel (grid over q
  blocks × KV blocks, KV innermost), both recomputing the probability tile
  from the saved logsumexp in f32 so only [BQ, BK] tiles ever exist.
  ``_bwd_3d`` (plain-JAX blockwise) is kept as the oracle the Pallas
  kernels are tested against.

Accumulation is float32 throughout regardless of input dtype.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Measured on TPU v5e (d=64): 512x512 beats 128x128 by 2.4x at t=2048 —
# streaming K/V makes VMEM independent of T, so blocks this large are safe
# and amortize the per-grid-step overhead. Sequences shorter than a block
# fall back to one block. End-to-end vs XLA attention (in-jit chained
# scan, the honest timing on this platform — see bench.py): ~2x on full
# fwd+bwd (grads wrt q,k,v) at t=8192 (b=1, h=12), 1.6x on the full
# GPT-2-small train step at t=1024; XLA attention additionally OOMs
# where flash streams
# (b=4, t=8192 materializes a ~12.9 GB float32 score tensor — scores
# upcast to f32 for the softmax — plus a same-size probs tensor).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _tile_mask(i, j, block_q, block_k, causal, t_valid, t, window=0):
    """NEG_INF mask for score tile (q block i, kv block j); None if no-op.

    ``window > 0`` adds the sliding-window band ``q_pos - k_pos < window``
    (Mistral-style, combined with ``causal``)."""
    need = causal or t_valid < t or window > 0
    if not need:
        return None
    q_pos = i * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = j * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    ok = jnp.full((block_q, block_k), True)
    if causal:
        ok = q_pos >= k_pos
    if window > 0:
        ok = ok & (q_pos - k_pos < window)
    if t_valid < t:  # keys past t_valid are padding
        ok = ok & (k_pos < t_valid)
    return ok


def _band_start(i, block_q, block_k, window):
    """First KV tile that can intersect q block ``i``'s sliding band.
    Floor division of a possibly-negative numerator rounds toward -inf,
    which the max-with-0 absorbs."""
    return jnp.maximum(0, (i * block_q - (window - 1)) // block_k)


def _num_band_tiles(span_block, tile_block, window):
    """Tiles of size ``tile_block`` intersecting a band that spans
    ``span_block + window - 1`` positions, +1 slack for tile misalignment
    (static). Used for the KV band per q block (span=block_q,
    tile=block_k) and, with the roles swapped, the q band per KV block in
    the dkv backward."""
    return (span_block + window - 1 + tile_block - 1) // tile_block + 1


def _q_band_start(j, block_q, block_k):
    """First q block whose rows can (causally) see KV tile ``j`` — the
    diagonal block. Shared by the dkv kernel and its index map so data
    placement and predication cannot desync."""
    return (j * block_k) // block_q


def _banded_index(start_fn, num_blocks):
    """Index map for a banded grid axis: block = clip(start(outer) + off).
    The kernel predicates with the UNclipped index; the clip only keeps
    the prefetch legal at the edges."""

    def index(b, outer, off):
        return b, jnp.clip(start_fn(outer) + off, 0, num_blocks - 1), 0

    return index


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, t_valid: int, t: int,
                num_kv: int, window: int = 0, banded: bool = False,
                nb: int = 0):
    # grid (BH, num_q, num_kv) — or (BH, num_q, nb) when ``banded`` (causal
    # sliding window: only the ~window-wide KV tile band per q block is in
    # the grid at all, so both the compute AND the HBM->VMEM K/V streaming
    # are O(T * window)). kv innermost. q_ref/o_ref: [1, BQ, D];
    # k_ref/v_ref: [1, BK, D] (streamed); lse_ref: [1, BQ, 1] (the trailing
    # unit lane axis keeps the block shape legal under Mosaic's
    # (8, 128)-or-equal tiling rule). Scratch m/l: [BQ, 1] f32, acc:
    # [BQ, D] f32 — the online-softmax state carried across the kv dim.
    i = pl.program_id(1)
    jb = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    if banded:
        j = _band_start(i, block_q, block_k, window) + jb
        last = nb - 1
    else:
        j = jb
        last = num_kv - 1

    @pl.when(jb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale       # [BQ, D]
        k_blk = k_ref[0].astype(jnp.float32)           # [BK, D]
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                              # [BQ, BK]
        ok = _tile_mask(i, j, block_q, block_k, causal, t_valid, t,
                        window)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    pred = None
    if causal:
        # tiles strictly beyond the diagonal are predicated away entirely
        pred = j * block_k < (i + 1) * block_q
    if window > 0:
        # tiles entirely below the band contribute nothing
        in_band = (j + 1) * block_k > i * block_q - window + 1
        pred = in_band if pred is None else (pred & in_band)
    if banded:
        pred = pred & (j <= num_kv - 1)  # nb overshoot near the edges
    if pred is not None:
        pl.when(pred)(_compute)
    else:
        _compute()

    @pl.when(jb == last)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l_safe)


def _flash_fwd_3d(q, k, v, *, causal: bool, block_q: int, block_k: int,
                  t_valid: int, interpret: bool, window: int = 0):
    """q,k,v: [BH, T, D] (T block-padded) -> (out, lse [BH, T])."""
    bh, t, d = q.shape
    scale = d ** -0.5
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    assert t % block_q == 0 and t % block_k == 0, (t, block_q, block_k)
    num_kv = t // block_k
    banded = causal and 0 < window < t
    nb = min(_num_band_tiles(block_q, block_k, window), num_kv)
    if banded and nb >= num_kv:
        banded = False  # band covers everything: plain grid is simpler
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, t_valid=t_valid, t=t,
        num_kv=num_kv, window=window, banded=banded, nb=nb,
    )
    if banded:
        kv_grid = nb
        kv_index = _banded_index(
            lambda i: _band_start(i, block_q, block_k, window), num_kv
        )
    else:
        kv_grid, kv_index = num_kv, (lambda b, i, j: (b, j, 0))
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, t // block_q, kv_grid),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse[..., 0]


def _bwd_3d(causal, block_k, t_valid, residuals, g, window: int = 0):
    """Blockwise flash backward over KV blocks (plain JAX, O(T*BK) memory)."""
    q, k, v, out, lse = residuals
    bh, t, d = q.shape
    scale = d ** -0.5
    block_k = min(block_k, t)
    num_kv = t // block_k

    qf = q.astype(jnp.float32)
    g = g.astype(jnp.float32)
    out = out.astype(jnp.float32)
    delta = jnp.sum(g * out, axis=-1)                 # [BH, T]
    q_pos = jnp.arange(t)

    def per_block(j):
        sl = lambda x: lax.dynamic_slice_in_dim(x, j * block_k, block_k, 1)
        k_blk = sl(k).astype(jnp.float32)             # [BH, BK, D]
        v_blk = sl(v).astype(jnp.float32)
        s = jnp.einsum("bqd,bkd->bqk", qf, k_blk) * scale
        k_pos = j * block_k + jnp.arange(block_k)
        if causal:
            s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None], s, NEG_INF)
        if window > 0:
            band = q_pos[:, None] - k_pos[None, :] < window
            s = jnp.where(band[None], s, NEG_INF)
        if t_valid < t:
            s = jnp.where((k_pos < t_valid)[None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])               # [BH, T, BK]
        dv = jnp.einsum("bqk,bqd->bkd", p, g)
        dp = jnp.einsum("bqd,bkd->bqk", g, v_blk)
        ds = p * (dp - delta[..., None]) * scale
        dq_j = jnp.einsum("bqk,bkd->bqd", ds, k_blk)
        dk = jnp.einsum("bqk,bqd->bkd", ds, qf)
        return dq_j, dk, dv

    def body(dq, j):
        dq_j, dk_j, dv_j = per_block(j)
        return dq + dq_j, (dk_j, dv_j)

    dq, (dk_blocks, dv_blocks) = lax.scan(
        body, jnp.zeros_like(qf), jnp.arange(num_kv)
    )
    dk = jnp.moveaxis(dk_blocks, 0, 1).reshape(bh, t, d)
    dv = jnp.moveaxis(dv_blocks, 0, 1).reshape(bh, t, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _bwd_dkv_kernel(q_ref, g_ref, lse_ref, delta_ref, k_ref, v_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    causal: bool, t_valid: int, t: int, num_q: int,
                    window: int = 0, banded: bool = False, nqb: int = 0):
    # grid (BH, num_kv, num_q) — or (BH, num_kv, nqb) when ``banded``
    # (sliding window: only q blocks within ``window`` above this KV block
    # are visited). q innermost (streamed). k/v/dk/dv refs:
    # [1, BK, D] (this program's KV block); q_ref/g_ref: [1, BQ, D];
    # lse_ref/delta_ref: [1, BQ, 1]. Scratch dk/dv: [BK, D] f32.
    j = pl.program_id(1)
    ib = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    if banded:
        i = _q_band_start(j, block_q, block_k) + ib
        last = nqb - 1
    else:
        i = ib
        last = num_q - 1

    @pl.when(ib == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _compute():
        q_blk = q_ref[0].astype(jnp.float32)           # [BQ, D]
        g_blk = g_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                               # [BQ, 1]
        delta = delta_ref[0]
        k_blk = k_ref[0].astype(jnp.float32)           # [BK, D]
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # [BQ, BK]
        ok = _tile_mask(i, j, block_q, block_k, causal, t_valid, t,
                        window)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse)                           # [BQ, BK]
        dv_scr[...] += jax.lax.dot_general(
            p, g_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            g_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    pred = None
    if causal:
        # q blocks strictly above this KV block's first row see none of it
        pred = (i + 1) * block_q > j * block_k
    if window > 0:
        in_band = (j + 1) * block_k > i * block_q - window + 1
        pred = in_band if pred is None else (pred & in_band)
    if banded:
        pred = pred & (i <= num_q - 1)
    if pred is not None:
        pl.when(pred)(_compute)
    else:
        _compute()

    @pl.when(ib == last)
    def _finalize():
        dk = dk_scr[...]
        dv = dv_scr[...]
        if t_valid < t:  # padded keys: their grads must be exactly 0
            kv_valid = (
                j * block_k
                + lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
                < t_valid
            )
            dk = jnp.where(kv_valid, dk, 0.0)
            dv = jnp.where(kv_valid, dv, 0.0)
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, g_ref, lse_ref, delta_ref, k_ref, v_ref, dq_ref,
                   dq_scr, *, scale: float, causal: bool, t_valid: int,
                   t: int, num_kv: int, window: int = 0,
                   banded: bool = False, nb: int = 0):
    # grid (BH, num_q, num_kv) — or (BH, num_q, nb) when ``banded``
    # (sliding window: only the band's KV tiles are visited). kv innermost
    # (streamed). q/g/dq refs: [1, BQ, D]; k_ref/v_ref: [1, BK, D];
    # lse_ref/delta_ref: [1, BQ, 1]. Scratch dq: [BQ, D] f32.
    i = pl.program_id(1)
    jb = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    if banded:
        j = _band_start(i, block_q, block_k, window) + jb
        last = nb - 1
    else:
        j = jb
        last = num_kv - 1

    @pl.when(jb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _compute():
        q_blk = q_ref[0].astype(jnp.float32)
        g_blk = g_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q_blk, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        ok = _tile_mask(i, j, block_q, block_k, causal, t_valid, t,
                        window)
        if ok is not None:
            s = jnp.where(ok, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            g_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dq_scr[...] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    pred = None
    if causal:
        pred = j * block_k < (i + 1) * block_q
    if window > 0:
        in_band = (j + 1) * block_k > i * block_q - window + 1
        pred = in_band if pred is None else (pred & in_band)
    if banded:
        pred = pred & (j <= num_kv - 1)
    if pred is not None:
        pl.when(pred)(_compute)
    else:
        _compute()

    @pl.when(jb == last)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_pallas_3d(causal, block_q, block_k, t_valid, interpret,
                   residuals, g, g_lse=None, window: int = 0):
    """Pallas two-kernel flash backward. Same signature/result as _bwd_3d.

    ``g_lse`` ([BH, T] or None): cotangent of the logsumexp output when the
    caller consumed it (flash_attention_lse — e.g. the ring-merge weights).
    d(lse)/ds is the normalized probability tile p, so its contribution is
    ``ds += p * g_lse`` — which folds into the existing ``ds = p*(dp-delta)``
    as ``delta' = delta - g_lse``. The kernels are unchanged.
    """
    q, k, v, out, lse = residuals
    bh, t, d = q.shape
    scale = d ** -0.5
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    num_q = t // block_q
    num_kv = t // block_k
    # delta_i = g_i . out_i (rowwise) — cheap, XLA-fused outside the kernels.
    # Both row-stat tensors carry a trailing unit lane axis (see _fwd_kernel).
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)[..., None]
    lse = lse.astype(jnp.float32)[..., None]

    banded = causal and 0 < window < t
    nqb = min(_num_band_tiles(block_k, block_q, window), num_q)
    nb = min(_num_band_tiles(block_q, block_k, window), num_kv)
    if banded and (nqb >= num_q and nb >= num_kv):
        banded = False

    if banded:
        q_grid = nqb
        q_index = _banded_index(
            lambda j: _q_band_start(j, block_q, block_k), num_q
        )
    else:
        q_grid, q_index = num_q, (lambda b, j, i: (b, i, 0))

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal, t_valid=t_valid,
            t=t, num_q=num_q, window=window, banded=banded, nqb=nqb,
        ),
        grid=(bh, num_kv, q_grid),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_index),                    # q
            pl.BlockSpec((1, block_q, d), q_index),                    # g
            pl.BlockSpec((1, block_q, 1), q_index),                    # lse
            pl.BlockSpec((1, block_q, 1), q_index),                    # delta
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),  # k
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),  # v
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), k.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(q, g, lse, delta, k, v)

    if banded:
        kv_grid = nb
        kv_index = _banded_index(
            lambda i: _band_start(i, block_q, block_k, window), num_kv
        )
    else:
        kv_grid, kv_index = num_kv, (lambda b, i, j: (b, j, 0))

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal, t_valid=t_valid,
            t=t, num_kv=num_kv, window=window, banded=banded, nb=nb,
        ),
        grid=(bh, num_q, kv_grid),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),  # q
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),  # g
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),  # lse
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),  # delta
            pl.BlockSpec((1, block_k, d), kv_index),                   # k
            pl.BlockSpec((1, block_k, d), kv_index),                   # v
        ],
        out_specs=[pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, t, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(q, g, lse, delta, k, v)[0]
    return dq, dk, dv


# the minor dimension of a TPU tile: in the kernels' ``[heads, tokens,
# head size]`` layout a shorter head size is padded to it in HBM
LANES = 128


def named_residual_bytes(b: int, t: int, h: int, d: int, dtype) -> dict:
    """Bytes of what ``_named_forward`` names, for ``flash_attention`` on
    ``[b, t, h, d]`` operands of ``dtype``: the output and the three
    operands as the kernel lays them out (tokens padded to the blocks, the
    head size to the lanes) and the float32 log-sum-exp rows."""
    t_pad = _padded_len(t, *pick_block_sizes(t, d))
    d_pad = -(-d // LANES) * LANES
    tensor = b * h * t_pad * d_pad * jnp.dtype(dtype).itemsize
    return {"attn_out": tensor, "attn_lse": b * h * t_pad * 4,
            "attn_qkv": 3 * tensor}


def _named_forward(q, k, v, **kw):
    """The forward kernel for the custom-vjp forward rules, its operands
    and results under the names a block's checkpoint policy may keep
    (models/remat_policy.py): ``attn_out`` and ``attn_lse``, which only
    the kernel can make, so that keeping both spares the backward a
    second forward call; and ``attn_qkv``, the operands as the kernel
    takes them (rotated, heads repeated, folded), which the forward holds
    anyway. Returns ``(out, lse)`` and the residuals."""
    q, k, v = (checkpoint_name(x, "attn_qkv") for x in (q, k, v))
    out, lse = _flash_fwd_3d(q, k, v, **kw)
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return (out, lse), (q, k, v, out, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_3d(q, k, v, causal, block_q, block_k, t_valid, interpret,
              window=0):
    out, _ = _flash_fwd_3d(q, k, v, causal=causal, block_q=block_q,
                           block_k=block_k, t_valid=t_valid,
                           interpret=interpret, window=window)
    return out


def _flash_3d_fwd(q, k, v, causal, block_q, block_k, t_valid, interpret,
                  window=0):
    (out, _), residuals = _named_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        t_valid=t_valid, interpret=interpret, window=window)
    return out, residuals


def _flash_3d_bwd(causal, block_q, block_k, t_valid, interpret, window,
                  residuals, g):
    return _bwd_pallas_3d(causal, block_q, block_k, t_valid, interpret,
                          residuals, g, window=window)


_flash_3d.defvjp(_flash_3d_fwd, _flash_3d_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_3d_lse(q, k, v, causal, block_q, block_k, t_valid, interpret,
                  window=0):
    """Like ``_flash_3d`` but also returns the logsumexp rows [BH, T] —
    the composition primitive: softmaxes over disjoint key blocks merge
    exactly from (out, lse) pairs (ops/attention.py ring 'flash' bodies)."""
    return _flash_fwd_3d(q, k, v, causal=causal, block_q=block_q,
                         block_k=block_k, t_valid=t_valid,
                         interpret=interpret, window=window)


def _flash_3d_lse_fwd(q, k, v, causal, block_q, block_k, t_valid, interpret,
                      window=0):
    return _named_forward(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        t_valid=t_valid, interpret=interpret, window=window)


def _flash_3d_lse_bwd(causal, block_q, block_k, t_valid, interpret, window,
                      residuals, cotangents):
    g, g_lse = cotangents
    return _bwd_pallas_3d(causal, block_q, block_k, t_valid, interpret,
                          residuals, g, g_lse=g_lse, window=window)


_flash_3d_lse.defvjp(_flash_3d_lse_fwd, _flash_3d_lse_bwd)


def _on_tpu() -> bool:
    # a backend that fails to initialize raises here: answering False
    # would quietly turn a broken chip into interpret mode
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# Paged attention: decode directly from the KV block pool (ISSUE 7 tentpole)
# ---------------------------------------------------------------------------
#
# The serving-path KV cache lives in a bounded block pool
# (engine/kvcache.py): one ``[pool_blocks, block_tokens, H, D]`` leaf per
# cache leaf, with each request's logical token positions mapped to pool
# blocks through a per-row BLOCK TABLE (vLLM/PagedAttention, Kwon et al.
# SOSP 2023 — the TPU shape of it). This kernel consumes that layout
# IN PLACE: grid (batch, q-head, kv-block) with the kv dimension
# innermost, and the KV tile for (row b, block j) fetched straight from
# the pool page ``tables[b, j]`` via Pallas scalar prefetch — the block
# table drives the HBM->VMEM DMA index map, so a warm prefix admit is a
# pointer update instead of the HBM scatter copy the round-5 path paid
# per admit. Online-softmax state streams across the kv grid exactly
# like ``_fwd_kernel``.
#
# Positions are ROW-LOCAL (canonical): row ``b``'s token at logical
# position p lives at ``pool[tables[b, p // bt], p % bt]`` and its RoPE
# angle is p itself — block content is therefore position- and
# era-independent, which is what lets the radix index share pages
# between requests with zero copies (engine/kvcache.py).

PAGED_MIN_Q = 8      # q lanes padded up to this (Mosaic sublane tile)


def _paged_kernel(tables_ref, starts_ref, pads_ref, *refs, scale: float,
                  bt: int, nb: int, window: int = 0,
                  quant: bool = False):
    # grid (B, Hq, NB), kv innermost. q_ref/o_ref: [1, T, 1, D];
    # k_ref/v_ref: [1, bt, 1, D] — the pool page ``tables[b, j]`` for
    # this row's j-th logical block (scalar-prefetched index map; -1
    # lanes clip to the scratch page and are predicated away here).
    # Scratch m/l: [T, 1] f32, acc: [T, D] f32.
    #
    # ``quant`` (int8-KV pool layout, ISSUE 15): k/v pages are int8 and
    # two extra scale refs ``[1, bt, 1]`` f32 ride along — the DEQUANT
    # EPILOGUE multiplies each fetched tile by its per-(token, head)
    # scale right after the HBM->VMEM DMA, so only half the KV bytes
    # ever cross HBM (decode's binding constraint, BASELINE.md).
    #
    # ``window > 0`` (sliding-window ring layout, ISSUE 15): the block
    # table is a RING — table slot ``s`` holds the newest logical block
    # ``j ≡ s (mod nb)`` the row has written. k positions are derived
    # from the query's own block (``j_log = jq - (jq - s) mod nb``);
    # slots holding content newer than the query's block resolve to an
    # out-of-band j_log and are masked (see engine/kvcache.py ring
    # geometry: the +1/slack pages guarantee in-band content is never
    # clobbered mid-dispatch).
    if quant:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)
    t = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    start = starts_ref[b]
    pad = pads_ref[b]
    page = tables_ref[b, j]

    def _compute():
        q = q_ref[0, :, 0].astype(jnp.float32) * scale     # [T, D]
        k_blk = k_ref[0, :, 0].astype(jnp.float32)         # [bt, D]
        v_blk = v_ref[0, :, 0].astype(jnp.float32)
        if quant:
            k_blk = k_blk * ks_ref[0]                      # [bt, 1]
            v_blk = v_blk * vs_ref[0]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                  # [T, bt]
        lane = lax.broadcasted_iota(jnp.int32, (t, bt), 0)
        q_pos = start + lane
        k_off = lax.broadcasted_iota(jnp.int32, (t, bt), 1)
        if window > 0:
            jq = q_pos // bt
            j_log = jq - jnp.mod(jq - j, nb)
            k_pos = j_log * bt + k_off
            # causal band over ROW-LOCAL positions; k_pos < 0 marks a
            # slot this row has not written yet
            ok = ((k_pos >= 0) & (k_pos <= q_pos)
                  & (q_pos - k_pos < window) & (lane >= pad))
        else:
            k_pos = j * bt + k_off
            # causal over ROW-LOCAL positions + leading pad lanes
            # invalid
            ok = (k_pos <= q_pos) & (lane >= pad)
        s = jnp.where(ok, s, NEG_INF)
        m = m_scr[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    # unused table lanes (-1: past the row's allocation) and blocks
    # entirely beyond the last query position contribute nothing. In
    # ring mode any slot may hold in-band content, so only the
    # unallocated-lane predicate applies.
    pred = page >= 0
    if window <= 0:
        pred = pred & (j * bt <= start + t - 1)
    pl.when(pred)(_compute)

    @pl.when(j == nb - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, :, 0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)


def paged_attention_ref(q, k_pool, v_pool, tables, row_starts, pad_lens,
                        window: int = 0, k_scale=None, v_scale=None):
    """Plain-JAX oracle for :func:`paged_attention` (same contract):
    gather every row's pages, mask, and run the grouped-query einsum.
    Materializes the ``[B, NB*bt, KVH, D]`` gather — the HBM cost the
    Pallas kernel exists to avoid — so it is the CPU/test path and the
    allclose reference, not the TPU path. ``k_scale``/``v_scale``
    dequantize int8 pages on the gather; ``window > 0`` applies the
    ring-table position mapping + sliding band (see ``_paged_kernel``).
    """
    from .attention import grouped_query_attention

    b, t, hq, d = q.shape
    bt = k_pool.shape[1]
    nb = tables.shape[1]
    safe = jnp.maximum(tables, 0)

    def gather(pool, pscale):
        arr = pool[safe].reshape(b, nb * bt, *pool.shape[2:])
        if pscale is not None:
            s = pscale[safe].reshape(b, nb * bt, *pscale.shape[2:])
            arr = (arr.astype(jnp.float32) * s[..., None]).astype(
                q.dtype)
        return arr

    k_all, v_all = gather(k_pool, k_scale), gather(v_pool, v_scale)
    lane = jnp.arange(t)
    q_pos = row_starts[:, None] + lane[None, :]                 # [B, T]
    used = jnp.repeat(tables >= 0, bt, axis=1)                  # [B, L]
    valid = lane[None, :, None] >= pad_lens[:, None, None]
    if window > 0:
        # ring layout: table slot s holds the newest logical block
        # j ≡ s (mod nb) at or below the query's own block
        jq = q_pos // bt                                        # [B, T]
        slot = jnp.arange(nb)
        j_log = jq[:, :, None] - jnp.mod(
            jq[:, :, None] - slot[None, None, :], nb)       # [B, T, NB]
        k_pos = (j_log[..., None] * bt
                 + jnp.arange(bt)).reshape(b, t, nb * bt)
        ok = ((k_pos >= 0) & (k_pos <= q_pos[:, :, None])
              & (q_pos[:, :, None] - k_pos < window)
              & valid & used[:, None, :])
    else:
        k_pos = jnp.arange(nb * bt)
        ok = (
            (k_pos[None, None, :] <= q_pos[:, :, None])
            & valid & used[:, None, :]
        )                                                       # [B, T, L]
    return grouped_query_attention(q, k_all, v_all, mask=ok[:, None])


def paged_attention(q, k_pool, v_pool, tables, row_starts, pad_lens,
                    impl: str = "auto", interpret: bool | None = None,
                    window: int = 0, k_scale=None, v_scale=None):
    """Paged decode attention over the KV block pool.

    :param q: ``[B, T, Hq, D]`` query rows (RoPE already applied at
        their row-local positions), T = this call's token window.
    :param k_pool / v_pool: ``[P, bt, KVH, D]`` pool leaves (page 0 is
        the reserved scratch page).
    :param tables: ``[B, NB]`` int32 block table — row ``b``'s logical
        block ``j`` lives in pool page ``tables[b, j]``; ``-1`` =
        unallocated (masked, fetch clipped to the scratch page).
    :param row_starts: ``[B]`` int32 — row-local position of q lane 0
        (may be negative when leading lanes are padding).
    :param pad_lens: ``[B]`` int32 — number of leading INVALID q lanes
        (their output rows are garbage; callers ignore them).
    :param impl: ``"auto"`` (Pallas on TPU, oracle elsewhere),
        ``"pallas"``, or ``"ref"``.
    :param window: sliding-window size (ISSUE 15). ``> 0`` switches the
        block table to RING semantics — logical block ``j`` lives in
        table slot ``j % NB`` — and masks keys outside the band
        ``q_pos - k_pos < window``; the table width bounds decode reads
        at O(window), independent of sequence length.
    :param k_scale / v_scale: ``[P, bt, KVH]`` f32 per-(token, head)
        scales for int8 pools (ISSUE 15): pages dequantize in the
        kernel's tile fetch (the decode-bandwidth win — half the KV
        bytes cross HBM), or on the gather in the oracle.
    :returns: ``[B, T, Hq, D]`` attention output.

    Query lane ``i`` of row ``b`` (valid iff ``i >= pad_lens[b]``)
    attends key positions ``0 .. row_starts[b] + i`` through the block
    table — the call's own tokens must already be written into the pool
    (models/llama.py writes before attending, same as the contiguous
    DUS path).

    TP serving (ISSUE 10): this kernel is HEAD-RANGE OBLIVIOUS — every
    shape it reads is local (``groups = hq // kvh`` holds per shard
    because both counts divide by the same tp), so under a tensor mesh
    it runs inside ``ops/attention.paged_gqa_attention``'s shard_map
    with each shard's instance walking only its local ``KVH/tp`` slice
    of the pool; nothing here needs to know the mesh exists.
    """
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return paged_attention_ref(q, k_pool, v_pool, tables, row_starts,
                                   pad_lens, window=window,
                                   k_scale=k_scale, v_scale=v_scale)
    if interpret is None:
        interpret = not _on_tpu()
    b, t, hq, d = q.shape
    p, bt, kvh, _ = k_pool.shape
    nb = tables.shape[1]
    groups = hq // kvh
    quant = k_scale is not None
    t_pad = max(t, PAGED_MIN_Q)
    if t_pad != t:
        # LEFT-pad the q window (the last lane must stay last): the new
        # lanes are invalid by construction
        q = jnp.pad(q, ((0, 0), (t_pad - t, 0), (0, 0), (0, 0)))
        row_starts = row_starts - (t_pad - t)
        pad_lens = pad_lens + (t_pad - t)
    page_index = lambda bb, h, j, tbl, st, pd: (       # noqa: E731
        jnp.maximum(tbl[bb, j], 0), 0, h // groups, 0)
    scale_index = lambda bb, h, j, tbl, st, pd: (      # noqa: E731
        jnp.maximum(tbl[bb, j], 0), 0, h // groups)
    in_specs = [
        pl.BlockSpec((1, t_pad, 1, d),
                     lambda bb, h, j, tbl, st, pd: (bb, 0, h, 0)),
        pl.BlockSpec((1, bt, 1, d), page_index),
        pl.BlockSpec((1, bt, 1, d), page_index),
    ]
    args = [q, k_pool, v_pool]
    if quant:
        # dequant epilogue inputs: per-(token, head) f32 scales, same
        # page-table-driven DMA as the int8 tiles they rescale
        in_specs += [pl.BlockSpec((1, bt, 1), scale_index),
                     pl.BlockSpec((1, bt, 1), scale_index)]
        args += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hq, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, t_pad, 1, d),
                               lambda bb, h, j, tbl, st, pd: (bb, 0, h, 0)),
        scratch_shapes=[
            pltpu.VMEM((t_pad, 1), jnp.float32),
            pltpu.VMEM((t_pad, 1), jnp.float32),
            pltpu.VMEM((t_pad, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=d ** -0.5, bt=bt, nb=nb,
                          window=window, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t_pad, hq, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), row_starts.astype(jnp.int32),
      pad_lens.astype(jnp.int32), *args)
    return out[:, t_pad - t:]


def pick_block_sizes(t: int, d: int) -> tuple:
    """(block_q, block_k) for a [*, t, *, d] attention, from the round-3
    measurement sweep on TPU v5e (full fwd+bwd through ``jax.grad``,
    in-jit chained scan timing — the 7-point (bq, bk) grid at each of
    (t, d) in {1024, 4096, 8192}x64 and 2048x128, causal):

    - **(512, 1024)** is fastest or tied-fastest at every measured point
      up to t=4096 — 30% over the old 512x512 default at t=1024
      (11.7 vs 16.9 ms) and 16% at t=4096. Wide KV tiles suit the
      KV-innermost forward stream; 1024x1024 gives the gain back.
    - **(1024, 512)** wins at t=8192 with small batch (17.4 vs 21.5 ms):
      once b*h programs no longer fill the chip, coarser q-grids put
      more work in each program.

    Sequences shorter than a block fall back to one block (the ``min``
    in the caller). Lengths that don't divide the asymmetric pair's
    lcm (1024) keep the old square 512x512 — the caller pads to the
    block lcm, and taxing a t=1536 call with 512 columns of masked
    padding would cost more than the block win."""
    del d  # same winner at d=64 and d=128 everywhere measured
    if t % 1024:
        return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    if t >= 8192:
        return 1024, 512
    return 512, 1024


def _padded_len(t: int, block_q: int, block_k: int) -> int:
    """``t``, or the next multiple of both blocks where the blocks, clipped
    to ``t`` as the kernels clip them, do not divide it."""
    if t % min(block_q, t) == 0 and t % min(block_k, t) == 0:
        return t
    lcm = block_q * block_k // math.gcd(block_q, block_k)
    return -(-t // lcm) * lcm


def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = 0,
                    block_k: int = 0,
                    interpret: bool | None = None,
                    window: int = 0):
    """Fused attention. q,k,v: [B, T, H, D] -> [B, T, H, D].

    ``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere
    (CPU tests). ``block_q/block_k = 0`` (the default) auto-picks via
    ``pick_block_sizes(t, d)``. Any sequence length works: lengths that
    don't divide the block sizes are zero-padded to the next block multiple
    and the padded keys are masked out inside the kernel (padded query rows
    are sliced off, and ``jnp.pad``'s VJP zeroes their gradients).

    ``window > 0`` (with ``causal``): sliding-window banding. The grid
    itself is banded — only the ~window-wide KV tile strip per q block is
    visited in forward and both backward kernels, so compute and K/V
    streaming are O(T * window).
    """
    if interpret is None:
        interpret = not _on_tpu()
    b, t, h, d = q.shape
    if not block_q or not block_k:
        auto_q, auto_k = pick_block_sizes(t, d)
        block_q = block_q or auto_q
        block_k = block_k or auto_k
    t_pad = _padded_len(t, block_q, block_k)
    fold = lambda x: jnp.moveaxis(x, 2, 1).reshape(b * h, t, d)
    q, k, v = fold(q), fold(k), fold(v)
    if t_pad != t:
        pad = ((0, 0), (0, t_pad - t), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    out = _flash_3d(q, k, v, causal, block_q, block_k, t, interpret,
                    window)
    out = out[:, :t]
    return jnp.moveaxis(out.reshape(b, h, t, d), 1, 2)


def flash_attention_lse(q, k, v, causal: bool = False,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool | None = None,
                        window: int = 0):
    """Fused attention returning ``(out, lse)``.

    ``window > 0`` (with ``causal``) applies the same-origin sliding-window
    band ``q_pos - k_pos < window`` with the banded grid of
    ``flash_attention`` — used by the ring bodies for the DIAGONAL block
    (off-diagonal ring blocks have shifted position origins and are
    handled by the callers: fully-visible blocks need no mask, band-edge
    blocks go through a masked einsum merge).

    q, k, v: [B, T, H, D]; out: [B, T, H, D]; lse: [B, H, T] float32 —
    ``logsumexp_k(q·k/sqrt(d))`` per query row. Disjoint-key-block results
    combine exactly:

        lse = logaddexp(lse_a, lse_b)
        out = exp(lse_a - lse)·out_a + exp(lse_b - lse)·out_b

    which is how the ring bodies (ops/attention.py) chain this kernel over
    K/V blocks arriving via ppermute (ring blocks are always square, so
    Tq == Tk is required). Gradients flow through BOTH outputs (the lse
    cotangent folds into the backward kernels' delta term).
    """
    if interpret is None:
        interpret = not _on_tpu()
    b, t, h, d = q.shape
    if k.shape[1] != t:
        raise ValueError(f"flash_attention_lse needs Tq == Tk; "
                         f"{t} vs {k.shape[1]}")
    t_pad = _padded_len(t, block_q, block_k)
    fold = lambda x: jnp.moveaxis(x, 2, 1).reshape(b * h, t, d)
    qf, kf, vf = fold(q), fold(k), fold(v)
    if t_pad != t:
        pad = ((0, 0), (0, t_pad - t), (0, 0))
        qf, kf, vf = jnp.pad(qf, pad), jnp.pad(kf, pad), jnp.pad(vf, pad)
    out, lse = _flash_3d_lse(qf, kf, vf, causal, block_q, block_k,
                             t, interpret, window)
    out = out[:, :t]
    lse = lse[:, :t]
    return (jnp.moveaxis(out.reshape(b, h, t, d), 1, 2),
            lse.reshape(b, h, t))
