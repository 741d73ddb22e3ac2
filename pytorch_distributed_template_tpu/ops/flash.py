"""Fused flash attention: Pallas TPU forward kernel + blockwise backward.

The reference delegates all kernels to cuDNN (SURVEY.md §2.2); here the one
op XLA doesn't fuse perfectly at long sequence length — attention — gets an
in-tree Pallas kernel (see /opt/skills/guides/pallas_guide.md):

- **forward**: grid (batch*head, q-block, kv-block) with the KV dimension
  innermost — K/V blocks STREAM through VMEM (Pallas double-buffers the
  HBM→VMEM copies against compute), and the online-softmax state (m, l,
  accumulator) lives in VMEM scratch carried across the KV grid steps. Only
  a [BQ, BK] score tile ever exists, and VMEM use is independent of T, so
  sequence length is bounded by HBM, not VMEM. Outputs carry the
  logsumexp rows (trailing unit lane axis: Mosaic tiling-legal).
- **backward**: the standard two-kernel flash backward, also Pallas and
  also fully streamed. A dk/dv kernel (grid over KV blocks × q blocks, q
  innermost, dk/dv accumulated in scratch) and a dq kernel (grid over q
  blocks × KV blocks, KV innermost), both recomputing the probability tile
  from the saved logsumexp in f32 so only [BQ, BK] tiles ever exist.
  ``_bwd_3d`` (plain-JAX blockwise) is kept as the oracle the Pallas
  kernels are tested against.
- **what a tile pays** (``_tile_branches``): nothing where no query of it
  sees a key of it; no mask where every query sees every key; on a square
  tile that the diagonal or the band's lower edge crosses corner to
  corner, the backward kernels compute strips that cover the seen half
  and little more. ``flash/tiles`` (one INFO line and span a process and
  call shape, ``tile_counts``) says what a call's kernels visit.

The MXU gets the operands in the dtype the arrays have; accumulation and
the softmax statistics are float32 regardless of it. What the kernels
take on this chip, and the blocks chosen from it, is under
``pick_block_sizes``.
"""
from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..observability.trace import say_once

NEG_INF = -1e30
# ``flash_attention_lse``'s blocks (the ring and zig-zag bodies of
# ops/attention.py, whose blocks are square); ``flash_attention`` asks
# ``pick_block_sizes``.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512

logger = logging.getLogger(__name__)


# -- a score tile's place in the mask ---------------------------------------
# Tile (i, j) holds queries i*block_q .. (i+1)*block_q - 1 and keys
# j*block_k .. (j+1)*block_k - 1. A query sees key k when k <= q
# (``causal``), q - k < window (``window > 0``) and k < t_valid (keys past
# it are padding). The predicates below are scalar arithmetic and work
# alike on Python integers (the counter, the tests) and on program ids;
# ``geo`` is (block_q, block_k, causal, t_valid, t, window) throughout.

# a triangular tile is computed in this many strips (``_strips``), of
# queries in the dq kernel and of keys in the dkv kernel; the forward
# computes it whole (``_tile_branches`` says why)
STRIPS = 4
STRIPS_OF = {"fwd": None, "dkv": "cols", "dq": "rows"}
# the minor dimension of a TPU tile: a strip's keys are whole lanes, and
# in the kernels' ``[heads, tokens, head size]`` layout a shorter head
# size is padded to it in HBM
LANES = 128


def _tile_visible(i, j, block_q, block_k, causal, t_valid, t, window):
    """Whether any query of the tile sees any key of it; None where every
    tile of the call is (no diagonal, no band, no padding). It never says
    no where a pair is seen."""
    visible = None
    if causal:
        # tiles strictly beyond the diagonal
        visible = j * block_k < (i + 1) * block_q
    if window > 0:
        # tiles entirely below the band
        in_band = (j + 1) * block_k > i * block_q - window + 1
        visible = in_band if visible is None else visible & in_band
    if t_valid < t:
        # tiles of padded keys alone
        valid = j * block_k < t_valid
        visible = valid if visible is None else visible & valid
    return visible


def _tile_is_edge(i, j, block_q, block_k, causal, t_valid, t, window):
    """Whether the tile holds a (query, key) pair the query does not see:
    the diagonal, the band's lower edge or the padding boundary crosses
    it, and it needs its mask. None where no tile of the call does."""
    edge = None
    if causal:
        # the tile's last key lies after its first query
        edge = (j + 1) * block_k - 1 > i * block_q
    if window > 0:
        # its first key lies a window or more before its last query
        low = (i + 1) * block_q - 1 - j * block_k >= window
        edge = low if edge is None else edge | low
    if t_valid < t:
        # it holds padded keys
        pad = (j + 1) * block_k > t_valid
        edge = pad if edge is None else edge | pad
    return edge


def _tile_triangles(i, j, block_q, block_k, causal, t_valid, t, window):
    """(lower, upper): whether the tile is a square one that nothing but
    the diagonal crosses, corner to corner (its queries see the keys at or
    before their own place in the tile), or nothing but the band's lower
    edge (they see the keys after their own place). Half of such a tile
    is seen, and ``_strips`` computes little more than that half. None
    where the call has no such tile: blocks that are not square or do not
    split into ``STRIPS`` strips of whole lanes, a band the blocks do not
    divide."""
    if not (causal and block_q == block_k
            and block_q % (STRIPS * LANES) == 0):
        return None, None
    unpadded = True if t_valid >= t else (j + 1) * block_k <= t_valid
    lower = upper = None
    if window == 0 or window >= block_q:    # else the band crosses it too
        lower = (i == j) & unpadded
    if window > 0 and window % block_q == 0:
        upper = ((i - j) * block_q == window) & unpadded
    return lower, upper


def _strips(triangle: str, by_rows: bool, block: int) -> list:
    """The (rows, columns) rectangles that cover what is seen of a
    ``lower`` or ``upper`` triangular tile: ``STRIPS`` strips of queries,
    each with the keys up to (from) its last (first) query's place, for
    the kernel that accumulates by query (dq); ``by_rows`` False gives
    strips of keys, each with the queries that see them (dkv). 10 of a
    tile's 16 squares: 1.25 times the seen half, not 2."""
    size = block // STRIPS
    cuts = [(n * size, (n + 1) * size) for n in range(STRIPS)]
    if (triangle == "lower") == by_rows:
        # the strip, and the other axis from 0 to the strip's end
        pieces = [(slice(lo, hi), slice(0, hi)) for lo, hi in cuts]
    else:
        # the strip, and the other axis from the strip's start on
        pieces = [(slice(lo, hi), slice(lo, block)) for lo, hi in cuts]
    return pieces if by_rows else [(b, a) for a, b in pieces]


def _not(x):
    return not x if isinstance(x, bool) else jnp.logical_not(x)


def _tile_branches(i, j, geo, strips=None, in_grid=None) -> list:
    """What a kernel does on tile (i, j), as (name, predicate, rectangles)
    branches of which at most one holds: nothing where no query
    of the tile sees a key of it (or a banded grid's step lies past the
    last block: ``in_grid``); the whole tile without a mask where every
    pair is seen (an interior tile pays for no iota, compare or select);
    the strips of a triangular tile, masked, where the kernel takes
    ``strips`` (``"rows"``: of queries, ``"cols"``: of keys; the forward
    takes none: its running maximum and sum make every strip a pass of
    its own, which costs more than the spared scores, PERF.md section 6);
    the whole tile, masked, on the other edge tiles. The names are
    ``whole`` (no mask), ``lower``, ``upper`` and ``masked``; a predicate
    is None where it always holds."""
    block_q, block_k = geo[:2]
    whole = [(slice(0, block_q), slice(0, block_k))]
    visible = _tile_visible(i, j, *geo)
    if in_grid is not None:
        visible = in_grid if visible is None else visible & in_grid
    edge = _tile_is_edge(i, j, *geo)
    if edge is None:
        return [("whole", visible, whole)]
    branches = []
    other = edge
    triangles = _tile_triangles(i, j, *geo) if strips else ()
    for name, triangle in zip(("lower", "upper"), triangles):
        if triangle is not None:
            branches.append(
                (name, triangle, _strips(name, strips == "rows", block_q)))
            other = other & _not(triangle)
    branches += [("masked", other, whole), ("whole", _not(edge), whole)]
    if visible is not None:
        branches = [(name, visible & pred, pieces)
                    for name, pred, pieces in branches]
    return branches


def _tile_mask(i, j, rows, cols, block_q, block_k, causal, t_valid, t,
               window):
    """The mask of rectangle (rows, cols) of an edge tile: True where the
    query sees the key."""
    shape = (rows.stop - rows.start, cols.stop - cols.start)
    q_pos = i * block_q + rows.start + lax.broadcasted_iota(
        jnp.int32, shape, 0)
    k_pos = j * block_k + cols.start + lax.broadcasted_iota(
        jnp.int32, shape, 1)
    ok = jnp.full(shape, True)
    if causal:
        ok = q_pos >= k_pos
    if window > 0:
        ok = ok & (q_pos - k_pos < window)
    if t_valid < t:  # keys past t_valid are padding
        ok = ok & (k_pos < t_valid)
    return ok


def _on_tile(body, i, j, block_q, block_k, in_grid=None, *, strips=None,
             live, causal, t_valid, t, window):
    """Run ``body(rows, cols, mask)`` on the rectangles of score tile
    (i, j) that ``_tile_branches`` names; ``mask`` puts NEG_INF on the
    pairs no query sees, or is None. One body: each branch that some step
    of the grid takes (``live``, from ``_grid_walk``) is a ``pl.when``
    that traces it on its rectangles."""
    geo = (block_q, block_k, causal, t_valid, t, window)

    def run(name, pieces):
        for rows, cols in pieces:
            body(rows, cols, None if name == "whole" else (
                lambda s, rows=rows, cols=cols: jnp.where(
                    _tile_mask(i, j, rows, cols, *geo), s, NEG_INF)))

    for name, pred, pieces in _tile_branches(i, j, geo, strips, in_grid):
        if name not in live:
            continue
        if pred is None:
            run(name, pieces)
        else:
            pl.when(pred)(functools.partial(run, name, pieces))


def _band_start(i, block_q, block_k, window):
    """First KV tile that can intersect q block ``i``'s sliding band.
    Floor division of a possibly-negative numerator rounds toward -inf,
    which the max-with-0 absorbs."""
    lo = (i * block_q - (window - 1)) // block_k
    return max(lo, 0) if isinstance(lo, int) else jnp.maximum(lo, 0)


def _q_band_start(j, block_q, block_k):
    """First q block whose rows can (causally) see KV tile ``j`` — the
    diagonal block. Shared by the dkv kernel and its index map so data
    placement and predication cannot desync."""
    return (j * block_k) // block_q


def _band_steps(span_block, tile_block, num_tiles, t, causal, window):
    """Steps of a banded grid axis (static): the tiles of size
    ``tile_block`` that intersect a band spanning ``span_block + window -
    1`` positions, +1 slack for tile misalignment; 0 where the call has
    no band or the band covers the axis, and the plain grid is simpler.
    The KV band of a q block (span=block_q, tile=block_k) in the forward
    and dq kernels and, with the roles swapped, the q band of a KV block
    in the dkv kernel: compute AND the HBM->VMEM streaming are then
    O(T * window)."""
    if not (causal and 0 < window < t):
        return 0
    steps = (span_block + window - 1 + tile_block - 1) // tile_block + 1
    return steps if steps < num_tiles else 0


def _banded_index(start_fn, num_blocks):
    """Index map for a banded grid axis: block = clip(start(outer) + off).
    The kernel predicates with the UNclipped index; the clip only keeps
    the prefetch legal at the edges."""

    def index(b, outer, off):
        return b, jnp.clip(start_fn(outer) + off, 0, num_blocks - 1), 0

    return index


@functools.lru_cache(maxsize=None)
def _grid_walk(kernel, t, t_valid, block_q, block_k, causal, window):
    """One head's walk of ``kernel``'s own grid (``fwd``, ``dkv``, ``dq``;
    the inner two axes, banded or plain) with the kernel's own predicates,
    from shapes alone: (grid steps, tiles whose body runs, those of them
    that take their mask, scores computed, the names of
    ``_tile_branches``' branches that some step takes). The kernels leave
    the branches no step takes out of the program: one diagonal tile a
    head (1024 tokens in one block) compiles one body, not three."""
    num_q, num_kv = t // block_q, t // block_k
    geo = (block_q, block_k, causal, t_valid, t, window)
    by_kv = kernel == "dkv"     # its outer axis is the KV block, q inner
    if by_kv:
        outer, inner = num_kv, num_q
        band = _band_steps(block_k, block_q, num_q, t, causal, window)
        start = functools.partial(_q_band_start, block_q=block_q,
                                  block_k=block_k)
    else:
        outer, inner = num_q, num_kv
        band = _band_steps(block_q, block_k, num_kv, t, causal, window)
        start = functools.partial(_band_start, block_q=block_q,
                                  block_k=block_k, window=window)
    steps = visited = edge = computed = 0
    live = set()
    for a in range(outer):
        lo = start(a) if band else 0
        for b in range(lo, lo + (band or inner)):
            steps += 1
            i, j = (b, a) if by_kv else (a, b)
            for name, pred, pieces in _tile_branches(
                    i, j, geo, STRIPS_OF[kernel], b <= inner - 1):
                if pred is None or pred:
                    live.add(name)
                    visited += 1
                    edge += name != "whole"
                    computed += sum(
                        (rows.stop - rows.start) * (cols.stop - cols.start)
                        for rows, cols in pieces)
    return steps, visited, edge, computed, tuple(sorted(live))


# -- the three kernels ------------------------------------------------------
# The MXU gets q, k, v and g in the dtype the arrays have (bfloat16 in
# training; the float32 of the CPU tests stays float32) and accumulates
# in float32; the probabilities and their gradient are cast to that dtype
# for the second matmuls. Running maximum, sum, log-sum-exp, delta, the
# accumulators and the exponentials are float32. ``scale`` is paid once:
# where ``d ** -0.5`` is a power of two it is exact on an operand in any
# float type, and the operand that stays in VMEM through the inner grid
# axis is scaled into a scratch on its first step; otherwise the float32
# scores are multiplied. The backward's second ``scale`` (on ds) is
# linear in the accumulators and is applied to them once, in finalize.

def _scale_folds(d: int) -> bool:
    """Whether ``d ** -0.5`` is a power of two (d a power of four)."""
    return math.log2(d) % 2 == 0


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _scaled(x, scale):
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b
_TN = ((0,), (0,))      # a.T @ b


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                q_scr=None, *, scale: float, num_kv: int, nb: int, live: tuple,
                **geo):
    # grid (BH, num_q, num_kv) — or (BH, num_q, nb) when ``nb`` (causal
    # sliding window: only the ~window-wide KV tile band per q block is in
    # the grid at all). kv innermost. q_ref/o_ref: [1, BQ, D];
    # k_ref/v_ref: [1, BK, D] (streamed); lse_ref: [1, BQ, 1] (the trailing
    # unit lane axis keeps the block shape legal under Mosaic's
    # (8, 128)-or-equal tiling rule). Scratch m/l: [BQ, 1] f32, acc:
    # [BQ, D] f32 — the online-softmax state carried across the kv dim;
    # q_scr: [BQ, D], the scaled q block, where the scale folds.
    i = pl.program_id(1)
    jb = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    j = _band_start(i, block_q, block_k, geo["window"]) + jb if nb else jb

    @pl.when(jb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if q_scr is not None:
            q_scr[...] = _scaled(q_ref[0], scale)

    def _compute(rows, cols, mask):
        if q_scr is None:
            s = _dot(q_ref[0, rows], k_ref[0, cols], _NT) * scale
        else:
            s = _dot(q_scr[rows], k_ref[0, cols], _NT)
        if mask is not None:
            s = mask(s)                                    # [rows, cols]
        m = m_scr[rows]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l_scr[rows] = l_scr[rows] * corr + jnp.sum(p, axis=1, keepdims=True)
        v_blk = v_ref[0, cols]
        acc_scr[rows] = acc_scr[rows] * corr + _dot(
            p.astype(v_blk.dtype), v_blk, _NN)
        m_scr[rows] = m_new

    # nb overshoots the last block near the sequence's end
    _on_tile(_compute, i, j, block_q, block_k,
             j <= num_kv - 1 if nb else None, strips=STRIPS_OF["fwd"],
             live=live, **geo)

    @pl.when(jb == (nb or num_kv) - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l_safe)


def _flash_fwd_3d(q, k, v, *, causal: bool, block_q: int, block_k: int,
                  t_valid: int, interpret: bool, window: int = 0):
    """q,k,v: [BH, T, D] (T block-padded) -> (out, lse [BH, T])."""
    bh, t, d = q.shape
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    assert t % block_q == 0 and t % block_k == 0, (t, block_q, block_k)
    num_kv = t // block_k
    nb = _band_steps(block_q, block_k, num_kv, t, causal, window)
    walk = ("fwd", t, t_valid, block_q, block_k, causal, window)
    kernel = functools.partial(
        _fwd_kernel, scale=d ** -0.5, num_kv=num_kv, nb=nb,
        live=_grid_walk(*walk)[-1], causal=causal, t_valid=t_valid, t=t,
        window=window,
    )
    if nb:
        kv_index = _banded_index(
            lambda i: _band_start(i, block_q, block_k, window), num_kv
        )
    else:
        kv_index = lambda b, i, j: (b, j, 0)
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, d), jnp.float32),
    ]
    if _scale_folds(d):
        scratch.append(pltpu.VMEM((block_q, d), q.dtype))
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, t // block_q, nb or num_kv),
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
        scratch_shapes=scratch,
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
                    dk_ref, dv_ref, dk_scr, dv_scr, k_scr=None, *,
                    scale: float, num_q: int, nqb: int, live: tuple, **geo):
    # grid (BH, num_kv, num_q) — or (BH, num_kv, nqb) when ``nqb``
    # (sliding window: only q blocks within ``window`` above this KV block
    # are visited). q innermost (streamed). k/v/dk/dv refs:
    # [1, BK, D] (this program's KV block); q_ref/g_ref: [1, BQ, D];
    # lse_ref/delta_ref: [1, BQ, 1]. Scratch dk/dv: [BK, D] f32; k_scr:
    # [BK, D], the scaled KV block, where the scale folds.
    j = pl.program_id(1)
    ib = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    i = _q_band_start(j, block_q, block_k) + ib if nqb else ib

    @pl.when(ib == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)
        if k_scr is not None:
            k_scr[...] = _scaled(k_ref[0], scale)

    def _compute(rows, cols, mask):
        q_blk = q_ref[0, rows]
        g_blk = g_ref[0, rows]
        if k_scr is None:
            s = _dot(q_blk, k_ref[0, cols], _NT) * scale
        else:
            s = _dot(q_blk, k_scr[cols], _NT)
        if mask is not None:
            s = mask(s)                                # [rows, cols]
        p = jnp.exp(s - lse_ref[0, rows])
        dv_scr[cols] += _dot(p.astype(g_blk.dtype), g_blk, _TN)
        dp = _dot(g_blk, v_ref[0, cols], _NT)
        ds = p * (dp - delta_ref[0, rows])
        dk_scr[cols] += _dot(ds.astype(q_blk.dtype), q_blk, _TN)

    _on_tile(_compute, i, j, block_q, block_k,
             i <= num_q - 1 if nqb else None, strips=STRIPS_OF["dkv"],
             live=live, **geo)

    @pl.when(ib == (nqb or num_q) - 1)
    def _finalize():
        dk = dk_scr[...] * scale
        dv = dv_scr[...]
        if geo["t_valid"] < geo["t"]:
            # padded keys: their grads must be exactly 0
            kv_valid = (
                j * block_k
                + lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
                < geo["t_valid"]
            )
            dk = jnp.where(kv_valid, dk, 0.0)
            dv = jnp.where(kv_valid, dv, 0.0)
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, g_ref, lse_ref, delta_ref, k_ref, v_ref, dq_ref,
                   dq_scr, q_scr=None, *, scale: float, num_kv: int,
                   nb: int, live: tuple, **geo):
    # grid (BH, num_q, num_kv) — or (BH, num_q, nb) when ``nb``
    # (sliding window: only the band's KV tiles are visited). kv innermost
    # (streamed). q/g/dq refs: [1, BQ, D]; k_ref/v_ref: [1, BK, D];
    # lse_ref/delta_ref: [1, BQ, 1]. Scratch dq: [BQ, D] f32; q_scr as in
    # the forward.
    i = pl.program_id(1)
    jb = pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    j = _band_start(i, block_q, block_k, geo["window"]) + jb if nb else jb

    @pl.when(jb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        if q_scr is not None:
            q_scr[...] = _scaled(q_ref[0], scale)

    def _compute(rows, cols, mask):
        g_blk = g_ref[0, rows]
        k_blk = k_ref[0, cols]
        if q_scr is None:
            s = _dot(q_ref[0, rows], k_blk, _NT) * scale
        else:
            s = _dot(q_scr[rows], k_blk, _NT)
        if mask is not None:
            s = mask(s)
        p = jnp.exp(s - lse_ref[0, rows])
        dp = _dot(g_blk, v_ref[0, cols], _NT)
        ds = p * (dp - delta_ref[0, rows])
        dq_scr[rows] += _dot(ds.astype(k_blk.dtype), k_blk, _NN)

    _on_tile(_compute, i, j, block_q, block_k,
             j <= num_kv - 1 if nb else None, strips=STRIPS_OF["dq"],
             live=live, **geo)

    @pl.when(jb == (nb or num_kv) - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _flash_dkv_3d(q, g, lse, delta, k, v, *, causal: bool, block_q: int,
                  block_k: int, t_valid: int, interpret: bool,
                  window: int = 0):
    """The dkv kernel's call: q, g, k, v [BH, T, D], lse and delta
    [BH, T, 1] float32 -> (dk, dv)."""
    bh, t, d = q.shape
    num_q, num_kv = t // block_q, t // block_k
    nqb = _band_steps(block_k, block_q, num_q, t, causal, window)
    if nqb:
        q_index = _banded_index(
            lambda j: _q_band_start(j, block_q, block_k), num_q
        )
    else:
        q_index = lambda b, j, i: (b, i, 0)
    scratch = [
        pltpu.VMEM((block_k, d), jnp.float32),
        pltpu.VMEM((block_k, d), jnp.float32),
    ]
    if _scale_folds(d):
        scratch.append(pltpu.VMEM((block_k, d), k.dtype))
    return pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=d ** -0.5, num_q=num_q, nqb=nqb,
            live=_grid_walk("dkv", t, t_valid, block_q, block_k, causal,
                            window)[-1],
            causal=causal, t_valid=t_valid, t=t, window=window,
        ),
        grid=(bh, num_kv, nqb or num_q),
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
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_dkv",
    )(q, g, lse, delta, k, v)


def _flash_dq_3d(q, g, lse, delta, k, v, *, causal: bool, block_q: int,
                 block_k: int, t_valid: int, interpret: bool,
                 window: int = 0):
    """The dq kernel's call, on the operands of ``_flash_dkv_3d`` -> dq."""
    bh, t, d = q.shape
    num_q, num_kv = t // block_q, t // block_k
    nb = _band_steps(block_q, block_k, num_kv, t, causal, window)
    if nb:
        kv_index = _banded_index(
            lambda i: _band_start(i, block_q, block_k, window), num_kv
        )
    else:
        kv_index = lambda b, i, j: (b, j, 0)
    scratch = [pltpu.VMEM((block_q, d), jnp.float32)]
    if _scale_folds(d):
        scratch.append(pltpu.VMEM((block_q, d), q.dtype))
    return pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=d ** -0.5, num_kv=num_kv, nb=nb,
            live=_grid_walk("dq", t, t_valid, block_q, block_k, causal,
                            window)[-1],
            causal=causal, t_valid=t_valid, t=t, window=window,
        ),
        grid=(bh, num_q, nb or num_kv),
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
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_dq",
    )(q, g, lse, delta, k, v)[0]


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
    t = q.shape[1]
    # delta_i = g_i . out_i (rowwise) — cheap, XLA-fused outside the kernels.
    # Both row-stat tensors carry a trailing unit lane axis (see _fwd_kernel).
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)[..., None]
    lse = lse.astype(jnp.float32)[..., None]
    call = dict(causal=causal, block_q=min(block_q, t),
                block_k=min(block_k, t), t_valid=t_valid,
                interpret=interpret, window=window)
    dk, dv = _flash_dkv_3d(q, g, lse, delta, k, v, **call)
    dq = _flash_dq_3d(q, g, lse, delta, k, v, **call)
    return dq, dk, dv


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
    """(block_q, block_k) for a [*, t, *, d] attention: 1024 x 1024 where
    1024 divides ``t``, else the square 512 (clipped to ``t`` by the
    callers, so a shorter sequence is one block).

    Measured on one TPU v5e by ``scripts/flash_sweep.py`` (PR 31; the
    three kernels alone, each chained inside one jit, bfloat16, causal;
    the whole table is in PERF.md section 6), ms a call at 1024 x 1024
    against the pair the rule gave before (512 x 1024 up to 4096 tokens,
    1024 x 512 from 8192), forward / dkv / dq:

    - ``[8, 1024, 20, 64]``: 0.65 / 0.91 / 0.58 against 0.83 / 1.12 / 0.86;
    - ``[1, 8192, 32, 128]``, band 4096: 4.59 / 6.35 / 4.89 against
      7.94 / 7.74 / 5.68;
    - ``[2, 4096, 32, 128]``, band inactive: 3.16 / 4.62 / 3.37 against
      3.73 / 5.03 / 4.32.

    A tile's time follows its queries far more than its keys (the
    forward's reductions and running statistics, the backward's row
    broadcasts, a grid step's 0.4-1 us), so the widest key block wins in
    every kernel and smaller tiles lose although they compute fewer
    unseen scores: at 1024 tokens 256 x 256 tiles compute 1.25 times the
    seen scores and take 2.5 times as long as one 1024 x 1024 tile that
    computes twice them. What the large tile computes for nothing the
    backward kernels spare by strips (``_strips``), which only square
    blocks have. The same pair won at both head sizes, with and without
    the band, so the rule reads neither ``d`` nor the mask.

    Lengths that 1024 does not divide keep 512 x 512: the callers pad to
    the blocks' least common multiple, and 512 more columns of masked
    padding on a 1536-token call would cost more than the larger tile
    gains (not measured on this chip)."""
    del d
    if t % 1024:
        return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K
    return 1024, 1024


def tile_counts(t: int, t_valid: int, block_q: int, block_k: int,
                causal: bool, window: int) -> dict:
    """What one head's three kernels visit, from shapes alone: for ``fwd``,
    ``dkv`` and ``dq`` the grid steps of the inner two axes, the tiles
    whose body runs (``tiles_visited``), those of them that build their
    mask (``tiles_edge``) and those that do not (``tiles_interior``), and
    the scores computed over the scores a query sees
    (``computed_over_useful``): ``_grid_walk``'s counts."""
    useful = 0              # pairs (query, key it sees), padding left out
    for q in range(t_valid):
        lo = max(0, q - window + 1) if window > 0 else 0
        useful += (q + 1 if causal else t_valid) - lo
    counts = {}
    for kernel in STRIPS_OF:
        steps, visited, edge, computed, _ = _grid_walk(
            kernel, t, t_valid, min(block_q, t), min(block_k, t), causal,
            window)
        counts[kernel] = dict(
            grid_steps=steps, tiles_visited=visited, tiles_edge=edge,
            tiles_interior=visited - edge,
            computed_over_useful=round(computed / useful, 4))
    return counts


def _say_tiles(t, t_valid, d, block_q, block_k, causal, window):
    """The tiles a call's kernels visit, once a process and distinct call
    shape: a log line and a zero-length span, as ``remat/policy`` has.
    What to read first when a cell's ``flash_ms_per_step`` moves."""
    counts = tile_counts(t, t_valid, block_q, block_k, causal, window)
    record = dict(t=t_valid, d=d, window=window, causal=causal,
                  block_q=min(block_q, t), block_k=min(block_k, t))
    for kernel, c in counts.items():
        record.update({f"{kernel}_{name}": n for name, n in c.items()})
    say_once(
        logger, "flash/tiles", record,
        "flash/tiles: t %d d %d window %d causal %s blocks %d x %d; %s",
        t_valid, d, window, causal, record["block_q"], record["block_k"],
        "; ".join(
            "%s visits %d of %d grid steps (%d edge, %d interior), "
            "computed/useful %.4f" % (
                kernel, c["tiles_visited"], c["grid_steps"],
                c["tiles_edge"], c["tiles_interior"],
                c["computed_over_useful"])
            for kernel, c in counts.items()))


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
    _say_tiles(t_pad, t, d, block_q, block_k, causal, window)
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
    _say_tiles(t_pad, t, d, block_q, block_k, causal, window)
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
