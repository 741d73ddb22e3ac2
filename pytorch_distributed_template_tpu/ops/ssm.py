"""The Mamba-2 state-space layer outside its projections (Dao & Gu 2024,
"state space duality"): the depthwise causal convolution in front of the
scan, and the scan.

**The convolution** (``causal_conv_silu``; ``sharded_conv_silu`` under a
mesh): ``silu(bias + sum_i taps[i] * x[t - (k - 1) + i])`` a channel, one
function with a backward rule of its own. Its forward is plain
``jax.numpy`` over the bfloat16 input as it lies in the layer's
projection, which the compiler makes one fusion; it keeps the projection
and no pre-activation. Its backward remakes the pre-activation, and on
the TPU is one kernel, positions on the lanes (the order both hybrid
steps keep the projection in): the input and the cotangent read once,
the input's gradient written once, float32 only in the block. Elsewhere
the backward is ``jax.numpy`` too. ``ssm/conv`` is its line and span.
``causal_conv`` is the same sum without bias or activation, for a mixer
whose convolution is linear between two gates; no rule of its own.

**The scan.** A head ``h`` with a scalar decay carries a state ``S [P, N]``:

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D_h * x_t

with ``x_t [P]`` the head's channels and ``B_t``, ``C_t [N]`` shared by
the ``H / G`` heads of a group. ``ssd_scan`` computes it in chunks of
``chunk`` positions, which is what makes it matrix products:

- inside a chunk ``y_t`` sums over the chunk's earlier positions
  ``(C_t . B_s) * exp(cs_t - cs_s) * dt_s * x_s`` (``cs`` the running sum
  of ``dt * A`` inside the chunk): one ``[L, L]`` product of C with B a
  group, a decay mask a head, one product with the inputs;
- a chunk's own state is one product of its decayed inputs with B;
- the state a chunk starts from is the decayed sum of the states of the
  chunks before it: ``chunks x chunks`` decays, in float32 whatever the
  compute type, because it is the one quantity that crosses the whole
  sequence;
- what that state adds to ``y_t`` is one product with C, decayed to t.

The products take the operands' type (bfloat16 in a bfloat16 model, on
the MXU) and accumulate in float32; ``dt``, the running decays and the
state between chunks are float32. All of the scan is plain
``jax.numpy``, so its backward is jax's own. Two
``jax.named_scope``s split it for the trace: ``ssm_intra`` (the running
decays, ``C . B``, the decay mask, its product with the inputs)
and ``ssm_state`` (each chunk's state, its passage from chunk to chunk,
the entering state read by ``C``).
``ssd_recurrence`` is the recurrence as written above, one position at a
time: the oracle of the tests.

A length the chunk does not divide is padded with positions of ``dt = 0``
(no decay, no input), whose outputs are cut off again.
"""
from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..observability.trace import say_once
from . import flash

logger = logging.getLogger(__name__)

HIGHEST = jax.lax.Precision.HIGHEST


def _conv_scoped(f):
    """The scope the trace reads the convolution by; on the rule's own
    bodies, because a backward rule is traced outside its caller's."""
    return jax.named_scope("ssm_scan")(jax.named_scope("ssm_conv")(f))


def _shifted(z, lo: int, hi: int):
    """``[z[t + s] for s in range(lo, hi)]`` along axis 1 in ``z``'s type,
    zeros outside the sequence: nothing crosses from one row of the batch
    to the next."""
    t, before = z.shape[1], max(-lo, 0)
    padded = jnp.pad(z, ((0, 0), (before, max(hi - 1, 0)), (0, 0)))
    return [padded[:, before + s:before + s + t] for s in range(lo, hi)]


def _dsilu(pre):
    sig = jax.nn.sigmoid(pre)
    return sig * (1.0 + pre * (1.0 - sig))


def _conv_pre(xbc, taps, bias):
    """The ``k`` shifts of the input, taken on its own type, and the
    float32 pre-activation: widened inside the one fusion a compiler
    makes of it."""
    xs = _shifted(xbc, 1 - taps.shape[0], 1)
    pre = sum(taps[i] * x.astype(jnp.float32) for i, x in enumerate(xs))
    return xs, pre if bias is None else bias + pre


def _conv_bwd_xla(xbc, taps, bias, dy):
    k = taps.shape[0]
    xs, pre = _conv_pre(xbc, taps, bias)
    dpre = dy.astype(jnp.float32) * _dsilu(pre)
    # tap i multiplied position t - (k - 1) + i into pre[t]
    dx = sum(taps[i] * d for i, d in enumerate(reversed(_shifted(dpre, 0, k))))
    dtaps = jnp.stack([(dpre * x.astype(jnp.float32)).sum(axis=(0, 1))
                       for x in xs])
    return dx.astype(xbc.dtype), dtaps, (
        None if bias is None else dpre.sum(axis=(0, 1)))


# -- the backward as a kernel: one pass over the input and the cotangent ----
#
# Channels on the sublanes, positions on the lanes: the order both hybrid
# steps keep the projection in (the scan's products want positions minor),
# so ``swapaxes`` around the call moves nothing. A grid step holds ``lanes``
# positions of ``rows`` channels of one row of the batch and reads beside
# them the 128 positions before and after, the few a tap reaches across
# the block's edge. Everything between the loads and the store of ``dx``
# is float32 values of the block; the five sums of a channel add up over
# the positions' blocks in one resident tile, a lane each.

_EDGE = 128     # positions read beside a block: one tile's lanes


def _cols_from(main, edge, s: int):
    """``out[:, t] = main[:, t - s]``; the columns that leaves open come
    from ``edge [rows, 128]``: its last ``s`` lead (``s > 0``), its first
    ``-s`` trail (``s < 0``)."""
    if s == 0:
        return main
    n = main.shape[1]
    rolled = pltpu.roll(main, s % n, 1)
    patch = pltpu.roll(edge, s % _EDGE, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, edge.shape, 1)
    if s > 0:
        parts = [jnp.where(col < s, patch, rolled[:, :_EDGE]),
                 rolled[:, _EDGE:]]
    else:
        parts = [rolled[:, :n - _EDGE],
                 jnp.where(col >= _EDGE + s, patch, rolled[:, n - _EDGE:])]
    return jnp.concatenate([p for p in parts if p.shape[1]], axis=1)


def _taps_over(x, before, taps, bias):
    """(the ``k`` shifts of ``x`` behind ``before``, the pre-activation)."""
    k = taps.shape[1]
    xs = [_cols_from(x, before, k - 1 - i) for i in range(k)]
    pre = bias
    for i in range(k):
        pre = pre + taps[:, i:i + 1] * xs[i]
    return xs, pre


def _conv_bwd_kernel(x_ref, before_ref, after_ref, dy_ref, dy_after_ref,
                     taps_ref, bias_ref, dx_ref, sums_ref):
    f32, k = jnp.float32, taps_ref.shape[1]
    taps, bias = taps_ref[...], bias_ref[...]
    x = x_ref[0].astype(f32)
    n = x.shape[1]
    first = pl.program_id(2) == 0
    last = pl.program_id(2) == pl.num_programs(2) - 1
    # each row of the batch starts from zeros
    xs, pre = _taps_over(
        x, jnp.where(first, 0.0, before_ref[0].astype(f32)), taps, bias)
    dpre = dy_ref[0].astype(f32) * _dsilu(pre)
    # the positions after the block read its last ones; past the row's
    # end nothing flows back
    _, pre_after = _taps_over(after_ref[0].astype(f32), x[:, n - _EDGE:],
                              taps, bias)
    dpre_after = jnp.where(last, 0.0, dy_after_ref[0].astype(f32)) \
        * _dsilu(pre_after)
    # tap i multiplied position t - (k - 1) + i into pre[t]
    dx = taps[:, k - 1:k] * dpre
    for i in range(k - 1):
        dx = dx + taps[:, i:i + 1] * _cols_from(dpre, dpre_after,
                                                i - (k - 1))
    dx_ref[0] = dx.astype(dx_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, sums_ref.shape[1:], 1)
    tile = jnp.where(lane == k, jnp.sum(dpre, axis=1, keepdims=True), 0.0)
    for i in range(k):
        tile = jnp.where(
            lane == i, jnp.sum(dpre * xs[i], axis=1, keepdims=True), tile)

    @pl.when(first)
    def _():
        sums_ref[0] = tile

    @pl.when(jnp.logical_not(first))
    def _():
        sums_ref[0] += tile


def conv_blocks(t: int, c: int, itemsize: int):
    """(channels, positions) of a grid step over ``[.., t, c]`` of
    ``itemsize`` bytes an entry: one tile of 128 channels (fewer, in whole
    sublane tiles of 16, where the width has fewer), and as many positions
    as keep the step's float32 values (a dozen of the block's size) within
    the kernel's fast memory, in whole tiles of 128 lanes and no more than
    the length holds. Long blocks win: a block remakes the pre-activation
    of the 128 positions after it (PERF.md section 5 has the v5e's
    timings of three blocks, PR 39)."""
    rows = min(128, -(-c // 16) * 16)
    lanes = (3 << 19) // (rows * (4 + itemsize))
    return rows, min(lanes, -(-t // _EDGE) * _EDGE) // _EDGE * _EDGE


def _conv_bwd_pallas(zxd, start: int, taps, bias, dy,
                     interpret: bool = False):
    """``(dx, dtaps, dbias)`` for the channels ``[start, start + C)`` of
    ``zxd [B, T, W]``, read where they lie when ``start`` and the shapes
    are whole blocks; else cut out and padded to them first: zeros behind
    the last position and past the last channel change nothing kept."""
    b, t, _ = zxd.shape
    k, c = taps.shape
    rows, lanes = conv_blocks(t, c, zxd.dtype.itemsize)
    pad_t, pad_c = -t % lanes, -c % rows
    if pad_t or pad_c or start % rows:
        zxd, start = jnp.pad(zxd[..., start:start + c],
                             ((0, 0), (0, pad_t), (0, pad_c))), 0
        dy = jnp.pad(dy, ((0, 0), (0, pad_t), (0, pad_c)))
        taps, bias = (jnp.pad(z, ((0, 0),) * (z.ndim - 1) + ((0, pad_c),))
                      for z in (taps, bias))
    blocks, tiles, skip = (t + pad_t) // lanes, lanes // _EDGE, start // rows

    def spec(width, position, offset=0):
        return pl.BlockSpec((1, rows, width),
                            lambda i, j, n: (i, j + offset, position(n)))

    def here(n):
        return n

    def before(n):
        return jnp.maximum(n * tiles - 1, 0)

    def after(n):
        return jnp.minimum((n + 1) * tiles, blocks * tiles - 1)

    dx, sums = pl.pallas_call(
        _conv_bwd_kernel, grid=(b, (c + pad_c) // rows, blocks),
        in_specs=[spec(lanes, here, skip), spec(_EDGE, before, skip),
                  spec(_EDGE, after, skip), spec(lanes, here),
                  spec(_EDGE, after),
                  pl.BlockSpec((rows, k), lambda i, j, n: (j, 0)),
                  pl.BlockSpec((rows, 1), lambda i, j, n: (j, 0))],
        out_specs=[spec(lanes, here), spec(_EDGE, lambda n: 0)],
        out_shape=[jax.ShapeDtypeStruct((b, c + pad_c, t + pad_t), zxd.dtype),
                   jax.ShapeDtypeStruct((b, c + pad_c, _EDGE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssm_conv_bwd", interpret=interpret,
    )(*(zxd.swapaxes(1, 2),) * 3, *(dy.swapaxes(1, 2),) * 2,
      taps.T, bias[:, None])
    sums = sums.sum(axis=0)[:c]
    return dx.swapaxes(1, 2)[:, :t, :c], sums[:, :k].T, sums[:, k]


def _say_conv(b, t, c, k, size):
    kernel = flash._on_tpu()
    rows, lanes = conv_blocks(t, c, size) if kernel else (c, b * t)
    say_once(
        logger, "ssm/conv",
        dict(taps=k, channels=c, positions=b * t, block_channels=rows,
             block_positions=lanes,
             backward="kernel" if kernel else "xla fusions",
             forward_bytes=2 * b * t * c * size,
             backward_bytes=b * t * c * (3 * size + (0 if kernel else 8))),
        "ssm/conv: %(taps)d taps over %(channels)d channels at "
        "%(positions)d positions; the forward one fusion that reads and "
        "writes %(forward_bytes)d bytes, the backward as %(backward)s "
        "over blocks of %(block_channels)d channels by %(block_positions)d "
        "positions, %(backward_bytes)d bytes")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
@_conv_scoped
def causal_conv_silu(zxd, taps, bias, start: int = 0):
    """``silu(bias + sum_i taps[i] * x[t - (k - 1) + i])`` a channel, with
    ``x`` the channels ``[start, start + C)`` of ``zxd [B, T, W]``: the
    depthwise causal convolution with ``taps [k, C]`` and ``bias [C]``
    (both float32; ``bias`` ``None``: a convolution without one, which
    then has no gradient for it either), each row of the batch from
    zeros, in ``zxd``'s type with float32 inside.

    The forward is one fusion over the input as it lies (the shifts are
    taken on its own type). The backward rule keeps ``zxd``, ``taps`` and
    ``bias`` and no pre-activation: it remakes it, and on the TPU it is
    one kernel that reads ``zxd`` and the cotangent once and writes the
    input's gradient once; elsewhere plain ``jax.numpy``. One device's
    call: ``sharded_conv_silu`` is the entrance under a mesh."""
    b, t, _ = zxd.shape
    k, c = taps.shape
    _say_conv(b, t, c, k, zxd.dtype.itemsize)
    pre = _conv_pre(zxd[..., start:start + c], taps, bias)[1]
    return (pre * jax.nn.sigmoid(pre)).astype(zxd.dtype)


def _conv_silu_fwd(zxd, taps, bias, start):
    return causal_conv_silu.fun(zxd, taps, bias, start), (zxd, taps, bias)


@_conv_scoped
def _conv_silu_bwd(start, kept, dy):
    zxd, taps, bias = kept
    c = taps.shape[1]
    if flash._on_tpu():
        # the kernel adds a bias: zeros made here are no leaf of a model
        dx, dtaps, dbias = _conv_bwd_pallas(
            zxd, start, taps, jnp.zeros((c,), jnp.float32)
            if bias is None else bias, dy)
        dbias = None if bias is None else dbias
    else:
        dx, dtaps, dbias = _conv_bwd_xla(zxd[..., start:start + c], taps,
                                         bias, dy)
    # beside the channels it read, the function's input got nothing back
    return jnp.pad(dx, ((0, 0), (0, 0), (
        start, zxd.shape[2] - start - c))), dtaps, dbias


causal_conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


def causal_conv(x, taps):
    """``sum_i taps[i] * x[t - (k - 1) + i]`` a channel of ``x [B, T, C]``:
    the depthwise causal convolution with ``taps [k, C]`` (float32) alone,
    no bias and no activation (a gated short convolution's, linear
    between its two gates: models/mixers.ShortConvMixer), each row of
    the batch from zeros, in ``x``'s type with float32 inside. Plain
    ``jax.numpy``, jax's own backward, no kernel; the caller scopes it."""
    return _conv_pre(x, taps, None)[1].astype(x.dtype)


def sharded_conv_silu(zxd, taps, bias, start: int, mesh):
    """``causal_conv_silu`` under a mesh. The TPU's compiler will not
    partition a kernel by itself, so on several devices the call runs
    inside ``shard_map``: the batch over the data axes (whole where they
    do not divide it: an init probe), the channels, ``taps`` and ``bias``
    whole on every device, whose gradients ``shard_map`` sums. A
    convolution of one row of the batch reads no other, so the body needs
    no collective. Every axis other than the data axes has to hold the
    operands replicated, as the partition rules of both stacks do today:
    ``in_specs`` names the data axes alone, so a mixer sharded over a
    tensor or a sequence axis would be gathered whole onto every device
    here, and has to give this call specs of its own first. One device,
    or no mesh, calls the function directly."""
    if mesh is None or mesh.size == 1:
        return causal_conv_silu(zxd, taps, bias, start)
    over = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
    if zxd.shape[0] % math.prod(mesh.shape[a] for a in over):
        over = ()
    rows = P(over or None, None, None)
    whole = (taps,) if bias is None else (taps, bias)
    return shard_map(
        lambda z, w, b=None: causal_conv_silu(z, w, b, start), mesh=mesh,
        in_specs=(rows,) + (P(),) * len(whole), out_specs=rows,
        check_vma=False,
    )(zxd, *whole)


def _segment_decays(a):
    """``a [..., L]`` -> ``[..., L, L]``: entry ``(i, j)`` is
    ``exp(a[j+1] + ... + a[i])`` for ``j <= i`` and 0 above the diagonal."""
    n = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    seen = jnp.tril(jnp.ones((n, n), bool))
    return jnp.exp(jnp.where(seen, seg, -jnp.inf))


def ssd_scan(x, dt, a, b, c, d, chunk: int):
    """``x [B, T, H, P]``, ``dt [B, T, H]`` (positive, float32), ``a [H]``
    (negative, float32), ``b``, ``c [B, T, G, N]``, ``d [H]``; returns
    ``y [B, T, H, P]`` in ``x``'s type."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    dtype = x.dtype
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))
                       for z in (x, dt, b, c))
    nc = (t + pad) // chunk

    def chunks(z):
        return z.reshape(bsz, nc, chunk, *z.shape[2:])

    dt = dt.astype(jnp.float32)
    xc = chunks(x).reshape(bsz, nc, chunk, g, r, p)
    dtc = chunks(dt).reshape(bsz, nc, chunk, g, r)
    bc, cc = chunks(b), chunks(c)

    # inside the chunks
    with jax.named_scope("ssm_intra"):
        # running decay inside each chunk, [B, nc, G, R, L]
        da = jnp.moveaxis(dtc * a.astype(jnp.float32).reshape(g, r), 2, -1)
        cs = jnp.cumsum(da, axis=-1)
        xdt = xc.astype(jnp.float32) * dtc[..., None]      # dt_s * x_s
        cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc,
                        preferred_element_type=jnp.float32)
        mix = (cb[:, :, :, None] * _segment_decays(da)).astype(dtype)
        y = jnp.einsum("bcgrls,bcsgrp->bclgrp", mix, xdt.astype(dtype),
                       preferred_element_type=jnp.float32)

    with jax.named_scope("ssm_state"):
        # each chunk's own state, decayed to the chunk's end:
        # [B, nc, G, R, P, N]
        to_end = jnp.exp(cs[..., -1:] - cs)                # [B, nc, G, R, L]
        decayed = (xdt * jnp.moveaxis(to_end, -1, 2)[..., None]
                   ).astype(dtype)
        states = jnp.einsum("bcsgn,bcsgrp->bcgrpn", bc, decayed,
                            preferred_element_type=jnp.float32)

        # the state each chunk starts from: float32 across the sequence
        total = jnp.moveaxis(cs[..., -1], 1, -1)           # [B, G, R, nc]
        across = _segment_decays(jnp.pad(total, ((0, 0),) * 3 + ((1, 0),)))
        entering = jnp.einsum("bgrzc,bcgrpn->bzgrpn", across[..., :-1, 1:],
                              states, precision=HIGHEST)
        # row z of `across[..., :-1, 1:]` holds, for every chunk c < z, the
        # decay from c's end to z's start; row 0 is empty: no state enters

        from_start = jnp.moveaxis(jnp.exp(cs), -1, 2)      # [B, nc, L, G, R]
        y = y + from_start[..., None] * jnp.einsum(
            "bclgn,bcgrpn->bclgrp", cc, entering.astype(dtype),
            preferred_element_type=jnp.float32)
    y = y + d.astype(jnp.float32).reshape(g, r)[..., None] \
        * xc.astype(jnp.float32)
    return y.reshape(bsz, t + pad, h, p)[:, :t].astype(dtype)


def ssd_recurrence(x, dt, a, b, c, d):
    """The recurrence of the module docstring, position by position, in
    float32. Same arguments as ``ssd_scan`` without the chunk."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    heads_of = jnp.arange(h) // (h // g)
    f32 = jnp.float32
    x, dt, b, c = (z.astype(f32) for z in (x, dt, b, c))

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = b_t[:, heads_of], c_t[:, heads_of]       # [B, H, N]
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HIGHEST)
        return state, y_t + d[:, None] * x_t

    first = jnp.zeros((bsz, h, p, n), f32)
    _, y = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(z, 1, 0) for z in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)
