"""What a layer of a pattern-built stack (models/hybrid.py) can mix with
and no older module holds: the Mamba-2 state-space mixer (Nemotron-H,
arXiv:2504.03624; ``granitemoehybrid``), the gated delta rule (Kimi
Delta Attention, arXiv:2510.26692) and the gated short convolution
(LFM2, ``lfm2_moe``). Attention is models/llama.py's, the experts
models/moe.py's.

Beside each mixer stands the function that says what a block with it
tells the checkpoint policy (models/remat_policy.py): the names the
mixer makes, each with its width in features a token of the compute
type, and the scratch its scan makes and no name can keep. A name's
width is stated here and nowhere else.

The head counts are what THIS chip holds: a chip's share of a layer is
the same mixer with fewer heads, and its last projection's result is one
summand of the mixer's output (for ``Mamba2Mixer``: with one group a
chip; one group over several chips has no exact share, ``B``, ``C`` and
the gated norm's mean square span every channel).
"""
from __future__ import annotations

import logging
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..observability.trace import say_once
from ..ops.linear_attention import SUB_CHUNK, kda_chunked
from ..ops.ssm import causal_conv, sharded_conv_silu, ssd_scan
from .llama import _dense_init
from .moe import sow_counter

logger = logging.getLogger(__name__)

L2_EPS = 1e-6


def _step_bias_init(lo: float = 0.001, hi: float = 0.1):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in
    ``[lo, hi]``, the families' ``time_step_min`` and ``time_step_max``."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        return dt + jnp.log(-jnp.expm1(-dt))
    return init


def _decay_init(key, shape, dtype=jnp.float32):
    """``A_log`` with ``A = -exp(A_log)`` uniform in ``[-16, -1]``."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2Mixer(nn.Module):
    """``[z, xBC, dt] = in_proj(u)`` of widths ``d_in``, ``d_in + 2 G N``
    and ``H`` (``d_in = H P``); ``xBC = silu(conv1d(xBC))``, depthwise,
    causal, ``conv`` taps, with bias, split into ``x [T, H, P]``,
    ``B``, ``C [T, G, N]``; ``dt = softplus(dt + dt_bias)``,
    ``A = -exp(A_log)``; the scan of ops/ssm.py with skip ``D``; then
    ``y = RMSNorm(y * silu(z)) * w`` with the mean square over each
    group's ``d_in / G`` channels, and ``out_proj``.

    For the trace: ``ssm_proj`` holds ``in_proj``, the gated norm and
    ``out_proj``; ``ssm_scan`` everything between (``ssm_conv``, and
    inside ops/ssm.py ``ssm_intra`` and ``ssm_state``)."""
    d_model: int
    n_head: int
    head_dim: int
    n_group: int
    state: int
    conv: int
    chunk: int
    rms_eps: float
    dtype: Any
    mesh: Optional[Any] = None

    @nn.compact
    def __call__(self, u):
        b, t, _ = u.shape
        h, p, g, n = self.n_head, self.head_dim, self.n_group, self.state
        d_in, d_bc = h * p, 2 * g * n
        f32 = jnp.float32
        dense = lambda width, name: nn.Dense(            # noqa: E731
            width, use_bias=False, dtype=self.dtype,
            kernel_init=_dense_init(), name=name)
        chunks = -(-t // self.chunk)
        say_once(
            logger, "ssm/chunks",
            dict(chunk=self.chunk, chunks=chunks, heads=h, groups=g,
                 mask_bytes=b * chunks * h * self.chunk ** 2 * 4),
            "ssm/chunks: %(chunks)d chunks of %(chunk)d positions a row, "
            "%(heads)d heads in %(groups)d group(s); one layer's float32 "
            "decay mask is %(mask_bytes)d bytes")
        with jax.named_scope("ssm_proj"):
            zxd = checkpoint_name(
                dense(2 * d_in + d_bc + h, "in_proj")(u), "ssm_in_proj")
        z, _, dt = jnp.split(zxd, [d_in, 2 * d_in + d_bc], axis=-1)
        taps = self.param("conv_kernel", _dense_init(),
                          (self.conv, d_in + d_bc), f32)
        bias = self.param("conv_bias", nn.initializers.zeros,
                          (d_in + d_bc,), f32)
        # scoped as ssm_conv, and read where it lies in the kept projection
        xbc = sharded_conv_silu(zxd, taps, bias, d_in, self.mesh)
        with jax.named_scope("ssm_scan"):
            x, bm, cm = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
            dt = jax.nn.softplus(dt.astype(f32) + self.param(
                "dt_bias", _step_bias_init(), (h,), f32))
            a = -jnp.exp(self.param("A_log", _decay_init, (h,), f32))
            skip = self.param("D", nn.initializers.ones, (h,), f32)
            y = ssd_scan(x.reshape(b, t, h, p), dt, a,
                         bm.reshape(b, t, g, n), cm.reshape(b, t, g, n),
                         skip, self.chunk)
        with jax.named_scope("ssm_proj"):
            gated = (y.reshape(b, t, g, d_in // g).astype(f32)
                     * nn.silu(z.astype(f32)).reshape(b, t, g, d_in // g))
            gated = gated * jax.lax.rsqrt(
                jnp.mean(gated * gated, axis=-1, keepdims=True)
                + self.rms_eps)
            w = self.param("norm_weight", nn.initializers.ones, (d_in,), f32)
            y = (gated.reshape(b, t, d_in) * w).astype(self.dtype)
            return dense(self.d_model, "out_proj")(y)


def mamba_block_sizes(n_head: int, head_dim: int, n_group: int, state: int,
                      chunk: int, itemsize: int) -> Tuple[dict, int]:
    """What a block with a ``Mamba2Mixer`` tells models/remat_policy.py, in
    features a token of the compute type: the name the mixer makes
    (``ssm_in_proj``), and the scan's scratch, the float32 decay mask
    ``[chunks, heads, chunk, chunk]`` and its product with ``C . B`` in the
    compute type (``heads x chunk`` entries a token each)."""
    widths = {"ssm_in_proj": 2 * n_head * head_dim + n_head
              + 2 * n_group * state}
    return widths, n_head * chunk * (4 + itemsize) // itemsize


class KdaMixer(nn.Module):
    """Kimi Delta Attention on ``u [B, T, d_model]``, ``H`` heads of ``P``:

    ``q', k', v = silu(conv(u W))``, three depthwise causal convolutions of
    ``conv`` taps without bias; a head's ``q = q' / |q'| * P ** -0.5``,
    ``k = k' / |k'|`` (the root over the sum of squares plus 1e-6); log
    decay a key channel ``g = -exp(A_log_h) * softplus(u Wf1 Wf2 +
    dt_bias)``; ``beta = 2 sigmoid(u w)``, in (0, 2): the delta rule may
    flip a key's component; the scan of ops/linear_attention.py; then
    ``y = RMSNorm_head(o) * sigmoid(u Wg1 Wg2 + b_g)`` with one norm
    weight ``[P]`` for all heads, and ``o_proj``. Heads are independent
    (the low-rank gates are cut by head in their second matrix).

    For the trace: ``kda_proj`` holds every projection, the normalisation
    of ``q`` and ``k``, the gates, the head norm and ``o_proj``;
    ``kda_scan`` the scan (inside it ``kda_intra`` and ``kda_state``); the
    three convolutions lie under ops/ssm.py's ``ssm_conv``. Counters of
    the step, sown under ``counters``: ``kda_chunk_log_decay_mean``, the
    mean over chunks, heads and channels of a chunk's summed ``g`` (how
    near the weights come to where the sub-chunks are needed: -88), and
    ``kda_beta_mean``, each ``1 / n_layers`` of it so that the layers' sum
    is their mean."""
    d_model: int
    n_head: int
    head_dim: int
    conv: int
    chunk: int
    rank: int
    rms_eps: float
    dtype: Any
    mesh: Optional[Any] = None
    n_layers: int = 1               # KDA layers in the model (counters)

    @nn.compact
    def __call__(self, u):
        b, t, _ = u.shape
        h, p = self.n_head, self.head_dim
        f32 = jnp.float32

        def dense(width, name, bias=False):
            return nn.Dense(width, use_bias=bias, dtype=self.dtype,
                            kernel_init=_dense_init(), name=name)

        def conved(name):
            with jax.named_scope("kda_proj"):
                z = checkpoint_name(dense(h * p, f"{name}_proj")(u),
                                    "kda_in_proj")
            taps = self.param(f"{name}_conv", _dense_init(),
                              (self.conv, h * p), f32)
            # scoped as ssm_conv by the function itself
            return sharded_conv_silu(z, taps, None, 0, self.mesh
                                     ).reshape(b, t, h, p)

        q, k, v = conved("q"), conved("k"), conved("v")
        with jax.named_scope("kda_proj"):
            def unit(z, scale=1.0):
                zf = z.astype(f32)
                return (zf * (scale * jax.lax.rsqrt(
                    jnp.sum(zf * zf, axis=-1, keepdims=True) + L2_EPS))
                ).astype(self.dtype)

            q, k = unit(q, p ** -0.5), unit(k)
            decay = dense(h * p, "f_b_proj")(dense(self.rank, "f_a_proj")(u))
            step = jax.nn.softplus(decay.astype(f32) + self.param(
                "dt_bias", _step_bias_init(), (h * p,), f32))
            rate = jnp.exp(self.param("A_log", _decay_init, (h,), f32))
            g = -step.reshape(b, t, h, p) * rate[:, None]
            beta = 2.0 * jax.nn.sigmoid(dense(h, "b_proj")(u).astype(f32))
            self._count("kda_chunk_log_decay_mean",
                        jnp.mean(g) * min(self.chunk, t))
            self._count("kda_beta_mean", jnp.mean(beta))
        o = kda_chunked(q, k, v, g, beta, self.chunk)
        with jax.named_scope("kda_proj"):
            gate = dense(h * p, "g_b_proj", bias=True)(
                dense(self.rank, "g_a_proj")(u))
            w = self.param("o_norm", nn.initializers.ones, (p,), f32)
            of = o.astype(f32)
            of = of * jax.lax.rsqrt(
                jnp.mean(of * of, axis=-1, keepdims=True) + self.rms_eps) * w
            y = (of.reshape(b, t, h * p)
                 * jax.nn.sigmoid(gate.astype(f32))).astype(self.dtype)
            return checkpoint_name(dense(self.d_model, "o_proj")(y),
                                   "kda_out_proj")

    def _count(self, name, value):
        sow_counter(self, name, value / self.n_layers)


def kda_block_sizes(d_model: int, n_head: int, head_dim: int,
                    itemsize: int) -> Tuple[dict, int]:
    """What a block with a ``KdaMixer`` tells models/remat_policy.py, in
    features a token of the compute type: the names the mixer makes (the
    three projections in front of their convolutions, ``kda_in_proj``,
    and ``o_proj``'s result, ``kda_out_proj``), and the scan's scratch,
    the float32 pairwise decays of its sub-chunks: ``heads x sub-chunk x
    head size`` entries a token."""
    width = n_head * head_dim
    widths = {"kda_in_proj": 3 * width, "kda_out_proj": d_model}
    return widths, SUB_CHUNK * width * 4 // itemsize


class ShortConvMixer(nn.Module):
    """The gated short convolution of LFM2 (``Lfm2ShortConv``) on
    ``h [B, T, d_model]``: ``[B, C, z] = split3(in_proj(h))``, each
    ``d_model`` wide and in that order; ``u = B * z``; ``c = conv(u)``,
    depthwise, causal, ``taps`` taps (tap i multiplies position
    ``t - (taps - 1) + i``), from zeros, no bias and NO activation (the
    convolution is linear between its two gates); ``out_proj(C * c)``.
    Both gates and the convolution are float32 values of one bfloat16
    projection, rounded once in front of ``out_proj``.

    Every channel goes its own way between the two projections, so a
    chip's share by channels would be exact; the cells hold it whole.

    For the trace: ``short_conv_proj`` holds ``in_proj`` and
    ``out_proj``, ``short_conv`` both gates and the convolution;
    ``conv/short`` is its line and span."""
    d_model: int
    taps: int
    dtype: Any

    @nn.compact
    def __call__(self, h):
        b, t, d = h.shape
        f32 = jnp.float32
        size = jnp.dtype(self.dtype).itemsize
        dense = lambda width, name: nn.Dense(            # noqa: E731
            width, use_bias=False, dtype=self.dtype,
            kernel_init=_dense_init(), name=name)
        say_once(
            logger, "conv/short",
            dict(taps=self.taps, channels=d, positions=b * t,
                 read_bytes=3 * b * t * d * size,
                 written_bytes=b * t * d * size),
            "conv/short: %(taps)d taps over %(channels)d channels at "
            "%(positions)d positions, linear between two gates: it reads "
            "the projection's %(read_bytes)d bytes and writes "
            "%(written_bytes)d")
        with jax.named_scope("short_conv_proj"):
            bcz = checkpoint_name(dense(3 * d, "in_proj")(h), "conv_in_proj")
        taps = self.param("conv_kernel", _dense_init(), (self.taps, d), f32)
        with jax.named_scope("short_conv"):
            gate_in, gate_out, z = (m.astype(f32)
                                    for m in jnp.split(bcz, 3, axis=-1))
            y = (gate_out * causal_conv(gate_in * z, taps)).astype(self.dtype)
        with jax.named_scope("short_conv_proj"):
            return checkpoint_name(dense(d, "out_proj")(y), "conv_out_proj")


def short_conv_block_sizes(d_model: int, itemsize: int) -> Tuple[dict, int]:
    """What a block with a ``ShortConvMixer`` tells models/remat_policy.py,
    in features a token of the compute type: the names the mixer makes
    (``conv_in_proj``, three gates wide, and ``out_proj``'s result,
    ``conv_out_proj``), and its scratch, the gated input and the
    convolution's result in float32."""
    widths = {"conv_in_proj": 3 * d_model, "conv_out_proj": d_model}
    return widths, 2 * d_model * 4 // itemsize
