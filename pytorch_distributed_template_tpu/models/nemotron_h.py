"""Hybrid stacks in which every layer is ONE mixer behind a pre-norm, its
kind given by a pattern string (the ``nemotron_h`` family: Nemotron-H,
arXiv:2504.03624; NVIDIA-Nemotron-3-Super-120B-A12B ``config.json``).

``x <- x + mixer(RMSNorm(x))`` for each character of ``pattern``:

- ``M``, a Mamba-2 state-space mixer (``Mamba2Mixer``; the scan is
  ops/ssm.py, in chunks of ``ssm_chunk``);
- ``E``, a latent mixture of experts (models/moe.ExpertLayer: sigmoid
  router with a selection bias, ``moe_top_k`` of ``moe_n_routed`` experts a
  token, the experts in a ``moe_latent``-wide latent, one shared expert);
- ``*``, grouped-query attention without rotation (``LlamaAttention`` with
  ``rope_base`` 0: the family states no position embedding, order is
  carried by the state-space layers), causal over all earlier keys.

A final RMSNorm; untied embedding and head (the fused head and loss of
the Llama family). No biases but the convolution's.

The head counts are what THIS chip holds, so a chip's share of a layer is
the same model with fewer heads: ``ssm_n_head`` heads of ``ssm_head_dim``
in ``ssm_n_group`` groups (one group a chip makes the share exact: its
``out_proj`` result is one summand of the mixer's output), ``n_head`` query
heads on ``n_kv_head`` key-value heads of ``head_dim``, and ``moe_held``
``(offset, count)`` of the routed experts. The layer runs without its
exchange; nothing here stands in for absent chips.

A family whose layer is a mixer AND a gated MLP, each behind its own
norm, with scaled residuals and a tied scaled head (``granitemoehybrid``)
is the sibling stack, models/granite_hybrid.py, which imports
``Mamba2Mixer`` from here.

Training only: a decode path needs the scan's state beside the attention
layers' pages (ROADMAP R5), and there is none yet.
"""
from __future__ import annotations

import logging
import math
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..config.registry import MODELS
from ..observability.trace import say_once
from ..ops.ssm import sharded_conv_silu, ssd_scan
from .llama import LlamaAttention, RMSNorm, _HeadKernel, _dense_init
from .moe import ExpertLayer
from .remat_policy import BlockKind, block_policy

logger = logging.getLogger(__name__)

KINDS = "ME*"


def _step_bias_init(lo: float = 0.001, hi: float = 0.1):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in
    ``[lo, hi]``, the family's ``time_step_min`` and ``time_step_max``."""
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


class LayerSizes(NamedTuple):
    """The model's fields a layer reads (a module cannot hold its parent)."""
    d_model: int
    n_head: int
    n_kv_head: int
    head_dim: int
    ssm_n_head: int
    ssm_head_dim: int
    ssm_n_group: int
    ssm_state: int
    ssm_conv: int
    ssm_chunk: int
    moe_n_routed: int
    moe_held: Tuple[int, int]
    moe_top_k: int
    moe_latent: int
    moe_d_ff: int
    moe_shared_d_ff: int
    moe_scale: float
    rms_eps: float
    dtype: Any
    attn_impl: str
    mesh: Optional[Any]
    n_expert_layers: int


class HybridLayer(nn.Module):
    """One mixer of kind ``kind`` behind its pre-norm, added to the
    residual stream."""
    kind: str
    cfg: LayerSizes

    @nn.compact
    def __call__(self, x, positions, train: bool):
        c = self.cfg
        h = RMSNorm(c.rms_eps, name="norm")(x)
        if self.kind == "M":
            y = Mamba2Mixer(
                c.d_model, c.ssm_n_head, c.ssm_head_dim, c.ssm_n_group,
                c.ssm_state, c.ssm_conv, c.ssm_chunk, c.rms_eps, c.dtype,
                c.mesh, name="mixer")(h)
        elif self.kind == "E":
            y = ExpertLayer(
                d_model=c.d_model, d_ff=c.moe_d_ff, n_routed=c.moe_n_routed,
                top_k=c.moe_top_k, held=tuple(c.moe_held),
                latent=c.moe_latent, shared_d_ff=c.moe_shared_d_ff,
                router="sigmoid", selection_bias=True, scale=c.moe_scale,
                n_layers=c.n_expert_layers, dtype=c.dtype,
                name="mixer")(h)
        else:
            y = LlamaAttention(
                c.d_model, c.n_head, c.n_kv_head, c.dtype, c.attn_impl,
                c.mesh, rope_base=0.0, head_dim=c.head_dim,
                name="mixer")(h, positions, train)
        return x + y


class NemotronHLM(nn.Module):
    """Decoder-only hybrid causal LM; see the module docstring."""
    vocab_size: int = 131072
    pattern: str = "EMEMEMEMEM*"
    d_model: int = 4096
    # attention ('*')
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    # Mamba-2 ('M')
    ssm_n_head: int = 128
    ssm_head_dim: int = 64
    ssm_n_group: int = 8
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # latent mixture of experts ('E')
    moe_n_routed: int = 512
    moe_held: Tuple[int, int] = (0, 0)      # (offset, count); count 0: all
    moe_top_k: int = 22
    moe_latent: int = 1024
    moe_d_ff: int = 2688
    moe_shared_d_ff: int = 5376
    moe_scale: float = 5.0
    # what a step moves each selection bias by, against its expert's load
    # (engine/steps.selection_bias_step); 0: the biases stay
    selection_bias_rate: float = 0.0
    rms_eps: float = 1e-5
    max_len: int = 262144
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Optional[Any] = None
    remat: bool = False             # recompute what does not fit
    fused_head: bool = False        # return (hidden, head_w) for chunked loss

    # what the expert layers count a step (engine/steps.py carries them
    # in the step's metrics, the trainer writes them to the flight record)
    step_counters = ("moe_pairs_here", "moe_load_max_over_mean",
                     "moe_tokens_unserved")

    @nn.compact
    def __call__(self, tokens, train: bool = False, decode: bool = False):
        if decode:
            raise NotImplementedError(
                "NemotronH has no decode path: the scan's state would have "
                "to live beside the attention layers' cache")
        if not self.pattern or set(self.pattern) - set(KINDS):
            raise ValueError(
                f"pattern {self.pattern!r}: one of {KINDS!r} a layer, each "
                "ONE mixer behind a pre-norm (a stack whose every layer is "
                "a mixer and a gated MLP is models/granite_hybrid.py)")
        for heads, groups, what in (
                (self.n_head, self.n_kv_head, "n_head over n_kv_head"),
                (self.ssm_n_head, self.ssm_n_group,
                 "ssm_n_head over ssm_n_group")):
            if heads % groups:
                raise ValueError(f"{what}: {heads} not divisible by {groups}")
        b, t = tokens.shape
        held = self.moe_held[1] or self.moe_n_routed
        say_once(
            logger, "model/pattern",
            dict(pattern=self.pattern, layers=len(self.pattern),
                 ssm_heads=self.ssm_n_head, ssm_head_dim=self.ssm_head_dim,
                 ssm_groups=self.ssm_n_group, ssm_state=self.ssm_state,
                 ssm_chunk=self.ssm_chunk, heads=self.n_head,
                 kv_heads=self.n_kv_head, head_dim=self.head_dim, held=held,
                 routed=self.moe_n_routed, first=self.moe_held[0],
                 top_k=self.moe_top_k, latent=self.moe_latent),
            "model/pattern: %(pattern)s (%(layers)d layers); M: %(ssm_heads)d "
            "heads of %(ssm_head_dim)d in %(ssm_groups)d group(s), state "
            "%(ssm_state)d, chunks of %(ssm_chunk)d; *: %(heads)d query "
            "heads on %(kv_heads)d of %(head_dim)d, no rotation; E: "
            "%(held)d of %(routed)d experts held from %(first)d, %(top_k)d a "
            "token, latent %(latent)d")

        x = nn.Embed(self.vocab_size, self.d_model,
                     embedding_init=_dense_init(), name="embed_tokens",
                     dtype=self.dtype)(tokens)
        positions = jnp.arange(t, dtype=jnp.int32)
        layer_cls = HybridLayer
        if self.remat:
            policy = block_policy(self, train, self._block_kinds(),
                                  batch=b, seq_len=t, block_key="layers_")
            # static_argnums count self as 0: train (3) is a Python bool
            layer_cls = nn.remat(HybridLayer, static_argnums=(3,),
                                 policy=policy)
        sizes = LayerSizes(
            n_expert_layers=self.pattern.count("E"),
            moe_held=tuple(self.moe_held),
            **{f: getattr(self, f) for f in LayerSizes._fields
               if f not in ("n_expert_layers", "moe_held")})
        for i, kind in enumerate(self.pattern):
            x = layer_cls(kind, sizes, name=f"layers_{i}")(
                x, positions, train)
        x = RMSNorm(self.rms_eps, name="norm")(x)
        w = _HeadKernel(self.d_model, self.vocab_size, name="lm_head")()
        if self.fused_head:
            return x.astype(self.dtype), w.astype(self.dtype)
        return jnp.matmul(x.astype(self.dtype), w.astype(self.dtype)
                          ).astype(jnp.float32)

    def _block_kinds(self):
        """The names each kind of layer makes, in features a token (the
        float32 router logits count twice a 16-bit model's item; the
        routed experts' first product is as wide as the experts held
        times their ``moe_d_ff``)."""
        item = jnp.dtype(self.dtype).itemsize
        ssm, scratch = mamba_block_sizes(
            self.ssm_n_head, self.ssm_head_dim, self.ssm_n_group,
            self.ssm_state, self.ssm_chunk, item)
        table = {
            "M": BlockKind(ssm, 0, scratch=scratch),
            "E": BlockKind({"moe_router": self.moe_n_routed * 4 // item,
                            "moe_latent": self.moe_latent,
                            "moe_experts_out": self.moe_latent,
                            "moe_shared_up": self.moe_shared_d_ff,
                            "moe_experts_up": (self.moe_held[1]
                                               or self.moe_n_routed)
                            * self.moe_d_ff}, 0),
            "*": BlockKind({"qkv_proj": (self.n_head + 2 * self.n_kv_head)
                            * self.head_dim, "attn_proj": self.d_model},
                           0, self.n_head, self.head_dim),
        }
        return [kind._replace(count=self.pattern.count(k))
                for k, kind in table.items() if k in self.pattern]

    def batch_template(self, batch_size: int = 1):
        return jnp.zeros((batch_size, min(self.max_len, 16)), jnp.int32)

    def partition_rules(self):
        """Replicated: a chip's share is stated by the head counts and
        ``moe_held``, not cut by a mesh axis (ROADMAP R2: the expert axis
        over several chips with its exchange is not here yet)."""
        return [(r".*", P())]


@MODELS.register("NemotronH")
def nemotron_h(vocab_size: int = 131072, pattern: str = "EMEMEMEMEM*",
               d_model: int = 4096, n_head: int = 32, n_kv_head: int = 2,
               head_dim: int = 128, ssm_n_head: int = 128,
               ssm_head_dim: int = 64, ssm_n_group: int = 8,
               ssm_state: int = 128, ssm_conv: int = 4, ssm_chunk: int = 128,
               moe_n_routed: int = 512, moe_held=(0, 0), moe_top_k: int = 22,
               moe_latent: int = 1024, moe_d_ff: int = 2688,
               moe_shared_d_ff: int = 5376, moe_scale: float = 5.0,
               selection_bias_rate: float = 0.0,
               rms_eps: float = 1e-5, max_len: int = 262144,
               bfloat16: bool = True, attn_impl: str = "flash",
               remat: bool = True, mesh=None, fused_head: bool = True):
    """NVIDIA-Nemotron-3-Super-120B-A12B-shaped defaults, one period of
    its pattern. A chip's share of a deployment is the same call with the
    head counts and ``moe_held`` that chip would hold."""
    return NemotronHLM(
        vocab_size=vocab_size, pattern=pattern, d_model=d_model,
        n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
        ssm_n_head=ssm_n_head, ssm_head_dim=ssm_head_dim,
        ssm_n_group=ssm_n_group, ssm_state=ssm_state, ssm_conv=ssm_conv,
        ssm_chunk=ssm_chunk, moe_n_routed=moe_n_routed,
        moe_held=tuple(moe_held), moe_top_k=moe_top_k,
        moe_latent=moe_latent, moe_d_ff=moe_d_ff,
        moe_shared_d_ff=moe_shared_d_ff, moe_scale=moe_scale,
        selection_bias_rate=selection_bias_rate,
        rms_eps=rms_eps, max_len=max_len,
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, mesh=mesh, fused_head=fused_head)


@MODELS.register("TinyNemotronH")
def tiny_nemotron_h(vocab_size: int = 256, pattern: str = "EM*",
                    attn_impl: str = "xla", remat: bool = False, mesh=None,
                    bfloat16: bool = False, fused_head: bool = False,
                    moe_held=(0, 0), selection_bias_rate: float = 0.0):
    """Every kind of layer at a size for tests and dry runs."""
    return NemotronHLM(
        vocab_size=vocab_size, pattern=pattern, d_model=64, n_head=4,
        n_kv_head=2, head_dim=16, ssm_n_head=4, ssm_head_dim=16,
        ssm_n_group=2, ssm_state=16, ssm_conv=4, ssm_chunk=16,
        moe_n_routed=8, moe_held=tuple(moe_held), moe_top_k=2,
        moe_latent=32, moe_d_ff=48, moe_shared_d_ff=96, moe_scale=2.5,
        selection_bias_rate=selection_bias_rate, max_len=128,
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, mesh=mesh, fused_head=fused_head)
