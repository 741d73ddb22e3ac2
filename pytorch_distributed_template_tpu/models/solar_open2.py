"""Hybrid stacks of gated delta-rule linear attention and gated softmax
attention, every layer followed by gated sparse experts (the
``solar_open2`` family: upstage/Solar-Open2-250B ``config.json``; the
linear layer is Kimi Delta Attention, arXiv:2510.26692).

    x = embedding[tok]
    for each character of pattern:
        x = x + mixer(RMSNorm(x))
        x = x + experts(RMSNorm(x))
    logits = RMSNorm(x) head

- ``K``, a KDA mixer (``KdaMixer``; the scan is ops/linear_attention.py,
  in chunks of ``kda_chunk``);
- ``*``, grouped-query attention without rotation (``use_rope`` false:
  order is carried by the linear layers) whose context is gated per
  output channel before ``o_proj`` (``LlamaAttention`` with ``rope_base``
  0 and ``out_gate``), causal over all earlier keys;
- the second sublayer is models/moe.ExpertLayer with gated experts:
  sigmoid router with a selection bias, ``moe_top_k`` of ``moe_n_routed``
  experts a token, three matrices an expert, one shared ``SwiGLU``.

No dense layer (``first_k_dense_replace`` 0), no position embedding, no
biases but the KDA output gate's; untied embedding and head (the fused
head and loss of the Llama family).

The head counts are what THIS chip holds, as in models/nemotron_h.py:
``kda_n_head`` heads of ``kda_head_dim``, ``n_head`` query heads on
``n_kv_head`` key-value heads of ``head_dim``, ``moe_held``
``(offset, count)`` of the routed experts. Heads are independent in both
mixers (the KDA's low-rank gates are cut by head in their second matrix,
its head norm has one weight for all heads), so a chip's ``o_proj``
result is one summand of the mixer's output. Nothing here stands in for
absent chips.

The two older hybrid stacks (models/nemotron_h.py: ONE mixer a layer;
models/granite_hybrid.py: a mixer and a dense gated MLP) keep their own
blocks. Training only, as there: a decode path needs the delta rule's
state beside the attention layers' pages (ROADMAP R5).
"""
from __future__ import annotations

import logging
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..config.registry import MODELS
from ..observability.trace import say_once
from ..ops.linear_attention import SUB_CHUNK, kda_chunked
from ..ops.ssm import sharded_conv_silu
from .llama import LlamaAttention, RMSNorm, _HeadKernel, _dense_init
from .moe import ExpertLayer, sow_counter
from .nemotron_h import _decay_init, _step_bias_init
from .remat_policy import BlockKind, block_policy

logger = logging.getLogger(__name__)

KINDS = "K*"
L2_EPS = 1e-6


class KdaMixer(nn.Module):
    """Kimi Delta Attention on ``u [B, T, d_model]``, ``H`` heads of ``P``:

    ``q', k', v = silu(conv(u W))``, three depthwise causal convolutions of
    ``conv`` taps without bias; a head's ``q = q' / |q'| * P ** -0.5``,
    ``k = k' / |k'|`` (the root over the sum of squares plus 1e-6); log
    decay a key channel ``g = -exp(A_log_h) * softplus(u Wf1 Wf2 +
    dt_bias)``; ``beta = 2 sigmoid(u w)``, in (0, 2): the delta rule may
    flip a key's component; the scan of ops/linear_attention.py; then
    ``y = RMSNorm_head(o) * sigmoid(u Wg1 Wg2 + b_g)`` with one norm
    weight ``[P]`` for all heads, and ``o_proj``.

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


class LayerSizes(NamedTuple):
    """The model's fields a layer reads (a module cannot hold its parent)."""
    d_model: int
    n_head: int
    n_kv_head: int
    head_dim: int
    kda_n_head: int
    kda_head_dim: int
    kda_conv: int
    kda_chunk: int
    kda_rank: int
    moe_n_routed: int
    moe_held: Tuple[int, int]
    moe_top_k: int
    moe_d_ff: int
    moe_shared_d_ff: int
    moe_scale: float
    rms_eps: float
    dtype: Any
    attn_impl: str
    mesh: Optional[Any]
    n_layers: int
    n_kda_layers: int


class SolarOpen2Layer(nn.Module):
    """A mixer of kind ``kind`` and the gated experts, each behind its
    norm, each added to the residual stream."""
    kind: str
    cfg: LayerSizes

    @nn.compact
    def __call__(self, x, positions, train: bool):
        c = self.cfg
        h = RMSNorm(c.rms_eps, name="input_layernorm")(x)
        if self.kind == "K":
            y = KdaMixer(
                c.d_model, c.kda_n_head, c.kda_head_dim, c.kda_conv,
                c.kda_chunk, c.kda_rank, c.rms_eps, c.dtype, c.mesh,
                c.n_kda_layers, name="mixer")(h)
        else:
            with jax.named_scope("gated_attn"):
                y = LlamaAttention(
                    c.d_model, c.n_head, c.n_kv_head, c.dtype, c.attn_impl,
                    c.mesh, rope_base=0.0, head_dim=c.head_dim,
                    out_gate=True, name="mixer")(h, positions, train)
        x = x + y
        h = RMSNorm(c.rms_eps, name="post_attention_layernorm")(x)
        return x + ExpertLayer(
            d_model=c.d_model, d_ff=c.moe_d_ff, n_routed=c.moe_n_routed,
            top_k=c.moe_top_k, held=tuple(c.moe_held),
            shared_d_ff=c.moe_shared_d_ff, router="sigmoid",
            selection_bias=True, scale=c.moe_scale, gated=True,
            n_layers=c.n_layers, dtype=c.dtype, name="experts")(h)


class SolarOpen2LM(nn.Module):
    """Decoder-only hybrid causal LM; see the module docstring."""
    vocab_size: int = 196608
    pattern: str = "*KKK"
    d_model: int = 4096
    # gated attention ('*')
    n_head: int = 64
    n_kv_head: int = 8
    head_dim: int = 128
    # KDA ('K')
    kda_n_head: int = 64
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_chunk: int = 64
    kda_rank: int = 128             # of the decay gate and the output gate
    # gated experts, in every layer
    moe_n_routed: int = 320
    moe_held: Tuple[int, int] = (0, 0)      # (offset, count); count 0: all
    moe_top_k: int = 8
    moe_d_ff: int = 1280
    moe_shared_d_ff: int = 1280
    moe_scale: float = 1.0
    # what a step moves each selection bias by (engine/steps.py); 0: stay
    selection_bias_rate: float = 0.0
    rms_eps: float = 1e-5
    max_len: int = 1048576
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Optional[Any] = None
    remat: bool = False             # recompute what does not fit
    fused_head: bool = False        # return (hidden, head_w) for chunked loss

    # what the layers count a step (engine/steps.py carries them in the
    # step's metrics, the trainer writes them to the flight record)
    step_counters = ("moe_pairs_here", "moe_load_max_over_mean",
                     "moe_tokens_unserved", "kda_chunk_log_decay_mean",
                     "kda_beta_mean")

    @nn.compact
    def __call__(self, tokens, train: bool = False, decode: bool = False):
        if decode:
            raise NotImplementedError(
                "SolarOpen2 has no decode path: the delta rule's state "
                "would have to live beside the attention layers' cache")
        if not self.pattern or set(self.pattern) - set(KINDS):
            raise ValueError(f"pattern {self.pattern!r}: one of {KINDS!r} a "
                             "layer, each followed by its experts")
        if self.n_head % self.n_kv_head:
            raise ValueError(f"n_head over n_kv_head: {self.n_head} not "
                             f"divisible by {self.n_kv_head}")
        b, t = tokens.shape
        held = self.moe_held[1] or self.moe_n_routed
        say_once(
            logger, "model/pattern",
            dict(pattern=self.pattern, layers=len(self.pattern),
                 kda_heads=self.kda_n_head, kda_head_dim=self.kda_head_dim,
                 kda_chunk=self.kda_chunk, kda_rank=self.kda_rank,
                 heads=self.n_head, kv_heads=self.n_kv_head,
                 head_dim=self.head_dim, held=held,
                 routed=self.moe_n_routed, first=self.moe_held[0],
                 top_k=self.moe_top_k, d_ff=self.moe_d_ff),
            "model/pattern: %(pattern)s (%(layers)d layers, each a mixer and "
            "gated experts); K: %(kda_heads)d delta-rule heads of "
            "%(kda_head_dim)d, a decay a key channel, gates of rank "
            "%(kda_rank)d, chunks of %(kda_chunk)d; *: %(heads)d query heads "
            "on %(kv_heads)d of %(head_dim)d, no rotation, gated output; "
            "experts: %(held)d of %(routed)d held from %(first)d, %(top_k)d a "
            "token, three matrices of %(d_ff)d, one shared")

        x = nn.Embed(self.vocab_size, self.d_model,
                     embedding_init=_dense_init(), name="embed_tokens",
                     dtype=self.dtype)(tokens)
        positions = jnp.arange(t, dtype=jnp.int32)
        layer_cls = SolarOpen2Layer
        if self.remat:
            policy = block_policy(self, train, self._block_kinds(),
                                  batch=b, seq_len=t, block_key="layers_")
            # static_argnums count self as 0: train (3) is a Python bool
            layer_cls = nn.remat(SolarOpen2Layer, static_argnums=(3,),
                                 policy=policy)
        sizes = LayerSizes(
            n_layers=len(self.pattern),
            n_kda_layers=max(self.pattern.count("K"), 1),
            moe_held=tuple(self.moe_held),
            **{f: getattr(self, f) for f in LayerSizes._fields
               if f not in ("n_layers", "n_kda_layers", "moe_held")})
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
        routed experts' first two products are as wide as the experts
        held times their ``moe_d_ff``), and the KDA scan's scratch: the
        float32 pairwise decays of its sub-chunks, ``heads x sub-chunk x
        head size`` entries a token."""
        item = jnp.dtype(self.dtype).itemsize
        held = (self.moe_held[1] or self.moe_n_routed) * self.moe_d_ff
        experts = {"moe_router": self.moe_n_routed * 4 // item,
                   "mlp_gate": self.moe_shared_d_ff,
                   "mlp_up": self.moe_shared_d_ff,
                   "moe_experts_gate": held, "moe_experts_up": held}
        width = self.kda_n_head * self.kda_head_dim
        table = {
            "K": BlockKind({"kda_in_proj": 3 * width,
                            "kda_out_proj": self.d_model, **experts}, 0,
                           scratch=SUB_CHUNK * width * 4 // item),
            "*": BlockKind({"qkv_proj": (self.n_head + 2 * self.n_kv_head)
                            * self.head_dim,
                            "attn_gate": self.n_head * self.head_dim,
                            "attn_proj": self.d_model, **experts},
                           0, self.n_head, self.head_dim),
        }
        return [kind._replace(count=self.pattern.count(k))
                for k, kind in table.items() if k in self.pattern]

    def batch_template(self, batch_size: int = 1):
        return jnp.zeros((batch_size, min(self.max_len, 16)), jnp.int32)

    def partition_rules(self):
        """Replicated: a chip's share is stated by the head counts,
        ``moe_held`` and ``vocab_size``, not cut by a mesh axis."""
        return [(r".*", P())]


@MODELS.register("SolarOpen2")
def solar_open2(bfloat16: bool = True, attn_impl: str = "flash",
                remat: bool = True, fused_head: bool = True, **fields):
    """Solar-Open2-250B's sizes (``SolarOpen2LM``'s defaults: one period
    of its pattern) unless ``fields`` say otherwise. A chip's share of a
    deployment is the same call with the head counts, ``moe_held`` and the
    rows of the vocabulary that chip would hold."""
    if "moe_held" in fields:
        fields["moe_held"] = tuple(fields["moe_held"])
    return SolarOpen2LM(
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, fused_head=fused_head, **fields)


@MODELS.register("TinySolarOpen2")
def tiny_solar_open2(vocab_size: int = 256, pattern: str = "*KK",
                     attn_impl: str = "xla", remat: bool = False, mesh=None,
                     bfloat16: bool = False, fused_head: bool = False,
                     moe_held=(0, 0), selection_bias_rate: float = 0.0):
    """Both kinds of layer at a size for tests and dry runs."""
    return SolarOpen2LM(
        vocab_size=vocab_size, pattern=pattern, d_model=64, n_head=4,
        n_kv_head=2, head_dim=16, kda_n_head=4, kda_head_dim=16,
        kda_conv=4, kda_chunk=16, kda_rank=8, moe_n_routed=8,
        moe_held=tuple(moe_held), moe_top_k=2, moe_d_ff=48,
        moe_shared_d_ff=48, moe_scale=1.0,
        selection_bias_rate=selection_bias_rate, max_len=128,
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, mesh=mesh, fused_head=fused_head)
