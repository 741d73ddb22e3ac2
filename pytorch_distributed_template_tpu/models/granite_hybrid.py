"""Hybrid stacks in which every layer is a mixer AND a gated MLP, each
behind its own RMSNorm and each added to the residual stream through one
scalar (the ``granitemoehybrid`` family without experts: IBM
granite-4.0-h-micro ``config.json``).

    x = embedding[tok] * embedding_multiplier
    for each entry of layer_types:
        x = x + residual_multiplier * mixer(RMSNorm(x))
        x = x + residual_multiplier * SwiGLU(RMSNorm(x))
    logits = RMSNorm(x) embedding^T / logits_scaling

- ``mamba``: the Mamba-2 mixer of models/nemotron_h.py (``Mamba2Mixer``
  over ops/ssm.py, in chunks of ``ssm_chunk``);
- ``attention``: grouped-query attention without rotation
  (``position_embedding_type`` ``nope``), causal over all earlier keys,
  scores ``q . k * attention_multiplier`` (``LlamaAttention``; 0 there
  means ``head_dim ** -0.5``);
- the MLP is ``SwiGLU``: the family's one ``[d_model, 2 d_ff]`` input
  matrix held as its two halves, ``gate_proj`` and ``up_proj``.

The head is the embedding, tied. The four scalars are applied to
activations, their results in the compute type, and never folded into
weights. ``logits_scaling`` divides the normed hidden state (a power of
two there, so exact) before the head, so the fused head and loss
(engine/losses.py) see a plain tied head and the gradient follows.

``vocab_size`` rows of the embedding are what THIS chip holds: a
vocabulary slice of a tied matrix is a smaller vocabulary (ids, logits and
loss are over the slice). One state-space group has no exact share by
heads (``B``, ``C`` and the gated norm's mean square span every channel),
so a chip holds each mixer whole.

The sibling of models/nemotron_h.py (ONE mixer a layer, untied head, no
scalars), whose ``Mamba2Mixer`` this imports. Training only, as there.
"""
from __future__ import annotations

import logging
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..config.registry import MODELS
from ..observability.trace import say_once
from .llama import LlamaAttention, RMSNorm, SwiGLU, _dense_init
from .nemotron_h import Mamba2Mixer, mamba_block_sizes
from .remat_policy import BlockKind, block_policy

logger = logging.getLogger(__name__)

KINDS = ("mamba", "attention")


class LayerSizes(NamedTuple):
    """The model's fields a layer reads (a module cannot hold its parent)."""
    d_model: int
    d_ff: int
    n_head: int
    n_kv_head: int
    head_dim: int
    attention_multiplier: float
    residual_multiplier: float
    ssm_n_head: int
    ssm_head_dim: int
    ssm_n_group: int
    ssm_state: int
    ssm_conv: int
    ssm_chunk: int
    rms_eps: float
    dtype: Any
    attn_impl: str
    mesh: Optional[Any]


class GraniteLayer(nn.Module):
    """A mixer of kind ``kind`` and a gated MLP, each behind its norm and
    each added times ``residual_multiplier``."""
    kind: str
    cfg: LayerSizes

    @nn.compact
    def __call__(self, x, positions, train: bool):
        c = self.cfg

        def add(x, y):
            # the scalar at full precision, the sum rounded once (0.22 in
            # bfloat16 is 0.2197: a scalar folded into the compute type
            # would be another model by a part in 800)
            return (x.astype(jnp.float32) + c.residual_multiplier
                    * y.astype(jnp.float32)).astype(x.dtype)

        h = RMSNorm(c.rms_eps, name="input_layernorm")(x)
        if self.kind == "mamba":
            y = Mamba2Mixer(
                c.d_model, c.ssm_n_head, c.ssm_head_dim, c.ssm_n_group,
                c.ssm_state, c.ssm_conv, c.ssm_chunk, c.rms_eps, c.dtype,
                c.mesh, name="mixer")(h)
        else:
            y = LlamaAttention(
                c.d_model, c.n_head, c.n_kv_head, c.dtype, c.attn_impl,
                c.mesh, rope_base=0.0, head_dim=c.head_dim,
                attention_multiplier=c.attention_multiplier,
                name="mixer")(h, positions, train)
        x = add(x, y)
        with jax.named_scope("dense_mlp"):
            h = RMSNorm(c.rms_eps, name="post_attention_layernorm")(x)
            return add(x, SwiGLU(c.d_model, c.d_ff, c.dtype, name="mlp")(h))


class GraniteHybridLM(nn.Module):
    """Decoder-only hybrid causal LM; see the module docstring."""
    vocab_size: int = 100352
    layer_types: Tuple[str, ...] = ("mamba",) * 5 + ("attention",) \
        + ("mamba",) * 4
    d_model: int = 2048
    d_ff: int = 8192
    # attention
    n_head: int = 32
    n_kv_head: int = 8
    head_dim: int = 64
    # Mamba-2
    ssm_n_head: int = 64
    ssm_head_dim: int = 64
    ssm_n_group: int = 1
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # the family's four scalars
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    # rows of the tied matrix a deployment shares among its chips, for the
    # log line alone; 0: ``vocab_size`` is the whole vocabulary
    vocab_published: int = 0
    rms_eps: float = 1e-5
    max_len: int = 131072
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Optional[Any] = None
    remat: bool = False             # recompute what does not fit
    fused_head: bool = False        # return (hidden, head_w) for chunked loss

    @nn.compact
    def __call__(self, tokens, train: bool = False, decode: bool = False):
        if decode:
            raise NotImplementedError(
                "GraniteHybrid has no decode path: the scan's state would "
                "have to live beside the attention layers' cache")
        if not self.layer_types or set(self.layer_types) - set(KINDS):
            raise ValueError(f"layer_types {self.layer_types!r}: each one "
                             f"of {KINDS!r}")
        for heads, groups, what in (
                (self.n_head, self.n_kv_head, "n_head over n_kv_head"),
                (self.ssm_n_head, self.ssm_n_group,
                 "ssm_n_head over ssm_n_group")):
            if heads % groups:
                raise ValueError(f"{what}: {heads} not divisible by {groups}")
        b, t = tokens.shape
        say_once(
            logger, "model/pattern",
            dict(pattern="".join(k[0] for k in self.layer_types),
                 layers=len(self.layer_types),
                 ssm_heads=self.ssm_n_head, ssm_head_dim=self.ssm_head_dim,
                 ssm_groups=self.ssm_n_group, ssm_state=self.ssm_state,
                 ssm_chunk=self.ssm_chunk, heads=self.n_head,
                 kv_heads=self.n_kv_head, head_dim=self.head_dim,
                 d_ff=self.d_ff, embedding=self.embedding_multiplier,
                 residual=self.residual_multiplier,
                 attention=self.attention_multiplier,
                 logits=self.logits_scaling, rows=self.vocab_size,
                 of_rows=self.vocab_published or self.vocab_size),
            "model/pattern: %(pattern)s (%(layers)d layers, each a mixer and "
            "a gated MLP of %(d_ff)d); m: %(ssm_heads)d heads of "
            "%(ssm_head_dim)d in %(ssm_groups)d group(s), state "
            "%(ssm_state)d, chunks of %(ssm_chunk)d; a: %(heads)d query "
            "heads on %(kv_heads)d of %(head_dim)d, no rotation; "
            "multipliers: embedding %(embedding)g, residual %(residual)g, "
            "attention %(attention)g, logits over %(logits)g; tied head over "
            "%(rows)d of %(of_rows)d rows of the vocabulary")

        embed = nn.Embed(self.vocab_size, self.d_model,
                         embedding_init=_dense_init(), name="embed_tokens")
        # the float32 row times the scalar, rounded once
        x = (embed(tokens) * self.embedding_multiplier).astype(self.dtype)
        positions = jnp.arange(t, dtype=jnp.int32)
        layer_cls = GraniteLayer
        if self.remat:
            policy = block_policy(self, train, self._block_kinds(),
                                  batch=b, seq_len=t, block_key="layers_")
            # static_argnums count self as 0: train (3) is a Python bool
            layer_cls = nn.remat(GraniteLayer, static_argnums=(3,),
                                 policy=policy)
        sizes = LayerSizes(**{f: getattr(self, f)
                              for f in LayerSizes._fields})
        for i, kind in enumerate(self.layer_types):
            x = layer_cls(kind, sizes, name=f"layers_{i}")(
                x, positions, train)
        x = RMSNorm(self.rms_eps, name="norm")(x)
        x = x / jnp.asarray(self.logits_scaling, self.dtype)
        w = embed.embedding.T.astype(self.dtype)            # [D, V], tied
        if self.fused_head:
            return x, w
        return jnp.matmul(x, w).astype(jnp.float32)

    def _block_kinds(self):
        """The names each kind of layer makes, in features a token, and
        the scan's scratch."""
        ssm, scratch = mamba_block_sizes(
            self.ssm_n_head, self.ssm_head_dim, self.ssm_n_group,
            self.ssm_state, self.ssm_chunk, jnp.dtype(self.dtype).itemsize)
        mlp = {"mlp_gate": self.d_ff, "mlp_up": self.d_ff}
        table = {
            "mamba": BlockKind({**ssm, **mlp}, 0, scratch=scratch),
            "attention": BlockKind({
                "qkv_proj": (self.n_head + 2 * self.n_kv_head)
                * self.head_dim, "attn_proj": self.d_model, **mlp},
                0, self.n_head, self.head_dim),
        }
        return [kind._replace(count=self.layer_types.count(k))
                for k, kind in table.items() if k in self.layer_types]

    def batch_template(self, batch_size: int = 1):
        return jnp.zeros((batch_size, min(self.max_len, 16)), jnp.int32)

    def partition_rules(self):
        """Replicated: a chip's share is stated by ``vocab_size``, not cut
        by a mesh axis."""
        return [(r".*", P())]


@MODELS.register("GraniteHybrid")
def granite_hybrid(bfloat16: bool = True, attn_impl: str = "flash",
                   remat: bool = True, fused_head: bool = True, **fields):
    """granite-4.0-h-micro's sizes (``GraniteHybridLM``'s defaults: one
    period of its ``layer_types``) unless ``fields`` say otherwise. A
    chip's share of a vocabulary-parallel deployment is the same call with
    the rows of the tied matrix that chip holds as ``vocab_size``."""
    if "layer_types" in fields:
        fields["layer_types"] = tuple(fields["layer_types"])
    return GraniteHybridLM(
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, fused_head=fused_head, **fields)


@MODELS.register("TinyGraniteHybrid")
def tiny_granite_hybrid(vocab_size: int = 256,
                        layer_types=("mamba", "attention", "mamba"),
                        attn_impl: str = "xla", remat: bool = False,
                        mesh=None, bfloat16: bool = False,
                        fused_head: bool = False):
    """Both kinds of layer at a size for tests and dry runs."""
    return GraniteHybridLM(
        vocab_size=vocab_size, layer_types=tuple(layer_types), d_model=64,
        d_ff=96, n_head=4, n_kv_head=2, head_dim=16, ssm_n_head=4,
        ssm_head_dim=16, ssm_n_group=1, ssm_state=16, ssm_conv=4,
        ssm_chunk=16, max_len=128,
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, mesh=mesh, fused_head=fused_head)
