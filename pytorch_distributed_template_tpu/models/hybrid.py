"""Decoder stacks built from a pattern: ONE block and ONE language model,
assembled from a family's data.

    x = embedding[tok]                          (times embedding_multiplier)
    for each symbol of pattern:
        x = x + mixer(RMSNorm(x))               (the sums times
        x = x + second(RMSNorm(x))               residual_multiplier)
    logits = RMSNorm(x) head                    (over logits_scaling)

A symbol names the layer's mixer (``MIXERS``):

- ``M`` or ``mamba``: a Mamba-2 state-space mixer (models/mixers.py; the
  scan is ops/ssm.py, in chunks of ``ssm_chunk``);
- ``K``: a gated delta-rule mixer, Kimi Delta Attention (models/mixers.py;
  the scan is ops/linear_attention.py, in chunks of ``kda_chunk``);
- ``*``, ``attention`` or ``full_attention``: grouped-query attention
  (``LlamaAttention``), causal over all earlier keys; rotated by the
  family's ``rope_base`` (0: no rotation: three families state no
  position embedding, order is carried by the recurrent layers); scores
  ``q . k * attention_multiplier`` (0 there means ``head_dim ** -0.5``);
  with ``out_gate`` the context is gated per output channel before
  ``o_proj``, under the scope ``gated_attn``; with ``qk_norm`` each head
  of q and of k is normed before the rotation, under ``qknorm_attn``;
- ``conv``: a gated short convolution (models/mixers.ShortConvMixer),
  ``conv_taps`` taps, linear between two multiplicative gates;
- ``E``: routed experts as the mixer (models/moe.ExpertLayer: sigmoid
  router with a selection bias, ``moe_top_k`` of ``moe_n_routed`` experts
  a token, in a ``moe_latent``-wide latent where there is one, one shared
  expert).

The second sublayer is none, a gated MLP (``SwiGLU``, named ``mlp``,
under the scope ``dense_mlp`` with its norm and its sum) or an
``ExpertLayer`` (named ``experts``); where it is experts, the first
``n_dense_layers`` layers take the gated MLP instead. What a family IS
is a ``Family``: its symbols, the norms' names, the second sublayer,
the four scalars, the attention's rotation and norms, the head, and
what is the family's in its experts. The four families here are
records, each with two registered factories that hand the one
``HybridLM`` the record and the published sizes:

- ``NEMOTRON_H`` (Nemotron-H, arXiv:2504.03624; NVIDIA-Nemotron-3-Super-
  120B-A12B ``config.json``): ``M``, ``E``, ``*``, each ONE mixer behind
  its norm, no second sublayer, untied head;
- ``GRANITE_HYBRID`` (``granitemoehybrid`` without experts: IBM
  granite-4.0-h-micro ``config.json``): ``mamba`` or ``attention`` and a
  gated MLP, the family's four scalars, the head the embedding, tied;
- ``SOLAR_OPEN2`` (upstage/Solar-Open2-250B ``config.json``): ``K`` or a
  gated ``*`` and gated experts (three matrices an expert, a shared
  ``SwiGLU``), untied head;
- ``LFM2_MOE`` (``lfm2_moe``: LiquidAI LFM2-24B-A2B ``config.json``):
  ``conv`` or a rotated ``full_attention`` with q/k norms, then a gated
  MLP in the leading dense layers and gated experts without a shared
  one in the rest, the head the embedding, tied.

A family added later is a record, its factories, and a mixer only if it
brings a new one. The block branches on what a record holds, never on
which record it is; where the families' arithmetic differs, each form
stands where its value selects it (the plain sum where
``residual_multiplier`` is 1), so no family's compiled step depends on
the others being here.

The scalars are applied to activations, their results in the compute
type, and never folded into weights. ``logits_scaling`` divides the
normed hidden state (a power of two in granite, so exact) before the
head, so the fused head and loss (engine/losses.py) see a plain head and
the gradient follows. No biases but the Mamba convolution's and the KDA
output gate's.

The sizes are what THIS chip holds, so a chip's share of a deployment is
the same model with fewer heads (models/mixers.py says when that is
exact), ``moe_held`` ``(offset, count)`` of the routed experts, and, for a
tied head, ``vocab_size`` rows of the embedding (a vocabulary slice of a
tied matrix is a smaller vocabulary: ids, logits and loss are over the
slice). The layer runs without its exchange; nothing here stands in for
absent chips.

Training only: a decode path needs the recurrent mixers' state (a
convolution mixer's last ``conv_taps - 1`` positions too) beside the
attention layers' pages (ROADMAP R5), and there is none yet.
"""
from __future__ import annotations

import collections
import contextlib
import logging
import re
from typing import Any, Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..config.registry import MODELS
from ..observability.trace import say_once
from .llama import LlamaAttention, RMSNorm, SwiGLU, _HeadKernel, _dense_init
from .mixers import (
    KdaMixer, Mamba2Mixer, ShortConvMixer, kda_block_sizes,
    mamba_block_sizes, short_conv_block_sizes,
)
from .moe import ExpertLayer, expert_block_sizes
from .remat_policy import BlockKind, block_policy

logger = logging.getLogger(__name__)

MOE_COUNTERS = ("moe_pairs_here", "moe_load_max_over_mean",
                "moe_tokens_unserved", "moe_rows_run")


class Family(NamedTuple):
    """What a family of pattern-built stacks is, as data the block reads."""
    name: str                               # in messages alone
    symbols: Tuple[str, ...]                # what a layer's entry may be
    mixer_norm: str = "norm"                # the mixer's pre-norm
    second: str = ""                        # "" | "mlp" | "experts"
    second_norm: str = ""                   # the second sublayer's pre-norm
    out_gate: bool = False                  # the attention's output gate
    rope_base: float = 0.0                  # the attention's; 0: no rotation
    qk_norm: bool = False                   # a norm a head of q and of k
    attention_multiplier: float = 0.0       # 0: head_dim ** -0.5
    residual_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tied_head: bool = False                 # the head is the embedding
    gated_experts: bool = False             # three matrices an expert
    # what the layers count a step (engine/steps.py carries them in the
    # step's metrics, the trainer writes them to the flight record)
    step_counters: Tuple[str, ...] = ()


NEMOTRON_H = Family("NemotronH", ("M", "E", "*"),
                    step_counters=MOE_COUNTERS)
GRANITE_HYBRID = Family(
    "GraniteHybrid", ("mamba", "attention"), "input_layernorm", "mlp",
    "post_attention_layernorm", attention_multiplier=0.015625,
    residual_multiplier=0.22, embedding_multiplier=12.0, logits_scaling=8.0,
    tied_head=True)
SOLAR_OPEN2 = Family(
    "SolarOpen2", ("K", "*"), "input_layernorm", "experts",
    "post_attention_layernorm", out_gate=True, gated_experts=True,
    step_counters=MOE_COUNTERS + ("kda_chunk_log_decay_mean",
                                  "kda_beta_mean"))
LFM2_MOE = Family(
    "Lfm2Moe", ("conv", "full_attention"), "operator_norm", "experts",
    "ffn_norm", rope_base=1e6, qk_norm=True, tied_head=True,
    gated_experts=True, step_counters=MOE_COUNTERS)


def _experts(c) -> dict:
    """The fields of the ``ExpertLayer`` of a model of sizes ``c``
    (``Sizes``, below the model), mixer or second sublayer."""
    return dict(
        d_model=c.d_model, d_ff=c.moe_d_ff, n_routed=c.moe_n_routed,
        top_k=c.moe_top_k, held=tuple(c.moe_held), latent=c.moe_latent,
        shared_d_ff=c.moe_shared_d_ff, router="sigmoid",
        selection_bias=True, scale=c.moe_scale,
        gated=c.family.gated_experts, n_layers=c.n_expert_layers,
        dtype=c.dtype)


class Mixer(NamedTuple):
    """One kind of mixer: the module a layer of a model of sizes ``c``
    builds, what its block tells the checkpoint policy (less the count),
    and its clause of the ``model/pattern`` line."""
    build: Callable[[Any], nn.Module]
    block: Callable[[Any], BlockKind]
    says: str


def _scan_block(sizes: Tuple[dict, int]) -> BlockKind:
    return BlockKind(sizes[0], 0, scratch=sizes[1])


MIXERS = {
    "M": Mixer(
        lambda c: Mamba2Mixer(
            c.d_model, c.ssm_n_head, c.ssm_head_dim, c.ssm_n_group,
            c.ssm_state, c.ssm_conv, c.ssm_chunk, c.rms_eps, c.dtype, c.mesh,
            name="mixer"),
        lambda c: _scan_block(mamba_block_sizes(
            c.ssm_n_head, c.ssm_head_dim, c.ssm_n_group, c.ssm_state,
            c.ssm_chunk, jnp.dtype(c.dtype).itemsize)),
        "%(ssm_n_head)d heads of %(ssm_head_dim)d in %(ssm_n_group)d "
        "group(s), state %(ssm_state)d, chunks of %(ssm_chunk)d"),
    "K": Mixer(
        lambda c: KdaMixer(
            c.d_model, c.kda_n_head, c.kda_head_dim, c.kda_conv, c.kda_chunk,
            c.kda_rank, c.rms_eps, c.dtype, c.mesh, c.n_kda_layers,
            name="mixer"),
        lambda c: _scan_block(kda_block_sizes(
            c.d_model, c.kda_n_head, c.kda_head_dim,
            jnp.dtype(c.dtype).itemsize)),
        "%(kda_n_head)d delta-rule heads of %(kda_head_dim)d, a decay a key "
        "channel, gates of rank %(kda_rank)d, chunks of %(kda_chunk)d"),
    "E": Mixer(
        lambda c: ExpertLayer(**_experts(c), name="mixer"),
        lambda c: BlockKind(expert_block_sizes(**_experts(c)), 0),
        "%(held)d of %(moe_n_routed)d experts held from %(first)d, "
        "%(moe_top_k)d a token, latent %(moe_latent)d"),
    "C": Mixer(
        lambda c: ShortConvMixer(c.d_model, c.conv_taps, c.dtype,
                                 name="mixer"),
        lambda c: _scan_block(short_conv_block_sizes(
            c.d_model, jnp.dtype(c.dtype).itemsize)),
        "a gated convolution of %(conv_taps)d taps over %(d_model)d "
        "channels"),
    "*": Mixer(
        lambda c: LlamaAttention(
            c.d_model, c.n_head, c.n_kv_head, c.dtype, c.attn_impl, c.mesh,
            rope_base=c.family.rope_base, head_dim=c.head_dim,
            attention_multiplier=c.family.attention_multiplier,
            out_gate=c.family.out_gate, qk_norm=c.family.qk_norm,
            qk_norm_eps=c.rms_eps, name="mixer"),
        # the names ``LlamaAttention``'s projections make; its kernel's own
        # names are the policy's to reckon, from the head count
        lambda c: BlockKind(
            {"qkv_proj": (c.n_head + 2 * c.n_kv_head) * c.head_dim,
             **({"attn_gate": c.n_head * c.head_dim} if c.family.out_gate
                else {}), "attn_proj": c.d_model},
            0, c.n_head, c.head_dim),
        "%(n_head)d query heads on %(n_kv_head)d of %(head_dim)d, "
        "%(rotation)s%(gated_output)s"),
}
KIND = {"M": "M", "mamba": "M", "K": "K", "E": "E", "*": "*",
        "attention": "*", "full_attention": "*", "conv": "C"}


class HybridLayer(nn.Module):
    """The mixer that ``symbol`` names behind its pre-norm, added to the
    residual stream; then the layer's second sublayer, if it has one,
    behind its own."""
    symbol: str
    cfg: Any                        # the model's ``Sizes``
    second: str = ""                # "" | "mlp" | "experts", at this depth

    @nn.compact
    def __call__(self, x, positions, train: bool):
        c, f, kind = self.cfg, self.cfg.family, KIND[self.symbol]

        def add(x, y):
            if f.residual_multiplier == 1:
                return x + y
            # the scalar at full precision, the sum rounded once (0.22 in
            # bfloat16 is 0.2197: a scalar folded into the compute type
            # would be another model by a part in 800)
            return (x.astype(jnp.float32) + f.residual_multiplier
                    * y.astype(jnp.float32)).astype(x.dtype)

        h = RMSNorm(c.rms_eps, name=f.mixer_norm)(x)
        mixer = MIXERS[kind].build(c)
        if kind == "*":
            scope = ("gated_attn" if f.out_gate
                     else "qknorm_attn" if f.qk_norm else "")
            with (jax.named_scope(scope) if scope
                  else contextlib.nullcontext()):
                y = mixer(h, positions, train)
        else:
            y = mixer(h)
        x = add(x, y)
        if self.second == "mlp":
            with jax.named_scope("dense_mlp"):
                h = RMSNorm(c.rms_eps, name=f.second_norm)(x)
                x = add(x, SwiGLU(c.d_model, c.d_ff, c.dtype, name="mlp")(h))
        elif self.second == "experts":
            h = RMSNorm(c.rms_eps, name=f.second_norm)(x)
            x = add(x, ExpertLayer(**_experts(c), name="experts")(h))
        return x


class HybridLM(nn.Module):
    """Decoder-only hybrid causal LM of ``family``; see the module
    docstring. ``pattern`` holds one of the family's symbols a layer, a
    string of characters or a tuple of words."""
    family: Family
    vocab_size: int
    pattern: Tuple[str, ...]
    d_model: int
    max_len: int
    # the sizes of what the family has; 0: it has no such part
    d_ff: int = 0                   # the gated MLP's
    # where the second sublayer is experts: the leading layers that take
    # the gated MLP of ``d_ff`` instead
    n_dense_layers: int = 0
    n_head: int = 0                 # attention
    n_kv_head: int = 0
    head_dim: int = 0
    ssm_n_head: int = 0             # Mamba-2
    ssm_head_dim: int = 0
    ssm_n_group: int = 0
    ssm_state: int = 0
    ssm_conv: int = 0
    ssm_chunk: int = 0
    kda_n_head: int = 0             # KDA
    kda_head_dim: int = 0
    kda_conv: int = 0
    kda_chunk: int = 0
    kda_rank: int = 0               # of the decay gate and the output gate
    conv_taps: int = 0              # the gated short convolution's
    moe_n_routed: int = 0           # experts, as mixer or second sublayer
    moe_held: Tuple[int, int] = (0, 0)      # (offset, count); count 0: all
    moe_top_k: int = 0
    moe_latent: int = 0             # 0: the experts read the token itself
    moe_d_ff: int = 0
    moe_shared_d_ff: int = 0
    moe_scale: float = 1.0
    # what a step moves each selection bias by, against its expert's load
    # (engine/steps.selection_bias_step); 0: the biases stay
    selection_bias_rate: float = 0.0
    # rows of a tied matrix a deployment shares among its chips, for the
    # log line alone; 0: ``vocab_size`` is the whole vocabulary
    vocab_published: int = 0
    rms_eps: float = 1e-5
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Optional[Any] = None
    remat: bool = False             # recompute what does not fit
    fused_head: bool = False        # return (hidden, head_w) for chunked loss

    @property
    def step_counters(self):
        return self.family.step_counters

    @nn.compact
    def __call__(self, tokens, train: bool = False, decode: bool = False):
        f = self.family
        if decode:
            raise NotImplementedError(
                f"{f.name} has no decode path: the recurrent mixers' state "
                "would have to live beside the attention layers' cache")
        if not self.pattern or set(self.pattern) - set(f.symbols):
            raise ValueError(f"pattern {self.pattern!r}: each one of "
                             f"{f.symbols!r} in a {f.name} stack")
        kinds = {KIND[s] for s in f.symbols}
        for kind, heads, groups, what in (
                ("*", self.n_head, self.n_kv_head, "n_head over n_kv_head"),
                ("M", self.ssm_n_head, self.ssm_n_group,
                 "ssm_n_head over ssm_n_group")):
            if kind in kinds and heads % groups:
                raise ValueError(f"{what}: {heads} not divisible by {groups}")
        b, t = tokens.shape
        sizes = self._sizes()
        self._say_pattern(sizes)

        scaled = f.embedding_multiplier != 1
        embed = nn.Embed(self.vocab_size, self.d_model,
                         embedding_init=_dense_init(), name="embed_tokens",
                         dtype=None if scaled else self.dtype)
        x = embed(tokens)
        if scaled:
            # the float32 row times the scalar, rounded once
            x = (x * f.embedding_multiplier).astype(self.dtype)
        positions = jnp.arange(t, dtype=jnp.int32)
        layer_cls = HybridLayer
        if self.remat:
            policy = block_policy(self, train, self._block_kinds(),
                                  batch=b, seq_len=t, block_key="layers_")
            # static_argnums count self as 0: train (3) is a Python bool
            layer_cls = nn.remat(HybridLayer, static_argnums=(3,),
                                 policy=policy)
        for i, (symbol, second) in enumerate(zip(self.pattern,
                                                 self._seconds())):
            x = layer_cls(symbol, sizes, second, name=f"layers_{i}")(
                x, positions, train)
        x = RMSNorm(self.rms_eps, name="norm")(x)
        if f.logits_scaling != 1:
            x = x / jnp.asarray(f.logits_scaling, self.dtype)
        if f.tied_head:
            w = embed.embedding.T                           # [D, V]
        else:
            w = _HeadKernel(self.d_model, self.vocab_size, name="lm_head")()
        x, w = x.astype(self.dtype), w.astype(self.dtype)
        if self.fused_head:
            return x, w
        return jnp.matmul(x, w).astype(jnp.float32)

    def _seconds(self) -> Tuple[str, ...]:
        """Each layer's second sublayer: the family's, but the gated MLP
        in the leading dense layers of a family whose second is experts."""
        second = self.family.second
        return tuple(
            "mlp" if second == "experts" and i < self.n_dense_layers
            else second for i in range(len(self.pattern)))

    def _sizes(self):
        experts = (self._seconds().count("experts")
                   or self.pattern.count("E"))
        return Sizes(*(getattr(self, name) for name in Sizes._fields[:-2]),
                     n_expert_layers=experts,
                     n_kda_layers=max(self.pattern.count("K"), 1))

    def _say_pattern(self, c):
        f = self.family
        second = {
            "": "",
            "mlp": ", each a mixer and a gated MLP of %(d_ff)d",
            "experts": ", each a mixer and "
            + ("gated " if f.gated_experts else "") + "experts"
            + (", the first %(n_dense_layers)d a gated MLP of %(d_ff)d"
               if self.n_dense_layers else ""),
        }[f.second]
        clauses = ["model/pattern: %(pattern)s (%(layers)d layers" + second
                   + ")"]
        clauses += [f"{s[0]}: " + MIXERS[KIND[s]].says for s in f.symbols]
        if f.second == "experts":
            clauses.append(
                "experts: %(held)d of %(moe_n_routed)d held from %(first)d, "
                "%(moe_top_k)d a token, "
                + ("three" if f.gated_experts else "two")
                + " matrices of %(moe_d_ff)d"
                + (", one shared" if c.moe_shared_d_ff else ""))
        if (f.embedding_multiplier, f.residual_multiplier,
                f.attention_multiplier, f.logits_scaling) != (1, 1, 0, 1):
            clauses.append(
                "multipliers: embedding %(embedding)g, residual "
                "%(residual)g, attention %(attention)g, logits over "
                "%(logits)g")
        if f.tied_head:
            clauses.append("tied head over %(rows)d of %(of_rows)d rows of "
                           "the vocabulary")
        text = "; ".join(clauses)
        values = dict(
            c._asdict(), pattern="".join(s[0] for s in self.pattern),
            layers=len(self.pattern), first=c.moe_held[0],
            gated_output=", gated output" if f.out_gate else "",
            rotation=(f"rotation of base {f.rope_base:g}" if f.rope_base
                      else "no rotation")
            + (", a norm a head of q and of k" if f.qk_norm else ""),
            held=c.moe_held[1] or c.moe_n_routed,
            embedding=f.embedding_multiplier, residual=f.residual_multiplier,
            attention=f.attention_multiplier, logits=f.logits_scaling,
            rows=self.vocab_size,
            of_rows=self.vocab_published or self.vocab_size)
        say_once(logger, "model/pattern",
                 {k: values[k] for k in re.findall(r"%\((\w+)\)", text)},
                 text)

    def _block_kinds(self):
        """What each kind of layer the pattern has tells the checkpoint
        policy: its mixer's names and scratch and its second sublayer's
        names, in features a token, and how many such layers there are.
        A kind is a mixer AND a second sublayer; in the family's order
        of symbols, a symbol's kinds as they come in the pattern."""
        c, f = self._sizes(), self.family
        seconds = {
            "": {}, "mlp": {"mlp_gate": c.d_ff, "mlp_up": c.d_ff},  # SwiGLU
            "experts": (expert_block_sizes(**_experts(c))
                        if f.second == "experts" else {})}
        layers = collections.Counter(zip(self.pattern, self._seconds()))
        kinds = []
        for (symbol, second), count in sorted(
                layers.items(), key=lambda kind: f.symbols.index(kind[0][0])):
            kind = MIXERS[KIND[symbol]].block(c)
            kinds.append(kind._replace(
                widths={**kind.widths, **seconds[second]}, count=count))
        return kinds

    def batch_template(self, batch_size: int = 1):
        return jnp.zeros((batch_size, min(self.max_len, 16)), jnp.int32)

    def partition_rules(self):
        """Replicated: a chip's share is stated by the head counts,
        ``moe_held`` and ``vocab_size``, not cut by a mesh axis (ROADMAP
        R2: the expert axis over several chips with its exchange is not
        here yet)."""
        return [(r".*", P())]


# the model's fields as a record a layer can hold (a module cannot hold its
# parent), and how many layers sow each counter
Sizes = collections.namedtuple(
    "Sizes", [name for name in HybridLM.__dataclass_fields__
              if name not in ("parent", "name")]
    + ["n_expert_layers", "n_kda_layers"])


def _register(name: str, family: Family, sizes: dict, doc: str, open_to=None,
              pattern_key: str = "pattern", **flags):
    """Registers the factory ``name``: ``HybridLM`` of ``family`` at
    ``sizes`` unless the call says otherwise, in the fields ``open_to``
    names (None: every one of ``sizes``) and in the four arguments every
    factory takes, whose defaults ``flags`` may change; any other keyword
    is refused as a constructor refuses it. A field the ``Family`` has
    (a scalar) goes to the record, the others to the model; the pattern
    is spelled ``pattern_key`` in the family's own configuration."""
    flags = dict(dict(bfloat16=True, attn_impl="flash", remat=True,
                      fused_head=True), **flags)

    def build(**given):
        for key in given:
            if key not in flags and key not in (
                    sizes if open_to is None else open_to):
                raise TypeError(f"{name}() got an unexpected keyword "
                                f"argument {key!r}")
        args = {key: tuple(value) if isinstance(value, list) else value
                for key, value in {**flags, **sizes, **given}.items()}
        scalars = {key: args.pop(key) for key in list(args)
                   if key in Family._fields}
        dtype = jnp.bfloat16 if args.pop("bfloat16") else jnp.float32
        return HybridLM(family._replace(**scalars), dtype=dtype,
                        pattern=args.pop(pattern_key), **args)

    build.__name__, build.__doc__ = name, doc
    return MODELS.register(name)(build)


# one period of the pattern each, at the published widths
NEMOTRON_3_SUPER = dict(
    vocab_size=131072, pattern="EMEMEMEMEM*", d_model=4096, n_head=32,
    n_kv_head=2, head_dim=128, ssm_n_head=128, ssm_head_dim=64,
    ssm_n_group=8, ssm_state=128, ssm_conv=4, ssm_chunk=128,
    moe_n_routed=512, moe_held=(0, 0), moe_top_k=22, moe_latent=1024,
    moe_d_ff=2688, moe_shared_d_ff=5376, moe_scale=5.0,
    selection_bias_rate=0.0, rms_eps=1e-5, max_len=262144, mesh=None)
GRANITE_4_H_MICRO = dict(
    vocab_size=100352,
    layer_types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4,
    d_model=2048, d_ff=8192, n_head=32, n_kv_head=8, head_dim=64,
    ssm_n_head=64, ssm_head_dim=64, ssm_n_group=1, ssm_state=128,
    ssm_conv=4, ssm_chunk=256, embedding_multiplier=12.0,
    residual_multiplier=0.22, attention_multiplier=0.015625,
    logits_scaling=8.0, vocab_published=0, rms_eps=1e-5, max_len=131072,
    mesh=None)
SOLAR_OPEN2_250B = dict(
    vocab_size=196608, pattern="*KKK", d_model=4096, n_head=64, n_kv_head=8,
    head_dim=128, kda_n_head=64, kda_head_dim=128, kda_conv=4, kda_chunk=64,
    kda_rank=128, moe_n_routed=320, moe_held=(0, 0), moe_top_k=8,
    moe_d_ff=1280, moe_shared_d_ff=1280, moe_scale=1.0,
    selection_bias_rate=0.0, rms_eps=1e-5, max_len=1048576, mesh=None)
LFM2_24B_A2B = dict(
    vocab_size=65536,
    layer_types=("conv", "conv") + ("full_attention", "conv", "conv",
                                    "conv") * 9 + ("full_attention", "conv"),
    d_model=2048, d_ff=11776, n_dense_layers=2, n_head=32, n_kv_head=8,
    head_dim=64, conv_taps=3, moe_n_routed=64, moe_held=(0, 0), moe_top_k=4,
    moe_d_ff=1536, moe_scale=1.0, selection_bias_rate=0.0,
    vocab_published=0, rms_eps=1e-5, max_len=128000, mesh=None)
# every kind of layer at a size for tests and dry runs
TINY = dict(d_model=64, n_head=4, n_kv_head=2, head_dim=16, max_len=128,
            vocab_size=256, mesh=None)
TINY_SSM = dict(ssm_n_head=4, ssm_head_dim=16, ssm_state=16, ssm_conv=4,
                ssm_chunk=16)
TINY_MOE = dict(moe_n_routed=8, moe_held=(0, 0), moe_top_k=2, moe_d_ff=48,
                selection_bias_rate=0.0)
TINY_FLAGS = dict(bfloat16=False, attn_impl="xla", remat=False,
                  fused_head=False)

nemotron_h = _register(
    "NemotronH", NEMOTRON_H, NEMOTRON_3_SUPER,
    """NVIDIA-Nemotron-3-Super-120B-A12B's sizes (``NEMOTRON_3_SUPER``)
    unless the call says otherwise. A chip's share of a deployment is
    the same call with the head counts and ``moe_held`` that chip would
    hold.""")
tiny_nemotron_h = _register(
    "TinyNemotronH", NEMOTRON_H,
    dict(TINY, **TINY_SSM, **TINY_MOE, pattern="EM*", ssm_n_group=2,
         moe_latent=32, moe_shared_d_ff=96, moe_scale=2.5),
    "Every kind of layer at a size for tests and dry runs.",
    ("vocab_size", "pattern", "mesh", "moe_held", "selection_bias_rate"),
    **TINY_FLAGS)
granite_hybrid = _register(
    "GraniteHybrid", GRANITE_HYBRID, GRANITE_4_H_MICRO,
    """granite-4.0-h-micro's sizes (``GRANITE_4_H_MICRO``) unless the call
    says otherwise. A chip's share of a vocabulary-parallel deployment is
    the same call with the rows of the tied matrix that chip holds as
    ``vocab_size``.""", pattern_key="layer_types")
tiny_granite_hybrid = _register(
    "TinyGraniteHybrid", GRANITE_HYBRID,
    dict(TINY, **TINY_SSM, layer_types=("mamba", "attention", "mamba"),
         d_ff=96, ssm_n_group=1),
    "Both kinds of layer at a size for tests and dry runs.",
    ("vocab_size", "layer_types", "mesh"), "layer_types", **TINY_FLAGS)
solar_open2 = _register(
    "SolarOpen2", SOLAR_OPEN2, SOLAR_OPEN2_250B,
    """Solar-Open2-250B's sizes (``SOLAR_OPEN2_250B``) unless the call
    says otherwise. A chip's share of a deployment is the same call with
    the head counts, ``moe_held`` and the rows of the vocabulary that
    chip would hold.""")
tiny_solar_open2 = _register(
    "TinySolarOpen2", SOLAR_OPEN2,
    dict(TINY, **TINY_MOE, pattern="*KK", kda_n_head=4, kda_head_dim=16,
         kda_conv=4, kda_chunk=16, kda_rank=8, moe_shared_d_ff=48,
         moe_scale=1.0),
    "Both kinds of layer at a size for tests and dry runs.",
    ("vocab_size", "pattern", "mesh", "moe_held", "selection_bias_rate"),
    **TINY_FLAGS)
lfm2_moe = _register(
    "Lfm2Moe", LFM2_MOE, LFM2_24B_A2B,
    """LFM2-24B-A2B's sizes (``LFM2_24B_A2B``) unless the call says
    otherwise. A chip's share of a deployment is the same call with
    ``moe_held`` and the rows of the tied matrix that chip holds as
    ``vocab_size``. Training only.""", pattern_key="layer_types")
tiny_lfm2_moe = _register(
    "TinyLfm2Moe", LFM2_MOE,
    dict(TINY, **TINY_MOE, layer_types=("conv", "full_attention", "conv"),
         d_ff=96, n_dense_layers=1, conv_taps=3, moe_scale=1.0),
    "Both kinds of layer and both second sublayers at a size for tests "
    "and dry runs.",
    ("vocab_size", "layer_types", "n_dense_layers", "mesh", "moe_held",
     "selection_bias_rate"), "layer_types", **TINY_FLAGS)
