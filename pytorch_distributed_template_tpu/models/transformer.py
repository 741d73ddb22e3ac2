"""GPT-2-family causal transformer LM, TPU-native.

The reference has no transformer (model zoo = one CNN, SURVEY.md §2.3); the
BASELINE.json ladder requires GPT-2-small as the large-param gradient-
reduction stress config. Designed for TPU:

- megatron-style **tensor parallelism** expressed purely as partition rules
  (``partition_rules()``): column-parallel QKV/up-projection, row-parallel
  output/down-projection, vocab-sharded embedding. XLA inserts the two
  per-block all-reduces from the shardings — no hand-written collectives;
- **sequence parallelism** for long context: ``attn_impl='ring'`` routes
  attention through ``ops.ring_attention`` (shard_map + ppermute over the
  ``seq`` mesh axis) so the T×T score matrix never materializes;
- ``remat=True`` wraps each block in ``jax.checkpoint`` and means
  "recompute in the backward what does not fit": a training call keeps, in
  every block, the longest prefix of attention output and log-sum-exp,
  ``qkv`` output, attention projection output, MLP ``up`` output, the
  flash kernel's operands that fits the device's memory beside what the
  training step says it holds (state, gradient accumulator;
  engine/steps.py), reckoned from shapes, mesh and the device's fixed
  capacity (models/remat_policy.py; the choice is one ``remat/policy``
  INFO line in ``info.log`` and one span). Where the capacity is unknown
  (the CPU), or the gradient is taken outside such a step, nothing is
  kept and the whole block is recomputed;
- bf16 compute / fp32 params + fp32 softmax and layernorm accumulation;
- weight-tied LM head (embedding transpose), GPT-2 initialization scheme
  (normal(0.02), residual projections scaled by 1/sqrt(2L)).
"""
from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..config.registry import MODELS
from ..ops.attention import (
    multihead_attention, ring_attention, sharded_flash_attention,
    ulysses_attention, zigzag_perm,
)
from .remat_policy import BlockKind, block_policy


def _dense_init(stddev):
    return nn.initializers.normal(stddev=stddev)


def _dense_or_quant_biased(dtype, quant: str, lora_rank: int = 0,
                           lora_alpha: float = 16.0):
    """Biased Dense factory honoring the serving-quantization and LoRA
    fine-tuning modes (the GPT-2 family's projections carry biases,
    unlike Llama's; single dispatch point: models/quant.dense_factory)."""
    from .quant import dense_factory

    return lambda feats, init, name: dense_factory(
        dtype, quant, use_bias=True, kernel_init=init,
        lora_rank=lora_rank, lora_alpha=lora_alpha)(feats, name)


class MlpBlock(nn.Module):
    d_model: int
    d_ff: int
    dropout: float
    n_layer: int
    dtype: Any
    quant: str = ""
    lora_rank: int = 0
    lora_alpha: float = 16.0

    @nn.compact
    def __call__(self, x, train: bool):
        dense = _dense_or_quant_biased(self.dtype, self.quant,
                                       self.lora_rank, self.lora_alpha)
        # a name the block's checkpoint policy may keep
        # (models/remat_policy.py); outside jax.checkpoint it is nothing
        y = checkpoint_name(dense(self.d_ff, _dense_init(0.02), "up")(x),
                            "mlp_up")
        y = nn.gelu(y)
        y = dense(self.d_model,
                  _dense_init(0.02 / (2 * self.n_layer) ** 0.5), "down")(y)
        return nn.Dropout(self.dropout, deterministic=not train)(y)


class SelfAttention(nn.Module):
    d_model: int
    n_head: int
    dropout: float
    n_layer: int
    dtype: Any
    # 'xla' | 'ring' | 'ring_flash' | 'ulysses' | 'ulysses_flash' | 'flash'
    attn_impl: str = "xla"
    mesh: Optional[Any] = None      # required for 'ring*' / 'ulysses*'
    seq_layout: str = "natural"     # 'zigzag' -> inputs are zigzag-permuted
    quant: str = ""                 # "" | "w8a16" (serving; models/quant.py)
    kv_quant: str = ""              # "" | "int8" (decode cache; quant.py)
    lora_rank: int = 0              # >0: LoRA fine-tuning (models/lora.py)
    lora_alpha: float = 16.0
    causal: bool = True             # False: bidirectional (BERT family)

    @nn.compact
    def __call__(self, x, train: bool, decode: bool = False,
                 decode_index=None, prefill: bool = False):
        b, t, _ = x.shape
        if decode and not self.causal:
            raise ValueError("decode is autoregressive by construction; "
                             "bidirectional attention has no decode mode")
        head_dim = self.d_model // self.n_head
        dense = _dense_or_quant_biased(self.dtype, self.quant,
                                       self.lora_rank, self.lora_alpha)
        qkv = checkpoint_name(
            dense(3 * self.d_model, _dense_init(0.02), "qkv")(x), "qkv_proj")
        qkv = qkv.reshape(b, t, 3, self.n_head, head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if decode:
            ctx = self._cached_attention(q, k, v, decode_index, prefill)
        elif self.attn_impl in ("ring", "ring_flash"):
            if self.mesh is None:
                raise ValueError(f"attn_impl={self.attn_impl!r} requires a mesh")
            ctx = ring_attention(
                q, k, v, self.mesh, causal=self.causal,
                layout=(
                    "zigzag" if self.seq_layout == "zigzag" else "contig"
                ),
                block_impl=(
                    "flash" if self.attn_impl == "ring_flash" else "einsum"
                ),
            )
        elif self.attn_impl in ("ulysses", "ulysses_flash"):
            if self.mesh is None:
                raise ValueError(f"attn_impl={self.attn_impl!r} requires a mesh")
            ctx = ulysses_attention(
                q, k, v, self.mesh, causal=self.causal,
                inner=(
                    "flash" if self.attn_impl == "ulysses_flash" else "xla"
                ),
            )
        elif self.attn_impl == "flash":
            ctx = sharded_flash_attention(q, k, v, self.mesh,
                                          causal=self.causal)
        else:
            ctx = multihead_attention(q, k, v, causal=self.causal)
        ctx = ctx.reshape(b, t, self.d_model)
        out = checkpoint_name(
            dense(self.d_model, _dense_init(0.02 / (2 * self.n_layer) ** 0.5),
                  "out")(ctx), "attn_proj")
        return nn.Dropout(self.dropout, deterministic=not train)(out)

    def _cached_attention(self, q, k, v, cur, prefill: bool = False):
        """Incremental attention against a KV cache (flax decode pattern).

        ``cur`` is the write position — the model-level ``pos_index``
        counter, threaded down so there is exactly ONE position counter
        (engine/generate.py drives it). Cache tensors are created on the
        FIRST decode-mode call with that call's sequence length as the
        decode budget; later calls insert ``t`` new K/V rows at ``cur``
        and attend causally over the filled prefix — supporting both
        multi-token prefill and single-token steps. The attention math is
        the shared ``ops.attention.multihead_attention`` with a visibility
        mask.

        ``kv_quant == "int8"`` stores the cache rows int8 with a f32
        scale per (token, head) — same contract as the Llama family
        (models/llama._cached_attention): history rows round-trip int8,
        the call's own rows attend exactly, writes quantize.
        """
        b, t, h, d = q.shape
        kvq = self.kv_quant == "int8"
        store_dtype = jnp.int8 if kvq else k.dtype
        is_init = self.has_variable("cache", "cached_key")
        cached_k = self.variable("cache", "cached_key", jnp.zeros,
                                 k.shape, store_dtype)
        cached_v = self.variable("cache", "cached_value", jnp.zeros,
                                 v.shape, store_dtype)
        k_scale = v_scale = None
        if kvq:
            k_scale = self.variable("cache", "cached_key_scale", jnp.zeros,
                                    k.shape[:3], jnp.float32)
            v_scale = self.variable("cache", "cached_value_scale",
                                    jnp.zeros, v.shape[:3], jnp.float32)
        if not is_init:
            # shape-setting pass: allocate the cache, no attention needed
            return jnp.zeros((b, t, h, d), q.dtype)
        max_len = cached_k.value.shape[1]
        if t > max_len:
            raise ValueError(f"decode input {t} exceeds cache {max_len}")
        if kvq:
            from .quant import dequantize_kv, quantize_kv

            hist_k = dequantize_kv(cached_k.value, k_scale.value, k.dtype)
            hist_v = dequantize_kv(cached_v.value, v_scale.value, v.dtype)
        else:
            hist_k, hist_v = cached_k.value, cached_v.value
        # attention reads the full-precision view (history dequantized
        # when kvq; the call's own rows always exact)...
        k_all = jax.lax.dynamic_update_slice(
            hist_k, k.astype(hist_k.dtype), (0, cur, 0, 0)
        )
        v_all = jax.lax.dynamic_update_slice(
            hist_v, v.astype(hist_v.dtype), (0, cur, 0, 0)
        )
        # ...and the WRITE stores the new rows in cache form
        if kvq:
            qk, sk = quantize_kv(k)
            qv, sv = quantize_kv(v)
            cached_k.value = jax.lax.dynamic_update_slice(
                cached_k.value, qk, (0, cur, 0, 0))
            cached_v.value = jax.lax.dynamic_update_slice(
                cached_v.value, qv, (0, cur, 0, 0))
            k_scale.value = jax.lax.dynamic_update_slice(
                k_scale.value, sk, (0, cur, 0))
            v_scale.value = jax.lax.dynamic_update_slice(
                v_scale.value, sv, (0, cur, 0))
        else:
            cached_k.value = k_all
            cached_v.value = v_all
        q_pos = cur + jnp.arange(t)                       # [t]
        visible = jnp.arange(max_len)[None, :] <= q_pos[:, None]  # [t, L]
        if prefill and t > 1:
            # STATIC prefill fast path (generate() passes prefill=True:
            # fresh cache, cur == 0, the call's own tokens are the whole
            # visible context): the flash kernel avoids the [t, max_len]
            # f32 score/prob tensors — pure HBM traffic. Static (not a
            # lax.cond on cur == 0) so XLA never traces — or reserves
            # temp memory for — the einsum branch.
            from ..ops.flash import flash_attention

            return flash_attention(q, k, v, causal=True)
        return multihead_attention(
            q, k_all, v_all, causal=False, mask=visible[None, None]
        )


class Block(nn.Module):
    d_model: int
    n_head: int
    d_ff: int
    dropout: float
    n_layer: int
    dtype: Any
    attn_impl: str
    mesh: Optional[Any]
    moe: Optional[dict] = None      # MoeMlp kwargs; None -> dense MLP
    ln_eps: float = 1e-5
    seq_layout: str = "natural"
    quant: str = ""                 # "" | "w8a16" (serving; models/quant.py)
    kv_quant: str = ""              # "" | "int8" (decode cache; quant.py)
    lora_rank: int = 0              # >0: LoRA fine-tuning (models/lora.py)
    lora_alpha: float = 16.0
    causal: bool = True             # False: bidirectional (BERT family)

    @nn.compact
    def __call__(self, x, train: bool, example_mask=None,
                 decode: bool = False, decode_index=None,
                 prefill: bool = False):
        h = nn.LayerNorm(epsilon=self.ln_eps, dtype=jnp.float32,
                         name="ln_1")(x)
        x = x + SelfAttention(
            self.d_model, self.n_head, self.dropout, self.n_layer,
            self.dtype, self.attn_impl, self.mesh,
            seq_layout=self.seq_layout, quant=self.quant,
            kv_quant=self.kv_quant, lora_rank=self.lora_rank,
            lora_alpha=self.lora_alpha, causal=self.causal, name="attn",
        )(h, train, decode, decode_index, prefill)
        h = nn.LayerNorm(epsilon=self.ln_eps, dtype=jnp.float32,
                         name="ln_2")(x)
        if self.moe:
            from .moe import MoeMlp

            x = x + MoeMlp(
                d_model=self.d_model, d_ff=self.d_ff,
                dropout=self.dropout, n_layer=self.n_layer,
                dtype=self.dtype, mesh=self.mesh, name="moe",
                **self.moe,
            )(h, train, example_mask)
        else:
            x = x + MlpBlock(
                self.d_model, self.d_ff, self.dropout, self.n_layer,
                self.dtype, quant=self.quant, lora_rank=self.lora_rank,
                lora_alpha=self.lora_alpha, name="mlp",
            )(h, train)
        return x


class TransformerLM(nn.Module):
    """Decoder-only causal LM (GPT-2 shape family)."""
    vocab_size: int = 50257
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 0                   # 0 -> 4*d_model
    max_len: int = 1024
    dropout: float = 0.1
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Optional[Any] = None
    remat: bool = False             # recompute what does not fit (docstring)
    seq_layout: str = "natural"     # 'zigzag': balanced causal ring (ops/attention.py)
    fused_head: bool = False        # return (hidden, head_w) for chunked loss
    tie_embeddings: bool = True
    ln_eps: float = 1e-5            # GPT-2's layer_norm_epsilon
    quant: str = ""                 # "w8a16": int8 serving weights (quant.py)
    kv_quant: str = ""              # "int8": int8 decode KV cache (quant.py)
    lora_rank: int = 0              # >0: LoRA fine-tuning (models/lora.py)
    lora_alpha: float = 16.0
    #   (the tied head attends through the float embedding either way)
    # --- MoE (models/moe.py); moe_experts == 0 -> all-dense blocks --------
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 2              # MoE FFN in every Nth block (GShard: 2)
    moe_capacity_factor: float = 1.25
    moe_aux_loss_weight: float = 0.01

    def _moe_kwargs(self, layer_idx: int) -> Optional[dict]:
        if self.moe_experts <= 0 or (layer_idx + 1) % self.moe_every != 0:
            return None
        return dict(
            num_experts=self.moe_experts, top_k=self.moe_top_k,
            capacity_factor=self.moe_capacity_factor,
            aux_loss_weight=self.moe_aux_loss_weight,
        )

    @nn.compact
    def __call__(self, tokens, train: bool = False, example_mask=None,
                 decode: bool = False, prefill: bool = False):
        """``example_mask`` ([B] bool): marks padded examples so MoE blocks
        keep them out of expert capacity/balance statistics (dense blocks
        are per-token and need no mask — the loss masking suffices).

        ``decode=True`` runs incremental KV-cached inference: the first
        decode call (over ``[B, total_len]`` zeros, mutable=["cache"])
        allocates the caches, later calls consume new tokens at the cached
        position (engine/generate.py drives this)."""
        if self.quant:
            from .quant import validate_quant_config

            validate_quant_config(self.quant, self.fused_head,
                                  self.moe_experts)
        if self.kv_quant not in ("", "int8"):
            raise ValueError(f"unknown kv_quant {self.kv_quant!r}")
        d_ff = self.d_ff or 4 * self.d_model
        b, t = tokens.shape
        # Zigzag sequence layout for balanced causal ring attention: permute
        # the tokens ONCE here (one resharding collective under a seq-sharded
        # mesh), run every block in zigzag order — positions ride along via
        # the permuted position embedding, and LayerNorm/dense-MLP are
        # per-token so only attention notices — and invert ONCE before the
        # LM head. The logits are therefore in natural order: loss/metrics/
        # generation are untouched. Amortized over all n_layer attention
        # calls. MoE models are excluded: capacity-based token dropping in
        # MoeMlp is flatten-order-sensitive, so a permuted layout would drop
        # different tokens than the natural one.
        zperm = None
        if (
            self.seq_layout == "zigzag" and not decode
            and self.moe_experts <= 0
            and self.attn_impl in ("ring", "ring_flash")
            and self.mesh is not None
            and "seq" in self.mesh.axis_names
            and self.mesh.shape["seq"] > 1
            and t % (2 * self.mesh.shape["seq"]) == 0
        ):
            zperm = zigzag_perm(t, self.mesh.shape["seq"])
            tokens = tokens[:, zperm]
        embed = nn.Embed(
            self.vocab_size, self.d_model,
            embedding_init=_dense_init(0.02), name="wte",
            dtype=self.dtype,
        )
        pos_embed = self.param(
            "wpe", _dense_init(0.01), (self.max_len, self.d_model),
            jnp.float32,
        )
        start = None
        if decode:
            # the ONE position counter for the whole decode state; each
            # attention layer receives it as its cache write index
            is_init = self.has_variable("cache", "pos_index")
            pos_index = self.variable(
                "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
            )
            start = pos_index.value if is_init else jnp.zeros((), jnp.int32)
            pos = jax.lax.dynamic_slice_in_dim(pos_embed, start, t, axis=0)
            if is_init:
                pos_index.value = start + t
        else:
            pos = pos_embed[:t]
            if zperm is not None:
                pos = pos[zperm]
        x = embed(tokens) + pos[None].astype(self.dtype)
        x = nn.Dropout(self.dropout, deterministic=not train)(x)

        block_cls = Block
        if self.remat:
            # features a token of the matmul outputs a block names; a
            # sparse block names no MLP output
            widths = {"qkv_proj": 3 * self.d_model,
                      "attn_proj": self.d_model}
            if self.moe_experts <= 0 or self.moe_every > 1:
                widths["mlp_up"] = d_ff
            policy = block_policy(
                self, train and not decode,
                [BlockKind(widths, self.n_layer, self.n_head,
                           self.d_model // self.n_head)],
                batch=b, seq_len=t, block_key="h_")
            # static_argnums count `self` as 0: train=2 and decode=4 are
            # Python bools and must stay static; example_mask (3) is a
            # traced [B] array and must NOT be listed
            block_cls = nn.remat(
                Block, static_argnums=(2, 4, 6), policy=policy)
        for i in range(self.n_layer):
            x = block_cls(
                d_model=self.d_model, n_head=self.n_head, d_ff=d_ff,
                dropout=self.dropout, n_layer=self.n_layer,
                dtype=self.dtype, attn_impl=self.attn_impl, mesh=self.mesh,
                moe=self._moe_kwargs(i), ln_eps=self.ln_eps,
                seq_layout="zigzag" if zperm is not None else "natural",
                quant=self.quant, kv_quant=self.kv_quant,
                lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
                name=f"h_{i}",
            )(x, train, example_mask, decode, start, prefill)
        x = nn.LayerNorm(epsilon=self.ln_eps, dtype=jnp.float32,
                         name="ln_f")(x)
        if zperm is not None:
            x = x[:, np.argsort(zperm)]  # back to natural order pre-head
        if decode and prefill and t > 1:
            # generate()'s prefill samples only from the LAST position:
            # skip the [B, T-1, V] logits rows — ~1 GB of f32 HBM writes
            # per 8x1024 prefill at GPT-2 vocab
            x = x[:, -1:]
        if self.fused_head and not decode:
            # Memory-efficient head: hand (hidden, head weights) to a fused
            # chunked loss (engine/losses.fused_lm_cross_entropy) so the
            # full [B, T, V] logits tensor never materializes — at large
            # vocab it dominates peak HBM. Decode still produces logits
            # (generation needs them token-by-token, where V is cheap).
            if self.tie_embeddings:
                w = embed.embedding.T.astype(self.dtype)  # [D, V]
            else:
                # Same param path as the Dense below ("lm_head/kernel") so
                # fused and plain modes share checkpoints.
                from .llama import _HeadKernel

                w = _HeadKernel(self.d_model, self.vocab_size,
                                name="lm_head")().astype(self.dtype)
            return x.astype(self.dtype), w
        if self.tie_embeddings:
            logits = embed.attend(x.astype(self.dtype))
        else:
            from .quant import dense_factory

            logits = dense_factory(
                self.dtype, self.quant, use_bias=False,
                kernel_init=_dense_init(0.02), lora_rank=self.lora_rank,
                lora_alpha=self.lora_alpha,
            )(self.vocab_size, "lm_head")(x)
        return logits.astype(jnp.float32)

    def batch_template(self, batch_size: int = 1):
        return jnp.zeros((batch_size, min(self.max_len, 16)), jnp.int32)

    def kv_cache_spec(self) -> dict:
        """Decode-cache layout contract for engine/kvcache.py (paged
        prefix caching). ``rotary=False``: position information lives in
        the learned embedding, so cached K/V rows carry no per-slot
        rotation — blocks copy verbatim. Only the batch-1 canonical
        path applies (this family is not pad-capable, so it never runs
        the continuous slot engine)."""
        return {
            "rotary": False,
            "rope_base": 0.0,
            "window": 0,
            "kv_quant": self.kv_quant,
            # no block_tables decode path in this family: prefix reuse
            # rides the scatter_blocks fallback arm (engine/kvcache.py)
            "paged": False,
            # TP sharding annotation (ISSUE 10): full MHA — cache
            # leaves carry all n_head KV heads on the pool's head axis
            "kv_heads": int(self.n_head),
        }

    def partition_rules(self):
        """Megatron-style TP rules over the ``tensor`` mesh axis.

        Columns (output features) of QKV/up are sharded; rows (input
        features) of out/down are sharded — one all-reduce after attention
        and one after the MLP, inserted by XLA from these specs. The
        embedding shards over vocab. Rules are no-ops on meshes without a
        ``tensor`` axis (sharding.apply_rules prunes absent axes).
        """
        rules = [
            (r"wte/embedding", P("tensor", None)),
            (r"attn/qkv/kernel", P(None, "tensor")),
            (r"attn/qkv/bias", P("tensor")),
            (r"attn/out/kernel", P("tensor", None)),
            (r"mlp/up/kernel", P(None, "tensor")),
            (r"mlp/up/bias", P("tensor")),
            (r"mlp/down/kernel", P("tensor", None)),
            (r"lm_head/kernel", P(None, "tensor")),
            (r"wpe", P()),
        ]
        if self.moe_experts > 0:
            from .moe import MoeMlp

            rules = MoeMlp.partition_rules() + rules
        return rules


_GPT2_SIZES = {
    "gpt2-small": dict(n_layer=12, n_head=12, d_model=768),
    "gpt2-medium": dict(n_layer=24, n_head=16, d_model=1024),
    "gpt2-large": dict(n_layer=36, n_head=20, d_model=1280),
    "gpt2-xl": dict(n_layer=48, n_head=25, d_model=1600),
}


@MODELS.register("GPT2")
def gpt2(size: str = "gpt2-small", vocab_size: int = 50257,
         max_len: int = 1024, dropout: float = 0.1, bfloat16: bool = False,
         attn_impl: str = "xla", remat: bool = False, mesh=None,
         seq_layout: str = "natural", fused_head: bool = False,
         **overrides):
    cfg = dict(_GPT2_SIZES[size])
    cfg.update(overrides)
    return TransformerLM(
        vocab_size=vocab_size, max_len=max_len, dropout=dropout,
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, mesh=mesh,
        seq_layout=seq_layout, fused_head=fused_head, **cfg,
    )


@MODELS.register("TinyLM")
def tiny_lm(vocab_size: int = 256, n_layer: int = 2, n_head: int = 4,
            d_model: int = 64, max_len: int = 128, dropout: float = 0.0,
            attn_impl: str = "xla", remat: bool = False, mesh=None,
            bfloat16: bool = False, seq_layout: str = "natural",
            fused_head: bool = False, tie_embeddings: bool = True,
            quant: str = "", kv_quant: str = "", lora_rank: int = 0,
            lora_alpha: float = 16.0):
    """Small config for tests and the multi-chip dry run."""
    return TransformerLM(
        vocab_size=vocab_size, n_layer=n_layer, n_head=n_head,
        d_model=d_model, max_len=max_len, dropout=dropout,
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, mesh=mesh,
        seq_layout=seq_layout, fused_head=fused_head,
        tie_embeddings=tie_embeddings, quant=quant, kv_quant=kv_quant,
        lora_rank=lora_rank, lora_alpha=lora_alpha,
    )
