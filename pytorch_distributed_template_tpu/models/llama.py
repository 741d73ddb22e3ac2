"""Llama-family causal LM: RMSNorm + SwiGLU + RoPE + grouped-query attention.

The reference's model zoo is one MNIST CNN (/root/reference/model/model.py);
this is the modern-LM counterpart to models/transformer.py's GPT-2 family,
TPU-native throughout:

- **RMSNorm** in float32 accumulation (no mean subtraction — one fewer HBM
  pass than LayerNorm);
- **SwiGLU** MLP (gate/up/down) with column/row-parallel TP rules;
- **RoPE** (rotary position embedding, HF rotate-half convention so
  HuggingFace checkpoints import without transposition games) — positions
  are threaded explicitly, so the zigzag ring layout works: the permuted
  token order simply carries permuted position ids into the rotation;
- **GQA** (``n_kv_head < n_head``): K/V are projected and KV-cached at the
  reduced head count (the decode-cache memory win) and broadcast to the
  query heads only at attention time;
- attention dispatches through the same ladder as the GPT-2 family:
  ``xla`` | ``flash`` | ``ring`` | ``ring_flash`` | ``ulysses`` |
  ``ulysses_flash`` (ops/attention.py, ops/flash.py);
- ``remat=True`` wraps each block in ``jax.checkpoint`` and means
  "recompute in the backward what does not fit": a training call keeps, in
  every block, the longest prefix of attention output and log-sum-exp,
  q/k/v projections' outputs, ``o_proj`` output, MLP ``gate`` output,
  MLP ``up`` output, the flash kernel's operands that fits the device's
  memory beside what the training step says it holds (state, gradient
  accumulator; engine/steps.py), reckoned from shapes, mesh and the
  device's fixed capacity (models/remat_policy.py; the choice is one
  ``remat/policy`` INFO line in ``info.log`` and one span). Where the
  capacity is unknown (the CPU), or the gradient is taken outside such a
  step, nothing is kept and the whole block is recomputed.
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
    grouped_query_attention, multihead_attention, ring_attention,
    sharded_flash_attention, ulysses_attention, zigzag_perm,
)
from .remat_policy import BlockKind, block_policy


def _dense_init(stddev=0.02):
    return nn.initializers.normal(stddev=stddev)


def _dense_or_quant(dtype, quant: str, lora_rank: int = 0,
                    lora_alpha: float = 16.0):
    """Bias-free Dense factory honoring the serving-quantization and
    LoRA fine-tuning modes (single dispatch point:
    models/quant.dense_factory)."""
    from .quant import dense_factory

    return dense_factory(dtype, quant, use_bias=False,
                         kernel_init=_dense_init(), lora_rank=lora_rank,
                         lora_alpha=lora_alpha)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        dtype = x.dtype
        xf = x.astype(jnp.float32)
        scale = self.param("weight", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + self.eps)
        return (y * scale).astype(dtype)


def rope_tables(positions, head_dim: int, base: float = 10000.0):
    """cos/sin tables for HF-convention RoPE.

    positions: int array [T]; returns (cos, sin) each [T, head_dim] with
    the half-frequencies duplicated (``concat(freqs, freqs)``), matching
    transformers' LlamaRotaryEmbedding so imported weights reproduce
    logits exactly.
    """
    inv_freq = 1.0 / (
        base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)        # [T, head_dim]
    return jnp.cos(emb), jnp.sin(emb)


def apply_rope(x, cos, sin):
    """Rotate [B, T, H, D] by per-position tables [T, D] (rotate-half)."""
    d = x.shape[-1]
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., d // 2:], xf[..., : d // 2]], axis=-1)
    out = xf * cos[None, :, None, :] + rot * sin[None, :, None, :]
    return out.astype(x.dtype)


def apply_rope_rows(x, cos, sin):
    """Rotate [B, T, H, D] by PER-ROW tables [B, T, D] — the paged
    decode path, where each row carries its own (row-local) positions
    instead of one shared cache-slot vector."""
    d = x.shape[-1]
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., d // 2:], xf[..., : d // 2]], axis=-1)
    out = xf * cos[:, :, None, :] + rot * sin[:, :, None, :]
    return out.astype(x.dtype)


class LlamaAttention(nn.Module):
    d_model: int
    n_head: int
    n_kv_head: int
    dtype: Any
    attn_impl: str = "xla"
    mesh: Optional[Any] = None
    seq_layout: str = "natural"
    rope_base: float = 10000.0      # 0: no rotation (the model states none)
    window: int = 0                 # sliding-window size; 0 = full causal
    quant: str = ""                 # "" | "w8a16" (models/quant.py)
    kv_quant: str = ""              # "" | "int8" (decode cache; quant.py)
    lora_rank: int = 0              # >0: LoRA fine-tuning (models/lora.py)
    lora_alpha: float = 16.0
    head_dim: int = 0               # 0 -> d_model // n_head
    # scores are q . k times this; 0 -> head_dim ** -0.5. Every attention
    # path and kernel fixes head_dim ** -0.5, so q is scaled by what is
    # left, multiplier * sqrt(head_dim), in q_proj's epilogue
    attention_multiplier: float = 0.0
    # the context times sigmoid(g_proj(x)), a gate per output channel,
    # before o_proj (gated attention, Qiu et al. arXiv:2505.06708)
    out_gate: bool = False
    # an RMSNorm over each head of q and of k (one weight [head_dim] each,
    # ``q_layernorm``, ``k_layernorm``) between projection and rotation
    qk_norm: bool = False
    qk_norm_eps: float = 1e-5

    @nn.compact
    def __call__(self, x, positions, train: bool, decode: bool = False,
                 decode_index=None, prefill: bool = False,
                 pad_lens=None, block_tables=None, row_starts=None):
        b, t, _ = x.shape
        hd = self.head_dim or self.d_model // self.n_head
        groups = self.n_head // self.n_kv_head
        dense = _dense_or_quant(self.dtype, self.quant, self.lora_rank,
                                self.lora_alpha)
        # matmul outputs carry names a block's checkpoint policy may keep
        # (models/remat_policy.py); outside jax.checkpoint a name is nothing
        def proj(name, heads):
            y = checkpoint_name(dense(heads * hd, name)(x), "qkv_proj")
            return y.reshape(b, t, heads, hd)

        def normed(y, name):
            return (RMSNorm(self.qk_norm_eps, name=name)(y) if self.qk_norm
                    else y)

        q = normed(proj("q_proj", self.n_head), "q_layernorm")
        if self.attention_multiplier:
            q = q * jnp.asarray(self.attention_multiplier * hd ** 0.5,
                                q.dtype)
        k = normed(proj("k_proj", self.n_kv_head), "k_layernorm")
        v = proj("v_proj", self.n_kv_head)

        if decode:
            if not self.rope_base:
                raise NotImplementedError(
                    "the decode caches rotate what they store: a model "
                    "without rotation has no decode path yet")
            ctx = self._cached_attention(q, k, v, decode_index, groups,
                                         prefill, pad_lens, block_tables,
                                         row_starts)
        else:
            if self.rope_base:
                cos, sin = rope_tables(positions, hd, self.rope_base)
                q = apply_rope(q, cos, sin)
                k = apply_rope(k, cos, sin)
            # GQA: the SP impls take COMPACT K/V (n_kv heads cross the
            # interconnect — groups x less traffic — and expand locally);
            # the single-device impls get the broadcast here
            if groups > 1 and self.attn_impl not in (
                "ring", "ring_flash", "ulysses", "ulysses_flash"
            ):
                k = jnp.repeat(k, groups, axis=2)
                v = jnp.repeat(v, groups, axis=2)
            if self.attn_impl in ("ring", "ring_flash"):
                if self.mesh is None:
                    raise ValueError(
                        f"attn_impl={self.attn_impl!r} requires a mesh")
                # window > 0 forces the contiguous layout: the band
                # balances the causal triangle by itself and enables the
                # ring's banded-skip early exit (LlamaLM skips the zigzag
                # permutation accordingly).
                ctx = ring_attention(
                    q, k, v, self.mesh, causal=True,
                    layout=("zigzag" if self.seq_layout == "zigzag"
                            and self.window == 0 else "contig"),
                    block_impl=("flash" if self.attn_impl == "ring_flash"
                                else "einsum"),
                    window=self.window,
                )
            elif self.attn_impl in ("ulysses", "ulysses_flash"):
                if self.mesh is None:
                    raise ValueError(
                        f"attn_impl={self.attn_impl!r} requires a mesh")
                ctx = ulysses_attention(
                    q, k, v, self.mesh, causal=True,
                    inner=("flash" if self.attn_impl == "ulysses_flash"
                           else "xla"),
                    window=self.window,
                )
            elif self.attn_impl == "flash":
                ctx = sharded_flash_attention(q, k, v, self.mesh,
                                              causal=True,
                                              window=self.window)
            else:
                ctx = multihead_attention(q, k, v, causal=True,
                                          window=self.window)
        ctx = ctx.reshape(b, t, self.n_head * hd)
        if self.out_gate:
            gate = checkpoint_name(
                dense(self.n_head * hd, "g_proj")(x), "attn_gate")
            ctx = (ctx.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(ctx.dtype)
        return checkpoint_name(dense(self.d_model, "o_proj")(ctx),
                               "attn_proj")

    def _paged_attention(self, q, k, v, cached_k, cached_v,
                         block_tables, row_starts, pad_lens,
                         k_scale=None, v_scale=None):
        """Paged decode (ISSUE 7): the supplied cache leaves ARE the KV
        block pool's ``[pool_blocks, block_tokens, KVH, D]`` pages, and
        this row's token positions map to pages through its block table
        — warm prefix admits are pointer updates, never HBM copies
        (engine/kvcache.py owns the tables).

        Positions are ROW-LOCAL (row ``b``'s lane ``i`` sits at
        ``row_starts[b] + i``; its RoPE angle is that position itself),
        so page content is canonical — position/era-independent — and
        the radix index can share pages between requests byte-for-byte.
        ``pad_lens`` here counts the leading INVALID lanes of THIS
        call's window (a right-aligned suffix feed, or 1 on a frozen
        1-token decode row): their K/V writes land in the reserved
        scratch page and their outputs are garbage the caller ignores.
        New K/V always lands in the row's PRIVATE tail pages — the
        engine never feeds a position covered by a shared radix page —
        so a write can never corrupt a page another row is reading.

        int8-KV pool layout (ISSUE 15, ``kv_quant="int8"``): new rows
        quantize per (token, kv-head) at the WRITE (models/quant
        ``quantize_kv``) — pages store int8 K/V plus f32 scale leaves —
        and attention reads dequantize in the kernel's tile fetch
        (ops/flash paged dequant epilogue). The call's own tokens
        round-trip through int8 too (unlike the contiguous kvq path),
        which keeps the page content the single source of truth: a
        radix hit replays EXACTLY the bytes the writer attended to, so
        warm == cold token-identically on the quantized paged path.

        Sliding-window ring layout (ISSUE 15, ``window > 0``): logical
        block ``j`` maps to table slot ``j % NB`` (the table is a ring
        over ~``window/block_tokens`` pages), the attention mask adds
        the ``q_pos - k_pos < window`` band, and out-of-band remnant
        content in recycled pages is masked by construction
        (engine/kvcache.py owns the ring geometry + slack contract)."""
        from ..ops.attention import paged_gqa_attention
        from ..engine.kvcache import SCRATCH_BLOCK

        b, t, _, d = q.shape
        pool_k, pool_v = cached_k.value, cached_v.value
        bt = pool_k.shape[1]
        nb = block_tables.shape[1]
        lane = jnp.arange(t)
        pos = row_starts[:, None] + lane[None, :]            # [B, t]
        if self.window > 0:
            # ring: positions may exceed the table span; the page for
            # position p is tables[(p // bt) % NB], offset p % bt
            safe_pos = jnp.maximum(pos, 0)
            blk = (safe_pos // bt) % nb
        else:
            safe_pos = jnp.clip(pos, 0, nb * bt - 1)
            blk = safe_pos // bt
        cos, sin = rope_tables(safe_pos.reshape(-1), d, self.rope_base)
        cos = cos.reshape(b, t, d)
        sin = sin.reshape(b, t, d)
        q = apply_rope_rows(q, cos, sin)
        k = apply_rope_rows(k, cos, sin)
        if pad_lens is None:
            pad_lens = jnp.zeros((b,), jnp.int32)
        valid = lane[None, :] >= pad_lens[:, None]
        page = jnp.take_along_axis(block_tables, blk, axis=1)
        ok = valid & (page >= 0)
        flat_idx = jnp.where(ok, page * bt + safe_pos % bt,
                             SCRATCH_BLOCK * bt + safe_pos % bt)

        def put(pool, new):
            flat = pool.reshape(-1, *pool.shape[2:])
            flat = flat.at[flat_idx.reshape(-1)].set(
                new.astype(pool.dtype).reshape(b * t, *new.shape[2:]))
            return flat.reshape(pool.shape)

        ks = vs = None
        if k_scale is not None:
            from .quant import quantize_kv

            kq, k_s = quantize_kv(k)      # int8 [B,t,H,D], f32 [B,t,H]
            vq, v_s = quantize_kv(v)
            cached_k.value = put(pool_k, kq)
            cached_v.value = put(pool_v, vq)
            k_scale.value = put(k_scale.value, k_s)
            v_scale.value = put(v_scale.value, v_s)
            ks, vs = k_scale.value, v_scale.value
        else:
            cached_k.value = put(pool_k, k)
            cached_v.value = put(pool_v, v)
        # TP serving (ISSUE 10): a mesh with a tensor axis routes the
        # read through per-shard head ranges (each shard's kernel walks
        # only its local KVH/tp pool slice); tables/starts replicate
        return paged_gqa_attention(q, cached_k.value, cached_v.value,
                                   block_tables, row_starts, pad_lens,
                                   mesh=self.mesh, window=self.window,
                                   k_scale=ks, v_scale=vs)

    def _cached_attention(self, q, k, v, cur, groups: int,
                          prefill: bool = False, pad_lens=None,
                          block_tables=None, row_starts=None):
        """Incremental decode against a K/V cache stored at the KV-head
        count (GQA memory win; same single-position-counter contract as
        models/transformer.SelfAttention._cached_attention). RoPE rotates
        the new rows by their absolute positions before insertion.

        ``pad_lens`` ([B] int32, optional) marks each row's LEFT-pad
        length for mixed-prompt-length batching: cache slots
        ``< pad_lens[b]`` are hidden from row ``b``'s attention. Exact
        for RoPE (positions here are cache-slot indices, a per-row
        constant shift of the true positions — RoPE scores depend only
        on q-k OFFSETS, which the shift preserves; pad slots' K/V are
        masked so their values never matter). "Exact" is mathematical:
        the padded run rotates at shifted angles and batched prefill
        uses the masked einsum path where solo uses the flash kernel,
        so logits agree to float tolerance, not bitwise — a greedy
        token can differ where the top-2 logits are ULP-tied. Left-padding aligns all
        rows' LAST token at the same slot, so the single position
        counter and last-slot logit sampling stay valid. Incompatible
        with the rolling window (eviction order differs per row) and
        routes batched prefill through the masked einsum path instead
        of the causal flash kernel.

        With ``window > 0`` the cache is a ROLLING ring buffer of
        ``window`` slots (Mistral-style): slot ``p % window`` holds
        position ``p``, old keys are overwritten as they fall out of the
        band, and an explicit per-slot position buffer drives the
        visibility mask — decode memory is O(window), independent of how
        long generation runs.

        With ``kv_quant == "int8"`` the cache stores int8 rows + a f32
        scale per (token, kv-head) (models/quant.quantize_kv): decode
        re-reads the whole cache every step, so this halves the cache's
        HBM traffic the way w8a16 halves the weights'. New rows are
        quantized at the WRITE; the call's own tokens attend in full
        precision (only history rows round-trip through int8)."""
        b, t, hq, d = q.shape

        def _fresh_prefill_ctx():
            # STATIC prefill contract (same as transformer.py): the
            # caller asserts via prefill=True that the cache is FRESH
            # (cur == 0, nothing decoded yet — generate() guarantees
            # this), so the call's own tokens are the ENTIRE visible
            # context and the Pallas flash kernel (causal + window band)
            # replaces the [t, hist + t] f32 einsum score tensor, which
            # is pure HBM traffic. UNCHECKED at runtime: prefill=True on
            # a warm cache silently ignores history — do not reuse the
            # prefill fn for chunked continuation.
            from ..ops.flash import flash_attention

            kr = jnp.repeat(k, groups, axis=2) if groups > 1 else k
            vr = jnp.repeat(v, groups, axis=2) if groups > 1 else v
            return flash_attention(q, kr, vr, causal=True,
                                   window=self.window)

        # The ALLOCATION call (generate's zeros pass over [B, total]) sizes
        # the cache: min(window, total) slots when windowed. Later calls
        # must derive `rolling` from the allocated length — their own t is
        # the prompt/token length, not the decode budget.
        alloc_len = (
            min(self.window, k.shape[1]) if self.window > 0 else k.shape[1]
        )
        kvq = self.kv_quant == "int8"
        store_dtype = jnp.int8 if kvq else k.dtype
        is_init = self.has_variable("cache", "cached_key")
        cached_k = self.variable(
            "cache", "cached_key", jnp.zeros,
            (b, alloc_len, k.shape[2], d), store_dtype,
        )
        cached_v = self.variable(
            "cache", "cached_value", jnp.zeros,
            (b, alloc_len, v.shape[2], d), store_dtype,
        )
        k_scale = v_scale = None
        if kvq:
            k_scale = self.variable(
                "cache", "cached_key_scale", jnp.zeros,
                (b, alloc_len, k.shape[2]), jnp.float32,
            )
            v_scale = self.variable(
                "cache", "cached_value_scale", jnp.zeros,
                (b, alloc_len, v.shape[2]), jnp.float32,
            )
        if is_init and block_tables is not None:
            # paged decode (ISSUE 7/15): the supplied leaves are pool
            # pages [P, bt, KVH, D] (+ [P, bt, KVH] scale leaves when
            # int8); positions ride in ``row_starts``, not the
            # contiguous-cache machinery below (``cur`` is unused, and
            # the rolling ring buffer + slot_pos never materialize —
            # window > 0 runs as a ring BLOCK TABLE instead)
            return self._paged_attention(q, k, v, cached_k, cached_v,
                                         block_tables, row_starts,
                                         pad_lens, k_scale, v_scale)
        cache_len = cached_k.value.shape[1]
        rolling = self.window > 0 and cache_len == self.window
        if pad_lens is not None and rolling:
            raise ValueError(
                "pad_lens (mixed-length batching) is incompatible with "
                "a rolling-window cache: ring eviction order would "
                "differ per row"
            )
        slot_pos = None
        if self.window > 0:
            # Which absolute position each slot holds, stored as pos + 1 so
            # 0 means EMPTY: generate() materializes fresh caches as
            # all-zeros pytrees from eval_shape (engine/generate.py) — the
            # init fn below never runs there, so the zero value itself must
            # encode "empty" or stale slots would masquerade as position 0.
            slot_pos = self.variable(
                "cache", "slot_pos",
                lambda: jnp.zeros((cache_len,), jnp.int32),
            )
        if not is_init:
            # shape-setting pass: allocate the cache, no attention needed
            return jnp.zeros((b, t, hq, d), q.dtype)
        if not rolling and t > cache_len:
            raise ValueError(f"decode input {t} exceeds cache {cache_len}")
        pos = cur + jnp.arange(t)
        cos, sin = rope_tables(pos, d, self.rope_base)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if kvq:
            from .quant import dequantize_kv, quantize_kv

            hist_k = dequantize_kv(cached_k.value, k_scale.value, k.dtype)
            hist_v = dequantize_kv(cached_v.value, v_scale.value, v.dtype)
            to_store = quantize_kv           # row -> (int8, f32 scale)
        else:
            hist_k, hist_v = cached_k.value, cached_v.value
            to_store = lambda x: (x.astype(store_dtype), None)  # noqa: E731
        if rolling:
            # Attend over HISTORY (ring buffer) + the call's own tokens —
            # every query sees its full band even when the call is longer
            # than the window; eviction applies only to the cache WRITE.
            hist_pos = slot_pos.value - 1                # [W], -1 = empty
            k_all = jnp.concatenate(
                [hist_k, k.astype(hist_k.dtype)], axis=1
            )                                            # [B, W + t, ...]
            v_all = jnp.concatenate(
                [hist_v, v.astype(hist_v.dtype)], axis=1
            )
            k_pos = jnp.concatenate([hist_pos, pos])[None, :]  # [1, W + t]
            visible = (k_pos >= 0) & (k_pos <= pos[:, None]) & (
                pos[:, None] - k_pos < self.window
            )
            # write the trailing <=W new tokens into their ring slots (a
            # static slice keeps the scatter duplicate-free/deterministic)
            if t > cache_len:
                kw, vw, wpos = k[:, -cache_len:], v[:, -cache_len:], \
                    pos[-cache_len:]
            else:
                kw, vw, wpos = k, v, pos
            # The write positions are CONTIGUOUS (wpos is a range), so a
            # ring-buffer write never needs a gather/scatter — it is a
            # roll and/or one dynamic_update_slice. The previous
            # `.at[:, wpos % W].set(...)` multi-index scatter compiled
            # into a pathologically serialized program on TPU (measured
            # round 3: 12-layer 8x1024 prefill 328 ms vs 33 ms without
            # it — ~28 ms PER LAYER for a 2 MB write).
            start = wpos[0] % cache_len
            qkw, skw = to_store(kw)
            qvw, svw = to_store(vw)
            writes = [(cached_k, qkw), (cached_v, qvw)]
            if kvq:
                writes += [(k_scale, skw), (v_scale, svw)]
            n_new = qkw.shape[1]
            if n_new == cache_len:
                # full replace: slot s must hold the row with pos % W == s,
                # i.e. kw rolled by start (kw[i] lands at (start + i) % W)
                for var, new in writes:
                    var.value = jnp.roll(new, start, axis=1)
                slot_pos.value = jnp.roll(wpos + 1, start)
            elif n_new == 1:
                # single-token decode step: one row, cannot wrap
                for var, new in writes:
                    var.value = jax.lax.dynamic_update_slice(
                        var.value, new, (0, start) + (0,) * (new.ndim - 2))
                slot_pos.value = jax.lax.dynamic_update_slice(
                    slot_pos.value, wpos + 1, (start,))
            else:
                # partial contiguous write that may wrap once: rotate the
                # ring so the span is slice [0, n), write, rotate back
                def write(buf, new, axis):
                    rolled = jnp.roll(buf, -start, axis=axis)
                    rolled = jax.lax.dynamic_update_slice(
                        rolled, new, (0,) * buf.ndim)
                    return jnp.roll(rolled, start, axis=axis)

                for var, new in writes:
                    var.value = write(var.value, new, 1)
                slot_pos.value = write(slot_pos.value, wpos + 1, 0)
            if t > 1 and prefill:
                return _fresh_prefill_ctx()
            # grouped GQA read: no jnp.repeat — the head expansion
            # materialized a groups-x cache copy per step at batch >= 32
            # (the "batch-32 cliff", scripts/debug_batch32_cliff.py).
            # Also measured FASTER at t > 1 (padded admission prefills:
            # serve_mixed uniform 906 vs 475 tok/s gated to t == 1)
            return grouped_query_attention(
                q, k_all, v_all, mask=visible[None, None]
            )
        else:
            # attention reads the DUS'd full-precision view (history rows
            # dequantized when kvq; the call's own rows always exact) ...
            k_all = jax.lax.dynamic_update_slice(
                hist_k, k.astype(hist_k.dtype), (0, cur, 0, 0)
            )
            v_all = jax.lax.dynamic_update_slice(
                hist_v, v.astype(hist_v.dtype), (0, cur, 0, 0)
            )
            k_pos = jnp.arange(cache_len)[None, :]
            visible = k_pos <= pos[:, None]
            if self.window > 0:
                visible = visible & (pos[:, None] - k_pos < self.window)
            if pad_lens is not None:
                # [B, t, L]: row b additionally hides its left-pad slots
                visible = visible[None] & (
                    k_pos[None] >= pad_lens[:, None, None]
                )
            # ... and the WRITE stores the rows in cache form
            qk, sk = to_store(k)
            qv, sv = to_store(v)
            cached_k.value = jax.lax.dynamic_update_slice(
                cached_k.value, qk, (0, cur, 0, 0))
            cached_v.value = jax.lax.dynamic_update_slice(
                cached_v.value, qv, (0, cur, 0, 0))
            if kvq:
                k_scale.value = jax.lax.dynamic_update_slice(
                    k_scale.value, sk, (0, cur, 0))
                v_scale.value = jax.lax.dynamic_update_slice(
                    v_scale.value, sv, (0, cur, 0))
        if t > 1 and prefill and pad_lens is None:
            return _fresh_prefill_ctx()
        mask = (visible[:, None] if visible.ndim == 3    # [B, 1, t, L]
                else visible[None, None])                # [1, 1, t, L]
        return grouped_query_attention(q, k_all, v_all, mask=mask)


class SwiGLU(nn.Module):
    d_model: int
    d_ff: int
    dtype: Any
    quant: str = ""
    lora_rank: int = 0
    lora_alpha: float = 16.0

    @nn.compact
    def __call__(self, x):
        dense = _dense_or_quant(self.dtype, self.quant, self.lora_rank,
                                self.lora_alpha)
        gate = checkpoint_name(dense(self.d_ff, "gate_proj")(x), "mlp_gate")
        up = checkpoint_name(dense(self.d_ff, "up_proj")(x), "mlp_up")
        return dense(self.d_model, "down_proj")(nn.silu(gate) * up)


class LlamaBlock(nn.Module):
    d_model: int
    n_head: int
    n_kv_head: int
    d_ff: int
    dtype: Any
    attn_impl: str
    mesh: Optional[Any]
    seq_layout: str
    rope_base: float
    rms_eps: float
    window: int = 0
    moe: Optional[dict] = None      # MoeMlp kwargs; None -> dense SwiGLU
    n_layer: int = 1                # model depth, for residual-init scaling
    quant: str = ""                 # "" | "w8a16" (serving; models/quant.py)
    kv_quant: str = ""              # "" | "int8" (decode cache; quant.py)
    lora_rank: int = 0              # >0: LoRA fine-tuning (models/lora.py)
    lora_alpha: float = 16.0

    @nn.compact
    def __call__(self, x, positions, train: bool, example_mask=None,
                 decode: bool = False, decode_index=None,
                 prefill: bool = False, pad_lens=None,
                 block_tables=None, row_starts=None):
        h = RMSNorm(self.rms_eps, name="input_layernorm")(x)
        x = x + LlamaAttention(
            self.d_model, self.n_head, self.n_kv_head, self.dtype,
            self.attn_impl, self.mesh, self.seq_layout, self.rope_base,
            window=self.window, quant=self.quant, kv_quant=self.kv_quant,
            lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
            name="self_attn",
        )(h, positions, train, decode, decode_index, prefill, pad_lens,
          block_tables, row_starts)
        h = RMSNorm(self.rms_eps, name="post_attention_layernorm")(x)
        if self.moe:
            # Mixtral-style sparse FFN: routed SwiGLU experts over the
            # ``expert`` mesh axis (models/moe.py)
            from .moe import MoeMlp

            return x + MoeMlp(
                d_model=self.d_model, d_ff=self.d_ff,
                dropout=0.0, n_layer=self.n_layer, dtype=self.dtype,
                mesh=self.mesh, expert_act="swiglu", **self.moe,
                name="moe",
            )(h, train, example_mask)
        return x + SwiGLU(self.d_model, self.d_ff, self.dtype,
                          quant=self.quant, lora_rank=self.lora_rank,
                          lora_alpha=self.lora_alpha, name="mlp")(h)


class _HeadKernel(nn.Module):
    """Param-only holder for the untied LM head weight.

    Exists so ``fused_head`` can hand the raw ``[D, V]`` kernel to the
    chunked loss (engine/losses.fused_lm_cross_entropy) without computing
    logits, while keeping the checkpoint/HF-import param path identical to
    the ``nn.Dense(name="lm_head")`` it replaces (``lm_head/kernel``).
    """
    d_model: int
    vocab_size: int

    @nn.compact
    def __call__(self):
        return self.param("kernel", _dense_init(),
                          (self.d_model, self.vocab_size), jnp.float32)


class LlamaLM(nn.Module):
    """Decoder-only Llama-architecture causal LM."""
    vocab_size: int = 32000
    n_layer: int = 12
    n_head: int = 12
    n_kv_head: int = 0              # 0 -> n_head (no GQA)
    d_model: int = 768
    d_ff: int = 0                   # 0 -> ceil(8/3 * d_model) (Llama ratio)
    max_len: int = 2048
    dtype: Any = jnp.float32
    attn_impl: str = "xla"
    mesh: Optional[Any] = None
    remat: bool = False             # recompute what does not fit (docstring)
    seq_layout: str = "natural"
    rope_base: float = 10000.0
    rms_eps: float = 1e-6
    window: int = 0                 # sliding-window attention; 0 = full
    fused_head: bool = False        # return (hidden, head_w) for chunked loss
    quant: str = ""                 # "w8a16": int8 serving weights (quant.py)
    kv_quant: str = ""              # "int8": int8 decode KV cache (quant.py)
    lora_rank: int = 0              # >0: LoRA fine-tuning (models/lora.py)
    lora_alpha: float = 16.0
    # --- MoE (models/moe.py, swiglu experts); 0 -> all-dense blocks -------
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 1              # Mixtral: every block is sparse
    moe_capacity_factor: float = 1.25
    moe_aux_loss_weight: float = 0.01

    def _moe_kwargs(self, layer_idx: int):
        if self.moe_experts <= 0 or (layer_idx + 1) % self.moe_every != 0:
            return None
        return dict(
            num_experts=self.moe_experts, top_k=self.moe_top_k,
            capacity_factor=self.moe_capacity_factor,
            aux_loss_weight=self.moe_aux_loss_weight,
        )

    @nn.compact
    def __call__(self, tokens, train: bool = False, example_mask=None,
                 decode: bool = False, prefill: bool = False,
                 pad_lens=None, block_tables=None, row_starts=None,
                 exit_layer: int = 0):
        """``block_tables``/``row_starts`` (decode only): paged decode
        against the KV block pool — the cache collection's K/V leaves
        must be pool pages ``[P, block_tokens, KVH, D]`` and each row's
        positions are row-local (engine/kvcache.py builds both).

        ``exit_layer > 0``: early-exit forward — run only the first
        ``exit_layer`` blocks, then the final norm + LM head. This is
        the built-in DRAFT model for speculative decoding
        (engine/generate.generate_speculative ``draft_layers``): the
        draft shares the target's params AND its KV cache/pool pages —
        layers past the exit are simply not visited, and the verify
        pass recomputes+overwrites the visited layers' rows with
        identical values for accepted tokens, so draft and verify reuse
        one cache with zero extra memory."""
        if self.quant:
            from .quant import validate_quant_config

            validate_quant_config(self.quant, self.fused_head,
                                  self.moe_experts)
        if self.kv_quant not in ("", "int8"):
            raise ValueError(f"unknown kv_quant {self.kv_quant!r}")
        if pad_lens is not None and not decode:
            raise ValueError(
                "pad_lens is a decode-time batching feature; training "
                "uses example_mask"
            )
        b, t = tokens.shape
        n_kv = self.n_kv_head or self.n_head
        if self.n_head % n_kv != 0:
            raise ValueError(
                f"n_head {self.n_head} not divisible by n_kv_head {n_kv}")
        # Llama's ~8/3 ratio, rounded up to a multiple of 16 so the MLP
        # kernels tile the MXU and split over typical TP factors (real
        # checkpoints pass their exact d_ff, e.g. 11008 for 7B)
        d_ff = self.d_ff or -(-int(self.d_model * 8 / 3) // 16) * 16

        # Zigzag layout (same transparency contract as TransformerLM): RoPE
        # makes this trivial here — the permuted token order just carries
        # permuted position ids into the rotation, no table reindex needed.
        zperm = None
        if (
            self.seq_layout == "zigzag" and not decode
            and self.window == 0  # SWA rides the contiguous banded ring
            and self.moe_experts <= 0  # MoE routing stays natural-order
            and self.attn_impl in ("ring", "ring_flash")
            and self.mesh is not None
            and "seq" in self.mesh.axis_names
            and self.mesh.shape["seq"] > 1
            and t % (2 * self.mesh.shape["seq"]) == 0
        ):
            zperm = zigzag_perm(t, self.mesh.shape["seq"])
            tokens = tokens[:, zperm]

        embed = nn.Embed(self.vocab_size, self.d_model,
                         embedding_init=_dense_init(), name="embed_tokens",
                         dtype=self.dtype)
        x = embed(tokens)

        start = None
        if decode:
            is_init = self.has_variable("cache", "pos_index")
            pos_index = self.variable(
                "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
            )
            start = pos_index.value if is_init else jnp.zeros((), jnp.int32)
            if is_init:
                pos_index.value = start + t
            positions = None  # per-layer caches rotate by absolute position
        elif zperm is not None:
            positions = jnp.asarray(zperm, jnp.int32)
        else:
            positions = jnp.arange(t, dtype=jnp.int32)

        n_run = (min(int(exit_layer), self.n_layer) if exit_layer
                 else self.n_layer)
        block_cls = LlamaBlock
        if self.remat:
            # features a token of the matmul outputs a block names; a
            # sparse block names no MLP output
            hd = self.d_model // self.n_head
            widths = {"qkv_proj": (self.n_head + 2 * n_kv) * hd,
                      "attn_proj": self.d_model}
            if self.moe_experts <= 0 or self.moe_every > 1:
                widths.update(mlp_gate=d_ff, mlp_up=d_ff)
            policy = block_policy(
                self, train and not decode,
                [BlockKind(widths, n_run, self.n_head, hd)],
                batch=b, seq_len=t, block_key="layers_")
            # static_argnums count self as 0: train=3 / decode=5 are Python
            # bools; positions (2) and example_mask (4) are traced
            block_cls = nn.remat(
                LlamaBlock, static_argnums=(3, 5, 7), policy=policy)
        for i in range(n_run):
            x = block_cls(
                d_model=self.d_model, n_head=self.n_head, n_kv_head=n_kv,
                d_ff=d_ff, dtype=self.dtype, attn_impl=self.attn_impl,
                mesh=self.mesh, seq_layout=(
                    "zigzag" if zperm is not None else "natural"
                ),
                rope_base=self.rope_base, rms_eps=self.rms_eps,
                window=self.window, moe=self._moe_kwargs(i),
                n_layer=self.n_layer, quant=self.quant,
                kv_quant=self.kv_quant, lora_rank=self.lora_rank,
                lora_alpha=self.lora_alpha,
                name=f"layers_{i}",
            )(x, positions, train, example_mask, decode, start, prefill,
              pad_lens, block_tables, row_starts)
        x = RMSNorm(self.rms_eps, name="norm")(x)
        if zperm is not None:
            x = x[:, np.argsort(zperm)]
        if decode and prefill and t > 1:
            # generate()'s prefill samples only from the LAST position:
            # skip the [B, T-1, V] logits rows — ~1 GB of f32 HBM writes
            # per 8x1024 prefill at 32k vocab
            x = x[:, -1:]
        if self.fused_head and not decode:
            # chunked head+loss (engine/losses.fused_lm_cross_entropy):
            # [B, T, V] logits never materialize. Same param path as the
            # Dense below, so the two modes share checkpoints/HF imports.
            w = _HeadKernel(self.d_model, self.vocab_size,
                            name="lm_head")()
            return x.astype(self.dtype), w.astype(self.dtype)
        head = _dense_or_quant(self.dtype, self.quant, self.lora_rank,
                               self.lora_alpha)
        logits = head(self.vocab_size, "lm_head")(x)
        return logits.astype(jnp.float32)

    def batch_template(self, batch_size: int = 1):
        return jnp.zeros((batch_size, min(self.max_len, 16)), jnp.int32)

    def kv_cache_spec(self) -> dict:
        """Decode-cache layout contract consumed by engine/kvcache.py
        (the paged prefix-cache pool). ``rotary=True``: cached K rows
        are RoPE-rotated at absolute cache-slot angles, so block
        capture/extraction must shift rotations by the row's start slot
        (rotations compose additively — kvcache.rotate_rows).

        ``paged=True``: the family implements the TRUE paged decode
        path (``block_tables``/``row_starts`` call args — attention
        reads pool pages in place through the block table, ISSUE 7) —
        for ALL of the family's layouts since ISSUE 15: the int8-KV
        pool stores quantized pages + scale leaves, and ``window > 0``
        runs the table as a ring over ~``window/block_tokens`` pages.
        Layouts without it fall back to ``kvcache.scatter_blocks``
        copies into a contiguous cache (the scatter arm still refuses
        ``window > 0`` — a rolling contiguous cache's eviction order is
        position-dependent).

        ``kv_heads`` (ISSUE 10): the TP sharding annotation — pool
        pages are ``[pool_blocks, block_tokens, KVH, D]`` and a
        serving mesh shards the head axis (axis 2, the
        parallel/tp.kv_pool_pspec contract) over its ``tensor`` axis;
        ``kv_heads % tp == 0`` is enforced up front by
        parallel/tp.validate_tp_geometry and defensively by the pool.
        Block tables and the radix index stay replicated host
        metadata."""
        n_kv = int(self.n_kv_head or self.n_head)
        return {
            "rotary": True,
            "rope_base": float(self.rope_base),
            "window": int(self.window),
            "kv_quant": self.kv_quant,
            "paged": True,
            "kv_heads": n_kv,
        }

    def partition_rules(self):
        """Megatron TP over ``tensor``: column-parallel q/k/v/gate/up,
        row-parallel o/down, vocab-sharded embedding + lm_head columns;
        expert-parallel rules join when the model is sparse."""
        rules = [
            (r"embed_tokens/embedding", P("tensor", None)),
            (r"self_attn/(q_proj|k_proj|v_proj)/kernel", P(None, "tensor")),
            (r"self_attn/o_proj/kernel", P("tensor", None)),
            (r"mlp/(gate_proj|up_proj)/kernel", P(None, "tensor")),
            (r"mlp/down_proj/kernel", P("tensor", None)),
            (r"lm_head/kernel", P(None, "tensor")),
        ]
        if self.moe_experts > 0:
            from .moe import MoeMlp

            rules = MoeMlp.partition_rules() + rules
        return rules


@MODELS.register("Llama")
def llama(vocab_size: int = 32000, n_layer: int = 12, n_head: int = 12,
          n_kv_head: int = 0, d_model: int = 768, d_ff: int = 0,
          max_len: int = 2048, bfloat16: bool = False,
          attn_impl: str = "xla", remat: bool = False, mesh=None,
          seq_layout: str = "natural", rope_base: float = 10000.0,
          rms_eps: float = 1e-6, window: int = 0, fused_head: bool = False,
          quant: str = "", kv_quant: str = "", lora_rank: int = 0,
          lora_alpha: float = 16.0):
    return LlamaLM(
        vocab_size=vocab_size, n_layer=n_layer, n_head=n_head,
        n_kv_head=n_kv_head, d_model=d_model, d_ff=d_ff, max_len=max_len,
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, mesh=mesh, seq_layout=seq_layout,
        rope_base=rope_base, rms_eps=rms_eps, window=window,
        fused_head=fused_head, quant=quant, kv_quant=kv_quant,
        lora_rank=lora_rank, lora_alpha=lora_alpha,
    )


@MODELS.register("Mistral")
def mistral(vocab_size: int = 32000, n_layer: int = 32, n_head: int = 32,
            n_kv_head: int = 8, d_model: int = 4096, d_ff: int = 14336,
            max_len: int = 32768, window: int = 4096,
            rope_base: float = 10000.0, rms_eps: float = 1e-5,
            bfloat16: bool = True, attn_impl: str = "flash",
            remat: bool = True, mesh=None, fused_head: bool = False,
            quant: str = "", kv_quant: str = "", lora_rank: int = 0,
            lora_alpha: float = 16.0):
    """Mistral-7B-shaped defaults: the Llama architecture with 4:1 GQA and
    a 4096-token sliding window (banded flash kernels + rolling decode
    cache). Same param tree as ``Llama``, so ``import_hf_llama`` applies
    to Mistral HF checkpoints too (they share the state-dict layout)."""
    return LlamaLM(
        vocab_size=vocab_size, n_layer=n_layer, n_head=n_head,
        n_kv_head=n_kv_head, d_model=d_model, d_ff=d_ff, max_len=max_len,
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, mesh=mesh, window=window,
        rope_base=rope_base, rms_eps=rms_eps, fused_head=fused_head,
        quant=quant, kv_quant=kv_quant, lora_rank=lora_rank,
        lora_alpha=lora_alpha,
    )


@MODELS.register("MixtralMoE")
def mixtral_moe(vocab_size: int = 32000, n_layer: int = 32, n_head: int = 32,
                n_kv_head: int = 8, d_model: int = 4096, d_ff: int = 14336,
                max_len: int = 32768, window: int = 4096,
                num_experts: int = 8, top_k: int = 2, moe_every: int = 1,
                capacity_factor: float = 1.25,
                aux_loss_weight: float = 0.01,
                rope_base: float = 1e6, rms_eps: float = 1e-5,
                bfloat16: bool = True, attn_impl: str = "flash",
                remat: bool = True, mesh=None, fused_head: bool = True,
                **overrides):
    """Mixtral-8x7B-shaped defaults: the Mistral trunk (4:1 GQA, sliding
    window) with every FFN replaced by 8 routed SwiGLU experts, top-2
    gating (models/moe.py, ``expert_act='swiglu'``). Expert weights
    shard over the ``expert`` mesh axis; combine with ``data``/``seq``
    axes for dp x ep x sp."""
    return LlamaLM(
        vocab_size=vocab_size, n_layer=n_layer, n_head=n_head,
        n_kv_head=n_kv_head, d_model=d_model, d_ff=d_ff, max_len=max_len,
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, mesh=mesh, window=window,
        rope_base=rope_base, rms_eps=rms_eps, fused_head=fused_head,
        moe_experts=num_experts, moe_top_k=top_k, moe_every=moe_every,
        moe_capacity_factor=capacity_factor,
        moe_aux_loss_weight=aux_loss_weight, **overrides,
    )


@MODELS.register("TinyLlama")
def tiny_llama(vocab_size: int = 256, n_layer: int = 2, n_head: int = 4,
               n_kv_head: int = 2, d_model: int = 64, d_ff: int = 0,
               max_len: int = 128, attn_impl: str = "xla",
               remat: bool = False, mesh=None, bfloat16: bool = False,
               seq_layout: str = "natural", window: int = 0,
               fused_head: bool = False, quant: str = "",
               kv_quant: str = "", lora_rank: int = 0,
               lora_alpha: float = 16.0):
    """Small GQA config for tests and dry runs."""
    return LlamaLM(
        vocab_size=vocab_size, n_layer=n_layer, n_head=n_head,
        n_kv_head=n_kv_head, d_model=d_model, d_ff=d_ff, max_len=max_len,
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, mesh=mesh, seq_layout=seq_layout,
        window=window, fused_head=fused_head, quant=quant,
        kv_quant=kv_quant, lora_rank=lora_rank, lora_alpha=lora_alpha,
    )
