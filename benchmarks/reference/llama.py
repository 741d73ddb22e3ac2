"""The Llama architecture as Mistral-7B uses it (Jiang et al. 2023;
mistralai/Mistral-7B-v0.1 config.json), plain.

Token embedding; per layer pre-RMSNorm attention (separate bias-free Q,
K, V projections, grouped-query: each of n_kv_head key/value heads serves
n_head / n_kv_head query heads; rotary position embedding in the
rotate-half convention; causal softmax restricted to the last `window`
keys, query t seeing keys (t - window, t]) and pre-RMSNorm SwiGLU MLP,
each added to the residual stream; a final RMSNorm; an untied head. Loss:
mean next-token cross entropy.

Attention is computed in blocks of query rows and each block's
probabilities are recomputed in the backward pass, so that a sequence of
8192 fits; that changes memory, not arithmetic.

`a` is the configuration's `arch.args`: n_layer, n_head, n_kv_head,
d_model, d_ff, vocab_size, window, rope_base, rms_eps.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import by_blocks, next_token_loss

QUERY_BLOCK = 512
MLP_BLOCK = 2048
EMBED_KEYS = ("embed_tokens/embedding",)
HEAD_KEYS = ("norm/weight", "lm_head/kernel")


def layer_names(a):
    return [f"layers_{i}" for i in range(a["n_layer"])]


def param_shapes(a) -> dict:
    d, v, ff = a["d_model"], a["vocab_size"], a["d_ff"]
    hd = d // a["n_head"]
    kv = a["n_kv_head"] * hd
    shapes = {"embed_tokens/embedding": (v, d), "norm/weight": (d,),
              "lm_head/kernel": (d, v)}
    for h in layer_names(a):
        shapes[f"{h}/input_layernorm/weight"] = (d,)
        shapes[f"{h}/post_attention_layernorm/weight"] = (d,)
        for name, n_in, n_out in (
                ("self_attn/q_proj", d, d), ("self_attn/k_proj", d, kv),
                ("self_attn/v_proj", d, kv), ("self_attn/o_proj", d, d),
                ("mlp/gate_proj", d, ff), ("mlp/up_proj", d, ff),
                ("mlp/down_proj", ff, d)):
            shapes[f"{h}/{name}/kernel"] = (n_in, n_out)
    return shapes


def matmul_weights(a) -> int:
    """Parameters that multiply every token: per block Q and O d^2 each,
    K and V d x (kv heads x head size) each, SwiGLU 3 x d x d_ff; the
    untied head d x vocab (the embedding is a lookup)."""
    d = a["d_model"]
    kv = a["n_kv_head"] * (d // a["n_head"])
    return a["n_layer"] * (2 * d * d + 2 * d * kv + 3 * d * a["d_ff"]) \
        + d * a["vocab_size"]


def parameters(a) -> int:
    return sum(math.prod(shape) for shape in param_shapes(a).values())


def init_rules(a) -> list:
    """initializer_range 0.02 everywhere, norms at identity."""
    return [(r"/weight$", "ones", 0.0), (r"", "normal", 0.02)]


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def _rope(x, base):
    """x: [B, T, H, D]; pair (i, i + D/2) is rotated by t * base^(-2i/D)."""
    t, d = x.shape[1], x.shape[-1]
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window):
    """q: [B, T, H, D]; k, v: [B, T, H, D] (already repeated per group)."""
    b, t, h, d = q.shape
    bq = min(QUERY_BLOCK, t)
    k_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, bq, axis=1)
        q_pos = start + jnp.arange(bq)[:, None]
        seen = k_pos <= q_pos
        if window > 0:
            seen &= q_pos - k_pos < window
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(block, jnp.arange(0, t, bq))      # [n, B, bq, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, d)


def embed(a, p, tok):
    return p["embed_tokens/embedding"][tok]


def layer(a, p, x, dot):
    b, t, d = x.shape
    nh, nkv = a["n_head"], a["n_kv_head"]
    hd = d // nh
    h = _rms_norm(x, p["input_layernorm/weight"], a["rms_eps"])
    q = dot(h, p["self_attn/q_proj/kernel"]).reshape(b, t, nh, hd)
    k = dot(h, p["self_attn/k_proj/kernel"]).reshape(b, t, nkv, hd)
    v = dot(h, p["self_attn/v_proj/kernel"]).reshape(b, t, nkv, hd)
    q, k = _rope(q, a["rope_base"]), _rope(k, a["rope_base"])
    k, v = (jnp.repeat(z, nh // nkv, axis=2) for z in (k, v))
    ctx = _attention(q, k, v, a["window"]).reshape(b, t, d)
    x = x + dot(ctx, p["self_attn/o_proj/kernel"])
    h = _rms_norm(x, p["post_attention_layernorm/weight"], a["rms_eps"])

    def swiglu(hb):
        gate = jax.nn.silu(dot(hb, p["mlp/gate_proj/kernel"]))
        return dot(gate * dot(hb, p["mlp/up_proj/kernel"]),
                   p["mlp/down_proj/kernel"])

    return x + jnp.moveaxis(by_blocks(swiglu, (h,), MLP_BLOCK), 0, 1
                            ).reshape(b, t, d)


def head_loss(a, p, x, tok, dot):
    h = _rms_norm(x, p["norm/weight"], a["rms_eps"])
    return next_token_loss(h, p["lm_head/kernel"], tok, dot)
