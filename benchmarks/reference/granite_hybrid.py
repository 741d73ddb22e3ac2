"""The `granitemoehybrid` architecture as granite-4.0-h-micro uses it
(config.json of ibm-granite/granite-4.0-h-micro: 40 layers, 36 `mamba` to
4 `attention` in a period of 10, hidden 2048, `num_local_experts` 0, so
dense; Mamba-2, Dao & Gu 2024), plain, and as one chip of a
vocabulary-parallel deployment holds it.

Residual stream `x [B, T, d_model]`; every layer is TWO sublayers, each
behind its own RMSNorm and each added through one scalar; eps `rms_eps`;
no biases but the convolution's; a final RMSNorm; the head is the
embedding, tied. Loss: mean next-token cross entropy.

    x = embedding[tok] * embedding_multiplier
    for each entry of layer_types:
        h = RMSNorm_in(x);   y = mamba(h) or attend(h)
        x = x + residual_multiplier * y
        h = RMSNorm_post(x); g, u = h W_gate, h W_up
        x = x + residual_multiplier * ((silu(g) * u) W_down)
    logits = RMSNorm_f(x) embedding^T / logits_scaling

`mamba`: Mamba-2 exactly as reference/nemotron_h.py states it (its
`_mamba`, the recurrence position by position), with H `ssm_n_head`, P
`ssm_head_dim`, G `ssm_n_group`, N `ssm_state`, `ssm_conv` taps; no clamp
on `dt` (`time_step_limit` is not in the config).

`attention`: `n_head` query heads on `n_kv_head` key-value heads of
`head_dim`, separate projections, causal softmax of
`q . k * attention_multiplier` over all earlier keys, no rotation
(`position_embedding_type` `nope`). In blocks of query rows whose
probabilities are recomputed in the backward pass: memory, not arithmetic.

The chip's share: `vocab_size` rows of the tied matrix. A sliced
vocabulary is a smaller vocabulary: ids, logits and loss are over the
slice (the logits over rows `[lo, lo + n)` are those columns of the uncut
model's logits; tests/benchmarks/test_bm_granite_hybrid.py). One
state-space group has no exact share by heads, so every mixer is whole.

Departures from the published description (the configuration's `assumed`
repeats them): the MLP's one input matrix `[d_model, 2 d_ff]` is held as
its halves `gate_proj` and `up_proj` (the same function and count);
`ssm_chunk` is the program's tiling and not here at all, but for the FLOP
count, which takes the published chunk as any schedule's causal half.

`a` is the configuration's `sizes`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import nemotron_h
from .common import by_blocks, next_token_loss
from .llama import _rms_norm
from .nemotron_h import _mamba

QUERY_BLOCK = 512
MLP_BLOCK = 2048
EMBED_KEYS = ("embed_tokens/embedding",)
HEAD_KEYS = ("norm/weight", "embed_tokens/embedding")
# a layer type's mixer in reference/nemotron_h.py's characters
MIXERS = {"mamba": "M", "attention": "*"}


def layer_names(a):
    return [f"layers_{i}" for i in range(len(a["layer_types"]))]


def layer_shapes(a, kind: str) -> dict:
    """One layer's leaves without the layer's prefix."""
    d, ff = a["d_model"], a["d_ff"]
    shapes = {"input_layernorm/weight": (d,),
              "post_attention_layernorm/weight": (d,),
              "mlp/gate_proj/kernel": (d, ff), "mlp/up_proj/kernel": (d, ff),
              "mlp/down_proj/kernel": (ff, d)}
    if kind not in MIXERS:
        raise ValueError(f"layer type {kind!r}: one of {tuple(MIXERS)!r}")
    mixer = nemotron_h.layer_shapes(a, MIXERS[kind])
    shapes.update({k: s for k, s in mixer.items() if k.startswith("mixer/")})
    return shapes


def param_shapes(a) -> dict:
    shapes = {"embed_tokens/embedding": (a["vocab_size"], a["d_model"]),
              "norm/weight": (a["d_model"],)}
    for name, kind in zip(layer_names(a), a["layer_types"]):
        shapes.update({f"{name}/{k}": s
                       for k, s in layer_shapes(a, kind).items()})
    return shapes


def parameters(a) -> int:
    return sum(math.prod(shape) for shape in param_shapes(a).values())


def matmul_weights(a) -> int:
    """Parameters that multiply every token. `mamba`: in_proj and out_proj
    (the convolution's taps are no matrix). `attention`: Q, K, V, O. Every
    layer's gated MLP, 3 x d_model x d_ff. The tied head d_model x vocab
    ONCE (the embedding is a lookup)."""
    d, hd = a["d_model"], a["head_dim"]
    d_in = a["ssm_n_head"] * a["ssm_head_dim"]
    per_kind = {
        "mamba": d * (2 * d_in + 2 * a["ssm_n_group"] * a["ssm_state"]
                      + a["ssm_n_head"]) + d_in * d,
        "attention": 2 * d * a["n_head"] * hd + 2 * d * a["n_kv_head"] * hd,
    }
    return sum(per_kind[kind] + 3 * d * a["d_ff"]
               for kind in a["layer_types"]) + d * a["vocab_size"]


def mixer_flops_per_token(a, seq_len: int) -> float:
    """Attention's scores and context, 12 x n_head x head_dim x
    `mean_visible_keys` a layer, and a scan's chunk products a layer, both
    by reference/nemotron_h.py's count."""
    pattern = "".join(MIXERS[kind] for kind in a["layer_types"])
    return nemotron_h.mixer_flops_per_token({**a, "pattern": pattern},
                                            seq_len)


def init_rules(a) -> list:
    """Normal 0.02 throughout (the catalog's row carries no
    `initializer_range`; the family's own is of that order); norms and the
    skip `D` at identity; `A_log` 0 (A = -1) and `dt_bias` the inverse
    softplus of 0.005, so that a chunk of 256 positions decays a state to
    about 0.28 and what chunks hand on matters (reference/nemotron_h.py's
    argument at its chunk of 128 and step 0.01)."""
    return [(r"norm/weight$|norm_weight$", "ones", 0.0),
            (r"/D$", "ones", 0.0),
            (r"/A_log$", "const", 0.0),
            (r"/dt_bias$", "const", math.log(math.expm1(0.005))),
            (r"", "normal", 0.02)]


def embed(a, p, tok):
    return p["embed_tokens/embedding"][tok] * a["embedding_multiplier"]


def _attention(q, k, v, scale):
    """q, k, v: [B, T, H, D] (k and v already repeated per group); scores
    are `q . k * scale`."""
    b, t, h, d = q.shape
    bq = min(QUERY_BLOCK, t)
    k_pos = jnp.arange(t)[None, :]

    @jax.checkpoint
    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, bq, axis=1)
        seen = k_pos <= start + jnp.arange(bq)[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(block, jnp.arange(0, t, bq))      # [n, B, bq, H, D]
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, d)


def _attend(a, p, h, dot):
    b, t, _ = h.shape
    nh, nkv, hd = a["n_head"], a["n_kv_head"], a["head_dim"]
    q = dot(h, p["mixer/q_proj/kernel"]).reshape(b, t, nh, hd)
    k = dot(h, p["mixer/k_proj/kernel"]).reshape(b, t, nkv, hd)
    v = dot(h, p["mixer/v_proj/kernel"]).reshape(b, t, nkv, hd)
    k, v = (jnp.repeat(m, nh // nkv, axis=2) for m in (k, v))
    ctx = _attention(q, k, v, a["attention_multiplier"])
    return dot(ctx.reshape(b, t, nh * hd), p["mixer/o_proj/kernel"])


def layer(a, p, x, dot):
    """One layer; its kind is told from the leaves it is given."""
    b, t, d = x.shape
    h = _rms_norm(x, p["input_layernorm/weight"], a["rms_eps"])
    mix = _mamba if "mixer/in_proj/kernel" in p else _attend
    x = x + a["residual_multiplier"] * mix(a, p, h, dot)
    h = _rms_norm(x, p["post_attention_layernorm/weight"], a["rms_eps"])

    def mlp(hb):
        gate = jax.nn.silu(dot(hb, p["mlp/gate_proj/kernel"]))
        return dot(gate * dot(hb, p["mlp/up_proj/kernel"]),
                   p["mlp/down_proj/kernel"])

    return x + a["residual_multiplier"] * jnp.moveaxis(
        by_blocks(mlp, (h,), MLP_BLOCK), 0, 1).reshape(b, t, d)


def head_loss(a, p, x, tok, dot):
    h = _rms_norm(x, p["norm/weight"], a["rms_eps"]) / a["logits_scaling"]
    return next_token_loss(h, p["embed_tokens/embedding"].T, tok, dot)
