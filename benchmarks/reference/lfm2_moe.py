"""The `lfm2_moe` architecture as LFM2-24B-A2B uses it (config.json of
LiquidAI/LFM2-24B-A2B: 40 layers, 30 `conv` to 10 `full_attention`, from
layer 2 on a period of four, hidden 2048, two leading dense layers and
then 64 routed experts a layer, 4 a token, no shared expert), plain, and
as one chip of a deployment holds it.

Residual stream `x [B, T, d_model]`; RMSNorm `n(.)`,
`w * x / sqrt(mean(x^2) + rms_eps)`; no bias anywhere:

    x = embedding[tok]
    for layer i, of kind layer_types[i]:
        x = x + mixer_i(n_operator(x))
        x = x + second_i(n_ffn(x))
    logits = n_embedding(x) embedding^T             (the head is tied)

Loss: mean next-token cross entropy.

`conv`, the gated short convolution (`Lfm2ShortConv`), on `h`:
`[B, C, z] = split3(h W_in)` (`W_in [d_model, 3 d_model]`, in that
order); `u = B * z`; `c_t = sum_i taps[i] * u_{t - (conv_taps - 1) + i}` a
channel: depthwise, causal, zeros before the sequence, no bias
(`conv_bias` false) and NO activation; `y = (C * c) W_out`. Taken
position by position as written (the shifted sum).

`full_attention`: `n_head` query heads on `n_kv_head` key-value heads of
`head_dim`; `q = n_q(h W_q)`, `k = n_k(h W_k)`, an RMSNorm over each
head's `head_dim` channels with one weight `[head_dim]` for all heads of
q and one for k, eps `rms_eps`; `v = h W_v`; rotate-half rotary embedding
of base `rope_base` on q and k AFTER the norms; causal softmax of
`q . k / sqrt(head_dim)` over all earlier keys; `W_o`. In blocks of query
rows (reference/llama.py's) whose probabilities are recomputed in the
backward pass: memory, not arithmetic.

Second sublayer of a layer `i < n_dense_layers`: a gated MLP,
`(silu(h w1) * (h w3)) w2`, `d_ff` wide. Of every other layer, the routed
experts: scores `s = sigmoid(h W_r)` over all `moe_n_routed`; the
`moe_top_k` with the largest `s + b` (`b` the selection bias,
`use_expert_bias`: no gradient reaches it); weights `w_e = moe_scale *
s_e / (sum of the chosen s + 1e-20)` (`norm_topk_prob`;
`routed_scaling_factor` 1); `y = sum over chosen e held here of w_e *
(silu(h G_e) * (h U_e)) D_e`, `moe_d_ff` wide. Each held expert is
computed for every token, in blocks of tokens, and weighed by `w_e`,
which is 0 where the token did not choose it.

The chip's share. `moe_held = [offset, count]` of the routed experts and
`vocab_size` rows of the tied matrix are what this chip holds. The router
keeps its `moe_n_routed` outputs and `moe_top_k` a token, the weights are
normalised over all the chosen, held or not, and what the absent experts
would add is left out: the four shares of a layer add up to the uncut
layer (tests/benchmarks/test_bm_lfm2_moe.py). A sliced vocabulary is a
smaller vocabulary: ids, logits and loss are over the slice. Mixers, the
leading layer's MLP, routers and norms are whole.

Departures and assumptions (the configuration's `assumed` repeats them):
the tied head (the config's row carries no `tie_word_embeddings`; the
LFM2 family ties); the published layer adds 1e-6 to the sum of the
chosen scores where this and the program add 1e-20 (the chosen sigmoid
scores sum to about 2, so 5e-7 relative); rotate-half halves (pair
`(i, i + head_dim / 2)`), the convention of the family's public code.

`a` is the configuration's `sizes`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..flops import mean_visible_keys
from .common import by_blocks, next_token_loss
from .llama import _attention, _rms_norm, _rope
from .solar_open2 import _swiglu

TOKEN_BLOCK = 2048
EMBED_KEYS = ("embed_tokens/embedding",)
HEAD_KEYS = ("norm/weight", "embed_tokens/embedding")
KINDS = ("conv", "full_attention")


def layer_names(a):
    return [f"layers_{i}" for i in range(len(a["layer_types"]))]


def _held(a) -> tuple:
    lo, count = a["moe_held"]
    return lo, count or a["moe_n_routed"]


def layer_shapes(a, kind: str, dense: bool = False) -> dict:
    """One layer's leaves without the layer's prefix; `dense`: one of
    the leading layers, whose second sublayer is the gated MLP."""
    d = a["d_model"]
    shapes = {"operator_norm/weight": (d,), "ffn_norm/weight": (d,)}
    if kind == "conv":
        shapes.update({"mixer/in_proj/kernel": (d, 3 * d),
                       "mixer/conv_kernel": (a["conv_taps"], d),
                       "mixer/out_proj/kernel": (d, d)})
    elif kind == "full_attention":
        hd = a["head_dim"]
        shapes.update({
            "mixer/q_proj/kernel": (d, a["n_head"] * hd),
            "mixer/k_proj/kernel": (d, a["n_kv_head"] * hd),
            "mixer/v_proj/kernel": (d, a["n_kv_head"] * hd),
            "mixer/q_layernorm/weight": (hd,),
            "mixer/k_layernorm/weight": (hd,),
            "mixer/o_proj/kernel": (a["n_head"] * hd, d)})
    else:
        raise ValueError(f"layer type {kind!r}: one of {KINDS!r}")
    if dense:
        ff = a["d_ff"]
        shapes.update({"mlp/gate_proj/kernel": (d, ff),
                       "mlp/up_proj/kernel": (d, ff),
                       "mlp/down_proj/kernel": (ff, d)})
    else:
        ff, n_held = a["moe_d_ff"], _held(a)[1]
        shapes.update({"experts/router": (d, a["moe_n_routed"]),
                       "experts/selection_bias": (a["moe_n_routed"],),
                       "experts/experts_gate": (n_held, d, ff),
                       "experts/experts_up": (n_held, d, ff),
                       "experts/experts_down": (n_held, ff, d)})
    return shapes


def param_shapes(a) -> dict:
    shapes = {"embed_tokens/embedding": (a["vocab_size"], a["d_model"]),
              "norm/weight": (a["d_model"],)}
    for i, (name, kind) in enumerate(zip(layer_names(a), a["layer_types"])):
        shapes.update({f"{name}/{k}": s for k, s in layer_shapes(
            a, kind, i < a["n_dense_layers"]).items()})
    return shapes


def parameters(a) -> int:
    return sum(math.prod(shape) for shape in param_shapes(a).values())


def matmul_weights(a) -> int:
    """Parameters that multiply every token. `conv`: `W_in` and `W_out`
    (the taps are no matrix). `full_attention`: Q, K, V, O. A leading
    layer's gated MLP, 3 x d_model x d_ff. Every other layer: the router
    whole; a routed expert held here is met by `moe_top_k /
    moe_n_routed` of the tokens (uniform routing over the published
    experts, which is how the cell routes since benchmarks/balance.py).
    The tied head d_model x vocab ONCE (the embedding is a lookup)."""
    d, hd = a["d_model"], a["head_dim"]
    per_kind = {
        "conv": 4 * d * d,
        "full_attention": 2 * d * a["n_head"] * hd
        + 2 * d * a["n_kv_head"] * hd}
    experts = d * a["moe_n_routed"] + _held(a)[1] * 3 * d * a["moe_d_ff"] \
        * a["moe_top_k"] // a["moe_n_routed"]
    return sum(
        per_kind[kind] + (3 * d * a["d_ff"] if i < a["n_dense_layers"]
                          else experts)
        for i, kind in enumerate(a["layer_types"])) + d * a["vocab_size"]


def mixer_flops_per_token(a, seq_len: int) -> float:
    """Forward plus backward (3 x forward) of the products no weight
    enters: attention's scores and context, 12 x n_head x head_dim x
    `mean_visible_keys` a `full_attention` layer. A convolution's
    2 x conv_taps x d_model a token and its two gates are LEFT OUT, as
    every other reference leaves its convolutions out: 0.04 MFLOP a
    layer beside 101 MFLOP of matrices."""
    return a["layer_types"].count("full_attention") * 12 * a["n_head"] \
        * a["head_dim"] * mean_visible_keys(seq_len)


def init_rules(a) -> list:
    """Normal 0.02 for every matrix and the convolutions' taps (the
    config's row gives no `initializer_range`; the family's default);
    every norm at identity, the q/k norms too; no bias exists; the
    selection bias 0, where benchmarks/balance.py starts its solve."""
    return [(r"norm/weight$", "ones", 0.0),
            (r"/selection_bias$", "const", 0.0),
            (r"", "normal", 0.02)]


def embed(a, p, tok):
    return p["embed_tokens/embedding"][tok]


def _short_conv(a, p, h, dot):
    t, taps = h.shape[1], a["conv_taps"]
    gate_in, gate_out, z = jnp.split(dot(h, p["mixer/in_proj/kernel"]), 3,
                                     axis=-1)
    u = jnp.pad(gate_in * z, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(p["mixer/conv_kernel"][i] * u[:, i:i + t] for i in range(taps))
    return dot(gate_out * c, p["mixer/out_proj/kernel"])


def _attend(a, p, h, dot):
    b, t, _ = h.shape
    nh, nkv, hd = a["n_head"], a["n_kv_head"], a["head_dim"]
    q = dot(h, p["mixer/q_proj/kernel"]).reshape(b, t, nh, hd)
    k = dot(h, p["mixer/k_proj/kernel"]).reshape(b, t, nkv, hd)
    v = dot(h, p["mixer/v_proj/kernel"]).reshape(b, t, nkv, hd)
    q = _rope(_rms_norm(q, p["mixer/q_layernorm/weight"], a["rms_eps"]),
              a["rope_base"])
    k = _rope(_rms_norm(k, p["mixer/k_layernorm/weight"], a["rms_eps"]),
              a["rope_base"])
    k, v = (jnp.repeat(m, nh // nkv, axis=2) for m in (k, v))
    ctx = _attention(q, k, v, 0).reshape(b, t, nh * hd)
    return dot(ctx, p["mixer/o_proj/kernel"])


def _blocks_of_tokens(f, u):
    return jnp.moveaxis(by_blocks(f, (u,), TOKEN_BLOCK), 0, 1
                        ).reshape(u.shape)


def router_scores(a, p, u, dot):
    """Every published expert's score of every token of `u`, the normed
    input of a layer's experts, `[..., moe_n_routed]`: what `experts`
    routes by, and what benchmarks/balance.py solves the selection bias
    on."""
    return jax.nn.sigmoid(dot(u, p["experts/router"]))


def experts(a, p, u, dot):
    lo, n_held = _held(a)

    def tokens(ub):
        scores = router_scores(a, p, ub, dot)
        _, chosen = jax.lax.top_k(jax.lax.stop_gradient(
            scores + p["experts/selection_bias"]), a["moe_top_k"])
        took = jnp.sum(jax.nn.one_hot(chosen, a["moe_n_routed"],
                                      dtype=scores.dtype), axis=-2)
        picked = scores * took
        weights = a["moe_scale"] * picked / (
            jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        return sum(
            weights[..., lo + e, None] * _swiglu(
                ub, p["experts/experts_gate"][e], p["experts/experts_up"][e],
                p["experts/experts_down"][e], dot)
            for e in range(n_held))

    return _blocks_of_tokens(tokens, u)


def expert_input(a, p, x, dot):
    """`(stream, u)` of a layer: the stream behind the mixer, its kind
    told from the leaves given, and the second sublayer's normed input.
    A layer with experts gives `stream + experts(a, p, u, dot)`."""
    h = _rms_norm(x, p["operator_norm/weight"], a["rms_eps"])
    mixer = _short_conv if "mixer/conv_kernel" in p else _attend
    stream = x + mixer(a, p, h, dot)
    return stream, _rms_norm(stream, p["ffn_norm/weight"], a["rms_eps"])


def layer(a, p, x, dot):
    """One layer; its mixer and whether it is a leading dense layer are
    told from the leaves it is given."""
    stream, u = expert_input(a, p, x, dot)
    if "mlp/gate_proj/kernel" in p:
        return stream + _blocks_of_tokens(lambda ub: _swiglu(
            ub, p["mlp/gate_proj/kernel"], p["mlp/up_proj/kernel"],
            p["mlp/down_proj/kernel"], dot), u)
    return stream + experts(a, p, u, dot)


def head_loss(a, p, x, tok, dot):
    h = _rms_norm(x, p["norm/weight"], a["rms_eps"])
    return next_token_loss(h, p["embed_tokens/embedding"].T, tok, dot)
