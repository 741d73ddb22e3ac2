"""GPT-2 (Radford et al. 2019; openai-community/gpt2* config.json), plain.

Token plus learned position embedding; per layer pre-LayerNorm attention
(fused QKV projection with bias, causal softmax, output projection) and
pre-LayerNorm MLP (4x, GELU in its tanh form, "gelu_new"), each added to
the residual stream; a final LayerNorm; the head is the token embedding
transposed. Loss: mean next-token cross entropy. No dropout (the
configuration file sets it to 0 and says why).

`a` is the configuration's `arch.args` with the size resolved:
n_layer, n_head, d_model, vocab_size, max_len.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import next_token_loss

LN_EPS = 1e-5            # layer_norm_epsilon
EMBED_KEYS = ("wte/embedding", "wpe")
HEAD_KEYS = ("ln_f/scale", "ln_f/bias", "wte/embedding")


def layer_names(a):
    return [f"h_{i}" for i in range(a["n_layer"])]


def param_shapes(a) -> dict:
    d, v = a["d_model"], a["vocab_size"]
    shapes = {"wte/embedding": (v, d), "wpe": (a["max_len"], d),
              "ln_f/scale": (d,), "ln_f/bias": (d,)}
    for h in layer_names(a):
        for ln in ("ln_1", "ln_2"):
            shapes[f"{h}/{ln}/scale"] = (d,)
            shapes[f"{h}/{ln}/bias"] = (d,)
        for name, n_in, n_out in (("attn/qkv", d, 3 * d), ("attn/out", d, d),
                                  ("mlp/up", d, 4 * d), ("mlp/down", 4 * d, d)):
            shapes[f"{h}/{name}/kernel"] = (n_in, n_out)
            shapes[f"{h}/{name}/bias"] = (n_out,)
    return shapes


def matmul_weights(a) -> int:
    """Parameters that multiply every token: per block QKV 3d^2, output
    d^2, MLP 8d^2; the tied head d x vocab once."""
    d = a["d_model"]
    return a["n_layer"] * 12 * d * d + d * a["vocab_size"]


def parameters(a) -> int:
    return sum(math.prod(shape) for shape in param_shapes(a).values())


def init_rules(a) -> list:
    """GPT-2's scheme: normal(0.02), residual projections scaled by
    1/sqrt(2 * n_layer), positions normal(0.01), norms at identity."""
    resid = 0.02 / math.sqrt(2 * a["n_layer"])
    return [(r"/scale$", "ones", 0.0), (r"/bias$", "zeros", 0.0),
            (r"^wpe$", "normal", 0.01),
            (r"(attn/out|mlp/down)/kernel$", "normal", resid),
            (r"", "normal", 0.02)]


def _layer_norm(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1 + jnp.tanh(math.sqrt(2 / math.pi)
                                   * (x + 0.044715 * x ** 3)))


def embed(a, p, tok):
    return p["wte/embedding"][tok] + p["wpe"][: tok.shape[1]][None]


def layer(a, p, x, dot):
    b, t, d = x.shape
    nh = a["n_head"]
    hd = d // nh
    h = _layer_norm(x, p["ln_1/scale"], p["ln_1/bias"])
    qkv = dot(h, p["attn/qkv/kernel"]) + p["attn/qkv/bias"]
    q, k, v = (qkv[..., i * d:(i + 1) * d].reshape(b, t, nh, hd)
               for i in range(3))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, d)
    x = x + dot(ctx, p["attn/out/kernel"]) + p["attn/out/bias"]
    h = _layer_norm(x, p["ln_2/scale"], p["ln_2/bias"])
    h = _gelu_new(dot(h, p["mlp/up/kernel"]) + p["mlp/up/bias"])
    return x + dot(h, p["mlp/down/kernel"]) + p["mlp/down/bias"]


def head_loss(a, p, x, tok, dot):
    h = _layer_norm(x, p["ln_f/scale"], p["ln_f/bias"])
    return next_token_loss(h, p["wte/embedding"].T, tok, dot)
