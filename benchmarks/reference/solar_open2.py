"""The `solar_open2` architecture as Solar-Open2-250B uses it (config.json
of upstage/Solar-Open2-250B; its linear layer is Kimi Delta Attention,
arXiv:2510.26692), plain, and as one chip of a deployment holds it.

Residual stream `x [B, T, d_model]`; RMSNorm `n(.)` with eps `rms_eps`;
every layer is `h = x + mixer(n1(x))`, `out = h + experts(n2(h))`, the
mixer's kind by the character of `pattern` (`first_k_dense_replace` 0: no
dense layer, and the config's `intermediate_size` is used by none). Token
embedding, a final RMSNorm, an untied head, no position embedding
(`use_rope` false). Loss: mean next-token cross entropy.

`K`, the KDA mixer (H `kda_n_head` heads of P `kda_head_dim`, `kda_conv`
taps, low rank `kda_rank`), on `u = n1(x)`:
`q' = silu(conv(u Wq))`, `k' = silu(conv(u Wk))`, `v = silu(conv(u Wv))`,
depthwise, causal, without bias (tap i multiplies position
`t - (taps - 1) + i`); a head's `q = q' / sqrt(|q'|^2 + 1e-6) * P^-0.5`,
`k = k' / sqrt(|k'|^2 + 1e-6)`; the log decay of each key channel
`g_t = -exp(A_log_h) * softplus((u Wf1 Wf2)_t + dt_bias)`, `[P]` a head;
`beta_t = 2 sigmoid(u w_h)`, in (0, 2) (`kda_allow_neg_eigval`). State a
head, `[P, P]`, from zeros:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

then `y = (n_head(o_t) * sigmoid((u Wg1 Wg2 + b_g)_t)) Wo`, `n_head` an
RMSNorm over a head's P channels with one weight `[P]` for all heads. The
recurrence is computed as written, position by position; segments of
`SCAN_SEGMENT` positions are recomputed in the backward pass, which
changes memory, not arithmetic.

`*`, gated attention: `n_head` query heads on `n_kv_head` key-value heads
of `head_dim`, separate projections, causal softmax over all earlier
keys, scores over sqrt(head_dim), no rotary embedding;
`y = (ctx * sigmoid(u Wgate)) Wo`, a gate per output channel. In blocks
of query rows (reference/llama.py's).

Experts, on `u = n2(h)`: scores `s = sigmoid(u Wr)` over all
`moe_n_routed`; the `moe_top_k` with the largest `s + b` (`b` the
selection bias: no gradient reaches it); weights `w_e = moe_scale * s_e /
(sum of the chosen s + 1e-20)`; `y = sum over chosen e held here of w_e *
(silu(u G_e) * (u U_e)) D_e + (silu(u G_s) * (u U_s)) D_s`. Each held
expert is computed for every token and weighed by `w_e`, which is 0 where
the token did not choose it.

The chip's share. `kda_n_head`, `n_head`, `n_kv_head` and `moe_held =
[offset, count]` are what this chip holds of a layer. Heads are
independent in both mixers: the KDA's first low-rank matrices (`Wf1`,
`Wg1`), its head norm's weight and the layer norms are whole on every
chip, `Wf2`, `Wg2`, `b_g`, `dt_bias`, `A_log` and `w` are cut by head, and
the chip's `Wo` result is one summand of the mixer's output; so is the
attention's, by query heads with their key-value head, and the routed
experts', by experts. The router keeps its `moe_n_routed` outputs and
`moe_top_k` a token, the weights are normalised over all the chosen, held
or not, and what the absent experts would add is left out. The shared
expert is whole on every chip.

Departures and assumptions (the configuration's `assumed` repeats them).
The config names the mechanism (`kda_*`, `linear_attn_config`) and not
every size; as Kimi Linear's public layer has them: convolutions without
bias, both low ranks equal to the head size, the L2 norm's eps 1e-6
inside the root, `b_g` the only bias, `A_log` one scalar a head, the
formulas of `g` and `y`. Of the attention: the gate's form (per channel,
from the normed input, before `Wo`), no norm on queries or keys, no
biases. Of the experts: sigmoid scores and a selection bias as the
family's earlier `solar_open` router, SiLU, one group.

`a` is the configuration's `sizes`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..flops import mean_visible_keys
from .common import by_blocks, next_token_loss
from .llama import _attention, _rms_norm

SCAN_SEGMENT = 128
TOKEN_BLOCK = 2048
L2_EPS = 1e-6
EMBED_KEYS = ("embed_tokens/embedding",)
HEAD_KEYS = ("norm/weight", "lm_head/kernel")


def layer_names(a):
    return [f"layers_{i}" for i in range(len(a["pattern"]))]


def _held(a) -> tuple:
    lo, count = a["moe_held"]
    return lo, count or a["moe_n_routed"]


def layer_shapes(a, kind: str) -> dict:
    """One layer's leaves without the layer's prefix."""
    d, ff, sh = a["d_model"], a["moe_d_ff"], a["moe_shared_d_ff"]
    n_held = _held(a)[1]
    shapes = {
        "input_layernorm/weight": (d,),
        "post_attention_layernorm/weight": (d,),
        "experts/router": (d, a["moe_n_routed"]),
        "experts/selection_bias": (a["moe_n_routed"],),
        "experts/experts_gate": (n_held, d, ff),
        "experts/experts_up": (n_held, d, ff),
        "experts/experts_down": (n_held, ff, d),
        "experts/shared/gate_proj/kernel": (d, sh),
        "experts/shared/up_proj/kernel": (d, sh),
        "experts/shared/down_proj/kernel": (sh, d)}
    if kind == "K":
        h, p, r = a["kda_n_head"], a["kda_head_dim"], a["kda_rank"]
        for name in "qkv":
            shapes[f"mixer/{name}_proj/kernel"] = (d, h * p)
            shapes[f"mixer/{name}_conv"] = (a["kda_conv"], h * p)
        shapes.update({
            "mixer/f_a_proj/kernel": (d, r),
            "mixer/f_b_proj/kernel": (r, h * p),
            "mixer/dt_bias": (h * p,), "mixer/A_log": (h,),
            "mixer/b_proj/kernel": (d, h),
            "mixer/g_a_proj/kernel": (d, r),
            "mixer/g_b_proj/kernel": (r, h * p),
            "mixer/g_b_proj/bias": (h * p,),
            "mixer/o_norm": (p,),
            "mixer/o_proj/kernel": (h * p, d)})
    elif kind == "*":
        hd = a["head_dim"]
        shapes.update({
            "mixer/q_proj/kernel": (d, a["n_head"] * hd),
            "mixer/k_proj/kernel": (d, a["n_kv_head"] * hd),
            "mixer/v_proj/kernel": (d, a["n_kv_head"] * hd),
            "mixer/g_proj/kernel": (d, a["n_head"] * hd),
            "mixer/o_proj/kernel": (a["n_head"] * hd, d)})
    else:
        raise ValueError(f"pattern character {kind!r}: one of 'K', '*'")
    return shapes


def param_shapes(a) -> dict:
    d, v = a["d_model"], a["vocab_size"]
    shapes = {"embed_tokens/embedding": (v, d), "norm/weight": (d,),
              "lm_head/kernel": (d, v)}
    for name, kind in zip(layer_names(a), a["pattern"]):
        shapes.update({f"{name}/{k}": s
                       for k, s in layer_shapes(a, kind).items()})
    return shapes


def parameters(a) -> int:
    return sum(math.prod(shape) for shape in param_shapes(a).values())


def matmul_weights(a) -> int:
    """Parameters that multiply every token. K: the three projections,
    both low-rank pairs, `beta`'s and `o_proj` (the convolutions' 4 taps a
    channel are no matrix). `*`: Q, K, V, the gate, O. Every layer: the
    router and the shared expert whole; a routed expert held here is met
    by `moe_top_k / moe_n_routed` of the tokens (uniform routing over the
    published experts, which is how the cell routes since
    benchmarks/balance.py). The untied head."""
    d = a["d_model"]
    hp, r = a["kda_n_head"] * a["kda_head_dim"], a["kda_rank"]
    hd = a["head_dim"]
    experts = d * a["moe_n_routed"] + 3 * d * a["moe_shared_d_ff"] \
        + _held(a)[1] * 3 * d * a["moe_d_ff"] \
        * a["moe_top_k"] // a["moe_n_routed"]
    per_kind = {
        "K": 4 * d * hp + 2 * (d * r + r * hp) + d * a["kda_n_head"],
        "*": 3 * d * a["n_head"] * hd + 2 * d * a["n_kv_head"] * hd,
    }
    return sum(per_kind[kind] + experts for kind in a["pattern"]) \
        + d * a["vocab_size"]


def mixer_flops_per_token(a, seq_len: int) -> float:
    """Forward plus backward (3 x forward) of the products no weight
    enters.

    `*`: scores and context, 4 x heads x head size a visible key:
    12 x n_head x head_dim x mean_visible_keys.

    K, forward a token and head, as the recurrence states it: `k^T S`
    (what the rule corrects), the rank-one update and `q^T S`, 2 P^2
    each: 18 x H x P^2 a layer. The decay's elementwise product, the
    triangular system a chunked form solves and the convolutions are
    left out: the schedule is the program's choice."""
    attn = 12 * a["n_head"] * a["head_dim"] * mean_visible_keys(seq_len)
    kda = 18 * a["kda_n_head"] * a["kda_head_dim"] ** 2
    return a["pattern"].count("*") * attn + a["pattern"].count("K") * kda


def init_rules(a) -> list:
    """Normal 0.02 for every matrix and the convolutions' taps; norms at
    identity; `b_g` zeros; the selection bias 0 (where
    benchmarks/balance.py starts its solve from); `A_log` 0 (a rate of 1)
    and `dt_bias` the inverse softplus of 0.01, so that a chunk of 64
    positions decays a state to about 0.53 and what chunks hand on
    matters. The family draws the rate in 1..16 and the step in
    0.001..0.1; benchmarks/weights.py draws no uniform leaf, and the
    strong end of that range is held by tests/test_linear_attention.py."""
    return [
        (r"norm/weight$|/o_norm$", "ones", 0.0),
        (r"/A_log$", "const", 0.0),
        (r"/dt_bias$", "const", math.log(math.expm1(0.01))),
        (r"/g_b_proj/bias$", "zeros", 0.0),
        (r"/selection_bias$", "const", 0.0),
        (r"", "normal", 0.02)]


def embed(a, p, tok):
    return p["embed_tokens/embedding"][tok]


def _delta_scan(q, k, v, g, beta):
    """The recurrence, position by position. q, k, v, g [B, T, H, P],
    beta [B, T, H]."""
    b, t, h, p = q.shape
    seg = SCAN_SEGMENT if t % SCAN_SEGMENT == 0 else t

    def position(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None] * state
        # (I - beta k k^T) state + beta k v^T
        fix = v_t - jnp.sum(state * k_t[..., None], axis=-2)
        state = state + (b_t[..., None] * k_t)[..., None] * fix[..., None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    @jax.checkpoint
    def segment(state, at):
        return jax.lax.scan(position, state, at)

    def by_time(z):         # [B, T, ...] -> [T / seg, seg, B, ...]
        z = jnp.moveaxis(z, 1, 0)
        return z.reshape(t // seg, seg, *z.shape[1:])

    first = jnp.zeros((b, h, p, p), jnp.float32)
    _, o = jax.lax.scan(segment, first,
                        tuple(by_time(z) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(t, b, h, p), 0, 1)


def _kda(a, p, u, dot):
    b, t, _ = u.shape
    h, hp, taps = a["kda_n_head"], a["kda_head_dim"], a["kda_conv"]

    def conved(name):
        z = jnp.pad(dot(u, p[f"mixer/{name}_proj/kernel"]),
                    ((0, 0), (taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(
            p[f"mixer/{name}_conv"][i] * z[:, i:i + t] for i in range(taps))
        ).reshape(b, t, h, hp)

    def unit(z):
        return z * jax.lax.rsqrt(
            jnp.sum(z * z, axis=-1, keepdims=True) + L2_EPS)

    q, k, v = unit(conved("q")) * hp ** -0.5, unit(conved("k")), conved("v")
    step = jax.nn.softplus(
        dot(dot(u, p["mixer/f_a_proj/kernel"]), p["mixer/f_b_proj/kernel"])
        + p["mixer/dt_bias"]).reshape(b, t, h, hp)
    g = -jnp.exp(p["mixer/A_log"])[:, None] * step
    beta = 2.0 * jax.nn.sigmoid(dot(u, p["mixer/b_proj/kernel"]))
    o = _delta_scan(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + a["rms_eps"]) * p["mixer/o_norm"]
    gate = dot(dot(u, p["mixer/g_a_proj/kernel"]),
               p["mixer/g_b_proj/kernel"]) + p["mixer/g_b_proj/bias"]
    return dot(o.reshape(b, t, h * hp) * jax.nn.sigmoid(gate),
               p["mixer/o_proj/kernel"])


def _attend(a, p, u, dot):
    b, t, _ = u.shape
    nh, nkv, hd = a["n_head"], a["n_kv_head"], a["head_dim"]
    q = dot(u, p["mixer/q_proj/kernel"]).reshape(b, t, nh, hd)
    k = dot(u, p["mixer/k_proj/kernel"]).reshape(b, t, nkv, hd)
    v = dot(u, p["mixer/v_proj/kernel"]).reshape(b, t, nkv, hd)
    k, v = (jnp.repeat(m, nh // nkv, axis=2) for m in (k, v))
    ctx = _attention(q, k, v, 0).reshape(b, t, nh * hd)
    gate = jax.nn.sigmoid(dot(u, p["mixer/g_proj/kernel"]))
    return dot(ctx * gate, p["mixer/o_proj/kernel"])


def _swiglu(x, gate, up, down, dot):
    return dot(jax.nn.silu(dot(x, gate)) * dot(x, up), down)


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
        routed = sum(
            weights[..., lo + e, None] * _swiglu(
                ub, p["experts/experts_gate"][e], p["experts/experts_up"][e],
                p["experts/experts_down"][e], dot)
            for e in range(n_held))
        return routed + _swiglu(
            ub, p["experts/shared/gate_proj/kernel"],
            p["experts/shared/up_proj/kernel"],
            p["experts/shared/down_proj/kernel"], dot)

    return jnp.moveaxis(by_blocks(tokens, (u,), TOKEN_BLOCK), 0, 1
                        ).reshape(u.shape)


def expert_input(a, p, x, dot):
    """`(stream, u)` of a layer, whose output is
    `stream + experts(a, p, u, dot)`: the stream behind the mixer, its
    kind told from the leaves given, and the experts' normed input."""
    u = _rms_norm(x, p["input_layernorm/weight"], a["rms_eps"])
    mixer = _kda if "mixer/A_log" in p else _attend
    h = x + mixer(a, p, u, dot)
    return h, _rms_norm(h, p["post_attention_layernorm/weight"],
                        a["rms_eps"])


def layer(a, p, x, dot):
    stream, u = expert_input(a, p, x, dot)
    return stream + experts(a, p, u, dot)


def head_loss(a, p, x, tok, dot):
    h = _rms_norm(x, p["norm/weight"], a["rms_eps"])
    return next_token_loss(h, p["lm_head/kernel"], tok, dot)
