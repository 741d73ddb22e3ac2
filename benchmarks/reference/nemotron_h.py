"""The `nemotron_h` architecture as NVIDIA-Nemotron-3-Super-120B-A12B uses
it (config.json of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16;
Nemotron-H, arXiv:2504.03624; Mamba-2, Dao & Gu 2024), plain, and as one
chip of a deployment holds it.

Residual stream `x [B, T, d_model]`; every layer is ONE mixer behind a
pre-norm, `x <- x + mixer(RMSNorm(x))`, its kind by the character of
`pattern`; eps `rms_eps`; no biases but the convolution's; a final
RMSNorm; untied embedding and head. Loss: mean next-token cross entropy.

`M`, Mamba-2 (H `ssm_n_head` heads of P `ssm_head_dim`, G `ssm_n_group`
groups, state N `ssm_state`, `d_in = H P`): `[z, xBC, dt] = in_proj(u)`
of widths `d_in`, `d_in + 2 G N`, `H`. `xBC = silu(conv1d(xBC))`,
depthwise, causal, `ssm_conv` taps (tap k multiplies position
`t - (taps - 1) + k`), with bias; split into `x [T, H, P]`, `B [T, G, N]`,
`C [T, G, N]`. `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`, a scalar
a head. State a head, `[P, N]`:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t
    y_t = S_t C_t + D x_t            (head h reads group h // (H / G))

then `y = RMSNorm_group(y * silu(z)) * w`, the mean square over each
group's `d_in / G` channels, and `out_proj(y)`. The recurrence is computed
as written, position by position; segments of `SCAN_SEGMENT` positions are
recomputed in the backward pass, which changes memory, not arithmetic.

`*`, attention: `n_head` query heads on `n_kv_head` key-value heads of
`head_dim`, separate projections, causal softmax over all earlier keys,
scores over sqrt(head_dim), NO rotary embedding. In blocks of query rows
(reference/llama.py's).

`E`, latent mixture of experts, on `h = RMSNorm(x)`: scores
`s = sigmoid(h W_r)` over all `moe_n_routed` experts; the `moe_top_k`
experts with the largest `s + b` (`b` the selection bias: no gradient
reaches it); weights `w_e = moe_scale * s_e / (sum of the chosen s +
1e-20)`. `l = h W_down` (`d_model -> moe_latent`); routed part
`r = sum over chosen e held here of w_e * relu(l U_e)^2 V_e`; output
`r W_up + relu(h U_s)^2 V_s` (the shared expert, `moe_shared_d_ff` wide).
Each held expert is computed for every token and weighed by `w_e`, which
is 0 where the token did not choose it.

The chip's share. `ssm_n_head`, `ssm_n_group`, `n_head`, `n_kv_head` and
`moe_held = [offset, count]` are what this chip holds of a layer; the
router keeps its `moe_n_routed` outputs and `moe_top_k` a token, the
weights are normalised over all the chosen, held or not, and what the
absent experts would add is left out. With one group a chip the Mamba-2
share is exact: the gated norm's mean square runs inside a group, and the
chip's `out_proj` result is one summand of the mixer's output; so is the
attention's, by heads, and the routed experts', by experts. The shared
expert and the latent projections are whole on every chip.

Departures and assumptions (the configuration's `assumed` repeats them):
no rotary embedding although `rope_theta` sits in the config (the family's
description uses none and `nemotron_h`'s model code applies none); the
router reads `h`, not `l`; no norm or activation on the latent
projections; the multi-token-prediction module is left out.

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
EMBED_KEYS = ("embed_tokens/embedding",)
HEAD_KEYS = ("norm/weight", "lm_head/kernel")


def layer_names(a):
    return [f"layers_{i}" for i in range(len(a["pattern"]))]


def _held(a) -> tuple:
    lo, count = a["moe_held"]
    return lo, count or a["moe_n_routed"]


def _ssm_widths(a) -> tuple:
    d_in = a["ssm_n_head"] * a["ssm_head_dim"]
    return d_in, 2 * a["ssm_n_group"] * a["ssm_state"]


def layer_shapes(a, kind: str) -> dict:
    """One layer's leaves without the layer's prefix."""
    d = a["d_model"]
    shapes = {"norm/weight": (d,)}
    if kind == "M":
        d_in, d_bc = _ssm_widths(a)
        h = a["ssm_n_head"]
        shapes.update({
            "mixer/in_proj/kernel": (d, 2 * d_in + d_bc + h),
            "mixer/conv_kernel": (a["ssm_conv"], d_in + d_bc),
            "mixer/conv_bias": (d_in + d_bc,),
            "mixer/dt_bias": (h,), "mixer/A_log": (h,), "mixer/D": (h,),
            "mixer/norm_weight": (d_in,),
            "mixer/out_proj/kernel": (d_in, d)})
    elif kind == "E":
        lat, ff, sh = a["moe_latent"], a["moe_d_ff"], a["moe_shared_d_ff"]
        n_held = _held(a)[1]
        shapes.update({
            "mixer/router": (d, a["moe_n_routed"]),
            "mixer/selection_bias": (a["moe_n_routed"],),
            "mixer/latent_down/kernel": (d, lat),
            "mixer/latent_up/kernel": (lat, d),
            "mixer/experts_up": (n_held, lat, ff),
            "mixer/experts_down": (n_held, ff, lat),
            "mixer/shared_up/kernel": (d, sh),
            "mixer/shared_down/kernel": (sh, d)})
    elif kind == "*":
        hd = a["head_dim"]
        shapes.update({
            "mixer/q_proj/kernel": (d, a["n_head"] * hd),
            "mixer/k_proj/kernel": (d, a["n_kv_head"] * hd),
            "mixer/v_proj/kernel": (d, a["n_kv_head"] * hd),
            "mixer/o_proj/kernel": (a["n_head"] * hd, d)})
    else:
        raise ValueError(f"pattern character {kind!r}: one of 'M', 'E', '*'")
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
    """Parameters that multiply every token. M: in_proj and out_proj (the
    convolution's 4 taps a channel are no matrix). `*`: Q, K, V, O. E: the
    router, both latent projections and the shared expert whole; a routed
    expert held here is met by `moe_top_k / moe_n_routed` of the tokens
    (uniform routing over the published experts, which is how the cells
    route since benchmarks/balance.py). The untied head."""
    d = a["d_model"]
    d_in, d_bc = _ssm_widths(a)
    hd = a["head_dim"]
    per_kind = {
        "M": d * (2 * d_in + d_bc + a["ssm_n_head"]) + d_in * d,
        "*": 2 * d * a["n_head"] * hd + 2 * d * a["n_kv_head"] * hd,
        "E": d * a["moe_n_routed"] + 2 * d * a["moe_latent"]
        + 2 * d * a["moe_shared_d_ff"]
        + _held(a)[1] * 2 * a["moe_latent"] * a["moe_d_ff"]
        * a["moe_top_k"] // a["moe_n_routed"],
    }
    return sum(per_kind[kind] for kind in a["pattern"]) \
        + d * a["vocab_size"]


def mixer_flops_per_token(a, seq_len: int) -> float:
    """Forward plus backward (3 x forward: a product of two activations
    has two gradients) of the products no weight enters.

    `*`: scores and context, 4 x heads x head size a visible key:
    12 x n_head x head_dim x mean_visible_keys.

    M, in chunks of L = `ssm_chunk` (the published `chunk_size`; any
    schedule needs the chunk's causal half), forward a token:
    C . B inside the chunk, a group: 2 N G x (L + 1) / 2;
    the decayed scores times the inputs, a head: 2 P H x (L + 1) / 2;
    the chunk's state from its inputs: 2 H P N;
    the entering state read by C: 2 H P N.
    So 3 x ((L + 1) (G N + H P) + 4 H P N) a layer. The state's passage
    from chunk to chunk (2 H P N a chunk, 1 / L of that a token) and the
    convolution's taps are left out."""
    attn = 12 * a["n_head"] * a["head_dim"] * mean_visible_keys(seq_len)
    chunk = min(a["ssm_chunk"], seq_len)
    h, p = a["ssm_n_head"], a["ssm_head_dim"]
    g, n = a["ssm_n_group"], a["ssm_state"]
    scan = 3 * ((chunk + 1) * (g * n + h * p) + 4 * h * p * n)
    return a["pattern"].count("*") * attn + a["pattern"].count("M") * scan


def init_rules(a) -> list:
    """The published `initializer_range` 0.02 throughout; norms and the
    skip `D` at identity; `A_log` 0 (A = -1) and `dt_bias` the inverse
    softplus of 0.01, so that a chunk of 128 positions decays a state to
    about 0.3 and what chunks hand on matters; the selection bias 0,
    which is where benchmarks/balance.py starts its solve from.

    What these weights do to routing (PERF.md, PR 33): behind the first
    mixer the stream is all mixer output, and `relu(.)^2` is never
    negative, so every token carries one common vector about half as
    large as its own part and, at a bias of 0, each router prefers the
    same few experts for every token (on the chip: the busiest of the 8
    held at 2.4-4.4 times their mean). A deployment's routers are
    balanced by a selection bias that hundreds of steps have trained;
    the benchmark solves that bias for the seed's weights and gives it to
    both sides (`Weights.give`, PR 44)."""
    return [
            (r"norm/weight$|norm_weight$", "ones", 0.0),
            (r"/D$", "ones", 0.0),
            (r"/A_log$", "const", 0.0),
            (r"/dt_bias$", "const", math.log(math.expm1(0.01))),
            (r"/selection_bias$", "const", 0.0),
            (r"", "normal", 0.02)]


def embed(a, p, tok):
    return p["embed_tokens/embedding"][tok]


def _scan(x, dt, decay, bm, cm):
    """The recurrence, position by position. x [B, T, H, P], dt [B, T, H],
    decay [H] (A), bm and cm [B, T, H, N] (already given to their heads)."""
    b, t, h, p = x.shape
    seg = SCAN_SEGMENT if t % SCAN_SEGMENT == 0 else t

    def position(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * decay)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    @jax.checkpoint
    def segment(state, at):
        return jax.lax.scan(position, state, at)

    def by_time(z):         # [B, T, ...] -> [T / seg, seg, B, ...]
        z = jnp.moveaxis(z, 1, 0)
        return z.reshape(t // seg, seg, *z.shape[1:])

    first = jnp.zeros((b, h, p, bm.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(segment, first,
                        tuple(by_time(z) for z in (x, dt, bm, cm)))
    return jnp.moveaxis(y.reshape(t, b, h, p), 0, 1)


def _mamba(a, p, u, dot):
    b, t, _ = u.shape
    h, hp = a["ssm_n_head"], a["ssm_head_dim"]
    g, n, taps = a["ssm_n_group"], a["ssm_state"], a["ssm_conv"]
    d_in, d_bc = _ssm_widths(a)
    z, xbc, dt = jnp.split(dot(u, p["mixer/in_proj/kernel"]),
                           [d_in, 2 * d_in + d_bc], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["mixer/conv_bias"] + sum(
        p["mixer/conv_kernel"][k] * padded[:, k:k + t] for k in range(taps)))
    x, bm, cm = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
    x = x.reshape(b, t, h, hp)
    bm, cm = (jnp.repeat(m.reshape(b, t, g, n), h // g, axis=2)
              for m in (bm, cm))
    dt = jax.nn.softplus(dt + p["mixer/dt_bias"])
    y = _scan(x, dt, -jnp.exp(p["mixer/A_log"]), bm, cm) \
        + p["mixer/D"][:, None] * x
    gated = (y * jax.nn.silu(z).reshape(b, t, h, hp)).reshape(
        b, t, g, d_in // g)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + a["rms_eps"])
    return dot(gated.reshape(b, t, d_in) * p["mixer/norm_weight"],
               p["mixer/out_proj/kernel"])


def _attend(a, p, h, dot):
    b, t, _ = h.shape
    nh, nkv, hd = a["n_head"], a["n_kv_head"], a["head_dim"]
    q = dot(h, p["mixer/q_proj/kernel"]).reshape(b, t, nh, hd)
    k = dot(h, p["mixer/k_proj/kernel"]).reshape(b, t, nkv, hd)
    v = dot(h, p["mixer/v_proj/kernel"]).reshape(b, t, nkv, hd)
    k, v = (jnp.repeat(m, nh // nkv, axis=2) for m in (k, v))
    ctx = _attention(q, k, v, 0).reshape(b, t, nh * hd)
    return dot(ctx, p["mixer/o_proj/kernel"])


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def router_scores(a, p, h, dot):
    """Every published expert's score of every token of `h`, the normed
    input of an expert layer, `[..., moe_n_routed]`: what `experts`
    routes by, and what benchmarks/balance.py solves the selection bias
    on."""
    return jax.nn.sigmoid(dot(h, p["mixer/router"]))


def expert_input(a, p, x, dot):
    """`(stream, h)` of an expert layer, whose output is
    `stream + experts(a, p, h, dot)`."""
    return x, _rms_norm(x, p["norm/weight"], a["rms_eps"])


def experts(a, p, h, dot):
    lo, n_held = _held(a)

    def tokens(hb):
        scores = router_scores(a, p, hb, dot)
        _, chosen = jax.lax.top_k(jax.lax.stop_gradient(
            scores + p["mixer/selection_bias"]), a["moe_top_k"])
        took = jnp.sum(jax.nn.one_hot(chosen, a["moe_n_routed"],
                                      dtype=scores.dtype), axis=-2)
        picked = scores * took
        weights = a["moe_scale"] * picked / (
            jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        latent = dot(hb, p["mixer/latent_down/kernel"])
        routed = sum(
            weights[..., lo + e, None] * dot(
                _relu2(dot(latent, p["mixer/experts_up"][e])),
                p["mixer/experts_down"][e])
            for e in range(n_held))
        return dot(routed, p["mixer/latent_up/kernel"]) + dot(
            _relu2(dot(hb, p["mixer/shared_up/kernel"])),
            p["mixer/shared_down/kernel"])

    return jnp.moveaxis(by_blocks(tokens, (h,), TOKEN_BLOCK), 0, 1
                        ).reshape(h.shape)


def layer(a, p, x, dot):
    """One layer; its kind is told from the leaves it is given."""
    if "mixer/router" in p:
        stream, h = expert_input(a, p, x, dot)
        return stream + experts(a, p, h, dot)
    h = _rms_norm(x, p["norm/weight"], a["rms_eps"])
    if "mixer/in_proj/kernel" in p:
        return x + _mamba(a, p, h, dot)
    return x + _attend(a, p, h, dot)


def head_loss(a, p, x, tok, dot):
    h = _rms_norm(x, p["norm/weight"], a["rms_eps"])
    return next_token_loss(h, p["lm_head/kernel"], tok, dot)
