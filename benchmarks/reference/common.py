"""What the plain references share: the matmul in the stated and in the
next-lower precision, the row-block / layer-by-layer loss and gradient,
and the optimizer the configuration states (global-norm clip, AdamW,
linear warm-up then cosine), all float32 `jax.numpy`.

Nothing here imports the program, and nothing takes a tensor the program
made: weights come from `benchmarks/weights.py`, tokens from
`benchmarks/data.py`, hyper-parameters from the configuration file.

An architecture module (`gpt2.py`, `llama.py`) gives
`param_shapes`, `init_rules`, `layer_names`, `EMBED_KEYS`, `HEAD_KEYS`,
`embed`, `layer` and `head_loss`; parameters are flat dicts
`{"h_0/attn/qkv/kernel": array}`.
"""
from __future__ import annotations

import functools
import math
import re
import statistics

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX, E5M2_MAX = 448.0, 57344.0


def _mm(x, w):
    return jnp.matmul(x, w, precision=HIGHEST)


def _round_to(x, dtype, top):
    """Per-tensor scaled round trip through an 8-bit float type."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def _fp8_dot(x, w):
    return _mm(_round_to(x, jnp.float8_e4m3fn, E4M3_MAX),
               _round_to(w, jnp.float8_e4m3fn, E4M3_MAX))


def _fp8_fwd(x, w):
    return _fp8_dot(x, w), (x, w)


def _fp8_bwd(res, g):
    x, w = res
    gq = _round_to(g, jnp.float8_e5m2, E5M2_MAX)
    xq = _round_to(x, jnp.float8_e4m3fn, E4M3_MAX)
    wq = _round_to(w, jnp.float8_e4m3fn, E4M3_MAX)
    dx = _mm(gq, wq.T)
    dw = _mm(xq.reshape(-1, x.shape[-1]).T, gq.reshape(-1, g.shape[-1]))
    return dx, dw


_fp8_dot.defvjp(_fp8_fwd, _fp8_bwd)


def _bf16_dot(x, w):
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


# "f32" is the reference. "fp8" is the control for a configuration that
# states bfloat16 compute: every weight matmul takes e4m3 operands and
# e5m2 gradients with per-tensor scales and accumulates in float32 (the
# usual fp8 training recipe; attention's own products stay float32).
# "bf16" is what the program itself does, kept for the CPU tests.
DOTS = {"f32": _mm, "fp8": _fp8_dot, "bf16": _bf16_dot}


def warmup_cosine(step: int, base_lr: float, warmup: int, total: int,
                  min_ratio: float = 0.0) -> float:
    """Learning rate of optimizer step `step` (0-based): linear from
    base/warmup, then a cosine to `min_ratio` at `total`."""
    if step < warmup:
        return base_lr * (step + 1) / max(warmup, 1)
    frac = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    return base_lr * (min_ratio + (1 - min_ratio)
                      * (1 + math.cos(math.pi * frac)) / 2)


def by_blocks(f, arrays, block: int):
    """`f` on slices of `block` positions (axis 1) of every array, one
    slice at a time and recomputed in the backward pass: bounds memory,
    changes no arithmetic. Returns the results stacked on a new axis 0."""
    t = arrays[0].shape[1]
    if t <= block or t % block:
        return f(*arrays)[None]

    def split(x):
        return jnp.moveaxis(
            x.reshape(x.shape[0], t // block, block, *x.shape[2:]), 1, 0)

    return jax.lax.map(lambda xs: jax.checkpoint(f)(*xs),
                       tuple(split(x) for x in arrays))


def next_token_loss(h, w, tok, dot, block: int = 1024):
    """Sum over rows of each row's mean cross entropy of token t+1
    predicted from `h[:, t] @ w`, for t < T-1."""
    t = tok.shape[1]
    labels = jnp.concatenate([tok[:, 1:], jnp.zeros_like(tok[:, :1])], axis=1)
    keep = (jnp.arange(t) < t - 1).astype(jnp.float32)
    keep = jnp.broadcast_to(keep, tok.shape)

    def rows_ce(hb, lb, kb):
        logp = jax.nn.log_softmax(dot(hb, w), axis=-1)
        picked = jnp.take_along_axis(logp, lb[..., None], axis=-1)[..., 0]
        return -jnp.sum(picked * kb, axis=-1)

    return jnp.sum(by_blocks(rows_ce, (h, labels, keep), block)) / (t - 1)


def layer_params(params: dict, name: str) -> dict:
    prefix = name + "/"
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


class Follower:
    """Loss and gradient of one batch, by blocks of rows and layer by
    layer, so that neither a [B, T, V] logits tensor nor every layer's
    activations are alive at once. The layer's backward recomputes its
    forward; that changes memory, not arithmetic."""

    def __init__(self, arch, a: dict, mode: str = "f32", place_rows=None):
        dot = DOTS[mode]
        self.arch, self.a = arch, a
        self.place_rows = place_rows or jnp.asarray
        self._embed = jax.jit(lambda p, tok: arch.embed(a, p, tok))
        self._embed_b = jax.jit(lambda p, tok, ct: jax.vjp(
            lambda q: arch.embed(a, q, tok), p)[1](ct)[0])
        self._layer = jax.jit(lambda p, x: arch.layer(a, p, x, dot))
        self._layer_b = jax.jit(lambda p, x, ct: jax.vjp(
            lambda q, y: arch.layer(a, q, y, dot), p, x)[1](ct))
        self._head = jax.jit(jax.value_and_grad(
            lambda p, x, tok: arch.head_loss(a, p, x, tok, dot),
            argnums=(0, 1)))

    def loss_and_grads(self, params: dict, tokens, rows_per_block: int):
        """Mean over rows of each row's mean next-token cross entropy,
        and its gradient for every parameter."""
        arch, names = self.arch, self.arch.layer_names(self.a)
        n_rows = tokens.shape[0]
        grads = {k: jnp.zeros_like(v) for k, v in params.items()}
        loss = 0.0

        def add(prefix, g):
            for k, v in g.items():
                grads[prefix + k] = grads[prefix + k] + v / n_rows

        with jax.default_matmul_precision("highest"):
            for r in range(0, n_rows, rows_per_block):
                tok = self.place_rows(tokens[r:r + rows_per_block])
                pe = {k: params[k] for k in arch.EMBED_KEYS}
                ph = {k: params[k] for k in arch.HEAD_KEYS}
                xs = [self._embed(pe, tok)]
                for name in names:
                    xs.append(self._layer(layer_params(params, name), xs[-1]))
                block_loss, (gh, ct) = self._head(ph, xs.pop(), tok)
                loss += float(block_loss) / n_rows
                add("", gh)
                for name in reversed(names):
                    gl, ct = self._layer_b(layer_params(params, name),
                                           xs.pop(), ct)
                    add(name + "/", gl)
                add("", self._embed_b(pe, tok, ct))
        return loss, grads


@jax.jit
def sq_sum(x):
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


def leaf_norms(tree: dict) -> dict:
    return {k: math.sqrt(float(sq_sum(v))) for k, v in tree.items()}


@functools.partial(jax.jit, static_argnames=("first", "last"),
                   donate_argnums=(0,))
def _adamw_leaf(p, g, m, v, lr, bc1, bc2, b1, b2, eps, wd, first, last):
    """One leaf's AdamW step. After the first step only the clipped
    gradient is kept (`m` holds it, `v` is None): both moments are
    functions of it, and a two-step follow then never holds them."""
    if first:
        m = v = jnp.zeros((), jnp.float32)
    elif v is None:
        m, v = (1 - b1) * m, (1 - b2) * m * m
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    p = p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p)
    if last:
        return p, None, None
    return (p, g, None) if first else (p, m, v)


def follow_steps(arch, a: dict, hp: dict, params: dict, batches, weights,
                 mode: str = "f32", rows_per_block: int = 1,
                 place_rows=None, place_leaf=None) -> dict:
    """Train `len(batches)` steps from `params` as the configuration
    states and return what the check compares: each step's loss, every
    leaf's norm of the first gradient as the optimizer gets it (after
    the global-norm clip), and every leaf's norm of the parameters'
    change after the last step. `weights.leaf(path)` regenerates the
    initial value of a leaf, so no second copy of the model is held.
    `place_rows` and `place_leaf` put a block of token rows and a
    regenerated leaf where the caller keeps `params` (several chips)."""
    follower = Follower(arch, a, mode, place_rows)
    place_leaf = place_leaf or (lambda x: x)
    b1, b2 = hp["betas"]
    no_decay = [re.compile(p) for p in hp["no_decay"]]
    m = dict.fromkeys(params)
    v = dict.fromkeys(params)
    out = {"losses": []}
    for t, tokens in enumerate(batches):
        loss, grads = follower.loss_and_grads(params, tokens, rows_per_block)
        out["losses"].append(loss)
        norms = leaf_norms(grads)
        gnorm = math.sqrt(sum(n * n for n in norms.values()))
        clip = hp["grad_clip_norm"]
        scale = min(1.0, clip / (gnorm + 1e-6)) if clip > 0 else 1.0
        if t == 0:
            out["grad_norms"] = {k: n * scale for k, n in norms.items()}
        lr = warmup_cosine(t, hp["lr"], hp["warmup_steps"], hp["total_steps"])
        for k in list(params):
            wd = 0.0 if any(p.search(k) for p in no_decay) \
                else hp["weight_decay"]
            params[k], m[k], v[k] = _adamw_leaf(
                params[k], grads.pop(k) * scale, m[k], v[k], lr,
                1 - b1 ** (t + 1), 1 - b2 ** (t + 1), b1, b2, hp["eps"], wd,
                first=t == 0, last=t == len(batches) - 1)
    out["update_norms"] = {
        k: math.sqrt(float(sq_sum(p - place_leaf(weights.leaf(k)))))
        for k, p in params.items()}
    return out


def worst_leaf_gap(got: dict, want: dict) -> tuple:
    """The largest gap between the two sides' norms of one leaf, against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero). Returns (gap, leaf)."""
    floor = statistics.median(want.values())
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
            for k in want}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf
