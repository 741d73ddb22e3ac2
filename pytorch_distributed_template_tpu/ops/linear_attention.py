"""The gated delta rule with a decay per key channel (Kimi Delta
Attention, arXiv:2510.26692; the delta rule of Schlag et al. 2021 and
Yang et al. 2024 under a diagonal gate), outside its projections.

A head carries a state ``S [K, V]`` (``K`` key channels, ``V`` value
channels), from ``S_0 = 0``:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

with ``g_t [K] <= 0`` the log decay of each key channel and ``beta_t`` the
step of the delta rule, in (0, 2) where negative eigenvalues are allowed.
Written with the value the rule corrects towards,

    u_t = v_t - (Diag(exp(g_t)) S_{t-1})^T k_t
    S_t = Diag(exp(g_t)) S_{t-1} + beta_t k_t u_t^T,

it is a rank-one update a position. ``kda_recurrence`` computes exactly
that, position by position in float32: the oracle of the tests.

``kda_chunked`` computes it in chunks of ``chunk`` positions, which is
what makes it matrix products. With ``G_t`` the running sum of ``g``
inside a chunk and ``S`` the state the chunk starts from,

- ``M[t, s] = sum_d k_t[d] k_s[d] exp(G_t[d] - G_s[d])`` for ``s < t``
  and ``P[t, s]`` the same with ``q_t`` for ``s <= t``;
- the chunk's ``u`` solve the unit lower triangular system
  ``(I + M Diag(beta)) U = V - (K * exp(G)) S``, so with
  ``T = (I + M Diag(beta))^-1`` (in float32, by doubling the inverted
  diagonal blocks: block forward substitution, as stable as the
  row-by-row one) ``beta U = U' - W S`` where
  ``U' = beta T V`` and ``W = beta T (K * exp(G))`` need no state;
- ``O = (Q * exp(G)) S + P (beta U)``, and the next chunk starts from
  ``Diag(exp(G_end)) S + (K * exp(G_end - G))^T (beta U)``.

Everything a chunk needs but ``S`` is made for all chunks at once
(``kda_intra``); the passage from chunk to chunk is a ``jax.lax.scan``
over the chunks with three products a step (``kda_state``).

**Where the decay is strong.** The factorised form of ``M`` is
``(K * exp(G)) (K * exp(-G))^T``, and ``exp(-G)`` overflows float32 once a
chunk's summed log decay passes -88 (the family draws ``A`` up to 16 and a
step up to 0.1: -102 in 64 positions). Nothing here is clamped: a chunk
is cut into sub-chunks of ``sub`` positions. Between two sub-chunks
``exp(G_t - G_s) = exp(G_t - R) exp(R - G_s)`` with ``R`` the running sum
where the later one starts, both exponents at most 0; inside a sub-chunk
the differences ``G_t - G_s`` are taken pair by pair before the
exponential (``[sub, sub, K]`` a sub-chunk: the largest float32
intermediate, and the reason the sub-chunk is short). A factor that
underflows to 0 stands for a product below float32's range either way.

Float32 whatever the operands: ``g``, its running sums, every
exponential, the triangular system and the state between chunks. The
products take the operands' type (bfloat16 in a bfloat16 model, on the
MXU) and accumulate in float32. All of it is plain ``jax.numpy``, so the
backward is jax's own. A length the chunk does not divide is padded with
``g = 0``, ``beta = 0`` (the state passes unchanged), cut off again.

Scopes for the trace: ``kda_scan`` round all of it, ``kda_intra`` and
``kda_state`` inside. ``kda/chunks`` is its line and span.
"""
from __future__ import annotations

import logging

import jax
import jax.numpy as jnp

from ..observability.trace import say_once

logger = logging.getLogger(__name__)

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
SUB_CHUNK = 16


def _unit_lower_inverse(low):
    """``(I + low)^-1`` for ``low [..., n, n]`` strictly lower triangular
    (what lies on or above the diagonal is not read), ``n`` a power of
    two, in float32. The inverses of the diagonal blocks of size ``b``
    give those of size ``2 b``:
    ``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``."""
    n = low.shape[-1]
    lead = low.shape[:-2]
    inv = jnp.ones(lead + (n, 1, 1), F32)              # blocks of one
    b = 1
    while b < n:
        m = n // (2 * b)
        # the diagonal blocks of size 2b of `low`, [..., m, 2b, 2b]
        blocks = jnp.einsum(
            "...iaib->...iab", low.reshape(lead + (m, 2 * b, m, 2 * b)))
        c = blocks[..., b:, :b]
        pairs = inv.reshape(lead + (m, 2, b, b))
        a_inv, b_inv = pairs[..., 0, :, :], pairs[..., 1, :, :]
        corner = -jnp.einsum("...ab,...bc,...cd->...ad", b_inv, c, a_inv,
                             precision=HIGHEST)
        top = jnp.concatenate([a_inv, jnp.zeros_like(a_inv)], axis=-1)
        inv = jnp.concatenate(
            [top, jnp.concatenate([corner, b_inv], axis=-1)], axis=-2)
        b *= 2
    return inv[..., 0, :, :]


def _block_diagonal(blocks):
    """``[..., m, b, b]`` -> ``[..., m b, m b]`` with the blocks on the
    diagonal and zeros elsewhere."""
    m, b = blocks.shape[-3], blocks.shape[-1]
    out = jnp.einsum("...iab,ij->...iajb", blocks, jnp.eye(m, dtype=F32))
    return out.reshape(blocks.shape[:-3] + (m * b, m * b))


@jax.named_scope("kda_scan")
def kda_chunked(q, k, v, g, beta, chunk: int = 64, sub: int = SUB_CHUNK):
    """``q``, ``k [B, T, H, K]``, ``v [B, T, H, V]``, ``g [B, T, H, K]``
    (the log decay, at most 0, float32), ``beta [B, T, H]`` (float32);
    returns ``o [B, T, H, V]`` in ``v``'s type. ``sub`` divides ``chunk``
    and both are powers of two."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    dtype = v.dtype
    if chunk % sub or chunk & (chunk - 1) or sub & (sub - 1):
        raise ValueError(f"chunk {chunk} and sub-chunk {sub}: powers of "
                         "two, the sub-chunk dividing the chunk")
    pad = -t % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))
            for z in (q, k, v, g, beta))
    n, ns = (t + pad) // chunk, chunk // sub
    say_once(
        logger, "kda/chunks",
        dict(chunk=chunk, sub_chunk=sub, chunks=n, heads=h,
             pair_bytes=bsz * n * h * chunk * sub * dk * 4),
        "kda/chunks: %(chunks)d chunks of %(chunk)d positions a row in "
        "sub-chunks of %(sub_chunk)d, %(heads)d heads; one layer's float32 "
        "pairwise decays are %(pair_bytes)d bytes")

    def chunks(z):          # [B, T, H, ...] -> [B, H, n, chunk, ...]
        z = z.reshape(bsz, n, chunk, *z.shape[2:])
        return jnp.moveaxis(z, 3, 1)

    def mm(spec, x, y):
        return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                          preferred_element_type=F32)

    with jax.named_scope("kda_intra"):
        qc, kc = (chunks(z).astype(F32) for z in (q, k))
        vc = chunks(v)                      # enters one product, as it is
        bc = chunks(beta.astype(F32))                       # [B, H, n, L]
        run = jnp.cumsum(chunks(g.astype(F32)), axis=3)     # G, [.., L, K]

        # sub-chunk i of a chunk: [B, H, n, ns, sub, K]
        def subs(z):
            return z.reshape(bsz, h, n, ns, sub, z.shape[-1])

        run_s, q_s, k_s = subs(run), subs(qc), subs(kc)
        # R_i: the running sum where sub-chunk i starts
        start = jnp.pad(run_s[..., :-1, -1, :],
                        ((0, 0),) * 3 + ((1, 0), (0, 0)))   # [.., ns, K]
        since = jnp.exp(run_s - start[..., None, :])        # exp(G_t - R_i)
        # k_s exp(R_i - G_s) for every s before sub-chunk i: [.., ns, L, K]
        earlier = (jnp.arange(chunk)[None, :]
                   < (jnp.arange(ns) * sub)[:, None])[..., None]
        until = jnp.exp(jnp.where(
            earlier, start[..., :, None, :] - run[..., None, :, :],
            -jnp.inf))
        k_until = kc[..., None, :, :] * until
        m_off = mm("...itd,...isd->...its", k_s * since, k_until)
        p_off = mm("...itd,...isd->...its", q_s * since, k_until)
        # inside a sub-chunk the differences pair by pair, s <= t
        seen = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
        pair = jnp.exp(jnp.where(
            seen, run_s[..., :, None, :] - run_s[..., None, :, :],
            -jnp.inf)) * k_s[..., None, :, :]               # [.., t, s, K]
        m_in = jnp.sum(k_s[..., :, None, :] * pair, axis=-1)
        p_in = jnp.sum(q_s[..., :, None, :] * pair, axis=-1)
        strict = jnp.tril(jnp.ones((sub, sub), F32), -1)
        shape = (bsz, h, n, chunk, chunk)
        m = m_off.reshape(shape) + _block_diagonal(m_in * strict)
        p = p_off.reshape(shape) + _block_diagonal(p_in)

        solve = _unit_lower_inverse(m * bc[..., None, :])
        decay = jnp.exp(run)                                # exp(G_t)
        total = run[..., -1:, :]                            # G_end
        # beta T (K exp(G)) and beta T V: what the u's are without a state
        w = bc[..., None] * mm("...ts,...sd->...td", solve, kc * decay)
        u0 = bc[..., None] * mm("...ts,...sd->...td", solve, vc)
        q_in = (qc * decay).astype(dtype)
        k_out = (kc * jnp.exp(total - run)).astype(dtype)
        w, p = w.astype(dtype), p.astype(dtype)
        keep = jnp.exp(total[..., 0, :])                    # [B, H, n, K]

    with jax.named_scope("kda_state"):
        def step(state, at):
            w_c, u_c, q_c, k_c, p_c, keep_c = at
            s_in = state.astype(dtype)
            # beta u = beta T V - beta T (K exp(G)) S
            u = u_c - jnp.einsum("bhtk,bhkv->bhtv", w_c, s_in,
                                 preferred_element_type=F32)
            ub = u.astype(dtype)
            o = jnp.einsum("bhtk,bhkv->bhtv", q_c, s_in,
                           preferred_element_type=F32) \
                + jnp.einsum("bhts,bhsv->bhtv", p_c, ub,
                             preferred_element_type=F32)
            state = keep_c[..., None] * state + jnp.einsum(
                "bhtk,bhtv->bhkv", k_c, ub, preferred_element_type=F32)
            return state, o.astype(dtype)

        _, o = jax.lax.scan(
            step, jnp.zeros((bsz, h, dk, dv), F32),
            tuple(jnp.moveaxis(z, 2, 0)
                  for z in (w, u0, q_in, k_out, p, keep)))
    # [n, B, H, L, V] -> [B, T, H, V]
    o = jnp.moveaxis(o, 0, 2).reshape(bsz, h, n * chunk, dv)
    return jnp.moveaxis(o, 1, 2)[:, :t]


def kda_recurrence(q, k, v, g, beta):
    """The recurrence of the module docstring, position by position, in
    float32. Same arguments as ``kda_chunked`` without the chunk."""
    bsz, _, h, dk = q.shape
    q, k, v, g, beta = (z.astype(F32) for z in (q, k, v, g, beta))

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None] * state
        u = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HIGHEST)
        state = state + (b_t[..., None] * k_t)[..., None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=HIGHEST)

    first = jnp.zeros((bsz, h, dk, v.shape[-1]), F32)
    _, o = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(z, 1, 0) for z in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)
