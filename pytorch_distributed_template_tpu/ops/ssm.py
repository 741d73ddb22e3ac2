"""The Mamba-2 state-space scan (Dao & Gu 2024, "state space duality").

A head ``h`` with a scalar decay carries a state ``S [P, N]``:

    S_t = exp(dt_t * A_h) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D_h * x_t

with ``x_t [P]`` the head's channels and ``B_t``, ``C_t [N]`` shared by
the ``H / G`` heads of a group. ``ssd_scan`` computes it in chunks of
``chunk`` positions, which is what makes it matrix products:

- inside a chunk ``y_t`` sums over the chunk's earlier positions
  ``(C_t . B_s) * exp(cs_t - cs_s) * dt_s * x_s`` (``cs`` the running sum
  of ``dt * A`` inside the chunk): one ``[L, L]`` product of C with B a
  group, a decay mask a head, one product with the inputs;
- a chunk's own state is one product of its decayed inputs with B;
- the state a chunk starts from is the decayed sum of the states of the
  chunks before it: ``chunks x chunks`` decays, in float32 whatever the
  compute type, because it is the one quantity that crosses the whole
  sequence;
- what that state adds to ``y_t`` is one product with C, decayed to t.

The products take the operands' type (bfloat16 in a bfloat16 model, on
the MXU) and accumulate in float32; ``dt``, the running decays and the
state between chunks are float32. All of it is plain ``jax.numpy``, so
the backward is jax's own; the step gains no kernel from it. Two
``jax.named_scope``s split it for the trace: ``ssm_intra`` (the running
decays, ``C . B``, the decay mask, its product with the inputs)
and ``ssm_state`` (each chunk's state, its passage from chunk to chunk,
the entering state read by ``C``).
``ssd_recurrence`` is the recurrence as written above, one position at a
time: the oracle of the tests.

A length the chunk does not divide is padded with positions of ``dt = 0``
(no decay, no input), whose outputs are cut off again.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _segment_decays(a):
    """``a [..., L]`` -> ``[..., L, L]``: entry ``(i, j)`` is
    ``exp(a[j+1] + ... + a[i])`` for ``j <= i`` and 0 above the diagonal."""
    n = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    seen = jnp.tril(jnp.ones((n, n), bool))
    return jnp.exp(jnp.where(seen, seg, -jnp.inf))


def ssd_scan(x, dt, a, b, c, d, chunk: int):
    """``x [B, T, H, P]``, ``dt [B, T, H]`` (positive, float32), ``a [H]``
    (negative, float32), ``b``, ``c [B, T, G, N]``, ``d [H]``; returns
    ``y [B, T, H, P]`` in ``x``'s type."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g
    dtype = x.dtype
    pad = -t % chunk
    if pad:
        x, dt, b, c = (jnp.pad(z, ((0, 0), (0, pad)) + ((0, 0),) * (z.ndim - 2))
                       for z in (x, dt, b, c))
    nc = (t + pad) // chunk

    def chunks(z):
        return z.reshape(bsz, nc, chunk, *z.shape[2:])

    dt = dt.astype(jnp.float32)
    xc = chunks(x).reshape(bsz, nc, chunk, g, r, p)
    dtc = chunks(dt).reshape(bsz, nc, chunk, g, r)
    bc, cc = chunks(b), chunks(c)

    # inside the chunks
    with jax.named_scope("ssm_intra"):
        # running decay inside each chunk, [B, nc, G, R, L]
        da = jnp.moveaxis(dtc * a.astype(jnp.float32).reshape(g, r), 2, -1)
        cs = jnp.cumsum(da, axis=-1)
        xdt = xc.astype(jnp.float32) * dtc[..., None]      # dt_s * x_s
        cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc,
                        preferred_element_type=jnp.float32)
        mix = (cb[:, :, :, None] * _segment_decays(da)).astype(dtype)
        y = jnp.einsum("bcgrls,bcsgrp->bclgrp", mix, xdt.astype(dtype),
                       preferred_element_type=jnp.float32)

    with jax.named_scope("ssm_state"):
        # each chunk's own state, decayed to the chunk's end:
        # [B, nc, G, R, P, N]
        to_end = jnp.exp(cs[..., -1:] - cs)                # [B, nc, G, R, L]
        decayed = (xdt * jnp.moveaxis(to_end, -1, 2)[..., None]
                   ).astype(dtype)
        states = jnp.einsum("bcsgn,bcsgrp->bcgrpn", bc, decayed,
                            preferred_element_type=jnp.float32)

        # the state each chunk starts from: float32 across the sequence
        total = jnp.moveaxis(cs[..., -1], 1, -1)           # [B, G, R, nc]
        across = _segment_decays(jnp.pad(total, ((0, 0),) * 3 + ((1, 0),)))
        entering = jnp.einsum("bgrzc,bcgrpn->bzgrpn", across[..., :-1, 1:],
                              states, precision=HIGHEST)
        # row z of `across[..., :-1, 1:]` holds, for every chunk c < z, the
        # decay from c's end to z's start; row 0 is empty: no state enters

        from_start = jnp.moveaxis(jnp.exp(cs), -1, 2)      # [B, nc, L, G, R]
        y = y + from_start[..., None] * jnp.einsum(
            "bclgn,bcgrpn->bclgrp", cc, entering.astype(dtype),
            preferred_element_type=jnp.float32)
    y = y + d.astype(jnp.float32).reshape(g, r)[..., None] \
        * xc.astype(jnp.float32)
    return y.reshape(bsz, t + pad, h, p)[:, :t].astype(dtype)


def ssd_recurrence(x, dt, a, b, c, d):
    """The recurrence of the module docstring, position by position, in
    float32. Same arguments as ``ssd_scan`` without the chunk."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    heads_of = jnp.arange(h) // (h // g)
    f32 = jnp.float32
    x, dt, b, c = (z.astype(f32) for z in (x, dt, b, c))

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = b_t[:, heads_of], c_t[:, heads_of]       # [B, H, N]
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=HIGHEST)
        return state, y_t + d[:, None] * x_t

    first = jnp.zeros((bsz, h, p, n), f32)
    _, y = jax.lax.scan(step, first, tuple(
        jnp.moveaxis(z, 1, 0) for z in (x, dt, b, c)))
    return jnp.moveaxis(y, 0, 1)
