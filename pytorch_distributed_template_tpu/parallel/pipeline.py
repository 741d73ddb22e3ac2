"""Pipeline parallelism: microbatch pipelining over the ``pipe`` mesh axis.

The reference is DP-only (SURVEY.md §2.3); pipeline parallelism is part of
this framework's first-class parallelism inventory. TPU-native formulation
(the pattern used by large JAX trainers on TPU pods):

- the model's repeated trunk is expressed as **stacked stage parameters**
  (leading dim = number of stages) sharded over the ``pipe`` mesh axis —
  each device physically holds only its stage's weights;
- ``shard_map`` runs one program per stage; microbatches stream through a
  ``lax.scan`` where activations hop stage→stage+1 via ``lax.ppermute``
  each tick;
- the ppermute rides ICI and XLA's latency-hiding scheduler overlaps it
  with the next tick's compute;
- gradients flow through the whole schedule by plain ``jax.grad`` — the
  transposed program pipelines in reverse automatically. Activation
  memory across the schedule is the caller's lever: wrap ``stage_fn`` in
  ``jax.checkpoint`` (models/pipelined.py ``remat``) and each tick's
  internals are recomputed in the backward instead of stored.

Two schedules:

- ``n_chunks=1`` — classic GPipe: ``M + S - 1`` ticks, fill / steady
  state / drain, bubble fraction ``(S-1)/(M+S-1)``.
- ``n_chunks=V > 1`` — circular (interleaved) schedule: each device holds
  ``V`` non-contiguous layer chunks (device s owns virtual stages
  ``v*S + s``), and each microbatch loops the ring ``V`` times.  Per-tick
  work shrinks to ``L/(S*V)`` layers while the fill cost stays ``S - 1``
  ticks, so the bubble fraction drops to ``(S-1)/(M*V + S - 1)`` —
  the Megatron "interleaved 1F1B" bubble, expressed as a forward
  schedule with jax.grad providing the reverse pipeline.

``pipeline_apply`` is the reusable op; models opt in by stacking their
trunk (e.g. ``nn.scan`` over homogeneous blocks) and calling it.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stage_params, microbatches,
                   mesh: Mesh, axis_name: str = "pipe",
                   rng: Optional[jax.Array] = None, n_chunks: int = 1,
                   extras=None):
    """Run ``microbatches`` through ``S`` pipeline stages.

    :param stage_fn: ``(params_one_chunk, x, rng_or_None) -> y`` applying
        ONE stage chunk to ONE microbatch; ``y`` must have ``x``'s
        shape/dtype (a homogeneous trunk — embeddings/heads live outside
        the pipeline). With ``extras`` the signature becomes
        ``(params_one_chunk, x, extras, rng_or_None) -> y``.
    :param stage_params: pytree whose leaves have leading dim ``S`` (the
        stacked per-stage weights), sharded ``P('pipe', ...)``. With
        ``n_chunks=V > 1`` the leading dims are ``[S, V]`` where entry
        ``[s, v]`` is virtual stage ``v*S + s`` (see
        ``regroup_for_pipeline``); ``stage_fn`` still receives one chunk.
    :param microbatches: ``[M, mb, ...]`` array of M microbatches.
    :param rng: optional base PRNG key; each (virtual stage, tick) folds
        in its own subkey so dropout differs per stage and microbatch.
    :param n_chunks: virtual chunks per device (circular schedule); 1 =
        GPipe.
    :param extras: optional pytree of arrays every stage needs whole and
        identical (e.g. RoPE cos/sin tables) — replicated over the mesh
        and handed to each ``stage_fn`` call. Closure capture would not
        survive ``shard_map``, hence the explicit channel.
    :returns: ``[M, mb, ...]`` outputs, replicated over ``axis_name``.
    """
    V = int(n_chunks)
    if V < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")
    has_extras = extras is not None
    if has_extras:
        call = stage_fn
    else:
        def call(p, x, _e, r):
            return stage_fn(p, x, r)

        extras = jnp.zeros(())  # placeholder riding the replicated spec
    if axis_name not in mesh.axis_names or mesh.shape[axis_name] == 1:
        # No pipe axis: run all virtual stages sequentially, in virtual
        # stage order g = v*S + s. With S absent the stacked leading dims
        # are [S(, V)]: flatten to [G] in g-order.
        if V > 1:
            flat = jax.tree.map(
                lambda a: jnp.transpose(
                    a, (1, 0) + tuple(range(2, a.ndim))
                ).reshape((-1,) + a.shape[2:]),
                stage_params,
            )
        else:
            flat = stage_params
        n_virtual = jax.tree.leaves(flat)[0].shape[0]

        def body(x, args):
            p, g_idx = args
            r = _stage_rng(rng, g_idx, jnp.int32(0))
            return call(p, x, extras, r), None

        def run_one(mb):
            out, _ = lax.scan(body, mb, (flat, jnp.arange(n_virtual)))
            return out

        return jax.vmap(run_one)(microbatches)

    S = mesh.shape[axis_name]
    has_rng = rng is not None
    rng_in = rng if has_rng else jax.random.key(0)
    m_total = microbatches.shape[0]
    # microbatches are injected in rounds of S; a partial last round runs
    # garbage ticks that never reach the output window
    groups = -(-m_total // S)
    total_ticks = groups * S * V + S - 1

    def per_stage(params, x_all, extras_r, rngs):
        s = lax.axis_index(axis_name)
        # shard_map hands this stage its own params slice with a leading
        # stage dim of 1; drop it. Leaves: [V, Lc, ...] (V=1: [Lc, ...]
        # via the same squeeze when n_chunks==1 params carry no V dim).
        p_local = jax.tree.map(lambda a: jnp.squeeze(a, 0), params)
        m = x_all.shape[0]
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            recv, outs = carry
            # virtual time: device s starts working S-1... ticks after
            # device 0; negative tau = fill bubble (garbage compute)
            tau = t - s
            slot = jnp.clip(tau, 0, None) % (S * V)
            g_idx = jnp.clip(tau, 0, None) // (S * V)
            v = slot // S
            member = slot % S
            mb_idx = g_idx * S + member
            # stage 0 ingests a fresh microbatch at chunk 0; every other
            # (device, chunk) takes the ring handoff (for s==0, v>0 that
            # is the wrap-around from the last device, one chunk back)
            x_in = jnp.where(
                (s == 0) & (v == 0),
                lax.dynamic_index_in_dim(
                    x_all, jnp.clip(mb_idx, 0, m - 1), keepdims=False
                ),
                recv,
            )
            if V > 1:
                p_chunk = jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(
                        a, v, keepdims=False
                    ),
                    p_local,
                )
            else:
                p_chunk = p_local
            r = _stage_rng(rngs, v * S + s, t) if has_rng else None
            y = call(p_chunk, x_in, extras_r, r)
            # the LAST virtual stage (device S-1, chunk V-1) finishes
            # microbatch mb_idx at this tick
            valid = (s == S - 1) & (v == V - 1) & (tau >= 0) & (mb_idx < m)
            idx = jnp.clip(mb_idx, 0, m - 1)
            cur = lax.dynamic_index_in_dim(outs, idx, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(valid, y, cur), idx, 0
            )
            recv_new = lax.ppermute(y, axis_name, perm)
            return (recv_new, outs), None

        recv0 = jnp.zeros_like(x_all[0])
        outs0 = jnp.zeros_like(x_all)
        (_, outs), _ = lax.scan(
            tick, (recv0, outs0), jnp.arange(total_ticks)
        )
        # only the last stage holds real outputs; replicate via psum
        outs = jnp.where(s == S - 1, outs, jnp.zeros_like(outs))
        return lax.psum(outs, axis_name)

    # Shard the per-microbatch batch dim over the data-like axes so DP
    # replicas each pipeline only their own slice (replicating it would make
    # every data group redo the full global trunk). Falls back to
    # replication when the microbatch size doesn't divide.
    import numpy as np

    from .sharding import DATA_AXES

    dp = tuple(
        a for a in DATA_AXES
        if a in mesh.axis_names and mesh.shape[a] > 1
    )
    dp_total = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
    mb_spec = (
        P(None, dp) if dp and microbatches.shape[1] % dp_total == 0 else P()
    )
    in_specs = (
        jax.tree.map(lambda _: P(axis_name), stage_params),
        mb_spec,        # replicated over pipe, sharded over data axes
        jax.tree.map(lambda _: P(), extras),  # whole and identical
        P(),
    )
    return shard_map(
        per_stage, mesh=mesh, in_specs=in_specs, out_specs=mb_spec,
        check_vma=False,
    )(stage_params, microbatches, extras, rng_in)


def regroup_for_pipeline(stacked, n_stages: int, n_chunks: int = 1):
    """[L, ...]-stacked layer params -> pipeline_apply's layout.

    GPipe (``n_chunks=1``): ``[S, L/S, ...]`` — stage ``s`` holds the
    contiguous layers ``[s*L/S, (s+1)*L/S)``.
    Circular (``n_chunks=V``): ``[S, V, L/(S*V), ...]`` where entry
    ``[s, v]`` holds the layers of VIRTUAL stage ``g = v*S + s`` —
    i.e. device ``s`` owns every S-th chunk, so each microbatch visits
    it V times per pass.
    """
    S, V = int(n_stages), int(n_chunks)

    def one(a):
        L = a.shape[0]
        if L % (S * V):
            raise ValueError(
                f"n_layer {L} not divisible by n_stages*n_chunks {S * V}"
            )
        lc = L // (S * V)
        g_major = a.reshape((S * V, lc) + a.shape[1:])   # [G, Lc, ...]
        if V == 1:
            return g_major.reshape((S, lc) + a.shape[1:])
        # [G, Lc, ...] -> [V, S, Lc, ...] -> [S, V, Lc, ...]
        vs = g_major.reshape((V, S, lc) + a.shape[1:])
        return jnp.transpose(vs, (1, 0) + tuple(range(2, vs.ndim)))

    return jax.tree.map(one, stacked)


def _stage_rng(rng, stage_idx, t):
    if rng is None:
        return None
    return jax.random.fold_in(jax.random.fold_in(rng, stage_idx), t)
