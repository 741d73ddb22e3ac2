"""Continuous (slot-based) batching for serving (VERDICT r4 next #3).

The static micro-batch scheduler (serving.BatchedGenerationService)
forms a batch once and decodes it to the longest budget: rows that
finish early keep occupying the chip, and new arrivals wait out the
whole loop. This module replaces that with a persistent decode engine:

- a shared KV cache of ``slots`` rows over the model's full
  ``max_len``, advanced by a single global position counter;
- requests ADMIT into free rows mid-flight: a batch-1 prefill against
  a fresh cache positioned at ``p - bucket`` computes the prompt's
  K/V with the correct absolute-slot RoPE rotations, and a row-scatter
  copies it into the shared cache, with the row's ``pad_len = p - L``
  hiding everything before its prompt (the same per-row-constant-shift
  argument that makes mixed-length batching exact — models/llama.py
  ``_cached_attention``);
- decode runs in CHUNKS of ``chunk`` in-graph steps (``lax.scan``)
  with per-row budgets, stop sets, sampling params, and rng streams —
  the round-5 per-row machinery from engine/generate — so rows finish
  independently and their slots free between chunks;
- the worker dispatches one chunk AHEAD when no arrivals are waiting,
  hiding the host round trip of each fenced dispatch;
- when the global position would not fit another request the engine
  waits for drain and starts a new ERA (reset the counter; stale K/V
  needs no zeroing — every row's ``pad_len`` masks it);
- with a prefix cache attached (engine/kvcache.py, the
  ``prefix_cache`` constructor arg), admissions whose prompt prefix is
  pooled scatter the cached block chain into their cache slots and
  prefill ONLY the suffix (``_warm_admit_fn``) — pool blocks are
  era-independent (canonical rotation space), so reuse survives era
  resets for free.

Token-exactness: a request's tokens depend only on its own prompt,
seed, and sampling config — never on admission time or batch
composition (tests pin this against solo ``generate()`` runs, float-
tolerance exact like the static scheduler's mixed-length batching).

Restricted to pad-capable models (RoPE positions + non-rolling cache);
``serve.py`` falls back to the static scheduler otherwise. The
reference has no serving path at all (/root/reference/test.py is batch
eval) — this subsystem is beyond-reference capability; no benchmark
cell measures it yet (PERF.md section 7).
"""
from __future__ import annotations

import functools
import logging
import queue as queue_mod
import threading
import time

import numpy as np

from ..observability.trace import span
from ..utils.promtext import percentile
from .serving import GenerationService

logger = logging.getLogger(__name__)


@functools.lru_cache(maxsize=64)
def _admit_fn(model, bucket: int, k: int, n_stop: int):
    """Compiled admission for ``k`` same-bucket prompts: ONE dispatch
    does the batched prefill (a fresh ``[k]``-row cache positioned so
    every prompt ends at ``pos0 + bucket``), samples the first tokens
    (stream index 0 per row — identical to solo ``generate()``'s key
    folding), scatters the prefilled rows into the shared cache,
    advances the shared ``pos_index``, and writes the slot-state
    arrays.

    Everything is fused into one executable with PACKED integer/float
    side inputs because every small dispatch pays a host round trip:
    the earlier shape of this path (per-request prefill + separate
    scatter + per-slot host scalars) paid one per request, and even
    split-but-batched dispatches left the uniform burst behind the
    static scheduler. Donates the shared cache and slot arrays.

    ``ints`` columns: [slot, budget, pad_len, stop_0..stop_{W-1},
    pos0] (pos0 replicated down its column; row 0 is read).
    ``floats`` columns: [temperature, top_p]; ``topk_k`` rides
    separately as int.
    """
    import jax
    import jax.numpy as jnp

    from ..parallel.tp import constrain_kv_tree
    from .generate import _sample_rows_traced

    total = int(model.max_len)
    mesh = getattr(model, "mesh", None)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def admit(params, shared, arrays, prompts, ints, floats,
              keys_data_k, topk_k):
        slots = ints[:, 0]
        budgets_k = ints[:, 1]
        pad_k = ints[:, 2]
        stops_k = ints[:, 3:3 + n_stop]
        pos0 = ints[0, 3 + n_stop]
        temps_k = floats[:, 0]
        ps_k = floats[:, 1]
        keys = jax.random.wrap_key_data(keys_data_k)
        shapes = jax.eval_shape(
            lambda p: model.apply(
                {"params": p}, jnp.zeros((k, total), jnp.int32),
                train=False, decode=True, mutable=["cache"],
            ),
            params,
        )[1]["cache"]
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             shapes)
        cache = dict(constrain_kv_tree(cache, mesh))  # TP head shard
        cache["pos_index"] = pos0.astype(jnp.int32)
        logits, vs = model.apply(
            {"params": params, "cache": cache}, prompts,
            train=False, decode=True, prefill=True, mutable=["cache"],
            pad_lens=pad_k,
        )
        tok0 = _sample_rows_traced(
            jax.vmap(jax.random.fold_in)(keys,
                                         jnp.zeros((k,), jnp.int32)),
            logits[:, -1], temps_k, topk_k, ps_k,
        )

        # scatter the k prefilled rows into the shared cache (every
        # K/V-shaped leaf; duplicate slots from group padding rewrite
        # identical content, so order doesn't matter)
        new = vs["cache"]

        def put(s, n):
            if (s.ndim >= 1 and n.ndim == s.ndim and n.shape[0] == k
                    and s.shape[1:] == n.shape[1:]):
                # one indexed scatter per leaf (duplicate padded slots
                # write identical rows, so scatter order is moot); the
                # earlier k-way DUS unroll bloated the executable
                return s.at[slots].set(n.astype(s.dtype))
            return s

        shared = dict(jax.tree.map(put, dict(shared), new))
        # the shared position counter advances to the admission point;
        # chunks advance it in-graph from here (no per-dispatch host
        # rewrite)
        shared["pos_index"] = (pos0 + bucket).astype(jnp.int32)

        (tok, emitted, done, budgets, pad_lens, keys_data, stops,
         temps, ks, ps) = arrays
        arrays_out = (
            tok.at[slots].set(tok0),
            emitted.at[slots].set(jnp.ones((k,), jnp.int32)),
            done.at[slots].set(jnp.zeros((k,), bool)),
            budgets.at[slots].set(budgets_k),
            pad_lens.at[slots].set(pad_k),
            keys_data.at[slots].set(keys_data_k),
            stops.at[slots].set(stops_k),
            temps.at[slots].set(temps_k),
            ks.at[slots].set(topk_k),
            ps.at[slots].set(ps_k),
        )
        return shared, arrays_out, tok0

    return admit


@functools.lru_cache(maxsize=64)
def _warm_admit_fn(model, feed: int, k: int, n_stop: int, nb: int,
                   block: int, rotary: bool, rope_base: float,
                   kv_quant: str = ""):
    """Prefix-cache-aware admission: ``_admit_fn`` with the paged KV
    pool spliced in (engine/kvcache.py). The fed token window is only
    ``feed`` wide — the group's largest UNCACHED suffix snapped to the
    same power-of-two ladder as cold admission buckets, so the
    compile-cache/warmup story is untouched — and each row's cached
    prefix blocks are scattered into its cache slots (re-rotated from
    canonical to absolute-slot RoPE space by the row's constant start
    angle) before the prefill runs.

    Correctness shape: row ``j``'s prompt occupies slots
    ``pad_j .. p-1``; its blocks cover ``pad_j .. pad_j + c_j - 1`` and
    the fed window covers ``[p - feed, p)``. Because
    ``feed >= suffix_j`` for every row, the two always tile the prompt;
    where they overlap, the prefill's own DUS write wins over the
    scattered copy at every layer, so overlapped positions are
    RECOMPUTED exactly as the cold path computes them. Unused block
    lanes (-1 ids, group padding) redirect into the fed window, where
    the same DUS overwrite makes their garbage dead by construction.

    ``ints`` layout is ``_admit_fn``'s with ``pos0 = p - feed``; the
    pool rides as a ``{path: [P, block, H, D]}`` dict plus ``[k, nb]``
    block ids. Donates the shared cache and slot arrays; the pool is
    read-only here (capture owns its donation).
    """
    import jax
    import jax.numpy as jnp

    from ..parallel.tp import constrain_kv_tree
    from .generate import _sample_rows_traced
    from .kvcache import scatter_blocks

    total = int(model.max_len)
    mesh = getattr(model, "mesh", None)

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def admit(params, shared, arrays, prompts, ints, floats,
              keys_data_k, topk_k, pool, block_ids):
        slots = ints[:, 0]
        budgets_k = ints[:, 1]
        pad_k = ints[:, 2]
        stops_k = ints[:, 3:3 + n_stop]
        pos0 = ints[0, 3 + n_stop]
        temps_k = floats[:, 0]
        ps_k = floats[:, 1]
        keys = jax.random.wrap_key_data(keys_data_k)
        shapes = jax.eval_shape(
            lambda p: model.apply(
                {"params": p}, jnp.zeros((k, total), jnp.int32),
                train=False, decode=True, mutable=["cache"],
            ),
            params,
        )[1]["cache"]
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             shapes)
        cache = constrain_kv_tree(cache, mesh)        # TP head shard
        cache = dict(scatter_blocks(
            dict(cache), pool, block_ids, pad_k, pos0, feed, block,
            rotary=rotary, rope_base=rope_base, kv_quant=kv_quant))
        cache["pos_index"] = pos0.astype(jnp.int32)
        logits, vs = model.apply(
            {"params": params, "cache": cache}, prompts,
            train=False, decode=True, prefill=True, mutable=["cache"],
            pad_lens=pad_k,
        )
        tok0 = _sample_rows_traced(
            jax.vmap(jax.random.fold_in)(keys,
                                         jnp.zeros((k,), jnp.int32)),
            logits[:, -1], temps_k, topk_k, ps_k,
        )
        new = vs["cache"]

        def put(s, n):
            if (s.ndim >= 1 and n.ndim == s.ndim and n.shape[0] == k
                    and s.shape[1:] == n.shape[1:]):
                return s.at[slots].set(n.astype(s.dtype))
            return s

        shared = dict(jax.tree.map(put, dict(shared), new))
        shared["pos_index"] = (pos0 + feed).astype(jnp.int32)

        (tok, emitted, done, budgets, pad_lens, keys_data, stops,
         temps, ks, ps) = arrays
        arrays_out = (
            tok.at[slots].set(tok0),
            emitted.at[slots].set(jnp.ones((k,), jnp.int32)),
            done.at[slots].set(jnp.zeros((k,), bool)),
            budgets.at[slots].set(budgets_k),
            pad_lens.at[slots].set(pad_k),
            keys_data.at[slots].set(keys_data_k),
            stops.at[slots].set(stops_k),
            temps.at[slots].set(temps_k),
            ks.at[slots].set(topk_k),
            ps.at[slots].set(ps_k),
        )
        return shared, arrays_out, tok0

    return admit


@functools.lru_cache(maxsize=64)
def _paged_admit_fn(model, feed: int, k: int, n_stop: int, nb: int):
    """TRUE paged admission (ISSUE 7 tentpole): NO cache build, NO
    scatter copy. The shared cache is gone — the engine's cache pytree
    IS the block pool, and this executable (a) writes the group's block
    tables into the shared table array (the entire "warm admit" for the
    cached prefix: a pointer update), (b) prefills ONLY each row's
    uncached suffix through the model's paged path (its K/V lands
    directly in the row's private pool pages), and (c) samples first
    tokens + writes slot state, all in one dispatch.

    Positions are row-local: row ``j``'s suffix occupies window lanes
    ``pad_j..feed-1`` at positions ``c_j..L_j-1`` (``rs_j = L_j - feed``
    is lane 0's position; lanes below ``pad_j`` write the scratch
    page). Shared radix pages cover positions ``< c_j`` and are never
    written — warm admit device-copy bytes are ZERO by construction.

    ``ints`` columns: [slot, budget, pad_0.., stop_0..stop_{W-1}, rs].
    Donates the pool cache, tables, slot arrays, and starts.
    """
    import jax
    import jax.numpy as jnp

    from .generate import _sample_rows_traced

    @functools.partial(jax.jit, donate_argnums=(1, 2, 3, 4))
    def admit(params, cache, tables, arrays, starts, prompts, ints,
              floats, keys_data_k, topk_k, tables_k):
        slots = ints[:, 0]
        budgets_k = ints[:, 1]
        pad_k = ints[:, 2]
        stops_k = ints[:, 3:3 + n_stop]
        rs_k = ints[:, 3 + n_stop]
        temps_k = floats[:, 0]
        ps_k = floats[:, 1]
        keys = jax.random.wrap_key_data(keys_data_k)
        tables = tables.at[slots].set(tables_k)
        logits, vs = model.apply(
            {"params": params, "cache": cache}, prompts,
            train=False, decode=True, prefill=True, mutable=["cache"],
            pad_lens=pad_k, block_tables=tables_k, row_starts=rs_k,
        )
        cache = dict(vs["cache"])
        tok0 = _sample_rows_traced(
            jax.vmap(jax.random.fold_in)(keys,
                                         jnp.zeros((k,), jnp.int32)),
            logits[:, -1], temps_k, topk_k, ps_k,
        )
        starts = starts.at[slots].set(rs_k + feed)
        (tok, emitted, done, budgets, pad_lens, keys_data, stops,
         temps, ks, ps) = arrays
        arrays_out = (
            tok.at[slots].set(tok0),
            emitted.at[slots].set(jnp.ones((k,), jnp.int32)),
            done.at[slots].set(jnp.zeros((k,), bool)),
            budgets.at[slots].set(budgets_k),
            pad_lens.at[slots].set(jnp.zeros((k,), jnp.int32)),
            keys_data.at[slots].set(keys_data_k),
            stops.at[slots].set(stops_k),
            temps.at[slots].set(temps_k),
            ks.at[slots].set(topk_k),
            ps.at[slots].set(ps_k),
        )
        return cache, tables, arrays_out, starts, tok0

    return admit


@functools.lru_cache(maxsize=16)
def _paged_chunk_fn(model, steps: int, n_stop: int):
    """``steps`` in-graph paged decode steps: every slot's single token
    feeds at its OWN row-local position (``starts``) and its K/V
    appends into its private pool page through the block table
    (models/llama._paged_attention); attention reads the pool in place
    (ops/flash paged kernel on TPU). Frozen rows pass ``pad_lens=1`` so
    their (ignored) writes land in the scratch page — a done row can
    never dirty a page the radix index might share. Donates the pool
    cache."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .generate import _isin, _sample_rows_traced

    @functools.partial(jax.jit, donate_argnums=1)
    def chunk(params, cache, tables, starts, tok, emitted, done, budgets,
              pad_lens, keys_data, stops, temps, ks, ps):
        del pad_lens               # paged rows have no left-pad space
        keys = jax.random.wrap_key_data(keys_data)
        done = done | _isin(tok, stops) | (emitted >= budgets)

        def body(carry, _):
            cache, starts, tok, emitted, done = carry
            logits, vs = model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                train=False, decode=True, mutable=["cache"],
                pad_lens=done.astype(jnp.int32),
                block_tables=tables, row_starts=starts,
            )
            lg = logits[:, -1]
            step_keys = jax.vmap(jax.random.fold_in)(keys, emitted)
            nxt = lax.cond(
                jnp.any((temps > 0.0) & ~done),
                lambda: _sample_rows_traced(step_keys, lg, temps, ks,
                                            ps),
                lambda: jnp.argmax(lg, axis=-1).astype(jnp.int32),
            )
            nxt = jnp.where(done, 0, nxt)
            live = (~done).astype(jnp.int32)
            emitted = emitted + live
            starts = starts + live
            done = done | _isin(nxt, stops) | (emitted >= budgets)
            return (dict(vs["cache"]), starts, nxt, emitted, done), nxt

        (cache, starts, tok, emitted, done), toks = lax.scan(
            body, (cache, starts, tok, emitted, done), None,
            length=steps)
        return cache, starts, jnp.swapaxes(toks, 0, 1), tok, emitted, \
            done

    return chunk


@functools.lru_cache(maxsize=16)
def _chunk_fn(model, steps: int, n_stop: int):
    """``steps`` in-graph decode steps over all slots: per-row rng
    streams (folded at each row's own emission index, matching solo
    ``generate()`` exactly), traced per-row sampling, stop sets,
    budgets; finished rows freeze. Donates the cache."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .generate import _isin, _sample_rows_traced

    @functools.partial(jax.jit, donate_argnums=1)
    def chunk(params, cache, tok, emitted, done, budgets, pad_lens,
              keys_data, stops, temps, ks, ps):
        keys = jax.random.wrap_key_data(keys_data)
        # re-derive done for the FED tokens: a freshly admitted row
        # whose first token already hit a stop (or whose budget is 1)
        # must freeze from step one — the host defers that check to
        # here so admission never forces a device sync
        done = done | _isin(tok, stops) | (emitted >= budgets)

        def body(carry, _):
            cache, tok, emitted, done = carry
            logits, vs = model.apply(
                {"params": params, "cache": cache}, tok[:, None],
                train=False, decode=True, mutable=["cache"],
                pad_lens=pad_lens,
            )
            lg = logits[:, -1]
            step_keys = jax.vmap(jax.random.fold_in)(keys, emitted)
            # all-greedy batches skip the sampling branch AT RUNTIME
            # (lax.cond executes one side): the traced sampler's
            # full-vocab sort is pure waste for greedy traffic, and
            # greedy rows inside a mixed batch still take argmax
            # per-row inside the sampled branch — outputs identical.
            # Gated on LIVE rows only: a completed slot keeps its
            # temperature until reused, and one stale sampled slot
            # would otherwise disable the shortcut for all later
            # greedy traffic
            nxt = lax.cond(
                jnp.any((temps > 0.0) & ~done),
                lambda: _sample_rows_traced(step_keys, lg, temps, ks,
                                            ps),
                lambda: jnp.argmax(lg, axis=-1).astype(jnp.int32),
            )
            nxt = jnp.where(done, 0, nxt)
            emitted = emitted + (~done).astype(jnp.int32)
            done = done | _isin(nxt, stops) | (emitted >= budgets)
            return (vs["cache"], nxt, emitted, done), nxt

        (cache, tok, emitted, done), toks = lax.scan(
            body, (cache, tok, emitted, done), None, length=steps)
        return cache, jnp.swapaxes(toks, 0, 1), tok, emitted, done

    return chunk


class ContinuousBatchingService(GenerationService):
    """``GenerationService`` with the slot scheduler above. The wire
    API is identical to the static scheduler's (prompt / budget /
    sampling / seed / stop per request); there are NO group keys —
    per-row budgets, stops, and sampling live in the executable, so
    ANY mix of requests shares the engine. ``stats`` adds slot
    occupancy and end-to-end latency percentiles (surfaced via
    ``/healthz``)."""

    MAX_STOPS = 8          # static stop-set width in the executable
    GROW_MAX = 8           # adaptive chunk growth cap, x base chunk
    # growth cap when live rows carry stop tokens (they can finish
    # mid-chunk); clamped by GROW_MAX so every pickable length stays
    # inside the precompiled ladder whatever GROW_MAX is tuned to
    GROW_MAX_STOPS = 4
    STREAM_DELTAS = True   # generate(on_tokens=...) emits incremental
    # per-chunk token deltas (serve.py "stream": true)

    def _setup(self, model, params, tokenizer=None, slots: int = 8,
               chunk: int = 8, window_ms: float = 5.0,
               warm_buckets=None, prefix_cache=None, recorder=None,
               spec_draft_layers: int = 0, tracer=None, slo=None,
               brownout=None, role: str = "both", tsdb=None,
               prefill_chunk_tokens: int = 0):
        super()._setup(model, params, tokenizer,
                       prefix_cache=prefix_cache,
                       spec_draft_layers=spec_draft_layers,
                       tracer=tracer, slo=slo, role=role)
        self._recorder = recorder
        # fleet timeline store (ISSUE 14): each absorbed chunk feeds
        # one observation — counters become interval rates, queue/slot
        # occupancy sample as gauges (observability/timeseries.py)
        self._tsdb = tsdb
        # pool_exhaust fault window: until this monotonic instant the
        # prefix pool reports dry (paged admissions defer, scatter
        # lookups miss) — 0 = no window active
        self._pool_dry_until = 0.0
        # sliding-window models (ISSUE 15): the rolling contiguous
        # cache disqualifies the scatter engine (_pad_ok is False),
        # but the paged RING layout serves them — positions are
        # row-local and pad masking is the paged path's own
        ring_ok = (self._prefix is not None and self._prefix.paged
                   and getattr(self._prefix, "window", 0) > 0)
        if not self._pad_ok and not ring_ok:
            raise ValueError(
                f"{type(model).__name__} is not pad-capable (RoPE "
                "positions + non-rolling cache needed): use the static "
                "BatchedGenerationService, or attach a paged prefix "
                "cache for the sliding-window ring layout")
        import jax

        self._slots = int(slots)
        self._chunk = int(chunk)
        self._init_brownout(brownout)   # needs _slots/_chunk above
        # TRUE paged decode (ISSUE 7): with a paged-capable pool the
        # shared contiguous cache is replaced by the block pool + a
        # per-slot block table — warm admits become pointer updates
        # (zero device copy), decode reads pool pages in place, and
        # finished requests' pages adopt into the radix index with no
        # capture kernel. Unsupported layouts keep the round-5 scatter
        # fallback below, unchanged.
        self._paged = self._prefix is not None and self._prefix.paged
        # chunked streaming prefill (ISSUE 15 tentpole): prompts whose
        # uncached suffix exceeds this stream through fixed-size
        # prefill chunks across scheduler ticks instead of minting one
        # giant admit-bucket executable that stalls the decode batch.
        # Power-of-two so bucketed feeds stay inside the warmed
        # ladder; MANDATORY (and capped at the ring slack) for window
        # models, whose single-dispatch feeds are bounded by the ring
        # geometry contract.
        chunk_tok = int(prefill_chunk_tokens or 0)
        if chunk_tok and (chunk_tok & (chunk_tok - 1)):
            raise ValueError(
                f"serving.prefill_chunk_tokens={chunk_tok} must be a "
                "power of two (admission feeds snap to the bucket "
                "ladder)")
        if self._paged and getattr(self._prefix, "window", 0) > 0:
            cap = int(self._prefix.ring_slack_tokens)
            chunk_tok = min(chunk_tok or cap, cap)
        elif chunk_tok and not self._paged:
            logger.warning(
                "prefill_chunk_tokens=%d ignored: chunked streaming "
                "prefill needs the paged pool (scatter/no-pool serves "
                "monolithically)", chunk_tok)
            chunk_tok = 0
        self._prefill_chunk = chunk_tok
        self._tables = None          # [slots, nb_max] device block table
        self._starts = None          # [slots] row-local next-fed position
        # host-side key derivation: the default threefry impl's key
        # data for integer seed s is [s >> 32, s & 0xffffffff]; going
        # through jax.random.key() per request costs a device round
        # trip IN THE CALLER'S THREAD, which serialized burst arrivals
        # and split them into admission waves.
        # Probe once; non-threefry impls fall back to the device path.
        probe = np.asarray(jax.random.key_data(
            jax.random.key(0x123456789A)))
        want = np.asarray([0x123456789A >> 32,
                           0x123456789A & 0xFFFFFFFF], np.uint32)
        self._host_keys = (probe.shape == (2,)
                           and np.array_equal(probe, want))
        self._window_s = float(window_ms) / 1e3
        self._queue: "queue_mod.Queue" = queue_mod.Queue()
        self._latencies: list = []
        # server-side TTFT per request (ISSUE 8 satellite): stamped at
        # the first absorb that hands a row its tokens — the earliest
        # moment the first token is actually servable to the client
        self._ttfts: list = []
        # prompt-length buckets whose (bucket, k) admit executables are
        # primed at startup alongside the chunk ladder: normalized
        # through the scheduler's own bucketing, deduped, and dropped
        # (LOUDLY — an operator asked for them) when even a 1-token
        # budget cannot fit the era
        self._warm_buckets = sorted({
            self._bucket(int(b)) for b in (warm_buckets or ())
            if int(b) > 0
            and self._bucket(int(b)) + 1 <= int(model.max_len)
        })
        dropped = [int(b) for b in (warm_buckets or ())
                   if int(b) <= 0
                   or self._bucket(int(b)) + 1 > int(model.max_len)]
        if dropped:
            logger.warning(
                "warm_buckets %s dropped (not in (0, max_len=%d) after "
                "bucketing): their admit executables will compile at "
                "the first matching arrival instead",
                dropped, int(model.max_len),
            )
        self.stats = {"requests": 0, "completed": 0, "chunks": 0,
                      "admissions": 0, "eras": 0, "max_active": 0,
                      "tokens_generated": 0, "cancelled": 0,
                      "paged_chunks": 0, "paged_admissions": 0,
                      "deferred_admissions": 0, "deadline_expired": 0,
                      "brownout_clamped": 0,
                      # disaggregated serving (ISSUE 12): pages shipped
                      # in from prefill-role replicas / exports served
                      "remote_admits": 0, "prefill_exports": 0,
                      # chunked streaming prefill (ISSUE 15): chunks
                      # dispatched, prompt tokens streamed through
                      # them, and requests that streamed at all
                      "prefill_chunks": 0, "streamed_prefill_tokens": 0,
                      "streamed_requests": 0}
        self._warm_chunk_ladder()
        if self.tp > 1:
            # precompute the per-step collective accounting with the
            # rest of the warmup (one AOT compile) so neither the
            # scheduler thread nor a /metrics scrape pays it later
            self.tp_stats()
        self._worker_thread = threading.Thread(
            target=self._worker, daemon=True, name="gen-continuous")
        self._worker_thread.start()

    # ---- brownout ladder (ISSUE 9) ---------------------------------------

    def _init_brownout(self, cfg) -> None:
        """Attach the hysteresis ladder (utils/brownout.py) from a
        ``serving.brownout`` config dict (``{"enabled": true, ...}``)
        or a prebuilt controller. Off by default: degradation modes
        change observable behavior (clamped budgets), so the operator
        opts in."""
        from ..utils.brownout import BrownoutController

        self._brownout = None
        self._bo_queue_norm = 1.0
        self._bo_max_new = 0
        self._bo_breach_ewma = 0.0
        self._bo_last = (0, 0)          # (breaches, completed) marks
        self._bo_lock = threading.Lock()
        if cfg is None:
            return
        if isinstance(cfg, BrownoutController):
            self._brownout = cfg
            return
        cfg = dict(cfg)
        if not cfg.get("enabled"):
            return
        # queue_norm: queue depth equal to slots*queue_norm reads as
        # pressure 1.0 ("at capacity") — the ladder thresholds are in
        # those units
        self._bo_queue_norm = float(cfg.get("queue_norm", 1.0))
        # level-3 budget cap; 0 derives a default from the chunk size
        self._bo_max_new = int(cfg.get("max_new_cap", 0)) \
            or self._chunk * 4
        kw = {}
        if "enter" in cfg:
            kw["enter"] = tuple(cfg["enter"])
        if "exit" in cfg:
            kw["exit"] = tuple(cfg["exit"])
        self._brownout = BrownoutController(
            dwell_s=float(cfg.get("dwell_s", 2.0)),
            on_change=self._on_brownout_change, **kw)

    def _on_brownout_change(self, old: int, new: int,
                            pressure: float) -> None:
        logger.warning("brownout level %d -> %d (pressure %.2f)",
                       old, new, pressure)
        if self._recorder is not None:
            self._recorder.record(
                self.stats["chunks"], event="brownout",
                brownout_level=new, brownout_prev=old,
                brownout_pressure=round(pressure, 4))

    @property
    def brownout_level(self) -> int:
        return self._brownout.level if self._brownout is not None else 0

    def brownout_stats(self) -> dict:
        if self._brownout is None:
            return {"brownout_level": 0}
        # scrape-driven refresh: ticks only run under traffic, so an
        # idle engine's ladder would otherwise freeze at its last
        # level forever — each /metrics read feeds the controller the
        # CURRENT pressure (hysteresis dwell still applies, so scrapes
        # cannot flap it)
        with self._bo_lock:
            self._brownout.update(self._brownout_pressure())
            return self._brownout.stats()

    def _brownout_pressure(self, waiting: int = 0) -> float:
        """Normalized pressure: the max of (a) waiting requests
        (still-queued plus the tick's drained-but-unadmitted pending
        set — the worker drains the queue into ``pending`` before each
        tick, so the raw qsize alone under-reads) over
        ``slots * queue_norm``, (b) the pool's live-referenced page
        fraction (resident-but-shareable pages are a HEALTHY cache —
        only pages pinned by live requests signal pressure), and
        (c) an EWMA of the recent SLO breach rate (breaches per
        completion), each normalized so 1.0 ≈ at capacity."""
        p = (self._queue.qsize() + waiting) / max(
            self._slots * self._bo_queue_norm, 1e-9)
        if self._prefix is not None:
            snap = self._prefix.stats_snapshot()
            total = max(snap.get("prefix_pool_blocks", 0), 1)
            p = max(p, snap.get("prefix_pool_blocks_referenced", 0)
                    / total)
        if self._slo is not None:
            s = self._slo.stats()
            breaches = s.get("slo_breach_total", 0)
            completed = self.stats.get("completed", 0)
            db = breaches - self._bo_last[0]
            dc = completed - self._bo_last[1]
            if dc > 0:
                self._bo_breach_ewma += 0.3 * (
                    min(db / dc, 1.0) - self._bo_breach_ewma)
                self._bo_last = (breaches, completed)
            p = max(p, self._bo_breach_ewma)
        return p

    def _pool_dry(self) -> bool:
        """The pool_exhaust fault window: while active, the paged
        reservation path reports dry (admissions defer) and the
        scatter lookup path reports a miss."""
        return (self._pool_dry_until > 0.0
                and time.monotonic() < self._pool_dry_until)

    def _warm_chunk_ladder(self):
        """Compile every chunk length the scheduler can pick — base
        chunk and its power-of-two growth ladder up to GROW_MAX — on
        throwaway all-done slot state, BEFORE the worker starts.

        Adaptive growth chooses a length from the ladder based on
        ``min_left``, which depends on which requests share the engine
        at that instant — timing-nondeterministic, so without this a
        length can be first seen mid-traffic and every slot stalls
        behind a fresh XLA compile (tens of seconds for the 124M
        serving model; the serve_mixed rung's chunk=8 arm measured
        ~10x slower from exactly that). One-time startup
        cost, same contract as the padded admission width in
        ``_admit_group``.

        Deliberately EXECUTES each length instead of AOT
        ``.lower().compile()``: the AOT path builds a separate
        executable that is not guaranteed to seed the dispatch-path
        jit cache the worker actually hits, and a warmup that only
        probably warms is worse than ~120 frozen-row decode steps
        (~1 s; all slots are done, rows freeze, nothing is emitted).

        ``warm_buckets`` (constructor arg) extends the same contract to
        the ADMIT executables: each configured prompt-length bucket's
        ``(bucket, k)`` admission compiles here on throwaway slot state
        — with them covering the deployment's traffic shape, the first
        arrival wave never stalls behind an XLA compile. Off by default
        (each bucket costs one batched-prefill compile at startup)."""
        from .generate import fresh_cache

        total = int(self.model.max_len)
        self._init_arrays()
        arrays = self._arrays
        if self._paged:
            # paged warmup runs against the REAL pool: with an all -1
            # table every write lands in the scratch page and every
            # read is masked, so executing the ladder cannot dirty a
            # sharable page — and the executables warmed are exactly
            # the dispatch-path ones
            import jax.numpy as jnp

            cache = self._prefix.paged_cache()
            tables = jnp.full((self._slots, self._prefix.nb_max), -1,
                              jnp.int32)
            starts = jnp.zeros((self._slots,), jnp.int32)
            if self._warm_buckets:
                self._warm_paged_signatures(cache, tables, starts,
                                            arrays, total)
                self._arrays = None
                return
            steps = self._chunk
            while steps <= min(self._chunk * self.GROW_MAX, total):
                fn = _paged_chunk_fn(self.model, steps, self.MAX_STOPS)
                out = fn(self.params, cache, tables, starts, *arrays)
                cache, starts = out[0], out[1]
                steps *= 2
            self._prefix.sync_pool_from_cache(cache)
            self._arrays = None
            return
        cache = fresh_cache(self.model, self.params, self._slots, total)
        steps = self._chunk
        while steps <= min(self._chunk * self.GROW_MAX, total):
            fn = _chunk_fn(self.model, steps, self.MAX_STOPS)
            out = fn(self.params, cache, *arrays)
            cache = out[0]           # the cache argument is donated
            steps *= 2
        if self._warm_buckets:
            self._warm_admit_ladder(cache, arrays)
        self._arrays = None          # the worker builds its own state

    def _warm_admit_once_paged(self, feed, cache, tables, arrays,
                               starts):
        """Execute ONE paged admission wave at ``feed`` on the given
        state (dummy rows: fully padded, budget 1 — every write lands
        in the scratch page) and return the donated-through state."""
        import jax
        import jax.numpy as jnp

        k, W = self._slots, self.MAX_STOPS
        nb = self._prefix.nb_max
        kd = np.asarray(jax.random.key_data(jax.random.key(0)))
        keys_data = jnp.asarray(np.tile(kd, (k, 1)))
        ints = np.zeros((k, 4 + W), np.int32)
        ints[:, 0] = np.arange(k)
        ints[:, 1] = 1                  # budget 1
        ints[:, 2] = feed               # all lanes padded
        ints[:, 3:3 + W] = -1
        ints[:, 3 + W] = -feed          # rs: last lane at position 0
        return _paged_admit_fn(self.model, feed, k, W, nb)(
            self.params, cache, tables, arrays, starts,
            jnp.zeros((k, feed), jnp.int32), jnp.asarray(ints),
            jnp.zeros((k, 2), jnp.float32), keys_data,
            jnp.zeros((k,), jnp.int32),
            jnp.full((k, nb), -1, jnp.int32))[:4]

    def _warm_paged_signatures(self, cache, tables, starts, arrays,
                               total: int):
        """Warm the paged executables at the SIGNATURES live traffic
        actually dispatches. A jit signature includes each argument's
        commitment/sharding, not just its shape: the pool starts life
        as uncommitted ``jnp.zeros`` but every jit OUTPUT is committed,
        so after the first real admission all engine state is committed
        — a ladder warmed only on construction-time (uncommitted)
        state compiles executables the dispatch path never hits, and
        the first arrival wave stalls behind fresh XLA compiles anyway
        (measured: ~2 s on CPU — long enough to trip the fleet's
        wedged-replica detector). Three signature classes cover the
        engine's lifetime:

        1. **first admission**: committed pool cache + fresh
           (uncommitted) tables/slot arrays — happens exactly once;
        2. **steady-state chunks**: everything committed (all chunk
           inputs come out of an admit/chunk dispatch);
        3. **steady-state admissions**: everything committed.

        Bootstrap: one admission on the all-uncommitted construction
        state (its signature is never dispatched again — the price of
        obtaining committed state without guessing shardings), pool
        synced so ``paged_cache()`` hands back committed leaves, then
        classes 1-3 executed in dispatch order per feed bucket /
        chunk-ladder step."""
        import jax
        import jax.numpy as jnp

        k = self._slots
        nb = self._prefix.nb_max
        b, feeds = 16, []
        while b <= max(self._warm_buckets):
            feeds.append(b)
            b *= 2
        # bootstrap: commit every state leaf the way jit outputs are
        cache, tables, arrays, starts = self._warm_admit_once_paged(
            feeds[0], cache, tables, arrays, starts)
        self._prefix.sync_pool_from_cache(cache)
        # class 1: committed pool, FRESH uncommitted tables/arrays —
        # the first real admission's exact signature, per feed bucket
        self._init_arrays()
        for feed in feeds:
            out = self._warm_admit_once_paged(
                feed, self._prefix.paged_cache(),
                jnp.full((k, nb), -1, jnp.int32), self._arrays,
                jnp.zeros((k,), jnp.int32))
            self._init_arrays()     # fresh (uncommitted) per feed
            self._prefix.sync_pool_from_cache(out[0])
        cache, tables, arrays, starts = out
        # class 2: the chunk ladder on fully-committed state,
        # rebuilding the arrays tuple exactly as _dispatch_chunk does
        steps = self._chunk
        while steps <= min(self._chunk * self.GROW_MAX, total):
            fn = _paged_chunk_fn(self.model, steps, self.MAX_STOPS)
            cache, starts, _, tok, emitted, done = fn(
                self.params, cache, tables, starts, *arrays)
            arrays = (tok, emitted, done) + tuple(arrays[3:])
            steps *= 2
        # class 3: steady-state admissions (everything committed)
        for feed in feeds:
            cache, tables, arrays, starts = \
                self._warm_admit_once_paged(feed, cache, tables,
                                            arrays, starts)
        jax.block_until_ready(arrays[0])
        self._prefix.sync_pool_from_cache(cache)

    def _warm_admit_ladder(self, cache, arrays):
        """Execute the admit executable for every configured bucket on
        the throwaway warmup state (cache/arrays donate through the
        chain and are discarded by the caller). Dummy rows: budget 1,
        fully-padded prompts at era position ``p = bucket`` — the
        values are irrelevant, the (bucket, k) specialization is the
        product."""
        import jax
        import jax.numpy as jnp

        k, W = self._slots, self.MAX_STOPS
        kd = np.asarray(jax.random.key_data(jax.random.key(0)))
        keys_data = jnp.asarray(np.tile(kd, (k, 1)))
        buckets = self._warm_buckets
        if self._prefix is not None and buckets:
            # prefix-cache hits admit with feed = bucket(largest
            # UNCACHED suffix) — any ladder value up to the configured
            # prompt bucket, not just the bucket itself. Prime the
            # whole power-of-two sub-ladder so the first shared-prefix
            # wave after startup never stalls every slot behind a
            # fresh XLA compile (the exact class of stall warm_buckets
            # exists to prevent)
            b, sub = 16, []
            while b <= max(buckets):
                sub.append(b)
                b *= 2
            buckets = sorted(set(buckets) | set(sub))
        for bucket in buckets:
            pos0 = 0                       # admission at p == bucket
            ints = np.zeros((k, 4 + W), np.int32)
            ints[:, 0] = np.arange(k)      # one row per slot
            ints[:, 1] = 1                 # budget 1
            ints[:, 2] = pos0 + bucket - 1  # pad_len: 1-token prompts
            ints[:, 3:3 + W] = -1
            ints[:, 3 + W] = pos0
            if self._prefix is not None:
                # prefix-cache deployments run every admission through
                # the warm executable (a full miss feeds block_ids of
                # all -1) — prime THAT shape, not the legacy one
                nb = self._prefix.nb_max
                cache, arrays, _ = _warm_admit_fn(
                    self.model, bucket, k, W, nb, self._prefix.block,
                    self._prefix.rotary, self._prefix.rope_base,
                    self._prefix.kv_quant)(
                    self.params, cache, arrays,
                    jnp.zeros((k, bucket), jnp.int32),
                    jnp.asarray(ints), jnp.zeros((k, 2), jnp.float32),
                    keys_data, jnp.zeros((k,), jnp.int32),
                    self._prefix.pool,
                    jnp.full((k, nb), -1, jnp.int32))
            else:
                cache, arrays, _ = _admit_fn(self.model, bucket, k, W)(
                    self.params, cache, arrays,
                    jnp.zeros((k, bucket), jnp.int32), jnp.asarray(ints),
                    jnp.zeros((k, 2), jnp.float32), keys_data,
                    jnp.zeros((k,), jnp.int32))
        jax.block_until_ready(arrays[0])

    # ---- request entry ---------------------------------------------------

    def generate(self, prompt=None, prompt_ids=None,
                 max_new_tokens: int = 64, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 0.0, seed: int = 0,
                 speculative: int = 0, stop=None,
                 on_tokens=None, cancel=None, request_id=None,
                 deadline=None) -> dict:
        """Same contract as the parent plus ``on_tokens``: a callback
        receiving each batch of freshly decoded token ids for THIS
        request as its chunks absorb (stop tokens filtered — the
        concatenated deltas equal the final response's ``ids``). Runs
        on the scheduler thread: must not block. Powers serve.py's
        ``"stream": true`` server-sent events.

        ``cancel``: an optional ``threading.Event``. Once set, the
        request is finalized at its NEXT chunk absorb — the row's slot
        frees immediately for waiting traffic instead of decoding out
        the rest of its budget (a disconnected streaming client's main
        cost). The call returns the tokens decoded so far with
        ``stop_reason: "cancelled"``; a request still in the queue is
        dropped without ever taking a slot. Speculative requests
        (``speculative > 0``) bypass the slot engine (batch-1 under
        the parent's lock) and IGNORE ``cancel`` — they run their
        whole budget.

        ``deadline``: an optional :class:`reqtrace.Deadline` (ISSUE 9).
        Treated as a CANCEL the engine raises itself: a queued request
        whose deadline expires is dropped before taking a slot, and a
        decoding row is finalized at its next absorb with
        ``stop_reason: "deadline"`` and whatever tokens it produced —
        the slot frees for live traffic instead of decoding tokens
        nobody is waiting for."""
        if speculative > 0 and self.brownout_level >= 1:
            # brownout level 1 (no_spec): speculative decode's extra
            # verify bandwidth goes back to the batch — the request is
            # served, just without the latency optimization
            speculative = 0
        if speculative > 0:
            # batch-1 by construction; runs under the parent's lock
            # (the scheduler's own dispatches take the same lock)
            result = super().generate(
                prompt=prompt, prompt_ids=prompt_ids,
                max_new_tokens=max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, seed=seed,
                speculative=speculative, stop=stop,
                request_id=request_id, deadline=deadline)
            if on_tokens is not None and result.get("ids"):
                on_tokens(list(result["ids"]))   # single final delta
            return result
        ids = self.encode_prompt(prompt, prompt_ids)
        stops = self.encode_stop(stop)
        max_new = int(max_new_tokens)
        # role gate (ISSUE 12): a prefill-role replica refuses decode-
        # scale budgets before they ever take a slot
        self._check_role(max_new)
        # ONE owner for the enqueue rules (shared with serve.py's
        # pre-SSE validate_request — a rule changed here cannot drift
        # from the 400 path): stop-set width, max_new >= 1, and the
        # budget on the BUCKETED prompt length (admission rounds
        # prompts up to the executable bucket, so a request that only
        # fits unbucketed could never be admitted and would hang)
        self._validate_budget(ids, max_new, stops)
        seed = int(seed)
        if self._host_keys and seed >= 0:
            key_data = np.asarray(
                [seed >> 32, seed & 0xFFFFFFFF], np.uint32)
        else:
            import jax

            key_data = np.asarray(
                jax.random.key_data(jax.random.key(seed)))
        req = {
            "ids": ids, "budget": max_new,
            "temperature": float(temperature), "top_k": int(top_k),
            "top_p": float(top_p), "seed": seed, "stop": stops,
            "on_tokens": on_tokens, "cancel": cancel, "rid": request_id,
            "deadline": deadline,
            # raw key data, derived WITHOUT device work in the
            # caller's thread (host path above): per-request device
            # ops serialized burst arrivals
            "key_data": key_data,
            "event": threading.Event(), "t0": time.monotonic(),
        }
        self._queue.put(req)
        req["event"].wait()
        if "error" in req:
            raise req["error"]
        return req["result"]

    def _validate_budget(self, ids, max_new: int, stops,
                         speculative: int = 0) -> None:
        """The slot engine's enqueue-time checks, for serve.py's
        pre-SSE validation: speculative requests bypass the engine
        (parent's plain budget rule); slot requests check the BUCKETED
        prompt length (admission rounds prompts up to the executable
        bucket — a request that only fits unbucketed could never admit
        and would hang) and the static stop-set width."""
        if speculative > 0:
            return super()._validate_budget(ids, max_new, stops)
        if len(stops) > self.MAX_STOPS:
            raise ValueError(
                f"at most {self.MAX_STOPS} stop tokens per request "
                f"(got {len(stops)})")
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        max_len = int(self.model.max_len)
        if getattr(self, "_paged", False):
            # paged admissions are position-free (row-local positions,
            # pages reserved up front): the raw prompt length is the
            # budget constraint, NOT its admission bucket — a long
            # prompt admits through chunked streaming prefill without
            # rounding itself out of the model (ISSUE 15)
            if len(ids) + max_new > max_len:
                raise ValueError(
                    f"prompt ({len(ids)} tokens) + max_new_tokens "
                    f"({max_new}) exceeds model.max_len {max_len}")
            return
        if self._bucket(len(ids)) + max_new > max_len:
            raise ValueError(
                f"prompt ({len(ids)} tokens, admission bucket "
                f"{self._bucket(len(ids))}) + max_new_tokens "
                f"({max_new}) exceeds model.max_len {max_len}")

    # ---- scheduler internals --------------------------------------------

    @classmethod
    def _grow_cap(cls, live) -> int:
        """Adaptive chunk-growth cap (x base chunk) for the CURRENT
        live set: full ``GROW_MAX`` only when no live row can exit a
        chunk early. Rows with stop tokens can finish mid-chunk, and
        rows carrying a CANCEL event (streaming clients that may
        disconnect) are honored at the next absorb — both classes cap
        growth at ``GROW_MAX_STOPS`` so a freed slot (or a cancelled
        client's slot) is recycled within a short chunk, not up to
        GROW_MAX x chunk + one pipelined chunk later (ADVICE r5)."""
        return (min(cls.GROW_MAX_STOPS, cls.GROW_MAX)
                if any(m["req"]["stop"]
                       or m["req"].get("cancel") is not None
                       or m["req"].get("deadline") is not None
                       for m in live)
                else cls.GROW_MAX)

    @staticmethod
    def _bucket(n: int) -> int:
        b = 16
        while b < n:
            b *= 2
        return b

    def _admissible(self, req) -> bool:
        """Fits at the CURRENT position? The prompt must land before
        the global counter (bucket <= p) and the budget inside the
        era's remaining room. (Era-start placement for an idle engine
        is the FIFO-prefix loop in ``_tick``.)"""
        bucket = self._bucket(len(req["ids"]))
        return (bucket <= self._p
                and self._p + req["budget"] <= int(self.model.max_len))

    def _admit_group(self, reqs: list, slots: list):
        """Admit same-bucket requests in ONE prefill dispatch + ONE
        scatter dispatch, nothing forced (the first tokens stay device
        futures until the next absorb — admission must never stall the
        pipeline).

        The group is PADDED to a fixed width ``k = self._slots`` by
        repeating the last request (its duplicate rows scatter onto
        the same slot — a same-content rewrite, harmless): admission
        executables specialize on (bucket, k), and arrival-wave sizes
        are timing-nondeterministic, so a variable k means fresh XLA
        compiles landing mid-traffic (measured: the serve_mixed rung
        collapsed 201 -> 43 tok/s from exactly that)."""
        import jax.numpy as jnp

        if self._paged:
            return self._admit_group_paged(reqs, slots)
        t_admit0 = time.monotonic()
        ev0 = (self._prefix.counter("prefix_evictions")
               if self._prefix is not None and self._tracer is not None
               else 0)
        n = len(reqs)
        k = self._slots
        W = self.MAX_STOPS
        pad_reqs = reqs + [reqs[-1]] * (k - n)
        pad_slots = list(slots) + [slots[-1]] * (k - n)
        bucket = self._bucket(max(len(r["ids"]) for r in reqs))
        # ---- prefix-cache lookup: longest fully-blocked cached prefix
        # per request; the fed window shrinks to the largest UNCACHED
        # suffix (snapped to the same ladder — always <= bucket, so the
        # admissibility/era math above stays valid unchanged). Refs are
        # held until the copy kernels are dispatched, so a same-tick
        # insert can never evict a block this group is about to read.
        matches = None
        if self._prefix is not None:
            if self._pool_dry():
                # pool_exhaust fault window (scatter arm): every
                # lookup misses — admissions pay the full prefill
                matches = [([], [], 0) for _ in reqs]
            else:
                # promote=False: spilled chains were promoted at tick
                # start — a donation here would kill the cache the
                # admit dispatch below aliases
                matches = [self._prefix.lookup(r["ids"], promote=False)
                           for r in reqs]
            feed = self._bucket(max(
                len(r["ids"]) - m[2] for r, m in zip(reqs, matches)))
        else:
            feed = bucket
        pos0 = self._p - feed
        prompts = np.zeros((k, feed), np.int32)
        ints = np.full((k, 4 + W), pos0, np.int32)
        floats = np.zeros((k, 2), np.float32)
        topks = np.zeros((k,), np.int32)
        for j, r in enumerate(pad_reqs):
            m = min(len(r["ids"]), feed)   # fed = trailing tokens; any
            # leading truncation is covered by the row's cached blocks
            prompts[j, feed - m:] = r["ids"][len(r["ids"]) - m:]
            ints[j, 0] = pad_slots[j]
            ints[j, 1] = r["budget"]
            ints[j, 2] = self._p - len(r["ids"])
            ints[j, 3:3 + W] = -1
            for jj, sid in enumerate(r["stop"]):
                ints[j, 3 + jj] = sid
            floats[j] = (r["temperature"], r["top_p"])
            topks[j] = r["top_k"]
        keys_data = jnp.asarray(
            np.stack([r["key_data"] for r in pad_reqs]))
        if self._prefix is None:
            self._cache, self._arrays, tok0 = _admit_fn(
                self.model, bucket, k, W)(
                self.params, self._cache, self._arrays,
                jnp.asarray(prompts), jnp.asarray(ints),
                jnp.asarray(floats), keys_data, jnp.asarray(topks))
        else:
            nb = self._prefix.nb_max
            block_ids = np.full((k, nb), -1, np.int32)
            pad_matches = matches + [matches[-1]] * (k - n)
            for j, (_, blocks, _) in enumerate(pad_matches):
                block_ids[j, :len(blocks)] = blocks
            try:
                self._cache, self._arrays, tok0 = _warm_admit_fn(
                    self.model, feed, k, W, nb, self._prefix.block,
                    self._prefix.rotary, self._prefix.rope_base,
                    self._prefix.kv_quant)(
                    self.params, self._cache, self._arrays,
                    jnp.asarray(prompts), jnp.asarray(ints),
                    jnp.asarray(floats), keys_data, jnp.asarray(topks),
                    self._prefix.pool, jnp.asarray(block_ids))
            except Exception:
                # a failed dispatch (e.g. an OOM'd first compile) must
                # not strand the lookup refs: leaked refs pin blocks
                # against eviction FOREVER on a server that recovers
                for nodes, _, _ in matches:
                    self._prefix.release(nodes)
                raise
            # the scatter arm's admit-copy cost, made observable (the
            # paged path above never pays it): every cached block each
            # row reused crossed HBM into the fresh group cache
            self._prefix.record_copy_bytes(
                sum(len(m[1]) for m in matches))
            # inserts + the ref release ride one helper (its finally
            # owns the release from here on)
            self._insert_prefixes(reqs, slots, ints, matches)
        from .kvcache import page_origin_flags

        for j, (r, slot) in enumerate(zip(reqs, slots)):
            # serve-path provenance (ISSUE 18): admit mode + the pool
            # events this request's cached blocks rode in on, finalized
            # into the fingerprint at _complete
            hit = matches[j][2] if matches is not None else 0
            path = {"mode": "warm" if hit else "cold",
                    "brownout": self.brownout_level}
            if matches is not None and hit:
                path.update(page_origin_flags(matches[j][0]))
            self._meta[slot] = {
                "req": r, "emitted": 1, "out": [],
                "tok0_ref": (tok0, j),
                "pad_len": int(ints[j, 2]), "done": False,
                "path": path,
            }
        self.stats["admissions"] += n
        if self._tracer is not None:
            t_admit1 = time.monotonic()
            evictions = (self._prefix.counter("prefix_evictions") - ev0
                         if self._prefix is not None else 0)
            for j, r in enumerate(reqs):
                rid = r.get("rid")
                if not rid:
                    continue
                # queue wait: enqueue -> this admit dispatch
                self._tracer.add(rid, "queue_wait", r["t0"], t_admit0,
                                 bucket=bucket)
                hit = matches[j][2] if matches is not None else 0
                self._tracer.add(
                    rid, "admit", t_admit0, t_admit1,
                    mode=("warm" if hit else "cold"),
                    bucket=bucket, feed=feed, group=n,
                    prefix_hit_tokens=hit,
                    copy_blocks=(len(matches[j][1])
                                 if matches is not None else 0))
            if evictions:
                # pool pressure attributed to the admission that paid
                # it (the group's first traced request carries it)
                rid = next((r.get("rid") for r in reqs
                            if r.get("rid")), None)
                if rid:
                    self._tracer.event(rid, "kv_evictions",
                                       blocks=evictions, group=n)

    def _reserve_pages(self, r):
        """Host-side page reservation for one paged admission —
        ``PrefixCache.paged_plan`` owns the math (lookup + private
        chain covering the uncached suffix AND the full decode budget,
        up front so a mid-decode row can never block on the pool).
        ``None`` = pool exhausted right now — the caller defers the
        admission (completions free pages; progress is guaranteed
        because one full-budget chain always fits an otherwise-idle
        pool, enforced at PrefixCache construction). A deferred
        request re-reserves EVERY tick: only its first attempt may
        count toward the hit/lookup stats, or a second of deferral
        would fabricate hundreds of phantom hit-tokens."""
        if self._pool_dry():
            # pool_exhaust fault window: the pool reports dry — the
            # caller defers exactly as it would for genuine exhaustion
            # (the machinery under test). ``_page_retry`` stays unset:
            # no lookup ran, so the first REAL attempt still records.
            r["_page_attempts"] = r.get("_page_attempts", 0) + 1
            return None
        first = not r.get("_page_retry")
        r["_page_retry"] = True
        r["_page_attempts"] = r.get("_page_attempts", 0) + 1
        # promote=False: tick-start promotion already ran; a pool
        # donation here would invalidate the live paged cache mid-tick
        return self._prefix.paged_plan(r["ids"], r["budget"],
                                       record=first, promote=False)

    def _needs_streaming(self, r) -> bool:
        """True while a reserved request's remaining uncached suffix
        is wider than one prefill chunk — it streams instead of
        admitting (ISSUE 15)."""
        plan = r.get("_pages")
        if plan is None or not self._prefill_chunk:
            return False
        done = plan.get("done", plan["c"])
        return len(r["ids"]) - done > self._prefill_chunk

    def _stream_prefill_step(self, r) -> str:
        """One chunk of streaming prefill for a pending long request
        (ISSUE 15 tentpole). Returns ``"chunked"`` when a chunk
        dispatched — the tick's single streaming slot is consumed, so
        decode rows get the engine back between chunks and TPOT holds
        flat under a long arrival — ``"deferred"`` when the pool
        cannot supply the reservation (the caller STOPS walking
        pending: reserving for a later request instead would starve
        this one, the same FIFO contract as the admission loop; the
        admission loop owns the deferred_admissions count), and
        ``"skip"`` when the request needs no streaming.

        The full page plan (shared prefix + private chain covering
        prompt AND budget) reserves up front on first sight — a dry
        pool defers the whole request, never a mid-stream chunk. Each
        chunk feeds ``prefill_chunk`` prompt tokens through the SAME
        batch-1 paged prefill executable (one shape for the stream's
        lifetime — no giant admit buckets), writes straight into the
        plan's private pages, and zero-copy ADOPTS the completed full
        blocks into the radix — a same-document request arriving
        mid-prefill warm-hits the chunks already landed. Runs before
        the tick's cache refresh (the dispatch donates the pool the
        engine cache aliases)."""
        import jax.numpy as jnp

        from .kvcache import _paged_prefill_fn

        ids = r["ids"]
        chunk = self._prefill_chunk
        plan = r.get("_pages")
        if plan is None:
            if len(ids) <= chunk:
                return "skip"
            plan = self._reserve_pages(r)
            if plan is None:
                return "deferred"       # dry pool: retried next tick
            r["_pages"] = plan
            plan["done"] = plan["c"]
            if len(ids) - plan["c"] > chunk:
                self.stats["streamed_requests"] += 1
        done = plan.get("done", plan["c"])
        if len(ids) - done <= chunk:
            return "skip"               # ready for normal admission
        pf = self._prefix
        t0 = time.monotonic()
        row = np.full((1, pf.nb_max), -1, np.int32)
        for i, b in enumerate(plan["blocks"]):
            row[0, i] = b
        for idx, bid in (plan.get("shared") or {}).items():
            row[0, idx] = bid
        for idx, bid in plan["private"].items():
            row[0, idx] = bid
        suffix = jnp.asarray(
            np.asarray(ids[done:done + chunk], np.int32)[None, :])
        _, cache = _paged_prefill_fn(self.model, chunk, pf.nb_max)(
            self.params, pf.paged_cache(), suffix, jnp.asarray(row),
            jnp.asarray([done], jnp.int32))
        pf.sync_pool_from_cache(cache)
        plan["done"] = done + chunk
        self.stats["prefill_chunks"] += 1
        self.stats["streamed_prefill_tokens"] += chunk
        if not plan.get("ring_wrap"):
            # mid-prefill sharing: completed full blocks adopt now,
            # ref-pinned (this request keeps reading them); pinned
            # nodes release with the plan at paged_finish. Adopted
            # pages move private -> "shared" so the row's block table
            # KEEPS pointing at them (they are the prompt's history —
            # later chunks and the final admit read through them).
            adopted, anodes = pf.adopt(
                ids[:plan["done"]], dict(plan["private"]), acquire=True)
            if adopted:
                taken = set(adopted)
                shared = dict(plan.get("shared") or {})
                for idx in [i for i, b in plan["private"].items()
                            if b in taken]:
                    shared[idx] = plan["private"].pop(idx)
                plan["shared"] = shared
                plan["adopt_nodes"] = (
                    list(plan.get("adopt_nodes") or []) + anodes)
        if self._tracer is not None and r.get("rid"):
            self._tracer.add(
                r["rid"], "prefill_chunk", t0, time.monotonic(),
                tokens=chunk, done=plan["done"], total=len(ids))
        return "chunked"

    def _admit_group_paged(self, reqs: list, slots: list):
        """Paged admission: ONE dispatch writes the group's block
        tables (the whole warm-prefix "copy" — a pointer update),
        prefills ONLY each row's uncached suffix straight into its
        private pool pages, and samples first tokens. Zero admit-path
        device copies; ``scatter_blocks`` never runs here. After the
        dispatch, each prompt's full blocks ADOPT into the radix index
        in place — the group's own pages become sharable with no
        capture kernel. Page reservations were made by
        ``_reserve_pages`` in ``_tick`` (so a dry pool defers the
        request instead of stranding a slot)."""
        import jax.numpy as jnp

        pf = self._prefix
        bt = pf.block
        t_admit0 = time.monotonic()
        ev0 = (pf.counter("prefix_evictions")
               if self._tracer is not None else 0)
        n = len(reqs)
        k = self._slots
        W = self.MAX_STOPS
        nb = pf.nb_max
        pad_reqs = reqs + [reqs[-1]] * (k - n)
        pad_slots = list(slots) + [slots[-1]] * (k - n)
        # "done" covers both the radix-cached prefix AND any chunks a
        # streamed prefill already landed (ISSUE 15): the admit feeds
        # only what remains, so a streamed long prompt admits through
        # the same small-bucket executable as a short one
        feed = self._bucket(max(
            len(r["ids"]) - r["_pages"].get("done", r["_pages"]["c"])
            for r in reqs))
        prompts = np.zeros((k, feed), np.int32)
        ints = np.zeros((k, 4 + W), np.int32)
        floats = np.zeros((k, 2), np.float32)
        topks = np.zeros((k,), np.int32)
        tables_k = np.full((k, nb), -1, np.int32)
        for j, r in enumerate(pad_reqs):
            plan = r["_pages"]
            ids = plan["ids"]
            c = plan.get("done", plan["c"])
            s = len(ids) - c               # unfed suffix (>= 1: the
            # radix lookup never serves the final prompt token, and a
            # streamed prefill always leaves the final chunk to the
            # admit)
            prompts[j, feed - s:] = ids[c:]
            ints[j, 0] = pad_slots[j]
            ints[j, 1] = r["budget"]
            ints[j, 2] = feed - s          # leading invalid lanes
            ints[j, 3:3 + W] = -1
            for jj, sid in enumerate(r["stop"]):
                ints[j, 3 + jj] = sid
            ints[j, 3 + W] = len(ids) - feed   # lane 0's position
            floats[j] = (r["temperature"], r["top_p"])
            topks[j] = r["top_k"]
            for i, b in enumerate(plan["blocks"]):
                tables_k[j, i] = b
            for idx, bid in (plan.get("shared") or {}).items():
                # pages this request streamed and adopted mid-prefill
                # (ISSUE 15): index-owned now, still its history
                tables_k[j, idx] = bid
            for idx, bid in plan["private"].items():
                tables_k[j, idx] = bid
        keys_data = jnp.asarray(
            np.stack([r["key_data"] for r in pad_reqs]))
        try:
            (self._cache, self._tables, self._arrays, self._starts,
             tok0) = _paged_admit_fn(self.model, feed, k, W, nb)(
                self.params, self._cache, self._tables, self._arrays,
                self._starts, jnp.asarray(prompts), jnp.asarray(ints),
                jnp.asarray(floats), keys_data, jnp.asarray(topks),
                jnp.asarray(tables_k))
        except Exception:
            # a failed dispatch must not strand refs or leak pages —
            # including the ref-pins a streamed prefill's per-chunk
            # adoptions accumulated in adopt_nodes (ISSUE 15)
            for r in reqs:
                plan = r.pop("_pages")
                pf.release(plan["nodes"])
                pf.release(plan.get("adopt_nodes") or [])
                pf.free_blocks(list(plan["private"].values()))
            raise
        pf.sync_pool_from_cache(self._cache)
        for j, (r, slot) in enumerate(zip(reqs, slots)):
            plan = r.pop("_pages")
            # zero-copy insert of the prompt's own full blocks: the
            # pages just written in place become sharable immediately
            # (ref-pinned — this slot keeps reading them). NEVER for a
            # ring_wrap plan (ISSUE 15): its decode will RECYCLE these
            # very slots, so adopting them would hand the radix pages
            # whose content a later wrap overwrites under other
            # readers — the same guard paged_finish and the streaming
            # path apply.
            if not plan.get("ring_wrap"):
                adopted, anodes = pf.adopt(
                    plan["ids"], dict(plan["private"]), acquire=True)
                for bid in adopted:
                    for idx in [i for i, b in plan["private"].items()
                                if b == bid]:
                        del plan["private"][idx]
                # EXTEND, never overwrite: a streamed prefill already
                # pinned its per-chunk adoptions here (ISSUE 15) —
                # clobbering them leaks the pins forever
                plan["adopt_nodes"] = (
                    list(plan.get("adopt_nodes") or []) + anodes)
            # serve-path provenance (ISSUE 18): "stream" marks prompts
            # whose prefill arrived via chunked streaming before this
            # admit; node origins name the pool events behind the
            # cached prefix (adopt/promote/pull/ship)
            from .kvcache import page_origin_flags

            streamed = plan.get("done", plan["c"]) > plan["c"]
            path = {"mode": "stream" if streamed else "paged",
                    "wrap": bool(plan.get("ring_wrap")),
                    "brownout": self.brownout_level,
                    **page_origin_flags(plan.get("nodes"))}
            self._meta[slot] = {
                "req": r, "emitted": 1, "out": [],
                "tok0_ref": (tok0, j),
                "pad_len": 0, "done": False, "pages": plan,
                "path": path,
            }
        self.stats["admissions"] += n
        self.stats["paged_admissions"] += n
        if self._tracer is not None:
            t_admit1 = time.monotonic()
            evictions = pf.counter("prefix_evictions") - ev0
            for j, (r, slot) in enumerate(zip(reqs, slots)):
                rid = r.get("rid")
                if not rid:
                    continue
                plan = self._meta[slot]["pages"]
                self._tracer.add(rid, "queue_wait", r["t0"], t_admit0,
                                 bucket=self._bucket(len(r["ids"])))
                self._tracer.add(
                    rid, "admit", t_admit0, t_admit1, mode="paged",
                    bucket=self._bucket(len(r["ids"])),
                    feed=feed, group=n,
                    prefix_hit_tokens=plan["c"],
                    # streamed = prompt tokens landed by chunked
                    # prefill before this admit (ISSUE 15) — honest
                    # split from genuine radix hits
                    streamed_tokens=(
                        plan.get("done", plan["c"]) - plan["c"]),
                    # the paged contract: warm admits are pointer
                    # updates — copy bytes are zero by construction
                    copy_blocks=0,
                    private_pages=len(plan["private"]),
                    deferred=r.get("_page_attempts", 1) > 1)
            if evictions:
                rid = next((r.get("rid") for r in reqs
                            if r.get("rid")), None)
                if rid:
                    self._tracer.event(rid, "kv_evictions",
                                       blocks=evictions, group=n)

    def _init_arrays(self):
        """The persistent device slot state, built ONCE (and after an
        error reset): every slot done with budget 0, so nothing runs
        until an admission writes real rows via ``_slot_update_fn``."""
        import jax
        import jax.numpy as jnp

        S, W = self._slots, self.MAX_STOPS
        kd = np.asarray(jax.random.key_data(jax.random.key(0)))
        self._arrays = (
            jnp.zeros((S,), jnp.int32),                  # tok
            jnp.zeros((S,), jnp.int32),                  # emitted
            jnp.ones((S,), bool),                        # done
            jnp.zeros((S,), jnp.int32),                  # budgets
            jnp.zeros((S,), jnp.int32),                  # pad_lens
            jnp.asarray(np.tile(kd, (S, 1))),            # key data
            jnp.full((S, W), -1, jnp.int32),             # stops
            jnp.zeros((S,), jnp.float32),                # temps
            jnp.zeros((S,), jnp.int32),                  # top_ks
            jnp.zeros((S,), jnp.float32),                # top_ps
        )

    def _dispatch_chunk(self, steps: int):
        """Queue one ``steps``-step chunk on the device (async —
        nothing is forced here) and advance the host position mirror.
        The cache's ``pos_index`` lives on device (set by admissions,
        advanced in-graph by each step) — no per-dispatch transfers.
        ``steps < self._chunk`` only at era end, where the remaining
        room is smaller than a full chunk (tail executables are
        lru-cached like any other)."""
        tok, emitted, done, budgets, pad_lens, keys, stops, temps, \
            ks, ps = self._arrays
        if self._paged:
            chunk = _paged_chunk_fn(self.model, steps, self.MAX_STOPS)
            with span("serve/chunk_dispatch", steps=steps, paged=True):
                cache, starts, toks, tok, emitted, done = chunk(
                    self.params, self._cache, self._tables,
                    self._starts, tok, emitted, done, budgets,
                    pad_lens, keys, stops, temps, ks, ps)
            self._cache = cache
            self._starts = starts
            self._prefix.sync_pool_from_cache(cache)
            self.stats["paged_chunks"] += 1
        else:
            chunk = _chunk_fn(self.model, steps, self.MAX_STOPS)
            with span("serve/chunk_dispatch", steps=steps):
                cache, toks, tok, emitted, done = chunk(
                    self.params, self._cache, tok, emitted, done,
                    budgets, pad_lens, keys, stops, temps, ks, ps)
            self._cache = cache
        self._arrays = (tok, emitted, done, budgets, pad_lens, keys,
                        stops, temps, ks, ps)
        self._p += steps
        self.stats["chunks"] += 1
        return toks, emitted, done

    def _absorb(self, toks, emitted, done):
        """Force a dispatched chunk's outputs and hand tokens to their
        requests; finished rows complete and free their slots."""
        with span("serve/absorb"):
            toks = np.asarray(toks)
            emitted = np.asarray(emitted)
            done = np.asarray(done)
        t_absorb = time.monotonic()
        tok0_np: dict = {}          # one D2H read per admission group
        for s in range(self._slots):
            m = self._meta[s]
            if m is None or m["done"]:
                continue
            n_before = len(m["out"])
            if not m["out"]:
                # first absorb for this row: its admission-time token
                # future is long since resolved (the chunk that just
                # forced ran after it). Memoized per group — a
                # np.asarray per ROW was 8 separate serialized device
                # reads per wave.
                arr, j = m["tok0_ref"]
                if id(arr) not in tok0_np:
                    tok0_np[id(arr)] = np.asarray(arr)
                m["out"].append(int(tok0_np[id(arr)][j]))
            fresh = int(emitted[s]) - m["emitted"]
            m["out"].extend(int(t) for t in toks[s, :fresh])
            m["emitted"] = int(emitted[s])
            m["done"] = bool(done[s])
            if "t_first" not in m and m["out"]:
                # server-side TTFT: the first absorb that makes this
                # row's first token servable (host-observed — the
                # device produced it earlier, but nothing could be
                # streamed before this force)
                m["t_first"] = t_absorb
                ttft = t_absorb - m["req"]["t0"]
                self._ttfts.append(ttft)
                if len(self._ttfts) > 1024:
                    del self._ttfts[:512]
                self.hist["ttft_seconds"].observe(ttft)
                rid = m["req"].get("rid")
                if self._tracer is not None and rid:
                    self._tracer.event(rid, "first_token",
                                      ttft_s=round(ttft, 6))
            elif self._tracer is not None and fresh > 0:
                rid = m["req"].get("rid")
                if rid:
                    self._tracer.event(rid, "decode_chunk",
                                       tokens=fresh)
            ev = m["req"].get("cancel")
            if ev is not None and not m["done"] and ev.is_set():
                # cancelled mid-flight: finalize with what's decoded,
                # free the slot for waiting traffic (the device row
                # keeps stepping until the slot is reused — bounded
                # waste; the SLOT availability is the win). In paged
                # mode the still-stepping zombie row keeps WRITING its
                # private pool pages, so their cleanup defers until
                # the slot is re-admitted or the engine idles
                # (_finish_pages zombie arm) — freeing them now could
                # hand a page the zombie still writes to a new request
                m["done"] = True
                m["zombie"] = True
            dl = m["req"].get("deadline")
            if (dl is not None and not m["done"]
                    and dl.expired(t_absorb)):
                # deadline expired mid-decode: the engine raises the
                # cancel itself (ISSUE 9) — same zombie bookkeeping as
                # a client disconnect, but classified "deadline"
                m["done"] = True
                m["zombie"] = True
                m["deadline"] = True
            cb = m["req"].get("on_tokens")
            if cb is not None:
                # delta = this absorb's emissions, minus stop ids (a
                # stop can only be the LAST emitted token — the row
                # freezes after it — so filtering ≡ the final
                # response's trailing-stop strip)
                stops = m["req"]["stop"]
                delta = [t for t in m["out"][n_before:]
                         if t not in stops]
                if delta:
                    try:
                        cb(delta)
                    except Exception:   # noqa: BLE001 — a consumer's
                        pass            # callback must not kill absorb
        for s in range(self._slots):
            m = self._meta[s]
            if m is not None and m["done"]:
                self._complete(s)
        if self._recorder is not None:
            # per-chunk serving telemetry: cumulative counters, so the
            # offline analyzer (scripts/telemetry_report.py) reads the
            # LAST record for totals; prefix-cache fields ride along
            # when the pool is enabled
            rec = {
                "event": "serve_chunk",
                "live_slots": sum(mm is not None for mm in self._meta),
                "queue_depth": self._queue.qsize(),
                "tokens_generated_total":
                    self.stats.get("tokens_generated", 0),
                "admissions_total": self.stats.get("admissions", 0),
            }
            if self.tp > 1:
                # TP serving telemetry (ISSUE 10): constant per-step
                # accounting (precomputed at setup — tp_stats caches),
                # recorded per chunk so the offline analyzer's
                # "Tensor parallel (serving)" section reads it from the
                # same JSONL as everything else
                tps = self.tp_stats()
                rec.update(
                    tp_degree=tps["tp_degree"],
                    tp_collective_count_per_step=tps[
                        "collective_count_per_step"],
                    tp_collective_bytes_per_step=tps[
                        "collective_bytes_per_step"],
                    tp_collective_floor_bytes=tps[
                        "analytic_floor_bytes"])
            if self._prefix is not None:
                snap = self._prefix.stats_snapshot()
                chunks = max(self.stats.get("chunks", 0), 1)
                rec.update(
                    prefix_hit_tokens_total=snap["prefix_hit_tokens"],
                    prefix_hit_requests_total=snap[
                        "prefix_hit_requests"],
                    prefix_lookups_total=snap["prefix_lookups"],
                    prefix_evictions_total=snap["prefix_evictions"],
                    prefix_pool_blocks_used=snap[
                        "prefix_pool_blocks_used"],
                    prefix_pool_blocks=snap["prefix_pool_blocks"],
                    prefix_pool_blocks_resident=snap[
                        "prefix_pool_blocks_resident"],
                    prefix_pool_blocks_referenced=snap[
                        "prefix_pool_blocks_referenced"],
                    prefix_adopted_blocks_total=snap[
                        "prefix_adopted_blocks"],
                    warm_admit_copy_bytes_total=snap[
                        "warm_admit_copy_bytes"],
                    paged_decode_frac=round(
                        self.stats.get("paged_chunks", 0) / chunks, 4),
                    # long-context serving (ISSUE 15): chunked-prefill
                    # progress + the pool-fallback family for the
                    # analyzer's prefix-cache section
                    prefill_chunks_total=self.stats.get(
                        "prefill_chunks", 0),
                    streamed_prefill_tokens_total=self.stats.get(
                        "streamed_prefill_tokens", 0),
                    pool_fallback_total=snap.get(
                        "pool_fallback_total", 0),
                )
                if snap.get("tier_enabled"):
                    # KV tier telemetry (ISSUE 13): cumulative demote/
                    # promote traffic + occupancy per tier, read by the
                    # offline analyzer's "KV tiers (serving)" section
                    rec.update(
                        tier_demoted_blocks_total=snap[
                            "tier_demoted_blocks"],
                        tier_promoted_blocks_total=snap[
                            "tier_promoted_blocks"],
                        tier_demote_bytes_total=snap[
                            "tier_demote_bytes"],
                        tier_promote_bytes_total=snap[
                            "tier_promote_bytes"],
                        tier_checksum_failures_total=snap[
                            "tier_checksum_failures"],
                        tier_exhaust_drops_total=snap[
                            "tier_exhaust_drops"],
                        tier_host_blocks=snap["tier_host_blocks"],
                        tier_host_bytes=snap["tier_host_bytes"],
                        tier_disk_blocks=snap["tier_disk_blocks"],
                        tier_disk_bytes=snap["tier_disk_bytes"],
                    )
            self._recorder.record(self.stats["chunks"], **rec)
        if self._tsdb is not None:
            counters = {
                "tokens_generated_total":
                    self.stats.get("tokens_generated", 0),
                "admissions_total": self.stats.get("admissions", 0),
                "chunks_total": self.stats.get("chunks", 0),
                "completed_total": self.stats.get("completed", 0),
                "cancelled_total": self.stats.get("cancelled", 0),
                "deadline_expired_total":
                    self.stats.get("deadline_expired", 0),
            }
            gauges = {
                "queue_depth": self._queue.qsize(),
                "live_slots": sum(mm is not None
                                  for mm in self._meta),
                "brownout_level": self.brownout_level,
            }
            if self._prefix is not None:
                snap = self._prefix.stats_snapshot()
                counters["prefix_hit_tokens_total"] = snap[
                    "prefix_hit_tokens"]
                gauges["prefix_pool_blocks_used"] = snap[
                    "prefix_pool_blocks_used"]
            self._tsdb.observe(counters=counters, gauges=gauges)

    def _insert_prefixes(self, reqs, slots, ints, matches):
        """Put the admitted prompts' own full blocks back into the pool:
        plan the index inserts on the host (allocating from the free
        list, LRU-evicting unreferenced blocks when full), then ONE
        fixed-shape capture dispatch — padded to ``(slots, nb_max)``
        like the admit itself, so arrival-wave sizes never mint fresh
        XLA executables mid-traffic. Lookup refs release only after
        both copy kernels are enqueued (device program order makes the
        reads safe against any later overwrite)."""
        try:
            nb = self._prefix.nb_max
            rows, cap_slots, cap_pads = [], [], []
            any_new = False
            for j, r in enumerate(reqs):
                blocks, start = self._prefix.plan_insert(r["ids"])
                row = [-1] * nb
                for i, b in enumerate(blocks):
                    row[start + i] = b
                if blocks:
                    any_new = True
                rows.append(row)
                cap_slots.append(slots[j])
                cap_pads.append(int(ints[j, 2]))
            while len(rows) < self._slots:   # fixed executable shape
                rows.append([-1] * nb)
                cap_slots.append(cap_slots[-1])
                cap_pads.append(cap_pads[-1])
            if any_new:
                self._prefix.capture(self._cache, cap_slots, cap_pads,
                                     rows)
        finally:
            for nodes, _, _ in matches:
                self._prefix.release(nodes)

    def _finish_pages(self, slot: int, m: dict) -> None:
        """Paged end-of-request page bookkeeping: ADOPT the request's
        full (prompt + decoded) blocks into the radix index in place —
        the zero-copy insert that makes freshly decoded tokens
        immediately sharable — then free the unadoptable tail and drop
        the slot's refs. Cancelled rows are ZOMBIES (the device lane
        keeps stepping into its private pages until the slot is
        reused): their cleanup is stashed and re-run from the next
        admit to this slot or the next idle tick."""
        pf = self._prefix
        plan = m.get("pages")
        if plan is None:
            return
        if m.get("zombie"):
            self._zombies[slot] = (plan, list(m["out"]),
                                   int(m["emitted"]))
            return
        self._cleanup_pages(plan, list(m["out"]), int(m["emitted"]))

    def _cleanup_pages(self, plan, out, emitted: int) -> None:
        # PrefixCache.paged_finish owns the end-of-request page
        # bookkeeping (adopt written blocks, free the tail, release
        # plan + adopt refs) — shared with the batch-1 path
        self._prefix.paged_finish(plan, out, emitted)

    def _reap_zombies(self, slot=None) -> None:
        """Run deferred page cleanup — for one slot (about to be
        re-admitted: the admit dispatch replaces the zombie's row
        state, so its writes stop targeting the old pages) or for all
        (engine idle: no chunks dispatch, nothing steps)."""
        slots = ([slot] if slot is not None
                 else list(self._zombies.keys()))
        for s in slots:
            stash = self._zombies.pop(s, None)
            if stash is not None:
                self._cleanup_pages(*stash)

    def _complete(self, slot: int):
        m = self._meta[slot]
        req = m["req"]
        if self._paged:
            ad0 = (self._prefix.counter("prefix_adopted_blocks")
                   if self._tracer is not None else 0)
            self._finish_pages(slot, m)
            if self._tracer is not None and req.get("rid"):
                adopted = (self._prefix.counter("prefix_adopted_blocks")
                           - ad0)
                if adopted:
                    # zero-copy radix adoption of this request's pages
                    # (prompt + decoded tokens become sharable)
                    self._tracer.event(req["rid"], "kv_adopt",
                                       blocks=adopted)
        resp = self._response(
            m["out"], stops=req["stop"], emitted=m["emitted"])
        ev = req.get("cancel")
        if (ev is not None and ev.is_set()
                and resp["stop_reason"] == "length"
                and m["emitted"] < req["budget"]):
            # finalized early by cancellation, not by budget — a row
            # that genuinely hit its stop token keeps "stop"
            resp["stop_reason"] = "cancelled"
            self.stats["cancelled"] = self.stats.get("cancelled", 0) + 1
        if (m.get("deadline") and resp["stop_reason"] == "length"
                and m["emitted"] < req["budget"]):
            # finalized by its own expired deadline, not by budget
            resp["stop_reason"] = "deadline"
            self.stats["deadline_expired"] = (
                self.stats.get("deadline_expired", 0) + 1)
        path = self._base_path()
        path.update(m.get("path") or {})
        self._finalize_path(resp, path, req.get("rid"))
        req["result"] = resp
        req["event"].set()
        self._meta[slot] = None
        self.stats["completed"] += 1
        t_done = time.monotonic()
        lat = t_done - req["t0"]
        self._latencies.append(lat)
        if len(self._latencies) > 1024:
            del self._latencies[:512]
        # latency exports + SLO check at the engine's own observation
        # point: e2e covers enqueue -> completion, TPOT the decode
        # cadence after the first token (ISSUE 8). Cancelled and
        # deadline-truncated requests stay OUT of the served-e2e
        # histogram (ISSUE 9): their latency is the client's/deadline's
        # choice, and counting them would reward truncation with
        # "better" tails. TPOT stays in — the decode cadence was real.
        served = resp["stop_reason"] not in ("cancelled", "deadline")
        if served:
            self.hist["e2e_seconds"].observe(lat)
        t_first = m.get("t_first")
        emitted_n = int(m["emitted"])
        ttft = (t_first - req["t0"]) if t_first is not None else None
        if t_first is not None and emitted_n > 1:
            self.hist["tpot_seconds"].observe(
                (t_done - t_first) / (emitted_n - 1))
        rid = req.get("rid")
        if self._tracer is not None and rid:
            self._tracer.event(
                rid, "complete", e2e_s=round(lat, 6),
                tokens=emitted_n, stop_reason=resp["stop_reason"])
        if self._slo is not None and rid:
            self._slo.observe(rid, ttft_s=ttft, e2e_s=lat,
                              tokens=emitted_n,
                              stop_reason=resp["stop_reason"])

    def queue_depth(self) -> int:
        """Requests waiting for a slot (not yet admitted)."""
        return self._queue.qsize()

    def live_slots(self) -> int:
        """Slots currently decoding a request."""
        meta = getattr(self, "_meta", None) or []
        return sum(m is not None for m in meta)

    def latency_percentiles(self) -> dict:
        lats = sorted(self._latencies[-1024:])
        if not lats:
            return {}
        pick = lambda q: round(percentile(lats, q), 4)   # noqa: E731
        out = {"p50_s": pick(0.50), "p95_s": pick(0.95),
               "p99_s": pick(0.99), "n": len(lats)}
        # server-side TTFT (ISSUE 8 satellite): stamped at the first
        # absorb per request, so serving latency decomposes into
        # first-token wait vs decode tail without a client in the loop
        ttfts = sorted(self._ttfts[-1024:])
        if ttfts:
            tp = lambda q: round(percentile(ttfts, q), 4)    # noqa: E731
            out.update(ttft_p50_s=tp(0.50), ttft_p95_s=tp(0.95),
                       ttft_p99_s=tp(0.99))
        return out

    def _worker(self):
        """The scheduler loop. Single thread owns the device state;
        the outer try mirrors the static worker's contract: an
        exception surfaces on every in-flight request rather than
        silently killing the thread."""
        self._meta = [None] * self._slots
        self._cache = None
        self._arrays = None
        self._p = 0
        self._zombies: dict = {}
        pending: list = []
        while True:
            involved = [m["req"] for m in self._meta if m is not None]
            try:
                active = any(m is not None for m in self._meta)
                if not active and not pending:
                    pending.append(self._queue.get())   # block when idle
                    deadline = time.monotonic() + self._window_s
                    while time.monotonic() < deadline:
                        try:
                            pending.append(self._queue.get_nowait())
                        except queue_mod.Empty:
                            time.sleep(self._window_s / 10)
                while True:
                    try:
                        pending.append(self._queue.get_nowait())
                    except queue_mod.Empty:
                        break
                involved = ([m["req"] for m in self._meta
                             if m is not None]
                            + [r for r in pending])
                self.stats["requests"] = (self.stats["completed"]
                                          + len(involved))
                with self._lock:
                    self._tick(pending)
            except Exception as e:  # noqa: BLE001 — surfaced per request
                logger.exception("continuous scheduler error")
                for r in involved:
                    r["error"] = e
                    r["event"].set()
                if self._paged:
                    # drop every page reservation this wreckage holds:
                    # leaked refs would pin pool pages against eviction
                    # forever on a recovering server
                    pf = self._prefix
                    plans = (
                        [m["pages"] for m in self._meta
                         if m is not None and m.get("pages")]
                        + [r["_pages"] for r in pending
                           if r.get("_pages")]
                        + [z[0] for z in self._zombies.values()]
                    )
                    for plan in plans:
                        try:
                            pf.release(plan["nodes"])
                            pf.release(plan["adopt_nodes"])
                            pf.free_blocks(
                                list(plan["private"].values()))
                        except Exception:  # noqa: BLE001 — best effort
                            pass
                    self._zombies = {}
                    self._tables = None
                    self._starts = None
                    # a dispatch that failed AFTER donating the cache
                    # leaves the pool's buffers dead — rebuilding the
                    # next era's cache from them would fail forever.
                    # Reset (content is unrecoverable) AFTER the plan
                    # cleanup above, so its host bookkeeping ran
                    # against the index that issued the refs.
                    if not pf.pool_alive():
                        pf.reset_pool()
                pending.clear()
                self._meta = [None] * self._slots
                self._cache = None
                self._arrays = None
                self._p = 0

    def _tick(self, pending: list):
        """One scheduler round under the lock: era management,
        admissions, one (or two, pipelined) chunk dispatches."""
        from ..resilience import faults

        from .generate import fresh_cache

        # serving fault hook (ISSUE 9): slow_decode sleeps here, hang
        # wedges this thread forever (the designated wedge — /healthz
        # keeps answering from the HTTP threads), pool_exhaust comes
        # back as a spec whose duration opens the dry-pool window
        spec = faults.on_serve_tick(self.stats["chunks"])
        if spec is not None:
            self._pool_dry_until = time.monotonic() + spec.duration_s
            logger.warning("fault pool_exhaust: pool reads dry for "
                           "%.2fs", spec.duration_s)
        if self._brownout is not None:
            with self._bo_lock:
                self._brownout.update(
                    self._brownout_pressure(waiting=len(pending)))
        active = any(m is not None for m in self._meta)
        # drop queued requests whose cancel event fired — or whose
        # deadline expired — before they ever took a slot (zero device
        # work spent on them) — BEFORE era-start positioning, so a
        # dead request's bucket or budget can't inflate/starve the new
        # era's position
        for r in list(pending):
            ev = r.get("cancel")
            dl = r.get("deadline")
            dead = (ev is not None and ev.is_set())
            expired = (not dead and dl is not None and dl.expired())
            if dead or expired:
                pending.remove(r)
                plan = r.pop("_pages", None)
                if plan is not None:
                    # a cancel/expiry BETWEEN streaming-prefill chunks
                    # (ISSUE 15): the plan's remaining private pages
                    # free through the existing paged bookkeeping;
                    # chunks already adopted stay in the radix (valid
                    # content — a same-prefix request still warm-hits
                    # them) with their pins released here
                    self._prefix.paged_finish(
                        plan, [], 0, written=plan.get("done", 0))
                resp = self._response([], stops=r["stop"], emitted=0)
                resp["stop_reason"] = ("cancelled" if dead
                                       else "deadline")
                r["result"] = resp
                r["event"].set()
                key = "cancelled" if dead else "deadline_expired"
                self.stats[key] = self.stats.get(key, 0) + 1
                self.stats["completed"] += 1
        # tiered-spill promotion (ISSUE 13): pending requests whose
        # prefix was demoted to the host/disk tier promote HERE — the
        # one point in the tick where a pool donation is still safe
        # (the refresh below re-adopts the swapped leaves before any
        # dispatch). Mid-tick lookups all pass promote=False for
        # exactly this reason. The pool_exhaust window also reads the
        # tier dry — the fault drains the WHOLE hierarchy.
        if (self._prefix is not None and self._prefix.spill is not None
                and pending and not self._pool_dry()):
            for r in pending[:self._slots]:
                t_tier0 = time.monotonic()
                n = self._prefix.promote_spilled(r["ids"])
                if n and self._tracer is not None and r.get("rid"):
                    # the "tier" attribution segment: time this
                    # admission spent pulling its prefix back up the
                    # hierarchy (reqtrace subtracts it from the
                    # scheduler_queue segment it overlaps)
                    self._tracer.add(r["rid"], "tier", t_tier0,
                                     time.monotonic(), blocks=n)
        # chunked streaming prefill (ISSUE 15 tentpole): ONE chunk of
        # ONE long pending prompt per tick — decode rows interleave
        # between chunks, so a 32k arrival never stalls the decode
        # batch for its whole prefill. Runs BEFORE the cache refresh
        # below: the chunk dispatch donates the pool the engine cache
        # aliases, and the refresh re-adopts the swapped leaves.
        if (self._paged and self._prefill_chunk and pending
                and not self._pool_dry()):
            for r in pending:
                if (len(r["ids"]) > self._prefill_chunk
                        or r.get("_pages") is not None):
                    verdict = self._stream_prefill_step(r)
                    if verdict != "skip":
                        # "chunked": this tick's streaming slot is
                        # spent; "deferred": a dry pool must not
                        # reserve for LATER requests over this one
                        # (FIFO, same as the admission loop)
                        break
        if self._paged and self._cache is not None:
            # a batch-1 speculative request between ticks (same lock)
            # may have reassigned the pool — its scatter insert's
            # capture kernel donates the very leaves this cache
            # aliases. Re-adopt before any dispatch touches them.
            self._cache = self._prefix.refresh_cache_from_pool(
                self._cache)
        if not active:
            # idle: new era (stale K/V is masked by pad_lens; only the
            # position counter resets). Paged mode has NO eras — pages
            # are position-independent — but idle is when zombie
            # (cancelled) rows are provably quiescent, so their
            # deferred page cleanup runs here.
            self._p = 0
            self.stats["eras"] += 1
            if self._paged:
                import jax.numpy as jnp

                self._reap_zombies()
                if self._cache is None:
                    self._cache = self._prefix.paged_cache()
                    self._tables = jnp.full(
                        (self._slots, self._prefix.nb_max), -1,
                        jnp.int32)
                    self._starts = jnp.zeros((self._slots,), jnp.int32)
            elif self._cache is None:
                self._cache = fresh_cache(
                    self.model, self.params, self._slots,
                    int(self.model.max_len))
            if self._arrays is None:
                self._init_arrays()
        # era start positions the counter at the largest bucket a FIFO
        # prefix of pending requests tolerates: the OLDEST request is
        # always admitted (no starvation), and same-wave arrivals of
        # mixed lengths admit together when their budgets all still
        # fit the era at the larger start position. (Paged rows carry
        # their own positions — no era placement needed.)
        if not active and pending and not self._paged:
            max_len = int(self.model.max_len)
            p_cand, chosen = 0, []
            # only the first `slots` pending requests can admit this
            # wave — a longer prefix would inflate the era start (and
            # burn budget room) for requests that must wait anyway
            for r in pending[:self._slots]:
                cand = max(p_cand, self._bucket(len(r["ids"])))
                if all(cand + q["budget"] <= max_len
                       for q in chosen + [r]):
                    p_cand, chosen = cand, chosen + [r]
                else:
                    break
            self._p = p_cand
        # group admissible arrivals by bucket: each group admits in ONE
        # prefill + ONE scatter dispatch (a same-wave burst — the
        # static scheduler's best case — stays one batched prefill)
        free = [s for s in range(self._slots) if self._meta[s] is None]
        groups: dict = {}
        for r in list(pending):
            if not free:
                break
            if (self.brownout_level >= 3
                    and r["budget"] > self._bo_max_new):
                # brownout level 3 (clamp_budget): long generations
                # finish short so slots recycle under saturation; the
                # response's stop_reason stays "length" — honest, the
                # budget WAS exhausted, just a browned-out budget
                r["budget"] = self._bo_max_new
                self.stats["brownout_clamped"] = (
                    self.stats.get("brownout_clamped", 0) + 1)
            if self._paged:
                if self._needs_streaming(r):
                    # still streaming its prompt in chunks (ISSUE 15):
                    # not admissible yet, but LATER pending requests
                    # may admit around it — that interleaving is the
                    # whole point of chunked prefill
                    continue
                # position-free admission: reserve pool pages (shared
                # prefix refs + a private chain for suffix AND budget).
                # A dry pool DEFERS the request — completions free
                # pages; FIFO order holds (we stop at the first
                # un-reservable request instead of skipping it)
                plan = r.get("_pages") or self._reserve_pages(r)
                if plan is None:
                    self.stats["deferred_admissions"] += 1
                    break
                r["_pages"] = plan
                if self._needs_streaming(r):
                    # freshly reserved long prompt: its first chunk
                    # streams next tick (or already streamed this one)
                    continue
                pending.remove(r)
                slot = free.pop(0)
                # this slot's admit dispatch (this tick) neutralizes
                # any zombie lane still writing its old pages
                self._reap_zombies(slot)
                b = self._bucket(len(r["ids"]))
                groups.setdefault(b, []).append((r, slot))
            elif self._admissible(r) and self._p > 0:
                pending.remove(r)
                b = self._bucket(len(r["ids"]))
                groups.setdefault(b, []).append((r, free.pop(0)))
        for pairs in groups.values():
            with span("serve/admit", n=len(pairs)):
                self._admit_group([r for r, _ in pairs],
                                  [s for _, s in pairs])
        self.stats["max_active"] = max(
            self.stats["max_active"],
            sum(m is not None for m in self._meta))
        live = [m for m in self._meta if m is not None]
        if not live:
            return
        min_left = min(m["req"]["budget"] - m["emitted"] for m in live)
        # era-end tail: the admission invariant bounds every live
        # budget by max_len, so min 1 step always remains. Paged rows
        # carry their own positions and preallocated chains — no era,
        # no tail clamp.
        room = (10 ** 9 if self._paged
                else int(self.model.max_len) - self._p)
        steps = min(self._chunk, room)
        # ADAPTIVE chunk growth: when every slot is occupied, no slot
        # can free before min_left steps (a row only exits early via a
        # stop token) — so running one long chunk straight to min_left
        # recycles slots exactly as fast while paying ONE host round
        # trip instead of min_left/chunk of them (the uniform-burst
        # case of the serve_mixed rung).
        # With free slots the base chunk stands, keeping admission
        # latency for new arrivals at one short chunk; with stop
        # tokens OR cancel events in play rows can exit mid-chunk
        # (a disconnect is only honored at the next absorb), so
        # growth is capped at 4x to bound the wasted frozen-row
        # steps, the slot-recycle delay, and the cancel latency.
        # brownout level 2 (short_chunks): growth disabled — admission
        # latency for the queue beats saturated-throughput batching
        if (min_left > self._chunk and self.brownout_level < 2
                and not any(m is None for m in self._meta)):
            limit = min(min_left, self._chunk * self._grow_cap(live))
            grown = self._chunk
            while grown * 2 <= limit:
                grown *= 2       # power-of-two LADDER: the executable
                # set is fixed and precompiled at startup
                # (_warm_chunk_ladder) — a length first seen mid-
                # traffic would stall every slot behind a fresh XLA
                # compile, the same timing-nondeterminism the padded
                # admission width kills (measured: the chunk=8 rung
                # collapsed ~10x from exactly that before the warmup)
            steps = min(grown, room)
        out1 = self._dispatch_chunk(steps)
        # dispatch ONE chunk ahead while the first runs, unless queue
        # traffic wants an admission slot between them or everyone
        # will finish inside the first chunk anyway
        min_left -= steps        # remaining after chunk 1
        steps2 = min(self._chunk,
                     (10 ** 9 if self._paged
                      else int(self.model.max_len) - self._p))
        if (self._queue.empty() and min_left > 0
                and not any(m is None for m in self._meta)
                and steps2 >= 1):
            out2 = self._dispatch_chunk(steps2)
            self._absorb(*out1)
            self._absorb(*out2)
        else:
            self._absorb(*out1)
