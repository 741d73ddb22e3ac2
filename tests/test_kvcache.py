"""Paged KV block pool + radix prefix index (engine/kvcache.py).

Host invariants first (block-granular matching, refcounts pin blocks
against eviction, LRU order under a full pool), then the load-bearing
device contract: greedy tokens after a WARM admit — prefix served from
the pool, only the suffix prefilled — are identical to the cold path,
on both the batch-1 plain service and the continuous slot engine
(whose admits land at era-dependent slots and therefore exercise the
canonical-space RoPE re-rotation).
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_template_tpu.config.registry import MODELS
import pytorch_distributed_template_tpu.models  # noqa: F401
from pytorch_distributed_template_tpu.engine.continuous import (
    ContinuousBatchingService,
)
from pytorch_distributed_template_tpu.engine.kvcache import (
    PrefixCache, RadixIndex, rotate_rows,
)
from pytorch_distributed_template_tpu.engine.serving import (
    GenerationService,
)

VOCAB = 64
BLOCK = 8


@pytest.fixture(scope="module")
def stack():
    model = MODELS.get("Llama")(vocab_size=VOCAB, n_layer=2, n_head=4,
                                n_kv_head=2, d_model=32, max_len=128)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    solo = GenerationService.from_model(model, params)
    return model, params, solo


def _ids(n, seed=0, lo=1):
    rng = np.random.default_rng(seed)
    return [int(x) for x in rng.integers(lo, VOCAB, n)]


# ---------------------------------------------------------------------------
# host-side: radix index + allocation invariants
# ---------------------------------------------------------------------------


def test_radix_insert_and_longest_match():
    idx = RadixIndex(4)
    ids = list(range(11))                       # 2 full blocks + 3 tail
    free = iter(range(1, 100))
    new, blocks, start = idx.insert(ids, lambda: next(free))
    assert len(new) == 2 and blocks == [1, 2] and start == 0
    nodes, got = idx.match(ids)
    assert got == [1, 2]
    # longest match is per FULL block: extending the prompt matches the
    # same chain; a prompt diverging INSIDE block 2 (the "split point")
    # shares only block 1 — block granularity means a partial edge is
    # never split, it just doesn't match
    assert idx.match(ids + [99])[1] == [1, 2]
    assert idx.match(ids[:4] + [63, 63, 63, 63])[1] == [1]
    assert idx.match([63] + ids[1:])[1] == []
    # re-inserting is idempotent; a longer prompt extends the chain
    new2, blocks2, _ = idx.insert(ids, lambda: next(free))
    assert not new2 and not blocks2
    _, blocks3, start3 = idx.insert(ids + list(range(11, 16)),
                                    lambda: next(free))
    assert start3 == 2 and len(blocks3) == 2    # blocks 3+4 are new


def test_radix_refcount_pins_blocks_and_lru_evicts_in_order():
    idx = RadixIndex(2)
    free = iter(range(1, 100))
    idx.insert([1, 2, 3, 4], lambda: next(free))    # chain A: blocks 1,2
    idx.insert([5, 6], lambda: next(free))          # chain B: block 3
    idx.insert([7, 8], lambda: next(free))          # chain C: block 4
    nodes_a, blocks_a = idx.match([1, 2, 3, 4])
    idx.acquire(nodes_a)
    # LRU candidates are unreferenced LEAVES: B was touched before C's
    # insert and never matched since, so B evicts first, then C; chain
    # A is pinned by the acquire, so eviction then returns None even
    # though A's leaf (block 2) is LRU-oldest
    idx.match([7, 8])                               # refresh C
    assert idx.evict_lru() == 3                     # B
    assert idx.evict_lru() == 4                     # C
    assert idx.evict_lru() is None                  # A pinned
    idx.release(nodes_a)
    assert idx.evict_lru() == 2                     # A's leaf first
    assert idx.evict_lru() == 1                     # then its parent
    assert idx.evict_lru() is None                  # empty


def test_insert_never_evicts_its_own_walk_path():
    """Extending a chain with the free list dry must NOT let LRU
    eviction take a node on the very path being walked — detaching it
    would link the new child under an unreachable subtree and leak its
    blocks forever. The walk pins its path; with no other candidate,
    the insert drops instead of corrupting."""
    idx = RadixIndex(2)
    free = iter([1, 2, 3])
    idx.insert([1, 2, 3, 4], lambda: next(free))
    new, blocks, _ = idx.insert([1, 2, 3, 4, 5, 6], idx.evict_lru)
    assert blocks == []                           # dropped, not linked
    assert idx.match([1, 2, 3, 4])[1] == [1, 2]   # chain intact
    # with an UNRELATED evictable chain present, the same insert
    # succeeds by evicting that one
    idx.insert([9, 8], lambda: next(free))        # block 3
    _, blocks2, _ = idx.insert([1, 2, 3, 4, 5, 6], idx.evict_lru)
    assert blocks2 == [3]
    assert idx.match([9, 8])[1] == []
    assert idx.match([1, 2, 3, 4, 5, 6])[1] == [1, 2, 3]


def test_pool_eviction_never_frees_in_use_and_counts(stack):
    model, params, _ = stack
    pc = PrefixCache(model, params, block_tokens=BLOCK, pool_blocks=4)
    # 3 usable blocks (block 0 is scratch): fill them with one chain
    ids_a = _ids(3 * BLOCK + 1, seed=1)
    blocks, start = pc.plan_insert(ids_a)
    assert start == 0 and len(blocks) == 3
    assert pc.used_blocks() == 3
    nodes, got, c = pc.lookup(ids_a)
    assert got == blocks and c == 3 * BLOCK
    # pool full + chain referenced: an insert for a new prompt cannot
    # evict anything — it drops, and the drop is counted
    dropped_before = pc.stats["prefix_dropped_inserts"]
    blocks_b, _ = pc.plan_insert(_ids(BLOCK, seed=2))
    assert blocks_b == []
    assert pc.stats["prefix_dropped_inserts"] > dropped_before
    pc.release(nodes)
    # released: the same insert now LRU-evicts chain A's leaf
    blocks_b, _ = pc.plan_insert(_ids(BLOCK, seed=2))
    assert len(blocks_b) == 1
    assert pc.stats["prefix_evictions"] == 1
    # chain A lost exactly its evicted tail
    _, got2, c2 = pc.lookup(ids_a)
    assert c2 == 2 * BLOCK


def test_lookup_never_serves_the_final_token(stack):
    """The prompt's last token must be re-fed — its logits sample the
    first output token — so an exactly-block-aligned, fully-cached
    prompt still matches only a PROPER prefix."""
    model, params, _ = stack
    pc = PrefixCache(model, params, block_tokens=BLOCK, pool_blocks=8)
    ids = _ids(2 * BLOCK, seed=3)
    pc.plan_insert(ids)
    nodes, blocks, c = pc.lookup(ids)
    assert c == BLOCK and len(blocks) == 1
    pc.release(nodes)


def test_rotation_composes_to_absolute_angles():
    """The canonical-space contract: K rotated at angle a then shifted
    by delta equals K rotated at a+delta (RoPE composition) — the fact
    the capture/extract kernels rely on."""
    from pytorch_distributed_template_tpu.models.llama import (
        apply_rope, rope_tables,
    )

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 6, 2, 8)).astype(np.float32))
    pos_a = jnp.arange(6)
    cos_a, sin_a = rope_tables(pos_a, 8)
    cos_b, sin_b = rope_tables(pos_a + 5, 8)
    shifted = rotate_rows(apply_rope(x, cos_a, sin_a),
                          jnp.asarray([5, 5]), 10000.0)
    direct = apply_rope(x, cos_b, sin_b)
    np.testing.assert_allclose(np.asarray(shifted), np.asarray(direct),
                               atol=1e-5)


def test_unsupported_layouts_raise(stack):
    """ISSUE 15 rewrote the old window/kv_quant refusals into real
    layouts (paged ring / int8 pool) — what REMAINS refused: the
    scatter-only path for window models, ring geometry the block size
    cannot tile, and unknown quant strings. Every refusal carries the
    machine-readable reason the pool_fallback counters consume."""
    from pytorch_distributed_template_tpu.engine.kvcache import (
        PoolUnsupported,
    )

    model, params, _ = stack
    win = MODELS.get("Llama")(vocab_size=VOCAB, n_layer=2, n_head=4,
                              n_kv_head=2, d_model=32, max_len=128,
                              window=32)
    # the PAGED ring layout constructs for window models now...
    pf = PrefixCache(win, params, block_tokens=8, pool_blocks=32)
    assert pf.paged and pf.window == 32 and pf.nb_max >= 5
    # ...but the scatter arm still cannot serve a rolling cache
    with pytest.raises(PoolUnsupported, match="paged") as ei:
        PrefixCache(win, params, block_tokens=8, pool_blocks=32,
                    paged=False)
    assert ei.value.reason == "window"
    # ring geometry the block size cannot tile refuses loudly
    with pytest.raises(PoolUnsupported, match="multiple") as ei:
        PrefixCache(win, params, block_tokens=12, pool_blocks=32)
    assert ei.value.reason == "window"
    # an undersized pool has no scatter fallback under a window
    with pytest.raises(PoolUnsupported, match="ring") as ei:
        PrefixCache(win, params, block_tokens=8, pool_blocks=4)
    assert ei.value.reason == "undersized"
    # the int8-KV pool layout constructs (scale leaves alongside pages)
    kvq = MODELS.get("Llama")(vocab_size=VOCAB, n_layer=2, n_head=4,
                              n_kv_head=2, d_model=32, max_len=128,
                              kv_quant="int8")
    pfq = PrefixCache(kvq, params, block_tokens=8, pool_blocks=32)
    scales = [ps for ps in pfq.pool if ps.endswith("_scale")]
    int8 = [ps for ps, leaf in pfq.pool.items()
            if str(leaf.dtype) == "int8"]
    assert len(scales) == 4 and len(int8) == 4    # 2 layers x K/V
    # a config asking for a genuinely refused layout degrades LOUDLY
    # to no pool instead of failing the server load, and the service
    # remembers WHY for the fallback counters
    svc = GenerationService.from_model(
        win, params,
        prefix_cache={"enabled": True, "block_tokens": 12})
    assert svc.prefix_cache_stats() is None
    assert svc.pool_refusal_reason == "window"


# ---------------------------------------------------------------------------
# e2e: warm output == cold output
# ---------------------------------------------------------------------------


def test_plain_service_warm_equals_cold_greedy_and_sampled(stack):
    model, params, solo = stack
    warm = GenerationService.from_model(
        model, params,
        prefix_cache={"enabled": True, "block_tokens": BLOCK,
                      "pool_blocks": 16})
    prefix = _ids(3 * BLOCK, seed=4)
    for i in range(3):
        ids = prefix + _ids(5, seed=10 + i)
        for kw in ({"temperature": 0.0},
                   {"temperature": 0.9, "top_k": 8},
                   {"temperature": 1.0, "top_p": 0.9}):
            a = solo.generate(prompt_ids=ids, max_new_tokens=10,
                              seed=i, **kw)
            b = warm.generate(prompt_ids=ids, max_new_tokens=10,
                              seed=i, **kw)
            assert a["ids"] == b["ids"], (i, kw)
    stats = warm.prefix_cache_stats()
    assert stats["prefix_hit_tokens"] >= 2 * 3 * BLOCK
    assert stats["prefix_hit_requests"] >= 2


def test_continuous_shared_prefix_equivalence(stack):
    """The acceptance bar: greedy tokens after a warm-prefix admit on
    the slot engine are identical to the cold path — including mixed
    sampled traffic sharing the engine and admits landing at nonzero
    era positions (the re-rotation path)."""
    model, params, solo = stack
    service = ContinuousBatchingService.from_model(
        model, params, slots=3, chunk=4, window_ms=30.0,
        prefix_cache={"enabled": True, "block_tokens": BLOCK,
                      "pool_blocks": 32})
    prefix = _ids(2 * BLOCK + 3, seed=5)
    rng = np.random.default_rng(6)

    def mkreq(i):
        return {
            "prompt_ids": prefix + [int(x) for x in
                                    rng.integers(1, VOCAB,
                                                 int(rng.integers(2, 8)))],
            "max_new_tokens": int(rng.integers(3, 10)),
            "temperature": [0.0, 0.8, 1.0][i % 3],
            "top_k": [0, 5, 0][i % 3],
            "seed": i,
        }

    for wave in range(2):      # wave 2 is fully warm
        reqs = [mkreq(10 * wave + i) for i in range(5)]
        ref = [solo.generate(**r) for r in reqs]
        out = [None] * len(reqs)
        errs = []

        def call(i):
            try:
                out[i] = service.generate(**reqs[i])
            except Exception as e:  # noqa: BLE001
                errs.append((i, e))

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not errs, errs
        for i, (a, b) in enumerate(zip(out, ref)):
            assert a["ids"] == b["ids"], (wave, i, reqs[i])
    stats = service.prefix_cache_stats()
    assert stats["prefix_hit_tokens"] > 0
    assert stats["prefix_pool_blocks_used"] > 0


def test_continuous_eviction_churn_stays_exact(stack):
    """A pool far too small for the traffic (constant LRU eviction)
    must still be token-exact — eviction changes WHAT is reused, never
    what is computed."""
    model, params, solo = stack
    service = ContinuousBatchingService.from_model(
        model, params, slots=2, chunk=4, window_ms=20.0,
        prefix_cache={"enabled": True, "block_tokens": BLOCK,
                      "pool_blocks": 3})
    for i in range(4):
        ids = _ids(2 * BLOCK + 2, seed=20 + i)   # distinct prefixes
        a = solo.generate(prompt_ids=ids, max_new_tokens=6, seed=i)
        b = service.generate(prompt_ids=ids, max_new_tokens=6, seed=i)
        assert a["ids"] == b["ids"], i
    # repeats of the LAST prompt hit what survived
    ids = _ids(2 * BLOCK + 2, seed=23)
    a = solo.generate(prompt_ids=ids, max_new_tokens=6, seed=99)
    b = service.generate(prompt_ids=ids, max_new_tokens=6, seed=99)
    assert a["ids"] == b["ids"]
    assert service.prefix_cache_stats()["prefix_evictions"] > 0


def test_gpt2_family_batch1_path(stack):
    """Non-rotary cache contract (models/transformer.kv_cache_spec):
    the batch-1 canonical path reuses GPT-2-family blocks verbatim."""
    model = MODELS.get("TinyLM")(vocab_size=VOCAB, n_layer=2, n_head=2,
                                 d_model=32, max_len=128)
    params = model.init(
        jax.random.key(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    solo = GenerationService.from_model(model, params)
    warm = GenerationService.from_model(
        model, params,
        prefix_cache={"enabled": True, "block_tokens": BLOCK,
                      "pool_blocks": 16})
    prefix = _ids(2 * BLOCK, seed=7)
    for i in range(2):
        ids = prefix + _ids(4, seed=30 + i)
        a = solo.generate(prompt_ids=ids, max_new_tokens=8, seed=i)
        b = warm.generate(prompt_ids=ids, max_new_tokens=8, seed=i)
        assert a["ids"] == b["ids"], i
    assert warm.prefix_cache_stats()["prefix_hit_tokens"] > 0
    # GPT-2 family has no block-table call path: the pool must have
    # degraded to the scatter fallback, loudly, not silently broken
    assert warm.prefix_cache_stats()["prefix_paged"] is False


# ---------------------------------------------------------------------------
# paged kernel vs plain-JAX oracle (ops/flash.paged_attention — ISSUE 7)
# ---------------------------------------------------------------------------


def _paged_case(seed, b, t, hq, kvh, d, bt, pool, lens, shuffle=True):
    """Random pools + RAGGED, NON-CONTIGUOUS block tables: row ``i``
    has ``lens[i]`` total tokens (last block partially filled unless
    ``lens[i] % bt == 0``), its pages drawn from a shuffled pool order
    (eviction-churned layout), unused table lanes -1."""
    rng = np.random.default_rng(seed)
    nb = max(-(-int(n) // bt) for n in lens)
    q = jnp.asarray(rng.standard_normal((b, t, hq, d)), jnp.float32)
    k_pool = jnp.asarray(rng.standard_normal((pool, bt, kvh, d)),
                         jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((pool, bt, kvh, d)),
                         jnp.float32)
    avail = list(range(1, pool))        # page 0 = scratch, never mapped
    if shuffle:
        rng.shuffle(avail)
    tables = np.full((b, nb), -1, np.int32)
    it = iter(avail)
    for i, n in enumerate(lens):
        for j in range(-(-int(n) // bt)):
            tables[i, j] = next(it)
    starts = jnp.asarray([int(n) - t for n in lens], jnp.int32)
    return q, k_pool, v_pool, jnp.asarray(tables), starts


@pytest.mark.parametrize("t,bt,lens", [
    (1, 8, [8, 24]),            # decode step, block-aligned rows
    (1, 8, [13, 21]),           # ragged last blocks
    (8, 8, [16, 29]),           # suffix window crossing a block edge
    (4, 16, [16, 61]),          # one-block vs many-block rows
])
def test_paged_kernel_matches_oracle(t, bt, lens):
    """The Pallas paged kernel (interpret mode off-TPU) against the
    plain-JAX gather oracle, across block counts, ragged last blocks,
    and shuffled (eviction-churned, non-contiguous) block tables."""
    from pytorch_distributed_template_tpu.ops.flash import (
        paged_attention, paged_attention_ref,
    )

    q, kp, vp, tables, starts = _paged_case(
        hash((t, bt, tuple(lens))) % 1000, len(lens), t, 4, 2, 32, bt,
        16, lens)
    pads = jnp.zeros((len(lens),), jnp.int32)
    ref = paged_attention_ref(q, kp, vp, tables, starts, pads)
    pal = paged_attention(q, kp, vp, tables, starts, pads,
                          impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-5)


def test_paged_kernel_pad_lanes_and_oracle_vs_dense():
    """Two contracts at once: (a) leading INVALID q lanes (pad_lens —
    a right-aligned suffix feed) produce the same VALID-lane outputs as
    the oracle; (b) the oracle itself, on a contiguously-laid pool,
    equals dense causal grouped-query attention — so kernel == oracle
    == textbook, transitively."""
    from pytorch_distributed_template_tpu.ops.attention import (
        grouped_query_attention,
    )
    from pytorch_distributed_template_tpu.ops.flash import (
        paged_attention, paged_attention_ref,
    )

    rng = np.random.default_rng(11)
    b, t, hq, kvh, d, bt, L = 2, 8, 4, 2, 32, 8, 32
    nb = L // bt
    k_all = jnp.asarray(rng.standard_normal((b, L, kvh, d)), jnp.float32)
    v_all = jnp.asarray(rng.standard_normal((b, L, kvh, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, t, hq, d)), jnp.float32)
    # per-row pools laid contiguously (pages 1.. for row 0, then row 1)
    pool = jnp.concatenate(
        [jnp.zeros((1, bt, kvh, d), jnp.float32)]
        + [k_all[i].reshape(nb, bt, kvh, d) for i in range(b)])
    vpool = jnp.concatenate(
        [jnp.zeros((1, bt, kvh, d), jnp.float32)]
        + [v_all[i].reshape(nb, bt, kvh, d) for i in range(b)])
    tables = jnp.asarray(
        [[1 + i * nb + j for j in range(nb)] for i in range(b)],
        jnp.int32)
    starts = jnp.asarray([L - t] * b, jnp.int32)
    pads = jnp.asarray([0, 3], jnp.int32)   # row 1: 3 leading dead lanes
    ref = paged_attention_ref(q, pool, vpool, tables, starts, pads)
    pal = paged_attention(q, pool, vpool, tables, starts, pads,
                          impl="pallas", interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=1e-5)
    # (b) dense reference: q lane i attends keys 0 .. L-t+i
    q_pos = (L - t) + np.arange(t)
    mask = jnp.asarray(np.arange(L)[None, :] <= q_pos[:, None])
    dense = grouped_query_attention(
        q, k_all, v_all, mask=jnp.broadcast_to(mask, (b, 1, t, L)))
    # valid lanes only (row 1's first 3 outputs are garbage by contract)
    np.testing.assert_allclose(np.asarray(ref[0]), np.asarray(dense[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref[1, 3:]),
                               np.asarray(dense[1, 3:]), atol=1e-5)


# ---------------------------------------------------------------------------
# e2e: paged decode == scatter fallback == cold (ISSUE 7 tentpole gate)
# ---------------------------------------------------------------------------


def _arm(model, params, paged, pool_blocks=32):
    return GenerationService.from_model(
        model, params,
        prefix_cache={"enabled": True, "block_tokens": BLOCK,
                      "pool_blocks": pool_blocks, "paged": paged})


def test_batch1_paged_vs_scatter_vs_cold(stack):
    """The ROADMAP item 2 gate, batch-1: greedy AND sampled tokens are
    identical across the paged path (block-table pointer admits, pool
    read in place), the scatter fallback, and the cold solo path — and
    the paged arm's warm-admit device-copy bytes are EXACTLY zero while
    the scatter arm pays per admit."""
    model, params, solo = stack
    paged = _arm(model, params, True)
    scatter = _arm(model, params, False)
    assert paged.prefix_cache_stats()["prefix_paged"] is True
    assert scatter.prefix_cache_stats()["prefix_paged"] is False
    prefix = _ids(3 * BLOCK, seed=40)
    for i in range(3):
        ids = prefix + _ids(5, seed=50 + i)
        for kw in ({"temperature": 0.0},
                   {"temperature": 0.9, "top_k": 8}):
            a = solo.generate(prompt_ids=ids, max_new_tokens=10,
                              seed=i, **kw)
            b = paged.generate(prompt_ids=ids, max_new_tokens=10,
                               seed=i, **kw)
            c = scatter.generate(prompt_ids=ids, max_new_tokens=10,
                                 seed=i, **kw)
            assert a["ids"] == b["ids"] == c["ids"], (i, kw)
    ps, ss = paged.prefix_cache_stats(), scatter.prefix_cache_stats()
    assert ps["prefix_hit_tokens"] > 0 and ss["prefix_hit_tokens"] > 0
    assert ps["warm_admit_copy_bytes"] == 0          # the zero-copy gate
    assert ss["warm_admit_copy_bytes"] > 0           # the cost deleted
    # zero-copy adoption: the paged arm shares pages it never captured
    assert ps["prefix_adopted_blocks"] > 0


def test_continuous_paged_vs_scatter_vs_cold(stack):
    """The slot engine, both arms vs solo, greedy + sampled + mixed
    concurrent traffic; the paged arm must serve every decode chunk
    through the block table (paged_chunks == chunks) with zero admit
    copy bytes."""
    model, params, solo = stack
    arms = {
        arm: ContinuousBatchingService.from_model(
            model, params, slots=3, chunk=4, window_ms=30.0,
            prefix_cache={"enabled": True, "block_tokens": BLOCK,
                          "pool_blocks": 40, "paged": arm == "paged"})
        for arm in ("paged", "scatter")
    }
    assert arms["paged"]._paged and not arms["scatter"]._paged
    prefix = _ids(2 * BLOCK + 3, seed=60)
    rng = np.random.default_rng(61)

    def mkreq(i):
        return {
            "prompt_ids": prefix + [int(x) for x in rng.integers(
                1, VOCAB, int(rng.integers(2, 8)))],
            "max_new_tokens": int(rng.integers(3, 10)),
            "temperature": [0.0, 0.8][i % 2],
            "top_k": [0, 5][i % 2],
            "seed": i,
        }

    for wave in range(2):          # wave 2 is fully warm
        reqs = [mkreq(10 * wave + i) for i in range(5)]
        ref = [solo.generate(**r) for r in reqs]
        for name, svc in arms.items():
            out = [None] * len(reqs)
            errs = []

            def call(i, svc=svc, out=out, errs=errs, reqs=reqs):
                try:
                    out[i] = svc.generate(**reqs[i])
                except Exception as e:  # noqa: BLE001
                    errs.append((i, e))

            threads = [threading.Thread(target=call, args=(i,))
                       for i in range(len(reqs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600)
            assert not errs, (name, errs)
            for i, (a, b) in enumerate(zip(out, ref)):
                assert a["ids"] == b["ids"], (name, wave, i)
    pstats = arms["paged"].prefix_cache_stats()
    assert pstats["warm_admit_copy_bytes"] == 0
    assert pstats["prefix_hit_tokens"] > 0
    assert arms["paged"].stats["paged_chunks"] == \
        arms["paged"].stats["chunks"] > 0
    assert arms["paged"].stats["paged_admissions"] > 0
    assert arms["scatter"].prefix_cache_stats()[
        "warm_admit_copy_bytes"] > 0
    assert arms["scatter"].stats["paged_chunks"] == 0


def test_continuous_paged_eviction_churn_stays_exact(stack):
    """Distinct prefixes through a pool barely above the paged floor:
    constant LRU churn hands every request a different, non-contiguous
    page layout — output must stay token-exact (churn changes WHAT is
    reused, never what is computed)."""
    model, params, solo = stack
    service = ContinuousBatchingService.from_model(
        model, params, slots=2, chunk=4, window_ms=20.0,
        prefix_cache={"enabled": True, "block_tokens": BLOCK,
                      "pool_blocks": 18, "paged": True})
    assert service._paged                       # nb_max=16 <= 17 usable
    for i in range(4):
        # 50-token prompts adopt 6 blocks each: 17 usable pages force
        # LRU eviction of earlier chains by the third request
        ids = _ids(6 * BLOCK + 2, seed=70 + i)  # distinct prefixes
        a = solo.generate(prompt_ids=ids, max_new_tokens=6, seed=i)
        b = service.generate(prompt_ids=ids, max_new_tokens=6, seed=i)
        assert a["ids"] == b["ids"], i
    ids = _ids(6 * BLOCK + 2, seed=73)          # repeat the last: warm
    a = solo.generate(prompt_ids=ids, max_new_tokens=6, seed=99)
    b = service.generate(prompt_ids=ids, max_new_tokens=6, seed=99)
    assert a["ids"] == b["ids"]
    st = service.prefix_cache_stats()
    assert st["prefix_evictions"] > 0
    assert st["warm_admit_copy_bytes"] == 0


def test_paged_pool_exhaustion_defers_and_completes(stack):
    """More concurrent full-budget requests than the pool can hold
    chains for: admissions DEFER (counted) until completions free
    pages — every request still completes, token-exact."""
    model, params, solo = stack
    service = ContinuousBatchingService.from_model(
        model, params, slots=4, chunk=4, window_ms=20.0,
        prefix_cache={"enabled": True, "block_tokens": BLOCK,
                      "pool_blocks": 18, "paged": True})
    reqs = [{"prompt_ids": _ids(6 * BLOCK, seed=80 + i),
             "max_new_tokens": 8, "seed": i} for i in range(4)]
    ref = [solo.generate(**r) for r in reqs]
    out = [None] * len(reqs)
    errs = []

    def call(i):
        try:
            out[i] = service.generate(**reqs[i])
        except Exception as e:  # noqa: BLE001
            errs.append((i, e))

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not errs, errs
    for i, (a, b) in enumerate(zip(out, ref)):
        assert a["ids"] == b["ids"], i
    # 4 requests x 7 blocks (6 prompt + budget) cannot co-reside in 17
    # usable pages: at least one admission must have deferred
    assert service.stats["deferred_admissions"] > 0


def test_occupancy_split_never_double_counts(stack):
    """The ISSUE 7 occupancy satellite: ``resident`` counts unique
    radix-owned pages, ``referenced`` counts pages live requests hold
    — a hot prefix idling in the pool is resident but NOT referenced
    (the old single counter folded both together)."""
    model, params, _ = stack
    for paged in (True, False):
        svc = _arm(model, params, paged)
        ids = _ids(3 * BLOCK + 2, seed=90)
        svc.generate(prompt_ids=ids, max_new_tokens=4, seed=0)
        svc.generate(prompt_ids=ids, max_new_tokens=4, seed=1)  # warm
        st = svc.prefix_cache_stats()
        pc = svc._prefix
        # idle engine: nothing referenced, the radix chain resident
        assert st["prefix_pool_blocks_referenced"] == 0, paged
        assert st["prefix_pool_blocks_resident"] == pc.index.nodes > 0
        # mid-request the split is visible: a lookup ref pins pages
        nodes, blocks, c = pc.lookup(ids)
        assert pc.stats_snapshot()[
            "prefix_pool_blocks_referenced"] == len(blocks) > 0
        pc.release(nodes)
        assert pc.stats_snapshot()[
            "prefix_pool_blocks_referenced"] == 0


def test_adopt_is_zero_copy_and_duplicate_safe(stack):
    """``PrefixCache.adopt``: privately-written pages hand to the index
    with no device work; where a concurrent request adopted the same
    content first, the duplicate stays private (freed by its owner) and
    the pre-existing node is reused."""
    model, params, _ = stack
    pc = PrefixCache(model, params, block_tokens=BLOCK, pool_blocks=32)
    ids = _ids(2 * BLOCK, seed=95)
    priv = pc.alloc_chain(2)
    adopted, nodes = pc.adopt(ids, {0: priv[0], 1: priv[1]},
                              acquire=True)
    assert adopted == priv and len(nodes) == 2
    assert pc.lookup(ids + [1])[1] == priv      # chain now matchable
    pc.release(pc.lookup(ids + [1])[0])
    pc.release(nodes)
    # a second request wrote the same content into its own pages:
    # nothing new adopts, its duplicates stay private for freeing
    priv2 = pc.alloc_chain(2)
    adopted2, nodes2 = pc.adopt(ids, {0: priv2[0], 1: priv2[1]},
                                acquire=True)
    assert adopted2 == [] and nodes2 == []
    pc.free_blocks(priv2)
    assert pc.used_blocks() == 2                # only the chain remains


def test_spec_request_between_ticks_does_not_invalidate_pool(stack):
    """serve.py routes speculative requests AROUND the slot engine:
    batch-1 under the same lock. On a prefix HIT they take
    ``warm_prefill``, whose block insert ends in the capture kernel —
    which DONATES the pool leaves the engine's persistent paged cache
    aliases. The engine must re-adopt the reassigned pool at its next
    tick: pre-fix, the post-spec call here died with "buffer has been
    deleted or donated". A MISS routes to the length-bucketed cold
    path and must leave the pool untouched."""
    model, params, solo = stack
    service = ContinuousBatchingService.from_model(
        model, params, slots=2, chunk=4, window_ms=20.0,
        prefix_cache={"enabled": True, "block_tokens": BLOCK,
                      "pool_blocks": 40, "paged": True})
    assert service._paged
    ids = _ids(4 * BLOCK + 2, seed=90)
    a = solo.generate(prompt_ids=ids, max_new_tokens=6, seed=0)
    b = service.generate(prompt_ids=ids, max_new_tokens=6, seed=0)
    assert a["ids"] == b["ids"]
    # MISS arm: a fresh prefix stays on the bucketed cold path —
    # no scatter copy, no pool mutation
    spec = service.generate(prompt_ids=_ids(4 * BLOCK, seed=91),
                            max_new_tokens=6, seed=0, speculative=2)
    assert len(spec["ids"]) == 6
    st = service.prefix_cache_stats()
    assert st["warm_admit_copy_bytes"] == 0
    # HIT arm: shares the engine request's adopted blocks -> warm
    # scatter prefill (copy bytes are the SPEC arm's documented cost)
    # + block insert via the donating capture kernel
    spec2 = service.generate(
        prompt_ids=ids[:3 * BLOCK] + _ids(BLOCK, seed=92),
        max_new_tokens=6, seed=0, speculative=2)
    assert len(spec2["ids"]) == 6
    st = service.prefix_cache_stats()
    copy_after_spec = st["warm_admit_copy_bytes"]
    assert copy_after_spec > 0
    # the engine's next dispatch must run on the re-adopted pool —
    # and still serve the first prompt warm, token-identically, with
    # ZERO further copy bytes (engine admits stay pointer updates)
    c = service.generate(prompt_ids=ids, max_new_tokens=6, seed=0)
    assert c["ids"] == a["ids"]
    st = service.prefix_cache_stats()
    assert st["warm_admit_copy_bytes"] == copy_after_spec
    assert st["prefix_hit_tokens"] > 0


def test_dry_pool_fallback_counts_the_lookup_once(stack):
    """A dry pool fails the paged arm's page reservation AFTER
    ``paged_plan`` recorded the request's lookup; the scatter
    fallback's own lookup must not record the SAME request again —
    ``prefix_hit_tokens`` feeds /metrics and the fleet router."""
    model, params, _ = stack
    svc = _arm(model, params, True, pool_blocks=18)
    pc = svc._prefix
    prefix = _ids(2 * BLOCK, seed=77)
    ids = prefix + _ids(4, seed=78)
    cold = svc.generate(prompt_ids=ids, max_new_tokens=6, seed=0,
                        temperature=0.0)
    # pin the cached chain (drain-by-allocation must not evict it),
    # then drain the free list so alloc_chain has nothing to give
    nodes, _, c = pc.lookup(ids, record=False)
    assert c == 2 * BLOCK
    try:
        while pc.alloc_chain(1) is not None:    # drain to genuinely
            pass                                # dry (evictions incl.)
        before = pc.stats_snapshot()
        warm = svc.generate(prompt_ids=ids, max_new_tokens=6, seed=0,
                            temperature=0.0)
    finally:
        pc.release(nodes)
    after = pc.stats_snapshot()
    assert warm["ids"] == cold["ids"]
    # served by the scatter fallback, counted as ONE lookup / ONE hit
    assert after["batch1_scatter_requests"] == \
        before["batch1_scatter_requests"] + 1
    assert after["prefix_lookups"] == before["prefix_lookups"] + 1
    assert after["prefix_hit_requests"] == \
        before["prefix_hit_requests"] + 1
    assert after["prefix_hit_tokens"] == before["prefix_hit_tokens"] + c


def test_failed_paged_prefill_leaves_a_healthy_pool(stack,
                                                    monkeypatch):
    """The batch-1 paged prefill DONATES the pool; a dispatch that
    fails after donation must reset the pool — dead leaves would
    otherwise wedge every later request (paged or scatter) until
    process restart."""
    import pytorch_distributed_template_tpu.engine.kvcache as kv

    model, params, solo = stack
    svc = _arm(model, params, True, pool_blocks=18)
    pc = svc._prefix
    ids = _ids(2 * BLOCK + 4, seed=85)

    def dead_arm(model, feed, nb):
        def fn(params, cache, suffix, tables, starts):
            for leaf in jax.tree_util.tree_leaves(dict(cache)):
                leaf.delete()          # donation consumed the buffers
            raise RuntimeError("dispatch failed after donation")
        return fn

    monkeypatch.setattr(kv, "_paged_prefill_fn", dead_arm)
    with pytest.raises(RuntimeError):
        svc.generate(prompt_ids=ids, max_new_tokens=4, seed=0,
                     temperature=0.0)
    assert pc.stats_snapshot()["prefix_pool_resets"] == 1
    assert pc.pool_alive()
    monkeypatch.undo()
    # the reset pool serves the next request correctly (cold — the
    # cached content died with the donated buffers)
    a = solo.generate(prompt_ids=ids, max_new_tokens=4, seed=0,
                      temperature=0.0)
    b = svc.generate(prompt_ids=ids, max_new_tokens=4, seed=0,
                     temperature=0.0)
    assert a["ids"] == b["ids"]
    assert pc.stats_snapshot()["warm_admit_copy_bytes"] == 0
