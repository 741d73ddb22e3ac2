"""Paged KV block pool + automatic prefix caching for the serving path.

Production LM traffic is dominated by requests sharing long system /
few-shot prefixes, and prefill is the compute-bound slice of serving
(~16 ms device time per 8x1024 prompt — BASELINE.md). vLLM's
PagedAttention (Kwon et al., SOSP 2023) and SGLang's RadixAttention
(Zheng et al., 2024) showed that block-granular KV management plus a
prefix index over token ids turns that shared work into an HBM copy
instead of a recompute. This module is the TPU-native version of that
idea for THIS framework's cache layout:

- **Block pool** (``PrefixCache``): one bounded device array per
  KV-cache leaf, shaped ``[pool_blocks, block_tokens, kv_heads,
  head_dim]`` — fixed-size token blocks allocated from a free list,
  ref-counted while an admission is reading them, LRU-evicted when the
  pool fills. Block id 0 is a reserved scratch block (never allocated)
  so padded/unused lanes of the fixed-shape kernels always have a legal
  destination.
- **Radix index** (``RadixIndex``): a trie over prompt token ids with
  one edge per FULL block (``block_tokens`` ids) mapping prefixes to
  block chains. Matching is block-granular — two prompts that diverge
  mid-block share nothing for that block (the vLLM hash-per-full-block
  contract); there are no partial-edge splits to manage.
- **Canonical rotation space**: the Llama-family cache stores K rotated
  at absolute cache-slot angles (models/llama._cached_attention), and
  the continuous engine admits a prompt wherever the era's global
  position counter happens to be — so the same prefix lands at
  different slots on different admits. Pool blocks therefore store K in
  CANONICAL space (prefix token ``j`` rotated at angle ``j``); RoPE
  rotations compose additively (``R(aθ)·R(bθ) = R((a+b)θ)``), so
  capture de-rotates by the row's start slot and extraction re-rotates
  by the target start slot — one constant-angle rotation per row,
  fused into the copy kernel. V (and non-rotary families) copy as-is.
  The round-trip is exact in real arithmetic and float-tolerance exact
  in practice — the same contract as the engine's mixed-length
  batching ("logits agree to float tolerance, not bitwise").
- **Suffix-only prefill**: an admission with ``c`` cached prefix tokens
  scatters the block chain into the row's cache slots and feeds only
  the suffix through the model. The fed window is snapped to the same
  power-of-two ladder as cold admissions (engine/continuous._bucket),
  so the compile-cache/warmup story is untouched. Inside the fed
  window the model RECOMPUTES any overlapped prefix positions exactly
  as the cold path would (its DUS write wins over the scattered copy),
  which keeps warm output equal to cold output.

- **Tiered spill hierarchy** (``SpillTier``, ISSUE 13): eviction
  DEMOTES instead of destroys — the LRU-evicted block's bytes move to
  a bounded host-RAM tier (and overflow optionally to a disk tier),
  sha256-checksummed at demote time. A radix miss that extends into a
  spilled chain PROMOTES it back: checksum-verified, landed as private
  pages through the same donating scatter as a page import, then
  adopted — a torn or corrupt spilled page fails verification and is
  recomputed cold, never served wrong. A full or faulted tier degrades
  to the classic destroy-on-evict, counted, with zero correctness
  impact; the whole hierarchy is chaos-tested via the ``slow_spill`` /
  ``corrupt_spill`` / ``tier_exhaust`` fault kinds (resilience/faults).

- **int8-KV pool layout** (ISSUE 15, ``kv_quant == "int8"``): pool
  K/V leaves store int8 pages with f32 scale leaves alongside
  (``[P, bt, KVH]``, one scale per token x kv-head — models/quant
  ``quantize_kv``). The paged path quantizes at the model's page
  write and dequantizes in the paged kernel's tile fetch
  (ops/flash.py dequant epilogue) — half the KV bytes cross HBM on
  decode, the binding constraint per BASELINE.md — and ship/spill/
  export move the quantized bytes (halving wire and tier traffic for
  free; the sha256 spill checksums cover the int8 bytes unchanged).
  Capture de-rotates in f32 then re-quantizes; the scatter fallback
  dequantizes on gather. Parity contract: quantized-vs-f32 agrees to
  the documented int8 tolerance, while warm-vs-cold stays
  token-identical ON THE PAGED PATH (hits replay the exact bytes the
  writer attended to).
- **Sliding-window ring layout** (ISSUE 15, ``window > 0``): per-row
  block tables become RINGS — logical block ``j`` lives in table slot
  ``j % nb_ring`` with ``nb_ring ≈ window/block_tokens + 1 + slack``
  — so decode reads O(window) pages regardless of sequence length.
  The +1 covers band/tile misalignment; the slack pages guarantee a
  multi-token prefill feed (bounded by ``ring_slack_tokens``) never
  clobbers in-band history before its own queries read it. Radix
  caching applies only to requests that never wrap
  (``prompt + budget <= nb_ring * block_tokens`` — the loud
  documented cap); a wrapping request runs fully private and adopts
  nothing. The scatter fallback still refuses ``window > 0`` (a
  rolling contiguous cache's eviction order is position-dependent).

Models declare their layout via ``kv_cache_spec()`` (models/llama.py,
models/transformer.py).
"""
from __future__ import annotations

import functools
import hashlib
import json
import logging
import os
import struct
import threading
import time

import numpy as np

logger = logging.getLogger(__name__)

#: reserved pool block: padded/unused kernel lanes read and write here
SCRATCH_BLOCK = 0

#: wire magic for serialized page payloads (disaggregated serving,
#: ISSUE 12): version bumps change the suffix, never the prefix, so a
#: receiver can refuse a foreign format with one 10-byte read
PAGE_MAGIC = b"PDTPAGES1\n"


def _path_str(path) -> str:
    """Flax cache pytree path -> stable string key ("layers_0/self_attn/
    cached_key") shared by the host pool dict and the traced kernels."""
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", p)))
    return "/".join(parts)


def _leaf_kind(path_s: str, leaf) -> str | None:
    """'key' / 'value' for poolable K/V cache leaves, 'scale' for the
    int8-KV layout's per-(token, head) scale leaves (ISSUE 15 — they
    pool alongside the pages they rescale), None for everything else
    (pos_index, slot_pos)."""
    name = path_s.rsplit("/", 1)[-1]
    if getattr(leaf, "ndim", 0) == 3 and name in (
            "cached_key_scale", "cached_value_scale"):
        return "scale"
    if getattr(leaf, "ndim", 0) != 4:
        return None
    if name == "cached_key":
        return "key"
    if name == "cached_value":
        return "value"
    return None


def rotate_rows(x, deltas, rope_base: float):
    """Rotate ``[B, T, H, D]`` K rows by a per-row CONSTANT RoPE angle
    ``deltas[b]`` (rotate-half convention, f32 math — the op-for-op
    broadcast form of models/llama.apply_rope). Because RoPE rotations
    compose additively, rotating canonical-space K by the row's start
    slot reproduces the cache's absolute-slot rotation; negative deltas
    invert (capture path)."""
    import jax.numpy as jnp

    from ..models.llama import rope_tables

    d = x.shape[-1]
    cos, sin = rope_tables(jnp.asarray(deltas, jnp.int32), d, rope_base)
    xf = x.astype(jnp.float32)
    rot = jnp.concatenate([-xf[..., d // 2:], xf[..., : d // 2]], axis=-1)
    out = xf * cos[:, None, None, :] + rot * sin[:, None, None, :]
    return out.astype(x.dtype)


def scatter_blocks(cache, pool, block_ids, pads, pos0, feed: int,
                   block: int, rotary: bool, rope_base: float,
                   kv_quant: str = ""):
    """Scatter pool block chains into a (fresh) per-row cache pytree.

    ``cache``: the group cache (leaves ``[k, total, H, D]``).
    ``pool``: ``{path_str: [P, block, H, D]}``.
    ``block_ids``: ``[k, nb]`` int32, ``-1`` = unused lane.
    ``pads``: ``[k]`` row start slots (= rotation delta for K).
    ``pos0``: scalar — the fed window start; unused lanes are
    redirected into ``[pos0, pos0 + feed)``, which the suffix prefill's
    own DUS writes overwrite at every layer before any read, so their
    garbage is dead by construction. Traced; shapes are static.

    ``kv_quant == "int8"`` (ISSUE 15): the pool holds int8 pages +
    ``*_scale`` leaves. V (and non-rotated K at delta 0) copies the
    int8 bytes and scales STRAIGHT across — exact; rotated K
    dequantizes on the gather, re-rotates in f32, and re-quantizes
    (the per-reuse rounding this layout's documented tolerance
    covers). The generic path below already lands 3-dim scale leaves
    (``dest`` indexes the token axis of any trailing shape).
    """
    import jax
    import jax.numpy as jnp

    k, nb = block_ids.shape
    tok = jnp.arange(nb * block)
    used = jnp.repeat(block_ids >= 0, block, axis=1)        # [k, nb*block]
    dest = jnp.where(used, pads[:, None] + tok[None, :],
                     pos0 + (tok % feed)[None, :])
    safe_ids = jnp.clip(block_ids, 0, None)                  # -1 -> scratch

    updates = {}
    if kv_quant and rotary:
        from ..models.quant import quantize_kv

        # K pages must re-rotate to the rows' absolute-slot angles:
        # dequant -> rotate -> requant, jointly producing the int8 page
        # AND its fresh scale leaf (the tree walk below consumes both)
        for ps in pool:
            if not ps.endswith("cached_key") or ps + "_scale" not in pool:
                continue
            sq = pool[ps][safe_ids]              # [k, nb, block, H, D]
            ss = pool[ps + "_scale"][safe_ids]   # [k, nb, block, H]
            deq = sq.astype(jnp.float32) * ss[..., None]
            deq = deq.reshape(k, nb * block, *sq.shape[3:])
            q2, s2 = quantize_kv(rotate_rows(deq, pads, rope_base))
            updates[ps] = q2
            updates[ps + "_scale"] = s2

    def put(path, leaf):
        ps = _path_str(path)
        if ps in updates:
            src = updates[ps]
        elif ps in pool:
            src = pool[ps][safe_ids]             # [k, nb, block, ...]
            src = src.reshape(k, nb * block, *src.shape[3:])
            if rotary and ps.endswith("cached_key"):
                src = rotate_rows(src, pads, rope_base)
        else:
            return leaf
        src = src.astype(leaf.dtype)
        return jax.vmap(lambda row, d, s: row.at[d].set(s))(leaf, dest,
                                                            src)

    return jax.tree_util.tree_map_with_path(put, cache)


@functools.lru_cache(maxsize=32)
def _capture_fn(model, k: int, nb: int, block: int, rotary: bool,
                rope_base: float, kv_quant: str = ""):
    """Compiled pool capture: gather ``nb`` blocks of each of ``k``
    cache rows (row ``slots[j]``, prompt starting at slot ``pads[j]``),
    de-rotate K to canonical space, and write them into the (donated)
    pool at ``block_ids``. Unused lanes (``-1``) read row 0 and write
    the scratch block. One async dispatch; never forces a sync.

    ``kv_quant == "int8"`` (ISSUE 15): cache rows are int8 + scale
    leaves — dequantize, de-rotate (K) in f32, re-quantize, and write
    page + scale leaf together. At delta 0 (batch-1 captures) the
    round-trip is exact (quantize_kv maps each row's max back to ±127,
    so requantizing a just-dequantized row reproduces its bytes)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=0)
    def capture(pool, cache, slots, pads, block_ids):
        from ..models.quant import quantize_kv

        tok = jnp.arange(nb * block)
        used = jnp.repeat(block_ids >= 0, block, axis=1)
        src_idx = jnp.where(used, pads[:, None] + tok[None, :], 0)
        ids = jnp.where(block_ids >= 0, block_ids, SCRATCH_BLOCK)
        flat = jax.tree_util.tree_flatten_with_path(dict(cache))[0]
        by_path = {_path_str(p): leaf for p, leaf in flat}
        out = {}

        def land(ps, content):
            pool_leaf = pool[ps]
            content = content.astype(pool_leaf.dtype).reshape(
                k, nb, block, *content.shape[2:])
            out[ps] = pool_leaf.at[ids.reshape(-1)].set(
                content.reshape(k * nb, block, *content.shape[3:]))

        for ps in sorted(pool):
            if kv_quant and ps.endswith("_scale"):
                continue                 # landed with its base leaf
            rows = by_path[ps][slots]                       # [k, T, ...]
            content = jax.vmap(lambda r, i: r[i])(rows, src_idx)
            if kv_quant and ps + "_scale" in pool:
                srows = by_path[ps + "_scale"][slots]       # [k, T, H]
                scont = jax.vmap(lambda r, i: r[i])(srows, src_idx)
                deq = content.astype(jnp.float32) * scont[..., None]
                if rotary and ps.endswith("cached_key"):
                    deq = rotate_rows(deq, -pads, rope_base)
                q2, s2 = quantize_kv(deq)
                land(ps, q2)
                land(ps + "_scale", s2)
                continue
            if rotary and ps.endswith("cached_key"):
                content = rotate_rows(content, -pads, rope_base)
            land(ps, content)
        return out

    return capture


@functools.lru_cache(maxsize=32)
def _warm_prefill_fn(model, total: int, feed: int, nb: int, block: int,
                     padded: bool):
    """Compiled batch-1 warm prefill: build a zero ``[1, total]`` cache
    in-graph, scatter the cached block chain at canonical slots 0..c-1
    (delta 0 — at batch 1 the prompt starts at slot 0, so pool space IS
    cache space and K needs no re-rotation), position the counter at
    ``pos0 = L - feed``, and run the trailing ``feed`` prompt tokens
    through the masked continuation path. Pad-capable models
    (``padded``) pass ``prefill=True`` with an all-zero ``pad_lens`` —
    that combination keeps the masked einsum path (the fresh-cache
    flash fast path requires ``pad_lens is None`` and would ignore the
    scattered history) while still taking the model-level
    last-position logits trim, so the ``[1, feed, V]`` head never
    materializes. Returns ``(last_logits, cache)`` — the same contract
    as engine/generate._prefill_fresh, so the normal decode step loop
    takes over unchanged. Full misses never come here (the caller
    routes c == 0 through the genuine flash prefill)."""
    import jax
    import jax.numpy as jnp

    from ..parallel.tp import constrain_kv_tree

    mesh = getattr(model, "mesh", None)

    @jax.jit
    def run(params, suffix, pool, block_ids, pos0):
        shapes = jax.eval_shape(
            lambda p: model.apply(
                {"params": p}, jnp.zeros((1, total), jnp.int32),
                train=False, decode=True, mutable=["cache"],
            ),
            params,
        )[1]["cache"]
        cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                             shapes)
        cache = constrain_kv_tree(cache, mesh)   # TP head sharding
        cache = scatter_blocks(
            dict(cache), pool, block_ids, jnp.zeros((1,), jnp.int32),
            pos0, feed, block, rotary=False, rope_base=0.0)
        cache["pos_index"] = pos0.astype(jnp.int32)
        extra = ({"prefill": True,
                  "pad_lens": jnp.zeros((1,), jnp.int32)}
                 if padded else {})
        logits, vs = model.apply(
            {"params": params, "cache": cache}, suffix,
            train=False, decode=True, mutable=["cache"], **extra,
        )
        return logits[:, -1], vs["cache"]

    return run


@functools.lru_cache(maxsize=32)
def _paged_prefill_fn(model, feed: int, nb: int):
    """Compiled batch-1 PAGED prefill (ISSUE 7): no cache build, no
    block scatter — the cache pytree IS the pool, the row's block
    table maps its positions to pages (shared radix pages for the
    cached prefix, freshly allocated private pages for the suffix),
    and only the ``feed``-token uncached suffix runs through the
    model, writing K/V straight into the private pages. Returns
    ``(last_logits, cache)`` like ``_warm_prefill_fn`` — the paged
    step loop takes over from there. The cache (= the pool) is
    DONATED, like every other paged executable: XLA aliases the page
    writes in place instead of copying every pool leaf per dispatch —
    the caller must ``sync_pool_from_cache`` the returned cache (the
    old leaves are dead)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=1)
    def run(params, cache, suffix, tables, rs):
        logits, vs = model.apply(
            {"params": params, "cache": cache}, suffix,
            train=False, decode=True, prefill=True, mutable=["cache"],
            pad_lens=jnp.zeros((1,), jnp.int32),
            block_tables=tables, row_starts=rs,
        )
        return logits[:, -1], vs["cache"]

    return run


@functools.lru_cache(maxsize=32)
def _paged_decode_fns(model, nb: int, temperature: float, top_k: int,
                      top_p: float):
    """Compiled batch-1 paged decode step per (model, sampling): one
    token feeds at its row-local position, its K/V appends into the
    row's private pool page through the block table, and attention
    reads the pool in place (the Pallas paged kernel on TPU). The
    cache is DONATED — without it XLA cannot alias the one-page
    append back to the input and every emitted token would copy the
    ENTIRE pool (orders of magnitude more HBM than the scatter arm
    this path replaces). The caller's step loop reassigns ``cache``
    each iteration and syncs the pool afterwards."""
    import jax

    from .generate import _sample_rows

    @functools.partial(jax.jit, donate_argnums=1)
    def step(params, cache, token, keys, tables, pos):
        logits, vs = model.apply(
            {"params": params, "cache": cache}, token[:, None],
            train=False, decode=True, mutable=["cache"],
            block_tables=tables, row_starts=pos,
        )
        nxt = _sample_rows(keys, logits[:, -1], temperature, top_k,
                           top_p)
        return nxt, vs["cache"]

    return step


@functools.lru_cache(maxsize=4)
def _import_scatter_fn():
    """Compiled page-import scatter: write ``n`` shipped blocks of
    content into the (donated) pool at ``ids``. One dispatch for every
    leaf; donation lets XLA alias the update in place instead of
    copying the whole pool per import. Under TP the donated input's
    head sharding carries through to the output — block ids stay
    replicated host metadata, exactly like every other pool write."""
    import jax

    @functools.partial(jax.jit, donate_argnums=0)
    def imp(pool, ids, content):
        return {ps: pool[ps].at[ids].set(
            content[ps].astype(pool[ps].dtype)) for ps in pool}

    return imp


def serialize_pages(payload: dict) -> bytes:
    """Page payload (``PrefixCache.export_pages``) -> self-contained
    bytes: magic + 4-byte header length + header JSON + concatenated
    raw leaf bytes (header order). The host-staged arm of page
    shipping — what crosses the wire between a prefill-role and a
    decode-role replica when they share no mesh (the CPU/CI arm)."""
    leaves = payload["leaves"]
    header = {
        "version": int(payload.get("version", 1)),
        "block_tokens": int(payload["block_tokens"]),
        "n_blocks": int(payload["n_blocks"]),
        "token_ids": [int(t) for t in payload["token_ids"]],
        "tp_geometry": dict(payload.get("tp_geometry") or {}),
        "leaves": [],
    }
    blobs = []
    nb = int(payload["n_blocks"])
    for ps in sorted(leaves):
        # trim export padding host-side (export gathers power-of-two
        # chains so device shapes never depend on the block count):
        # only real pages cross the wire
        arr = np.ascontiguousarray(np.asarray(leaves[ps])[:nb])
        header["leaves"].append({"path": ps,
                                 "shape": list(arr.shape),
                                 "dtype": str(arr.dtype)})
        blobs.append(arr.tobytes())
    hj = json.dumps(header).encode("utf-8")
    return PAGE_MAGIC + struct.pack(">I", len(hj)) + hj + b"".join(blobs)


def deserialize_pages(data: bytes) -> dict:
    """Inverse of :func:`serialize_pages`; raises ``ValueError`` on a
    foreign/torn payload (the receiving server maps it to HTTP 400)."""
    if not data.startswith(PAGE_MAGIC):
        raise ValueError("not a serialized page payload (bad magic)")
    off = len(PAGE_MAGIC)
    if len(data) < off + 4:
        raise ValueError("truncated page payload (no header length)")
    (hlen,) = struct.unpack(">I", data[off:off + 4])
    off += 4
    try:
        header = json.loads(data[off:off + hlen].decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise ValueError(f"bad page payload header: {e}")
    off += hlen
    leaves = {}
    for spec in header.get("leaves", ()):
        shape = tuple(int(d) for d in spec["shape"])
        dtype = np.dtype(spec["dtype"])
        n = int(np.prod(shape)) * dtype.itemsize
        if off + n > len(data):
            raise ValueError("truncated page payload (leaf bytes)")
        leaves[spec["path"]] = np.frombuffer(
            data[off:off + n], dtype=dtype).reshape(shape)
        off += n
    return {
        "version": int(header.get("version", 1)),
        "block_tokens": int(header["block_tokens"]),
        "n_blocks": int(header["n_blocks"]),
        "token_ids": [int(t) for t in header["token_ids"]],
        "tp_geometry": dict(header.get("tp_geometry") or {}),
        "leaves": leaves,
    }


def ship_pages(src: "PrefixCache", dst: "PrefixCache", ids) -> dict:
    """Move the cached block chain for ``ids`` from one pool to
    another in-process — the device-to-device arm of page shipping.
    When both pools live on the SAME mesh (or both are single-chip on
    one process) the gathered pages stay device arrays end to end and
    the copy rides the interconnect (ICI on real hardware); pools on
    different meshes host-stage, byte-identical to the serialized
    cross-process arm. Returns the import receipt (see
    :meth:`PrefixCache.import_pages`)."""
    device = src.mesh is dst.mesh
    payload = src.export_pages(ids, device=device)
    if payload is None:
        return {"imported_blocks": 0, "cached_tokens": 0, "bytes": 0}
    return dst.import_pages(payload)


def page_origin_flags(nodes) -> dict:
    """Collapse the ``origin`` tags of the radix nodes a request
    consumed into path-fingerprint flags (ISSUE 18). Locally captured
    nodes ("capture") are the baseline warm case and add no flag; the
    pool EVENTS that put content here some other way — a zero-copy
    adoption, a tier promote, a peer pull, a shipped import — each
    set their flag so the serve-path fingerprint names them."""
    flags: dict = {}
    for n in nodes or ():
        o = n.get("origin")
        if o in ("adopt", "promote", "pull", "ship"):
            flags[o] = True
    return flags


class SpillTier:
    """Bounded demote-on-evict store under the device pool (ISSUE 13).

    One entry per evicted pool block, keyed by the FULL token prefix
    up to and including that block (the same key the radix would
    match), holding the block's raw leaf bytes + a sha256 recorded at
    demote time. Two levels: a host-RAM dict bounded at
    ``host_blocks`` entries, whose own LRU overflow demotes further to
    a disk directory (bounded at ``disk_blocks`` files) when one is
    configured, else drops (the classic destroy). EVERY read verifies
    the checksum before the bytes go anywhere near the device pool —
    a failed verification removes the entry and reads as a miss, so a
    corrupt or torn spilled page costs a cold recompute, never a
    wrong token.

    The tier is an optimization with a fault plan: ``tier_exhaust``
    makes :meth:`put` refuse for a window (destroy-on-evict fallback),
    ``corrupt_spill`` flips a byte of the most recent demote AFTER
    checksumming, ``slow_spill`` stalls tier operations — all owned by
    the caller (PrefixCache) via ``faults.on_tier_event``.

    Thread-safety: one internal lock; entries are immutable after put.
    """

    def __init__(self, host_blocks: int = 0, disk_dir=None,
                 disk_blocks: int = 0):
        import threading as _threading

        self.host_blocks = max(int(host_blocks), 0)
        self.disk_dir = str(disk_dir) if disk_dir else None
        self.disk_blocks = max(int(disk_blocks), 0) if self.disk_dir \
            else 0
        if self.disk_dir:
            os.makedirs(self.disk_dir, exist_ok=True)
        self._host: "dict" = {}       # key -> entry (insertion = LRU)
        self._disk: "dict" = {}       # key -> {"path", "sha", "nbytes"}
        self._seq = 0
        self._lock = _threading.Lock()
        #: tier_exhaust fault window: until this instant put() refuses
        self.full_until = 0.0

    @property
    def enabled(self) -> bool:
        return self.host_blocks > 0 or self.disk_blocks > 0

    @staticmethod
    def digest(leaves: dict) -> str:
        """sha256 over the concatenated leaf bytes in sorted-path
        order — the ONE checksum formula (demote and verify share it)."""
        h = hashlib.sha256()
        for ps in sorted(leaves):
            h.update(leaves[ps])
        return h.hexdigest()

    def occupancy(self) -> dict:
        with self._lock:
            host_bytes = sum(e["nbytes"] for e in self._host.values())
            disk_bytes = sum(e["nbytes"] for e in self._disk.values())
            return {"tier_host_blocks": len(self._host),
                    "tier_host_bytes": int(host_bytes),
                    "tier_disk_blocks": len(self._disk),
                    "tier_disk_bytes": int(disk_bytes)}

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._host or key in self._disk

    def put(self, key, leaves: dict, sha: str) -> str | None:
        """Store one demoted block's bytes. Returns the tier it landed
        in (``"host"``) or None (tier full/faulted — the caller counts
        a destroy-on-evict). Host overflow demotes the LRU host entry
        to disk (when configured) or drops it."""
        import time as _time

        if not self.enabled or _time.monotonic() < self.full_until:
            return None
        nbytes = sum(len(b) for b in leaves.values())
        with self._lock:
            self._host.pop(key, None)       # re-demote refreshes LRU
            self._disk.pop(key, None)
            self._host[key] = {"leaves": dict(leaves), "sha": sha,
                               "nbytes": int(nbytes)}
            while len(self._host) > self.host_blocks:
                old_key = next(iter(self._host))
                entry = self._host.pop(old_key)
                self._spill_to_disk_locked(old_key, entry)
        return "host"

    def _spill_to_disk_locked(self, key, entry) -> None:
        """Move one host entry to the disk tier (caller holds the
        lock); no disk tier (or a write failure) drops it — degrade,
        never raise into the eviction path."""
        if not self.disk_blocks:
            return
        self._seq += 1
        path = os.path.join(self.disk_dir,
                            f"{entry['sha'][:12]}-{self._seq}.kvblk")
        try:
            with open(path, "wb") as f:
                for ps in sorted(entry["leaves"]):
                    blob = entry["leaves"][ps]
                    f.write(struct.pack(">I", len(ps)))
                    f.write(ps.encode("utf-8"))
                    f.write(struct.pack(">Q", len(blob)))
                    f.write(blob)
        except OSError:
            return
        self._disk[key] = {"path": path, "sha": entry["sha"],
                           "nbytes": entry["nbytes"]}
        while len(self._disk) > self.disk_blocks:
            old = self._disk.pop(next(iter(self._disk)))
            try:
                os.unlink(old["path"])
            except OSError:
                pass

    @staticmethod
    def _read_disk(path) -> dict:
        leaves = {}
        with open(path, "rb") as f:
            while True:
                head = f.read(4)
                if not head:
                    break
                (n,) = struct.unpack(">I", head)
                ps = f.read(n).decode("utf-8")
                (m,) = struct.unpack(">Q", f.read(8))
                leaves[ps] = f.read(m)
        return leaves

    def get(self, key):
        """Checksum-verified read -> ``(leaves_bytes, "verified")`` or
        ``(None, "miss"|"corrupt")``. A corrupt entry is REMOVED (the
        caller recomputes cold and the tier never serves it again)."""
        with self._lock:
            entry = self._host.get(key)
            disk = None if entry is not None else self._disk.get(key)
        if entry is not None:
            leaves = entry["leaves"]
            sha = entry["sha"]
        elif disk is not None:
            try:
                leaves = self._read_disk(disk["path"])
            except Exception:  # noqa: BLE001 — a torn/bit-rotted file
                # can raise ANYTHING out of the length-prefixed parse
                # (UnicodeDecodeError from the path string, struct
                # errors, OSError...); every parse failure IS the
                # corruption the checksum contract covers — degrade to
                # "corrupt" (cold recompute), never raise into serving
                leaves = {}
            sha = disk["sha"]
        else:
            return None, "miss"
        if not leaves or self.digest(leaves) != sha:
            self.drop(key)
            return None, "corrupt"
        # touch for LRU (host entries only; move-to-end via re-insert)
        with self._lock:
            if key in self._host:
                self._host[key] = self._host.pop(key)
        return leaves, "verified"

    def drop(self, key) -> None:
        with self._lock:
            self._host.pop(key, None)
            disk = self._disk.pop(key, None)
        if disk is not None:
            try:
                os.unlink(disk["path"])
            except OSError:
                pass

    def corrupt_latest(self) -> bool:
        """The ``corrupt_spill`` fault's effect: flip one byte of the
        most recently demoted HOST entry (after its checksum was
        recorded, so the next read fails verification). Returns
        whether an entry was corrupted."""
        with self._lock:
            if not self._host:
                return False
            key = next(reversed(self._host))
            entry = self._host[key]
            ps = sorted(entry["leaves"])[0]
            blob = bytearray(entry["leaves"][ps])
            if not blob:
                return False
            blob[0] ^= 0xFF
            entry["leaves"][ps] = bytes(blob)
            return True


class PoolUnsupported(ValueError):
    """A KV layout the pool cannot serve (ISSUE 15 satellite): carries
    the machine-readable ``reason`` (``window`` / ``kv_quant`` /
    ``undersized`` / ``gpt2_layout``) that feeds the
    ``pool_fallback_total{reason=...}`` counters on /metrics — today
    the refusal string went to logs only and fleet-level fallback was
    invisible."""

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason


class RadixIndex:
    """Block-granular radix/trie over prompt token ids.

    One edge per full ``block_tokens``-id chunk; each node owns exactly
    one pool block. Matching walks whole blocks (divergence mid-block
    shares nothing for that block). Nodes carry a refcount — held while
    an admission's copy kernel may still read the block — and an LRU
    clock; eviction only ever takes an UNREFERENCED LEAF (children pin
    their ancestors by construction of the walk)."""

    def __init__(self, block_tokens: int):
        self.block = int(block_tokens)
        self.root = {"children": {}, "block": None, "parent": None,
                     "refs": 0, "last_use": 0}
        self._clock = 0
        self.nodes = 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _chunks(self, ids):
        ids = list(ids)
        n = len(ids) // self.block
        return [tuple(ids[i * self.block:(i + 1) * self.block])
                for i in range(n)]

    def match(self, ids):
        """Longest fully-blocked cached prefix of ``ids`` ->
        ``(nodes, block_ids)`` (refs NOT acquired — see ``acquire``)."""
        now = self._tick()
        node, nodes, blocks = self.root, [], []
        for chunk in self._chunks(ids):
            nxt = node["children"].get(chunk)
            if nxt is None:
                break
            nxt["last_use"] = now
            nodes.append(nxt)
            blocks.append(nxt["block"])
            node = nxt
        return nodes, blocks

    def acquire(self, nodes):
        for n in nodes:
            n["refs"] += 1

    def release(self, nodes):
        for n in nodes:
            n["refs"] -= 1
            assert n["refs"] >= 0, "radix refcount underflow"

    def insert(self, ids, alloc):
        """Create nodes for every full block of ``ids`` not yet present.
        ``alloc()`` returns a free block id or None (pool exhausted —
        insertion stops there; the present prefix stays useful).
        Returns ``(new_nodes, new_block_ids, start_block_index)``.

        The walked path (existing AND just-created nodes) is PINNED
        for the duration: ``alloc`` may LRU-evict, and evicting the
        very chain being extended would detach the node the next new
        child links under — an unreachable subtree whose blocks leak
        forever."""
        now = self._tick()
        node = self.root
        pinned = []
        new_nodes, new_blocks, start = [], [], None
        try:
            for i, chunk in enumerate(self._chunks(ids)):
                nxt = node["children"].get(chunk)
                if nxt is None:
                    bid = alloc()
                    if bid is None:
                        break
                    # origin feeds per-request path provenance (ISSUE
                    # 18): capture = the scatter arm's capture kernel
                    # wrote this page from a live cache row
                    nxt = {"children": {}, "block": bid, "parent": node,
                           "chunk": chunk, "refs": 0, "last_use": now,
                           "origin": "capture"}
                    node["children"][chunk] = nxt
                    self.nodes += 1
                    new_nodes.append(nxt)
                    new_blocks.append(bid)
                    if start is None:
                        start = i
                nxt["refs"] += 1
                pinned.append(nxt)
                nxt["last_use"] = now
                node = nxt
        finally:
            for n in pinned:
                n["refs"] -= 1
        return new_nodes, new_blocks, (0 if start is None else start)

    def evict_lru(self):
        """Detach the least-recently-used unreferenced LEAF node and
        return its block id (None when everything is pinned)."""
        evicted = self.evict_lru_path()
        return None if evicted is None else evicted[0]

    def evict_lru_path(self):
        """Like :meth:`evict_lru`, but returns ``(block_id,
        token_path)`` where ``token_path`` is the full id prefix up to
        and including the evicted block — the demote tier's key (the
        chunks up the parent chain reconstruct it; the walk is
        O(depth), paid only on eviction)."""
        best, best_key = None, None
        stack = [self.root]
        while stack:
            node = stack.pop()
            for child in node["children"].values():
                if not child["children"]:
                    if child["refs"] == 0 and (
                            best is None
                            or child["last_use"] < best_key):
                        best, best_key = child, child["last_use"]
                else:
                    stack.append(child)
        if best is None:
            return None
        chunks = []
        node = best
        while node is not None and node is not self.root:
            chunks.append(node["chunk"])
            node = node["parent"]
        path = tuple(i for chunk in reversed(chunks) for i in chunk)
        del best["parent"]["children"][best["chunk"]]
        best["parent"] = None
        self.nodes -= 1
        return best["block"], path


class PrefixCache:
    """The serving-path prefix cache: radix index + bounded device
    block pool + the compiled capture/extract kernels.

    Thread-safety: host bookkeeping (index/free list/stats) is guarded
    by a lock; device kernels are dispatched by the caller's scheduler
    thread, whose program order gives the read-before-overwrite
    guarantee the immediate ref release relies on.
    """

    def __init__(self, model, params, block_tokens: int = 32,
                 pool_blocks: int = 256, eviction: str = "lru",
                 paged: bool = True, host_spill_blocks: int = 0,
                 disk_spill_dir=None, disk_spill_blocks: int = 0,
                 ring_slack_tokens: int = 512):
        import jax
        import jax.numpy as jnp

        spec = getattr(model, "kv_cache_spec", None)
        if spec is None:
            raise PoolUnsupported(
                "gpt2_layout",
                f"{type(model).__name__} declares no kv_cache_spec(): "
                "prefix caching needs the decode-cache layout contract")
        spec = spec()
        if spec.get("kv_quant") not in ("", None, "int8"):
            raise PoolUnsupported(
                "kv_quant",
                f"unknown kv_quant {spec['kv_quant']!r} (the int8-KV "
                "pool layout is the only quantized layout)")
        if eviction != "lru":
            raise ValueError(f"unknown eviction policy {eviction!r} "
                             "(only 'lru')")
        if int(block_tokens) < 1 or int(pool_blocks) < 2:
            raise ValueError("need block_tokens >= 1 and pool_blocks "
                             ">= 2 (block 0 is reserved scratch)")
        self.model = model
        self.block = int(block_tokens)
        self.pool_blocks = int(pool_blocks)
        self.rotary = bool(spec.get("rotary"))
        self.rope_base = float(spec.get("rope_base") or 0.0)
        self.kv_quant = str(spec.get("kv_quant") or "")
        # sliding-window ring layout (ISSUE 15): the pool can serve
        # window models ONLY through the paged path (the scatter
        # fallback's contiguous rolling cache has position-dependent
        # eviction order — the original refusal, now scoped to that
        # arm alone). Ring geometry lives here; the model's paged
        # attention consumes it as `j % nb` table semantics.
        self.window = int(spec.get("window", 0) or 0)
        self.ring_slack_tokens = 0
        if self.window:
            if not (bool(paged) and spec.get("paged", False)):
                raise PoolUnsupported(
                    "window",
                    f"window={self.window} needs the paged pool layout "
                    "(the scatter fallback's rolling cache is "
                    "position-dependent)")
            if self.window % self.block or self.window < self.block:
                raise PoolUnsupported(
                    "window",
                    f"window={self.window} must be a positive multiple "
                    f"of block_tokens={self.block} for the ring layout")
            # slack: the largest single prefill FEED the ring tolerates
            # without a dispatch's writes clobbering its own queries'
            # band (power-of-two so bucketed feeds stay inside it)
            slack = 16
            while slack < min(int(ring_slack_tokens), self.window):
                slack *= 2
            self.ring_slack_tokens = slack
        # TP serving (ISSUE 10): pool pages shard on the KV-HEAD axis
        # over the model's serving mesh — each tensor shard owns its
        # KVH/tp slice of every page, while block ids / the radix index
        # stay replicated host metadata (a page id means the same thing
        # on every shard). kv_cache_spec's kv_heads must divide tp —
        # validated up front at load (parallel/tp.validate_tp_geometry)
        # and defensively here.
        from ..parallel.tp import tp_degree

        self.mesh = getattr(model, "mesh", None)
        self._tp = tp_degree(self.mesh)
        if self._tp > 1:
            kv_heads = int(spec.get("kv_heads", 0) or 0)
            if kv_heads and kv_heads % self._tp:
                raise ValueError(
                    f"kv_heads={kv_heads} not divisible by the serving "
                    f"mesh's tensor axis ({self._tp}): the pool cannot "
                    "shard on the head axis")
        # device pool: one [P, block, H, D] leaf per poolable cache leaf,
        # discovered from a [1, block] eval_shape trace (no device work)
        shapes = jax.eval_shape(
            lambda p: model.apply(
                {"params": p}, jnp.zeros((1, self.block), jnp.int32),
                train=False, decode=True, mutable=["cache"],
            ),
            params,
        )[1]["cache"]
        flat = jax.tree_util.tree_flatten_with_path(dict(shapes))[0]
        self.pool = {}
        for path, leaf in flat:
            ps = _path_str(path)
            if _leaf_kind(ps, leaf) is not None:
                self.pool[ps] = self._alloc_pool_leaf(
                    (self.pool_blocks,) + tuple(leaf.shape[1:]),
                    leaf.dtype)
        if not self.pool:
            raise ValueError(
                f"{type(model).__name__} exposes no poolable KV leaves")
        import inspect

        self._padded = "pad_lens" in inspect.signature(
            type(model).__call__).parameters
        self.index = RadixIndex(self.block)
        self._free = list(range(1, self.pool_blocks))  # 0 = scratch
        # block ids allocated to live requests but NOT (yet) owned by
        # the radix index — paged-mode private tail pages (prompt
        # suffixes being written + decode appends). Disjoint from the
        # index's blocks by construction; freed or adopted at request
        # completion.
        self._private: set = set()
        self._lock = threading.Lock()
        self.stats = {
            "prefix_lookups": 0, "prefix_hit_requests": 0,
            "prefix_hit_tokens": 0, "prefix_inserted_blocks": 0,
            "prefix_evictions": 0, "prefix_dropped_inserts": 0,
            # device bytes copied by WARM admits: the scatter fallback
            # pays one block-chain HBM copy per admit; the paged path
            # must keep this at 0 (the ISSUE 7 gate is observable, not
            # aspirational)
            "warm_admit_copy_bytes": 0,
            # zero-copy insertions: pages written in place by a request
            # and handed to the radix index without any device work
            "prefix_adopted_blocks": 0,
            # batch-1 arm counts: which path actually served each
            # request (serve.py derives an honest paged_decode_frac
            # from these on the plain scheduler — the pool being
            # paged-CAPABLE says nothing about what traffic got)
            "batch1_paged_requests": 0,
            "batch1_scatter_requests": 0,
            # page shipping (disaggregated serving, ISSUE 12): blocks
            # exported to / imported from another replica's pool, plus
            # the raw page bytes that crossed. Imports ALSO count into
            # warm_admit_copy_bytes — a shipped page is a genuine
            # device copy the decode replica paid (the paged admit that
            # later reads it stays a zero-copy pointer update), so on a
            # decode-role replica warm_admit_copy_bytes_total equals
            # exactly the page-transfer bytes (accounted like PR 10's
            # collectives: observable, asserted in tests/test_disagg.py).
            "pages_exported": 0,
            "pages_imported": 0,
            "page_ship_out_bytes": 0,
            "page_ship_in_bytes": 0,
            "page_ship_dropped": 0,
            # tiered spill hierarchy (ISSUE 13): demote-on-evict /
            # promote-on-hit traffic, checksum verdicts, and the
            # degradation counters (a full or faulted tier falls back
            # to destroy-on-evict; a demote that cannot read its block
            # — donation loss mid-flight — likewise)
            "tier_demoted_blocks": 0,
            "tier_demote_bytes": 0,
            "tier_promoted_blocks": 0,
            "tier_promote_bytes": 0,
            "tier_checksum_failures": 0,
            "tier_exhaust_drops": 0,
            "tier_demote_errors": 0,
            # pool-fallback observability (ISSUE 15 satellite): WHY a
            # request degraded to the scatter/no-pool arm, counted per
            # request — /metrics renders these as
            # pool_fallback_total{reason=...}
            "pool_fallback_window": 0,
            "pool_fallback_kv_quant": 0,
            "pool_fallback_undersized": 0,
            "pool_fallback_gpt2_layout": 0,
            "pool_fallback_dry_pool": 0,
        }
        # corrupt_page fault (ISSUE 18): block id marked for a
        # deferred constant-pattern overwrite; applied at the next
        # safe pool-donation point
        self._corrupt_block = None
        # path provenance (ISSUE 18): origin flags of the nodes the
        # most recent warm_prefill consumed (scatter arm only)
        self.last_warm_flags: dict = {}
        # demote-on-evict spill tier (ISSUE 13): None keeps the
        # classic destroy-on-evict byte-identical
        self.spill = None
        if int(host_spill_blocks) > 0 or (
                disk_spill_dir and int(disk_spill_blocks) > 0):
            self.spill = SpillTier(
                host_blocks=int(host_spill_blocks),
                disk_dir=disk_spill_dir,
                disk_blocks=int(disk_spill_blocks))
        self.nb_max = -(-int(model.max_len) // self.block)
        if self.window:
            # ring table width: the in-band pages + 1 (band/tile
            # misalignment) + slack pages so a bounded prefill feed
            # never recycles a slot its own queries still read
            nb_ring = (self.window // self.block + 1
                       + self.ring_slack_tokens // self.block)
            self.nb_max = min(self.nb_max, nb_ring)
        # bytes of ONE pool block across every leaf — the unit of the
        # copy-bytes accounting above (int8 layouts: the quantized
        # bytes + their scale leaves — ~0.53x the f32 page, which is
        # exactly the wire/tier/HBM saving the layout exists for)
        self.page_bytes = int(sum(
            int(np.prod(leaf.shape[1:])) * leaf.dtype.itemsize
            for leaf in self.pool.values()))
        # TRUE paged decode (ISSUE 7): the engines read pool pages in
        # place through per-row block tables — needs the model's paged
        # call path AND a pool that can hold at least one full-budget
        # request's chain; otherwise the scatter fallback serves.
        # fallback_reason is the STRUCTURAL reason requests will take
        # the scatter arm ("" = fully paged) — per-request fallbacks
        # count it into pool_fallback_* (ISSUE 15 satellite).
        self.paged = bool(paged) and bool(spec.get("paged", False))
        self.fallback_reason = ""
        if not spec.get("paged", False):
            self.fallback_reason = "gpt2_layout"
            if bool(paged):
                logger.warning(
                    "paged decode unavailable for %s (kv_cache_spec "
                    "paged=False): warm admits use the scatter "
                    "fallback", type(model).__name__)
        if self.paged and self.pool_blocks - 1 < self.nb_max:
            if self.window:
                # no scatter arm exists for a window model — refuse
                # loudly instead of degrading to a layout that cannot
                # serve
                raise PoolUnsupported(
                    "undersized",
                    f"prefix_cache.pool_blocks={self.pool_blocks} "
                    f"cannot hold one ring request ({self.nb_max} "
                    f"blocks for window={self.window} + slack at "
                    f"block_tokens={self.block})")
            logger.warning(
                "prefix_cache.pool_blocks=%d cannot hold one full-"
                "budget request (%d blocks for max_len=%d at "
                "block_tokens=%d): paged decode disabled, scatter "
                "fallback serves", self.pool_blocks, self.nb_max,
                int(model.max_len), self.block)
            self.paged = False
            self.fallback_reason = "undersized"

    def _alloc_pool_leaf(self, shape, dtype):
        """One zeroed pool leaf, COMMITTED to the serving mesh's head
        sharding when TP is on (so warmup and dispatch signatures
        match); plain uncommitted zeros at tp=1 — byte-identical to the
        pre-TP path."""
        import jax
        import jax.numpy as jnp

        if self._tp <= 1:
            return jnp.zeros(shape, dtype)
        from jax.sharding import NamedSharding

        from ..parallel.tp import kv_pool_pspec

        return jax.device_put(
            jnp.zeros(shape, dtype),
            NamedSharding(self.mesh, kv_pool_pspec(len(shape))))

    # ---- host bookkeeping -------------------------------------------------

    def used_blocks(self) -> int:
        return self.pool_blocks - 1 - len(self._free)

    def _alloc(self):
        """One free block id, evicting the LRU unreferenced leaf when
        the free list is empty; None when everything is pinned. With a
        spill tier attached the evicted block DEMOTES (its bytes +
        checksum move to the tier) instead of being destroyed — the
        read happens synchronously here, before the returned id can be
        overwritten by the caller's (later, async) capture/scatter."""
        if self._free:
            return self._free.pop()
        if self.spill is None:
            bid = self.index.evict_lru()
            if bid is None:
                self.stats["prefix_dropped_inserts"] += 1
                return None
            self.stats["prefix_evictions"] += 1
            return bid
        evicted = self.index.evict_lru_path()
        if evicted is None:
            self.stats["prefix_dropped_inserts"] += 1
            return None
        bid, path = evicted
        self.stats["prefix_evictions"] += 1
        self._demote_block(bid, path)
        return bid

    def _demote_block(self, bid: int, path) -> None:
        """Move one evicted block's content into the spill tier
        (caller holds the lock; ``path`` is the full token prefix up
        to and including the block — the tier key a later promotion
        matches). Every failure mode degrades to the classic
        destroy-on-evict, counted, never raised: the eviction path
        must stay infallible."""
        from ..resilience import faults

        fired = faults.on_tier_event()
        if fired is not None and fired.get("exhaust") is not None:
            self.spill.full_until = (
                time.monotonic() + fired["exhaust"].duration_s)
            logger.warning("fault tier_exhaust: spill tier reads full "
                           "for %.2fs", fired["exhaust"].duration_s)
        try:
            # one D2H gather per leaf: the demote cost (a host sync on
            # the eviction path — bounded at one block, and only under
            # pool pressure; the promote direction is async like every
            # other pool write)
            leaves = {ps: np.asarray(leaf[bid]).tobytes()
                      for ps, leaf in self.pool.items()}
        except Exception:  # noqa: BLE001 — donated/dead leaf mid-error
            self.stats["tier_demote_errors"] += 1
            return
        sha = SpillTier.digest(leaves)
        landed = self.spill.put(path, leaves, sha)
        if landed is None:
            self.stats["tier_exhaust_drops"] += 1
            return
        self.stats["tier_demoted_blocks"] += 1
        self.stats["tier_demote_bytes"] += sum(
            len(b) for b in leaves.values())
        if fired is not None and fired.get("corrupt") is not None:
            if self.spill.corrupt_latest():
                logger.warning("fault corrupt_spill: flipped a byte of "
                               "the just-demoted spill entry")

    def promote_spilled(self, ids) -> int:
        """Extend the device radix with spilled blocks for ``ids``
        (the promote half of the tier hierarchy): walk the spill tier
        past the deepest resident block, checksum-verify each entry,
        land the verified chain as private pages through the same
        donating scatter as a page import, then adopt — a request
        admitted mid-promotion either misses (cold, correct) or hits
        fully-written pages. Returns blocks promoted (0 = nothing
        spilled, tier disabled, or pool too dry to land them).

        DONATES the pool on a nonzero promotion — callers follow the
        import_pages contract (the continuous engine promotes at tick
        start, before ``refresh_cache_from_pool``; batch-1 paths
        promote inside ``lookup`` before they read ``self.pool``).
        A checksum failure counts ``tier_checksum_failures``, drops
        the entry, and stops the walk: everything past it recomputes
        cold — the tier never serves an unverified byte."""
        import jax.numpy as jnp

        from ..resilience import faults

        if self.spill is None:
            return 0
        ids = [int(t) for t in ids]
        nfull = len(ids) // self.block
        with self._lock:
            _, have = self.index.match(ids)
        start = len(have)
        if start >= nfull:
            return 0
        # probe the tier BEFORE paying a fault hook / allocation: the
        # common case (nothing spilled for this prompt) must stay a
        # dict lookup
        probe = tuple(ids[:(start + 1) * self.block])
        if probe not in self.spill:
            return 0
        # slow_spill covers promotes too; a corrupt_spill/tier_exhaust
        # landing on a promote ordinal applies all the same (the most
        # recent demote corrupts / the put window closes) — the evt
        # ordinal counts every tier operation, so a fired spec must
        # never be silently swallowed
        fired = faults.on_tier_event()
        if fired is not None:
            if fired.get("exhaust") is not None:
                self.spill.full_until = (
                    time.monotonic() + fired["exhaust"].duration_s)
            if fired.get("corrupt") is not None:
                self.spill.corrupt_latest()
        chain = []                  # [(block_index, {ps: np_array})]
        for i in range(start, nfull):
            key = tuple(ids[:(i + 1) * self.block])
            blob, verdict = self.spill.get(key)
            if blob is None:
                if verdict == "corrupt":
                    with self._lock:
                        self.stats["tier_checksum_failures"] += 1
                    logger.warning(
                        "spill tier checksum failure at block %d: "
                        "entry dropped, recomputing cold", i)
                break
            content = {}
            ok = True
            for ps, leaf in self.pool.items():
                raw = blob.get(ps)
                shape = tuple(leaf.shape[1:])
                n = int(np.prod(shape)) * leaf.dtype.itemsize
                if raw is None or len(raw) != n:
                    ok = False      # geometry changed under the tier
                    break
                content[ps] = np.frombuffer(
                    raw, dtype=leaf.dtype).reshape(shape)
            if not ok:
                self.spill.drop(key)
                break
            chain.append((i, content))
        if not chain:
            return 0
        priv = self.alloc_chain(len(chain))
        if priv is None:
            return 0                # dry pool: promotion waits its turn
        # one donating scatter, padded to the power-of-two ladder like
        # the import path (a varying chain length must not mint fresh
        # executables on the admission path)
        cap = 1
        while cap < len(chain):
            cap *= 2
        ids_pad = np.full((cap,), SCRATCH_BLOCK, np.int32)
        ids_pad[:len(chain)] = priv
        stacked = {}
        for ps, leaf in self.pool.items():
            rows = np.zeros((cap,) + tuple(leaf.shape[1:]), leaf.dtype)
            for j, (_, content) in enumerate(chain):
                rows[j] = content[ps]
            stacked[ps] = jnp.asarray(rows)
        self.pool = _import_scatter_fn()(
            self.pool, jnp.asarray(ids_pad), stacked)
        owned = {i: bid for (i, _), bid in zip(chain, priv)}
        adopted, _ = self.adopt(ids[:nfull * self.block], owned,
                                origin="promote")
        taken = set(adopted)
        self.free_blocks([b for b in priv if b not in taken])
        # entries whose block actually ADOPTED leave the tier (their
        # content is resident again; a re-eviction re-demotes fresh
        # bytes) — entries the adopt walk never reached (a concurrent
        # eviction broke the resident prefix under us) KEEP their
        # spilled bytes, or the chain would be lost from both tiers
        for i, _ in chain:
            if owned[i] in taken:
                self.spill.drop(tuple(ids[:(i + 1) * self.block]))
        n = len(adopted)
        with self._lock:
            self.stats["tier_promoted_blocks"] += n
            self.stats["tier_promote_bytes"] += n * self.page_bytes
        return n

    def lookup(self, ids, record: bool = True, promote: bool = True):
        """Longest cached, fully-blocked, PROPER prefix of ``ids`` ->
        ``(nodes, block_ids, cached_tokens)``; refs acquired (callers
        MUST ``release(nodes)`` once the copy kernel is dispatched).
        Proper: the prompt's final token is never served from cache —
        its logits must be computed to sample the first output token —
        so ``cached_tokens <= len(ids) - 1``.

        ``record=False`` skips the hit/lookup counters: retries of the
        SAME request (a deferred paged admission re-reserves every
        tick) and routing probes must not inflate
        ``prefix_hit_tokens`` — that counter feeds /metrics, the fleet
        router, and the bench gates.

        ``promote=True`` (the batch-1 default) first promotes any
        spilled extension of the match back into the pool — which may
        DONATE the pool, so callers whose device state aliases it pass
        ``promote=False`` and promote at their own safe point (the
        continuous engine's tick start)."""
        if promote and self.spill is not None:
            self.promote_spilled(ids)
        with self._lock:
            if record:
                self.stats["prefix_lookups"] += 1
            nodes, blocks = self.index.match(ids)
            limit = (len(ids) - 1) // self.block     # proper-prefix cap
            nodes, blocks = nodes[:limit], blocks[:limit]
            c = len(nodes) * self.block
            if c:
                if record:
                    self.stats["prefix_hit_requests"] += 1
                    self.stats["prefix_hit_tokens"] += c
                self.index.acquire(nodes)
            return nodes, blocks, c

    def count_fallback(self, reason: str = "") -> None:
        """Count one request that degraded off the paged pool path
        (ISSUE 15 satellite): ``reason`` defaults to the pool's
        structural ``fallback_reason`` (gpt2_layout / undersized);
        transient dry-pool falls pass ``"dry_pool"``. An empty reason
        (operator turned paged off deliberately) is not counted — a
        choice is not a degradation."""
        reason = reason or self.fallback_reason
        if not reason:
            return
        key = f"pool_fallback_{reason}"
        with self._lock:
            if key in self.stats:
                self.stats[key] += 1

    def count_batch1(self, paged: bool) -> None:
        """Tally which arm served one batch-1 request (paged in-place
        vs scatter fallback) — the plain scheduler's honest
        ``paged_decode_frac`` numerator/denominator."""
        key = ("batch1_paged_requests" if paged
               else "batch1_scatter_requests")
        with self._lock:
            self.stats[key] += 1

    def counter(self, name: str) -> int:
        """One stats counter, cheaply. The engines diff these around
        admissions/completions to attribute pool events (evictions,
        zero-copy adoptions) to the request that triggered them in the
        request-scoped trace (ISSUE 8) — a full ``stats_snapshot()``
        per admit would rebuild the whole dict for one integer."""
        with self._lock:
            return int(self.stats.get(name, 0))

    def release(self, nodes):
        with self._lock:
            self.index.release(nodes)

    def plan_insert(self, ids):
        """Allocate blocks + index nodes for the full blocks of ``ids``
        not yet cached. Returns ``(block_ids, start_block)`` for the
        capture kernel (empty when nothing is new)."""
        with self._lock:
            _, blocks, start = self.index.insert(ids, self._alloc)
            self.stats["prefix_inserted_blocks"] += len(blocks)
            return blocks, start

    # ---- paged-mode chains (ISSUE 7) --------------------------------------

    def alloc_chain(self, n: int):
        """Allocate ``n`` PRIVATE blocks for a request's uncached tail
        (prompt suffix + decode budget), LRU-evicting unreferenced
        radix leaves under pressure. All-or-nothing: on a dry pool the
        partial allocation rolls back and ``None`` returns — the caller
        defers the admission until completions free pages."""
        with self._lock:
            got = []
            for _ in range(int(n)):
                bid = self._alloc()
                if bid is None:
                    self._free.extend(got)
                    return None
                got.append(bid)
            self._private.update(got)
            return got

    def free_blocks(self, ids) -> None:
        """Return private blocks to the free list (request completed or
        admission rolled back)."""
        if not ids:
            return
        with self._lock:
            for bid in ids:
                self._private.discard(bid)
            self._free.extend(ids)

    def adopt(self, token_ids, owned: dict, acquire: bool = False,
              origin: str = "adopt"):
        """ZERO-COPY radix insert: hand privately-written pool pages to
        the index so other requests share them — no capture kernel, no
        device work; the K/V is already canonical in place (ISSUE 7:
        "decoded tokens append into pool blocks the radix index can
        immediately share").

        ``owned`` maps full-block INDEX of ``token_ids`` -> private
        block id holding that block's K/V. The walk creates missing
        nodes where we own the page and stops at a missing node we
        cannot supply; where a node already exists (a concurrent
        request adopted the same content first) the private duplicate
        stays private — the caller frees it after completion.

        ``origin`` tags the created nodes for per-request path
        provenance (ISSUE 18): ``adopt`` (a local request's zero-copy
        pages), ``ship`` (a disaggregated prefill→decode import),
        ``pull`` (a peer-pool pull), ``promote`` (a spill-tier
        promotion). A later admission consuming the page surfaces the
        tag in its serve-path fingerprint.

        Returns ``(adopted_ids, nodes)``: the block ids now owned by
        the index (no longer private) and, when ``acquire``, the
        CREATED nodes ref-pinned for the (still-reading) caller to
        release at completion (pre-existing duplicates need no pin —
        the caller keeps reading its own private copy)."""
        from ..resilience import faults

        bt = self.block
        nfull = len(token_ids) // bt
        with self._lock:
            node = self.index.root
            adopted, nodes = [], []
            now = self.index._tick()
            for i in range(nfull):
                chunk = tuple(token_ids[i * bt:(i + 1) * bt])
                nxt = node["children"].get(chunk)
                if nxt is None:
                    bid = owned.get(i)
                    if bid is None:
                        break
                    nxt = {"children": {}, "block": int(bid),
                           "parent": node, "chunk": chunk,
                           "refs": 0, "last_use": now,
                           "origin": str(origin)}
                    node["children"][chunk] = nxt
                    self.index.nodes += 1
                    self._private.discard(int(bid))
                    adopted.append(int(bid))
                    if acquire:
                        nxt["refs"] += 1
                        nodes.append(nxt)
                nxt["last_use"] = now
                node = nxt
            self.stats["prefix_adopted_blocks"] += len(adopted)
            if adopted:
                # corrupt_page fault (ISSUE 18): mark the first block
                # this adoption landed; the overwrite itself is
                # DEFERRED to the pool's next safe device point
                # (_apply_pending_corruption) — corrupting here would
                # donate the pool out from under a live engine cache
                # mid-tick
                spec = faults.on_page_adopt()
                if spec is not None:
                    self._corrupt_block = int(adopted[0])
            return adopted, nodes

    def record_copy_bytes(self, n_blocks: int) -> None:
        """Account one warm admit's device scatter copy (the fallback
        arm): ``n_blocks`` pool blocks crossed HBM into a contiguous
        per-slot cache."""
        if n_blocks:
            with self._lock:
                self.stats["warm_admit_copy_bytes"] += (
                    int(n_blocks) * self.page_bytes)

    # ---- page shipping (disaggregated serving, ISSUE 12) -----------------

    def cached_block_count(self, ids) -> int:
        """Full blocks of ``ids`` the pool currently holds (NO refs, no
        proper-prefix cap — export ships every full block, and the
        receiving side's own admission lookup re-applies the cap)."""
        with self._lock:
            _, blocks = self.index.match(list(ids))
            return len(blocks)

    def export_pages(self, ids, device: bool = False):
        """Gather the cached full-block chain for ``ids`` out of the
        pool -> a ship payload (``None`` when not even one full block
        is pooled). ``device=True`` keeps the gathered pages as device
        arrays (the same-mesh ICI arm — :func:`ship_pages`); the
        default stages them to host numpy (the serialized arm).

        Refs are held across the gather so a concurrent insert cannot
        evict a block mid-export; the payload's ``token_ids`` cover
        exactly the exported blocks, so import adopts them under the
        same radix keys. ``tp_geometry`` records the exporter's shard
        layout for the receipt — page CONTENT is the logical
        ``[block, H, D]`` tensor either way (block ids and the radix
        are replicated host metadata under TP, PR 10), so a tp=2
        export imports into a tp=1 pool and vice versa."""
        import jax.numpy as jnp

        ids = list(ids)
        with self._lock:
            nodes, blocks = self.index.match(ids)
            if not blocks:
                return None
            self.index.acquire(nodes)
        try:
            nb = len(blocks)
            # pad the gather to the power-of-two ladder: chain lengths
            # are traffic-dependent, and an unpadded gather mints a
            # fresh executable per distinct count — a mid-traffic XLA
            # compile on the handoff path (the same stall class every
            # fixed-shape dispatch in this stack exists to kill).
            # Extra lanes read the scratch block and are sliced away.
            cap = 1
            while cap < nb:
                cap *= 2
            padded = np.full((cap,), SCRATCH_BLOCK, np.int32)
            padded[:nb] = blocks
            idx = jnp.asarray(padded)
            leaves = {}
            for ps, leaf in self.pool.items():
                # leaves stay PADDED [cap, block, H, D] — device
                # shapes must never depend on nb. serialize_pages
                # trims host-side; import_pages clamps to n_blocks.
                arr = leaf[idx]
                leaves[ps] = arr if device else np.asarray(arr)
        finally:
            self.release(nodes)
        with self._lock:
            self.stats["pages_exported"] += nb
            self.stats["page_ship_out_bytes"] += nb * self.page_bytes
        return {
            "version": 1,
            "block_tokens": self.block,
            "n_blocks": nb,
            "token_ids": ids[:nb * self.block],
            "tp_geometry": {"tp": self._tp},
            "leaves": leaves,
        }

    def import_pages(self, payload: dict, origin: str = "ship") -> dict:
        """Adopt a shipped page chain into THIS pool — the receiving
        half of the prefill→decode handoff. ``origin`` tags the
        adopted radix nodes for path provenance (ISSUE 18): "ship"
        for the disagg prefill→decode handoff, "pull" when the fleet
        poller dragged the chain here via peer pull. Blocks the pool
        already holds are skipped (a re-ship of a hot prefix costs
        nothing);
        the rest land as PRIVATE pages first (private pages are never
        evictable, so an in-flight import cannot lose a page to
        pressure), get their content written by one donating scatter
        dispatch, and only then adopt into the radix index — a request
        admitted mid-import either misses (cold prefill, correct) or
        hits fully-written pages, never a torn one.

        Returns ``{"imported_blocks", "cached_tokens", "bytes",
        "dropped"?}``; a pool that cannot supply the chain right now
        drops the import (the decode replica simply cold-prefills —
        shipping is an optimization, never a correctness dependency).
        Raises ``ValueError`` on a payload whose geometry cannot land
        here (block size / leaf shape mismatch)."""
        import jax.numpy as jnp

        if int(payload.get("block_tokens", 0)) != self.block:
            raise ValueError(
                f"page import: block_tokens "
                f"{payload.get('block_tokens')} != pool's {self.block}")
        leaves_in = payload.get("leaves") or {}
        for ps, leaf in self.pool.items():
            src = leaves_in.get(ps)
            if src is None:
                raise ValueError(f"page import: payload missing leaf "
                                 f"{ps!r}")
            if tuple(src.shape[1:]) != tuple(leaf.shape[1:]):
                raise ValueError(
                    f"page import: leaf {ps!r} shape "
                    f"{tuple(src.shape[1:])} != pool's "
                    f"{tuple(leaf.shape[1:])}")
        ids = [int(t) for t in payload["token_ids"]]
        nb = min(int(payload["n_blocks"]),
                 *(int(a.shape[0]) for a in leaves_in.values()))
        nb = min(nb, len(ids) // self.block)
        if nb <= 0:
            return {"imported_blocks": 0, "cached_tokens": 0,
                    "bytes": 0}
        with self._lock:
            _, have = self.index.match(ids)
            have_n = min(len(have), nb)
        need = list(range(have_n, nb))
        if not need:
            return {"imported_blocks": 0,
                    "cached_tokens": nb * self.block, "bytes": 0}
        priv = self.alloc_chain(len(need))
        if priv is None:
            with self._lock:
                self.stats["page_ship_dropped"] += 1
            return {"imported_blocks": 0, "cached_tokens": 0,
                    "bytes": 0, "dropped": True}
        # pad the scatter to the power-of-two ladder (mirror of the
        # export gather): extra lanes write the scratch block, so a
        # varying chain length never mints a fresh executable on the
        # handoff path
        cap = 1
        while cap < len(need):
            cap *= 2
        sel = np.zeros((cap,), np.int64)
        sel[:len(need)] = need
        ids_pad = np.full((cap,), SCRATCH_BLOCK, np.int32)
        ids_pad[:len(need)] = priv
        content = {}
        for ps in self.pool:
            arr = leaves_in[ps][sel]
            content[ps] = (arr if hasattr(arr, "devices")
                           else jnp.asarray(arr))
        self.pool = _import_scatter_fn()(
            self.pool, jnp.asarray(ids_pad), content)
        owned = {have_n + i: bid for i, bid in enumerate(priv)}
        adopted, _ = self.adopt(ids[:nb * self.block], owned,
                                origin=origin)
        taken = set(adopted)
        self.free_blocks([b for b in priv if b not in taken])
        n = len(adopted)
        nbytes = n * self.page_bytes
        with self._lock:
            self.stats["pages_imported"] += n
            self.stats["page_ship_in_bytes"] += nbytes
            # the transfer IS the decode replica's only genuine warm-
            # admit copy: the paged admit that reads these pages stays
            # a pointer update, so this counter's value on a decode
            # replica is exactly the bytes shipped in (tests/test_disagg.py)
            self.stats["warm_admit_copy_bytes"] += nbytes
        return {"imported_blocks": n,
                "cached_tokens": (have_n + n) * self.block,
                "bytes": nbytes}

    def _apply_pending_corruption(self) -> None:
        """Apply a deferred ``corrupt_page`` fault (ISSUE 18):
        overwrite the marked pool block with a constant pattern
        through the donating import scatter. Called from the pool-
        reading entry points (``refresh_cache_from_pool``,
        ``paged_prefill``, ``warm_prefill``) — places where a pool
        donation is already part of the caller's contract, so the
        corruption can never strand a live cache mid-dispatch."""
        import jax.numpy as jnp

        with self._lock:
            bid, self._corrupt_block = self._corrupt_block, None
        if bid is None:
            return
        content = {
            ps: jnp.ones((1,) + tuple(leaf.shape[1:]), leaf.dtype)
            for ps, leaf in self.pool.items()}
        self.pool = _import_scatter_fn()(
            self.pool, jnp.asarray(np.asarray([bid], np.int32)),
            content)
        logger.warning("fault corrupt_page: overwrote pool block %d "
                       "with a constant pattern", bid)

    def sync_pool_from_cache(self, cache) -> None:
        """Point ``self.pool`` at the pool leaves inside a paged cache
        pytree (the engines donate the pool through their executables —
        after each reassignment the old arrays are dead and this keeps
        the canonical pool reference current). Host-only."""
        import jax

        flat = jax.tree_util.tree_flatten_with_path(dict(cache))[0]
        by_path = {_path_str(p): leaf for p, leaf in flat}
        self.pool = {ps: by_path[ps] for ps in self.pool}

    def pool_alive(self, cache=None) -> bool:
        """True when every pool leaf (of ``cache`` if given, else the
        canonical pool) is still a live device buffer. Every paged
        executable DONATES the pool — a dispatch that fails AFTER
        donation leaves dead leaves behind, and syncing or re-wrapping
        those would wedge the pool permanently."""
        import jax

        if cache is None:
            leaves = list(self.pool.values())
        else:
            flat = jax.tree_util.tree_flatten_with_path(dict(cache))[0]
            leaves = [leaf for p, leaf in flat
                      if _path_str(p) in self.pool]
        return not any(getattr(leaf, "is_deleted", lambda: False)()
                       for leaf in leaves)

    def reset_pool(self) -> None:
        """Last-resort recovery after donation loss: reallocate zeroed
        pool leaves and drop the ENTIRE radix index + private set (the
        cached content died with the donated buffers — adopting or
        matching against zeroed pages would serve garbage). Cumulative
        counters survive; ``prefix_pool_resets`` records the event.
        Callers must drop any cache pytree that aliased the old
        pool."""
        with self._lock:
            self.pool = {
                ps: self._alloc_pool_leaf(leaf.shape, leaf.dtype)
                for ps, leaf in self.pool.items()}
            self.index = RadixIndex(self.block)
            self._free = list(range(1, self.pool_blocks))
            self._private = set()
            self._corrupt_block = None
            self.stats["prefix_pool_resets"] = (
                self.stats.get("prefix_pool_resets", 0) + 1)
        logger.warning(
            "prefix pool reset after donation loss: cached content "
            "dropped, pool reallocated")

    def refresh_cache_from_pool(self, cache):
        """Re-adopt the canonical pool leaves into an engine's paged
        cache pytree. A batch-1 request running between scheduler
        ticks under the shared lock (serve.py routes speculative
        requests that way) can reassign ``self.pool`` — its scatter
        insert ends in the capture kernel, which DONATES the pool
        leaves the engine's persistent cache aliases. Without this
        swap the engine's next dispatch throws "buffer has been
        deleted or donated"; with a non-donating capture it would be
        worse — a silently stale pool missing the request's freshly
        inserted radix blocks. Host-only pointer surgery; returns
        ``cache`` unchanged when already current."""
        import jax

        self._apply_pending_corruption()
        flat = jax.tree_util.tree_flatten_with_path(dict(cache))[0]
        by_path = {_path_str(p): leaf for p, leaf in flat}
        if all(by_path.get(ps) is leaf
               for ps, leaf in self.pool.items()):
            return cache
        out = dict(cache)
        for ps, leaf in self.pool.items():
            parts = ps.split("/")
            node = out
            for part in parts[:-1]:
                node[part] = dict(node[part])
                node = node[part]
            node[parts[-1]] = leaf
        return out

    def paged_cache(self, extra=None) -> dict:
        """A decode-cache pytree whose K/V leaves ARE the pool pages —
        what the paged engines hand to ``model.apply`` alongside a
        block table. Non-K/V cache entries (``pos_index``) ride in
        ``extra``."""
        import jax.numpy as jnp

        out = {}
        for ps, leaf in self.pool.items():
            node = out
            parts = ps.split("/")
            for part in parts[:-1]:
                node = node.setdefault(part, {})
            node[parts[-1]] = leaf
        out["pos_index"] = jnp.zeros((), jnp.int32)
        if extra:
            out.update(extra)
        return out

    def stats_snapshot(self) -> dict:
        with self._lock:
            out = dict(self.stats)
            resident = self.index.nodes
            private = len(self._private)
            referenced = private + self._count_referenced()
        out["prefix_pool_blocks"] = self.pool_blocks - 1
        out["prefix_pool_blocks_used"] = self.used_blocks()
        # occupancy WITHOUT double counting (ISSUE 7 satellite):
        # resident = unique shareable pages owned by the radix index;
        # referenced = pages live requests are actually reading/writing
        # (held shared refs + private tails). On the scatter fallback a
        # hot prefix is resident here AND copied into per-slot caches —
        # the split makes that visible instead of folding both into one
        # "used" number.
        out["prefix_pool_blocks_resident"] = resident
        out["prefix_pool_blocks_referenced"] = referenced
        out["prefix_paged"] = bool(self.paged)
        # spill-tier occupancy gauges (ISSUE 13) ride the same split:
        # spilled pages are neither resident nor referenced — they are
        # the tier below, one promotion away from resident
        out["tier_enabled"] = self.spill is not None
        if self.spill is not None:
            out.update(self.spill.occupancy())
        else:
            out.update({"tier_host_blocks": 0, "tier_host_bytes": 0,
                        "tier_disk_blocks": 0, "tier_disk_bytes": 0})
        lk = out["prefix_lookups"]
        out["prefix_hit_rate"] = round(
            out["prefix_hit_requests"] / lk, 4) if lk else 0.0
        # long-context layouts (ISSUE 15): the pool's geometry — page
        # bytes make the int8 HBM saving observable (the serve_longctx
        # high-water gate), window/ring expose the sliding layout
        out["pool_fallback_total"] = sum(
            v for k2, v in out.items()
            if k2.startswith("pool_fallback_"))
        out["prefix_page_bytes"] = int(self.page_bytes)
        out["prefix_pool_window"] = int(self.window)
        out["prefix_pool_kv_quant"] = 1 if self.kv_quant else 0
        return out

    def _count_referenced(self) -> int:
        """Radix blocks currently ref-pinned by live requests (callers
        hold the lock)."""
        n, stack = 0, [self.index.root]
        while stack:
            node = stack.pop()
            for child in node["children"].values():
                if child["refs"] > 0:
                    n += 1
                stack.append(child)
        return n

    # ---- device paths -----------------------------------------------------

    def capture(self, cache, slots, pads, per_row_block_ids):
        """Fill pool blocks from admitted rows of ``cache`` (one async
        dispatch; the pool leaves are donated through). ``slots`` /
        ``pads``: per-row cache row + prompt start slot;
        ``per_row_block_ids``: ``[k][nb]`` lists, ``-1`` padded."""
        import jax.numpy as jnp

        k = len(slots)
        nb = max((len(b) for b in per_row_block_ids), default=0)
        if nb == 0:
            return
        ids = np.full((k, nb), -1, np.int32)
        for j, row in enumerate(per_row_block_ids):
            ids[j, :len(row)] = row
        self.pool = _capture_fn(
            self.model, k, nb, self.block, self.rotary, self.rope_base,
            self.kv_quant,
        )(self.pool, cache, jnp.asarray(np.asarray(slots, np.int32)),
          jnp.asarray(np.asarray(pads, np.int32)), jnp.asarray(ids))

    def paged_plan(self, ids, budget: int, record: bool = True,
                   promote: bool = True):
        """Page reservation for one request: shared-prefix lookup
        (refs held for the request's lifetime — decode reads those
        pages in place) plus a private chain for the suffix and the
        full budget, allocated up front so a mid-decode row can never
        block on the pool. ``None`` when the pool cannot supply the
        chain right now (batch-1 falls back to the scatter arm; the
        continuous engine defers the admission and retries with
        ``record=False``). ONE owner of the reservation math — the
        continuous engine's ``_reserve_pages`` wraps this.

        Ring layout (``window > 0``, ISSUE 15): a request whose
        ``prompt + budget`` exceeds the ring span WRAPS — its table
        slots recycle, so shared radix pages must not sit in it (they
        would be overwritten under other readers) and nothing it
        writes is adoptable. Such requests run fully private on
        exactly ``nb_max`` pages (the documented "radix caches up to
        ~window deep" cap); non-wrapping requests share and adopt
        exactly like the flat layout."""
        ring_wrap = False
        nfull_total = -(-(len(ids) + int(budget)) // self.block)
        if self.window and nfull_total > self.nb_max:
            ring_wrap = True
            if record:
                with self._lock:
                    self.stats["prefix_lookups"] += 1
            nodes, blocks, c = [], [], 0
            n_need = self.nb_max
        else:
            nodes, blocks, c = self.lookup(ids, record=record,
                                           promote=promote)
            n_need = nfull_total - c // self.block
        priv = self.alloc_chain(n_need)
        if priv is None:
            self.release(nodes)
            return None
        return {
            "ids": list(ids), "c": c, "nodes": nodes, "blocks": blocks,
            "private": {c // self.block + i: bid
                        for i, bid in enumerate(priv)},
            "ring_wrap": ring_wrap,
            # extra shared nodes acquired AFTER reservation (the
            # continuous engine's group-admit dedup) — released by
            # ``paged_finish`` with the plan's own refs
            "adopt_nodes": [],
        }

    def paged_prefill(self, params, ids, budget: int):
        """Batch-1 TRUE paged prefill: the cached prefix is a block-
        table POINTER entry (zero device copy — contrast
        ``warm_prefill``'s scatter), the suffix prefills straight into
        private pages. Returns ``(last_logits, cache, tables, plan)``
        or ``None`` when the pool is dry (caller falls back). The
        caller drives the step loop with ``_paged_decode_fns`` and
        MUST call ``paged_finish(plan, out_ids, emitted)`` when done."""
        import jax.numpy as jnp

        self._apply_pending_corruption()
        plan = self.paged_plan(ids, budget)
        if plan is None:
            return None
        c = plan["c"]
        L = len(ids)
        row = np.full((1, self.nb_max), -1, np.int32)
        for i, b in enumerate(plan["blocks"]):
            row[0, i] = b
        for idx, bid in plan["private"].items():
            row[0, idx] = bid
        tables = jnp.asarray(row)
        done = c
        try:
            # ring layout (ISSUE 15): a single dispatch's feed is
            # bounded by the slack contract (a wider feed could recycle
            # a slot its own queries' band still reads), so a long
            # uncached suffix streams in fixed ``ring_slack_tokens``
            # chunks — every chunk reuses ONE executable shape, and
            # each chunk's writes land before the next chunk reads them
            while self.window and L - done > self.ring_slack_tokens:
                f = self.ring_slack_tokens
                suffix = jnp.asarray(
                    np.asarray(ids[done:done + f], np.int32)[None, :])
                _, cache = _paged_prefill_fn(
                    self.model, f, self.nb_max)(
                    params, self.paged_cache(), suffix, tables,
                    jnp.asarray([done], jnp.int32))
                self.sync_pool_from_cache(cache)
                done += f
            feed = L - done
            suffix = jnp.asarray(
                np.asarray(ids[done:], np.int32)[None, :])
            last_logits, cache = _paged_prefill_fn(
                self.model, feed, self.nb_max)(
                params, self.paged_cache(), suffix, tables,
                jnp.asarray([done], jnp.int32))
        except Exception:
            # the prefill DONATES the pool — a dispatch that fails
            # after donation leaves dead leaves behind, and every
            # later request (paged or scatter) would dispatch against
            # them. Mirror the caller's step-loop handler: normal
            # cleanup while the pool is alive, full reset when the
            # donation was lost (the plan's refs and pages die with
            # the index — releasing against the fresh one would
            # double-free).
            if self.pool_alive():
                self.release(plan["nodes"])
                self.free_blocks(list(plan["private"].values()))
            else:
                self.reset_pool()
            raise
        self.sync_pool_from_cache(cache)
        return last_logits, cache, tables, plan

    def paged_finish(self, plan, out_ids, emitted: int,
                     written=None) -> None:
        """End-of-request paged bookkeeping: zero-copy ADOPT the full
        (prompt + decoded) blocks into the radix index, free the
        unadoptable tail, release the shared-prefix refs.

        ``written`` overrides the default written-token count (prompt
        + fed decode tokens) — the chunked-streaming-prefill path
        finishes a cancelled request mid-prompt, where only the
        streamed chunks ever landed. A ``ring_wrap`` plan adopts
        NOTHING: its recycled slots clobbered the early blocks, so no
        prefix key describes the surviving content."""
        ids = plan["ids"]
        seq = list(ids) + [int(t) for t in out_ids]
        if written is None:
            # positions actually written: the prompt plus every fed
            # decode token (the final sampled token is never fed back)
            written = len(ids) + max(int(emitted) - 1, 0)
        if plan.get("ring_wrap"):
            adopted = []
        else:
            adopted, _ = self.adopt(seq[:int(written)],
                                    dict(plan["private"]))
        taken = set(adopted)
        self.free_blocks([b for b in plan["private"].values()
                          if b not in taken])
        self.release(plan["nodes"])
        self.release(plan.get("adopt_nodes") or [])

    def warm_prefill(self, params, ids, total: int,
                     record: bool = True):
        """Batch-1 prefill through the pool (the generate.py path):
        scatter the cached chain, feed only the suffix, then insert the
        prompt's own full blocks back. Returns ``(last_logits, cache,
        cached_tokens)`` — drop-in for engine/generate._prefill_fresh.
        ``record=False`` when the request's lookup was already counted
        (the paged arm's dry-pool fallback re-looks-up the SAME
        request).

        A full MISS routes through the regular flash prefill
        (engine/generate._prefill_fresh — the cache K/V writes land
        before the flash fast-path return, so the result is still
        capturable): miss-heavy traffic pays the cold path's cost, not
        the masked-einsum continuation's. The fed width on a hit is
        the exact suffix length — the plain path compiles per prompt
        length already, so there is no ladder to protect at batch 1."""
        import jax.numpy as jnp

        from .generate import _prefill_fresh

        if self.window:
            # belt-and-braces: the pool refuses to CONSTRUCT a window
            # layout without the paged path, and the batch-1 caller
            # falls back cold instead of here — scattering a ring into
            # a contiguous rolling cache would be silently wrong
            raise PoolUnsupported(
                "window", "the scatter arm cannot serve a rolling-"
                "window layout (paged ring only)")
        self._apply_pending_corruption()
        L = len(ids)
        nodes, blocks, c = self.lookup(ids, record=record)
        # per-request path provenance (ISSUE 18): the scatter arm
        # consumes its nodes internally, so the caller cannot read
        # their origins from a plan — stash the flags for the batch-1
        # service (single-threaded under the service lock) to pick up
        self.last_warm_flags = page_origin_flags(nodes) if c else {}
        try:
            if c == 0:
                prompt = jnp.asarray(np.asarray(ids, np.int32)[None, :])
                last_logits, cache = _prefill_fresh(
                    self.model, int(total))(params, prompt, None)
            else:
                feed = L - c
                nb = len(blocks)
                bid = np.asarray(blocks, np.int32)[None, :]
                suffix = jnp.asarray(
                    np.asarray(ids[c:], np.int32)[None, :])
                last_logits, cache = _warm_prefill_fn(
                    self.model, int(total), feed, nb, self.block,
                    self._padded,
                )(params, suffix, self.pool, jnp.asarray(bid),
                  jnp.int32(c))
                self.record_copy_bytes(nb)   # the scatter arm's HBM cost
        finally:
            self.release(nodes)
        new_blocks, start = self.plan_insert(ids)
        if new_blocks:
            row = [-1] * start + list(new_blocks)
            self.capture(cache, [0], [0], [row])
        return last_logits, cache, c
