"""Tiered KV pool (ISSUE 13): demote-on-evict spill hierarchy,
checksummed promotion, tier-fault chaos, peer page migration.

Host invariants first (SpillTier bounds + checksum contract, the new
fault kinds), then the load-bearing device contracts: eviction DEMOTES
and a repeat hit PROMOTES with token output identical to the cache-less
path; a corrupt spilled page is recomputed cold, never served; a full
tier degrades to classic destroy-on-evict. Fleet side: the placement
radix's re-warm plan extraction, the manager's miss-driven peer pull
and readmission-gated restart re-warm (HTTP mocked — the real wire
path has no test yet, ROADMAP D11), and the export/evict race
audit the demote tier widens (refs held across an export pin blocks
against eviction AND demotion).
"""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_template_tpu.config.registry import MODELS
import pytorch_distributed_template_tpu.models  # noqa: F401
from pytorch_distributed_template_tpu.engine.kvcache import (
    PrefixCache, SpillTier,
)
from pytorch_distributed_template_tpu.engine.serving import (
    GenerationService,
)
from pytorch_distributed_template_tpu.fleet.placement import FleetRadix
from pytorch_distributed_template_tpu.resilience import faults

VOCAB = 64
BLOCK = 8


@pytest.fixture(scope="module")
def stack():
    model = MODELS.get("Llama")(vocab_size=VOCAB, n_layer=2, n_head=4,
                                n_kv_head=2, d_model=32, max_len=128)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    faults.reset()
    yield
    faults.reset()


def _ids(n, seed=0):
    rng = np.random.default_rng(seed)
    return [int(x) for x in rng.integers(1, VOCAB, n)]


def _leaves(seed=0, nbytes=64):
    rng = np.random.default_rng(seed)
    return {"layers_0/k": rng.bytes(nbytes), "layers_0/v": rng.bytes(nbytes)}


# ---------------------------------------------------------------------------
# SpillTier: bounds + checksum contract
# ---------------------------------------------------------------------------


def test_spill_tier_roundtrip_and_checksum():
    tier = SpillTier(host_blocks=4)
    leaves = _leaves(0)
    sha = SpillTier.digest(leaves)
    assert tier.put(("k1",), leaves, sha) == "host"
    got, verdict = tier.get(("k1",))
    assert verdict == "verified" and got == leaves
    assert tier.get(("nope",)) == (None, "miss")


def test_spill_tier_corrupt_entry_reads_as_corrupt_then_miss():
    tier = SpillTier(host_blocks=4)
    leaves = _leaves(1)
    tier.put(("k",), leaves, SpillTier.digest(leaves))
    assert tier.corrupt_latest()
    got, verdict = tier.get(("k",))
    assert got is None and verdict == "corrupt"
    # the corrupt entry is REMOVED: a second read is a plain miss
    assert tier.get(("k",)) == (None, "miss")


def test_spill_tier_host_overflow_spills_to_disk(tmp_path):
    tier = SpillTier(host_blocks=2, disk_dir=str(tmp_path),
                     disk_blocks=2)
    entries = {}
    for i in range(4):
        leaves = _leaves(i)
        entries[i] = leaves
        tier.put((i,), leaves, SpillTier.digest(leaves))
    occ = tier.occupancy()
    assert occ["tier_host_blocks"] == 2
    assert occ["tier_disk_blocks"] == 2
    # oldest entries landed on disk and verify from there
    got, verdict = tier.get((0,))
    assert verdict == "verified" and got == entries[0]
    # a disk entry corrupted ON DISK fails verification too
    disk_path = tier._disk[(1,)]["path"]
    raw = bytearray(open(disk_path, "rb").read())
    raw[-1] ^= 0xFF
    open(disk_path, "wb").write(bytes(raw))
    assert tier.get((1,)) == (None, "corrupt")


def test_spill_tier_garbage_disk_file_reads_as_corrupt(tmp_path):
    """A disk entry whose HEADER region is garbage (invalid UTF-8 in
    the path string, not just a flipped payload byte) must still read
    as 'corrupt' — a parse failure is the same torn-page threat the
    checksum covers, and it must never raise into the serving path."""
    tier = SpillTier(host_blocks=1, disk_dir=str(tmp_path),
                     disk_blocks=2)
    leaves = _leaves(3)
    tier.put(("a",), leaves, SpillTier.digest(leaves))
    tier.put(("b",), _leaves(4), SpillTier.digest(_leaves(4)))  # spill
    path = tier._disk[("a",)]["path"]
    raw = bytearray(open(path, "rb").read())
    raw[4:8] = b"\xff\xff\xff\xff"          # wreck the path string
    open(path, "wb").write(bytes(raw))
    assert tier.get(("a",)) == (None, "corrupt")
    assert tier.get(("a",)) == (None, "miss")   # removed


def test_spill_tier_without_disk_drops_overflow():
    tier = SpillTier(host_blocks=1)
    for i in range(3):
        leaves = _leaves(i)
        tier.put((i,), leaves, SpillTier.digest(leaves))
    assert tier.occupancy()["tier_host_blocks"] == 1
    assert tier.get((0,)) == (None, "miss")
    assert tier.get((2,))[1] == "verified"


def test_spill_tier_full_window_refuses_puts():
    tier = SpillTier(host_blocks=4)
    tier.full_until = time.monotonic() + 60.0
    assert tier.put(("k",), _leaves(0), "x") is None
    tier.full_until = 0.0
    assert tier.put(("k",), _leaves(0),
                    SpillTier.digest(_leaves(0))) == "host"


# ---------------------------------------------------------------------------
# fault grammar: the four new kinds
# ---------------------------------------------------------------------------


def test_fault_plan_parses_tier_kinds():
    plan = faults.FaultPlan.parse(
        "slow_spill@evt:2:50ms;corrupt_spill@evt:3;"
        "tier_exhaust@evt:4:2s;peer_pull_timeout@pull:1:100ms")
    kinds = [s.kind for s in plan.specs]
    assert kinds == ["slow_spill", "corrupt_spill", "tier_exhaust",
                     "peer_pull_timeout"]
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("slow_spill@step:2")   # wrong unit
    with pytest.raises(ValueError):
        faults.FaultPlan.parse("tier_exhaust@evt:1:zzz")  # bad duration


def test_on_tier_event_ordinals_and_specs():
    faults.configure("corrupt_spill@evt:2;tier_exhaust@evt:3:1s")
    assert faults.on_tier_event() == {"corrupt": None, "exhaust": None}
    fired = faults.on_tier_event()
    assert fired["corrupt"] is not None and fired["exhaust"] is None
    fired = faults.on_tier_event()
    assert fired["exhaust"] is not None
    # once-per-process: the specs never fire again
    assert faults.on_tier_event() == {"corrupt": None, "exhaust": None}


def test_on_peer_pull_fires_once_at_ordinal():
    faults.configure("peer_pull_timeout@pull:2:10ms")
    assert faults.on_peer_pull() is None
    spec = faults.on_peer_pull()
    assert spec is not None and spec.kind == "peer_pull_timeout"
    assert faults.on_peer_pull() is None


# ---------------------------------------------------------------------------
# PrefixCache: demote on evict, promote on hit, token parity
# ---------------------------------------------------------------------------


def test_demote_promote_roundtrip_token_parity(stack):
    model, params = stack
    cold = GenerationService.from_model(model, params)
    groups = [_ids(40, seed=s) for s in range(5)]
    refs = [cold.generate(prompt_ids=g, max_new_tokens=6,
                          seed=0)["ids"] for g in groups]
    svc = GenerationService.from_model(model, params, prefix_cache={
        "enabled": True, "block_tokens": BLOCK, "pool_blocks": 18,
        "host_spill_blocks": 64})
    for g in groups:                            # round 1: populate
        svc.generate(prompt_ids=g, max_new_tokens=6, seed=0)
    s1 = svc.prefix_cache_stats()
    assert s1["tier_demoted_blocks"] > 0, \
        "eviction pressure never demoted — the tier is dead code here"
    assert s1["tier_host_blocks"] > 0
    outs = [svc.generate(prompt_ids=g, max_new_tokens=6,
                         seed=0)["ids"] for g in groups]
    s2 = svc.prefix_cache_stats()
    assert outs == refs, "warm-from-spill output diverged from cold"
    assert s2["tier_promoted_blocks"] > 0
    assert s2["tier_checksum_failures"] == 0
    # demote/promote byte accounting is per-block exact
    assert s2["tier_promote_bytes"] == \
        s2["tier_promoted_blocks"] * svc._prefix.page_bytes


def test_corrupt_spill_recomputes_cold_never_serves(stack):
    model, params = stack
    cold = GenerationService.from_model(model, params)
    groups = [_ids(40, seed=s) for s in range(5)]
    refs = [cold.generate(prompt_ids=g, max_new_tokens=6,
                          seed=0)["ids"] for g in groups]
    faults.configure("corrupt_spill@evt:2")
    svc = GenerationService.from_model(model, params, prefix_cache={
        "enabled": True, "block_tokens": BLOCK, "pool_blocks": 18,
        "host_spill_blocks": 64})
    for g in groups:
        svc.generate(prompt_ids=g, max_new_tokens=6, seed=0)
    outs = [svc.generate(prompt_ids=g, max_new_tokens=6,
                         seed=0)["ids"] for g in groups]
    snap = svc.prefix_cache_stats()
    assert outs == refs, "a corrupt spilled page leaked into output"
    assert snap["tier_checksum_failures"] >= 1, \
        "the corrupt entry was never probed — the test proves nothing"


def test_tier_exhaust_degrades_to_destroy_on_evict(stack):
    model, params = stack
    cold = GenerationService.from_model(model, params)
    groups = [_ids(40, seed=s) for s in range(5)]
    refs = [cold.generate(prompt_ids=g, max_new_tokens=6,
                          seed=0)["ids"] for g in groups]
    # a LONG exhaust window: every demote in round 1 drops
    faults.configure("tier_exhaust@evt:1:60s")
    svc = GenerationService.from_model(model, params, prefix_cache={
        "enabled": True, "block_tokens": BLOCK, "pool_blocks": 18,
        "host_spill_blocks": 64})
    for g in groups:
        svc.generate(prompt_ids=g, max_new_tokens=6, seed=0)
    outs = [svc.generate(prompt_ids=g, max_new_tokens=6,
                         seed=0)["ids"] for g in groups]
    snap = svc.prefix_cache_stats()
    assert outs == refs
    assert snap["tier_exhaust_drops"] > 0
    assert snap["tier_demoted_blocks"] == 0, \
        "demotes landed inside the exhaust window"


def test_pool_without_spill_is_byte_identical_legacy(stack):
    """host_spill_blocks=0 keeps the classic pool: no tier counters
    move, eviction destroys, outputs unchanged."""
    model, params = stack
    svc = GenerationService.from_model(model, params, prefix_cache={
        "enabled": True, "block_tokens": BLOCK, "pool_blocks": 18})
    assert svc._prefix.spill is None
    for s in range(4):
        svc.generate(prompt_ids=_ids(40, seed=s), max_new_tokens=4,
                     seed=0)
    snap = svc.prefix_cache_stats()
    assert snap["tier_enabled"] is False
    assert snap["tier_demoted_blocks"] == 0
    assert snap["prefix_evictions"] > 0


# ---------------------------------------------------------------------------
# export/evict race audit (ISSUE 13 satellite): refs pin blocks
# against eviction AND demotion while an export gathers
# ---------------------------------------------------------------------------


def test_export_refs_pin_chain_against_demote(stack):
    model, params = stack
    svc = GenerationService.from_model(model, params, prefix_cache={
        "enabled": True, "block_tokens": BLOCK, "pool_blocks": 18,
        "host_spill_blocks": 64})
    pf = svc._prefix
    hot = _ids(40, seed=100)
    svc.generate(prompt_ids=hot, max_new_tokens=4, seed=0)
    # simulate an in-flight export: the refs export_pages holds across
    # its gather (promote=False: the pin itself is under test)
    nodes, blocks, c = pf.lookup(hot, record=False, promote=False)
    assert c > 0 and blocks
    try:
        # eviction pressure: enough new chains to need every block
        for s in range(101, 107):
            svc.generate(prompt_ids=_ids(40, seed=s), max_new_tokens=4,
                         seed=0)
        # the pinned chain never evicted -> never demoted: no spill
        # key may carry the hot prefix
        for i in range(len(blocks)):
            key = tuple(hot[:(i + 1) * BLOCK])
            assert key not in pf.spill, \
                "a ref-pinned block was demoted mid-export"
        nodes2, blocks2, c2 = pf.lookup(hot, record=False,
                                        promote=False)
        pf.release(nodes2)
        assert blocks2 == blocks and c2 == c, \
            "the pinned chain changed under eviction pressure"
    finally:
        pf.release(nodes)
    # refs released: the same pressure may now demote the chain
    for s in range(107, 114):
        svc.generate(prompt_ids=_ids(40, seed=s), max_new_tokens=4,
                     seed=0)
    assert any(tuple(hot[:(i + 1) * BLOCK]) in pf.spill
               for i in range(5)), \
        "released chain never demoted under pressure"


def test_concurrent_export_and_eviction_pressure(stack):
    """Torn-export regression: exports racing genuine eviction
    pressure must stay self-consistent (n_blocks matches token_ids,
    payload verifies) and the service must keep serving."""
    model, params = stack
    svc = GenerationService.from_model(model, params, prefix_cache={
        "enabled": True, "block_tokens": BLOCK, "pool_blocks": 18,
        "host_spill_blocks": 64})
    hot = _ids(40, seed=200)
    svc.generate(prompt_ids=hot, max_new_tokens=4, seed=0)
    errs, payloads = [], []

    def exporter():
        try:
            for _ in range(4):
                payloads.append(svc.export_cached_pages(
                    prompt_ids=hot))
        except Exception as e:  # noqa: BLE001 — the assertion below
            errs.append(repr(e))

    def pressure():
        try:
            for s in range(201, 209):
                svc.generate(prompt_ids=_ids(40, seed=s),
                             max_new_tokens=4, seed=0)
        except Exception as e:  # noqa: BLE001
            errs.append(repr(e))

    ts = [threading.Thread(target=exporter),
          threading.Thread(target=pressure)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not errs, errs
    for p in payloads:
        assert len(p["token_ids"]) == p["n_blocks"] * BLOCK
        for leaf in p["leaves"].values():
            assert leaf.shape[0] >= p["n_blocks"]


# ---------------------------------------------------------------------------
# batched prefill export (ISSUE 13 satellite)
# ---------------------------------------------------------------------------


def test_prefill_export_coalesces_concurrent_calls(stack):
    model, params = stack
    svc = GenerationService.from_model(
        model, params, role="prefill", prefix_cache={
            "enabled": True, "block_tokens": BLOCK,
            "pool_blocks": 64})
    prompts = [_ids(40, seed=300 + i) for i in range(6)]
    res = [None] * 6
    errs = []

    def run(i):
        try:
            res[i] = svc.prefill_export(prompt_ids=prompts[i])
        except Exception as e:  # noqa: BLE001
            errs.append(repr(e))

    ts = [threading.Thread(target=run, args=(i,)) for i in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not errs, errs
    assert all(r is not None and r["n_blocks"] == 5 for r in res)
    assert svc.stats["prefill_exports"] == 6
    # coalescing engaged: fewer lock batches than exports
    assert 1 <= svc.stats["prefill_export_batches"] < 6
    assert svc.stats["prefill_export_max_batch"] >= 2


def test_prefill_export_single_caller_still_works(stack):
    model, params = stack
    svc = GenerationService.from_model(
        model, params, role="prefill", prefix_cache={
            "enabled": True, "block_tokens": BLOCK,
            "pool_blocks": 64})
    p = svc.prefill_export(prompt_ids=_ids(40, seed=400))
    assert p["n_blocks"] == 5
    assert svc.prefill_export(prompt_ids=_ids(4))["n_blocks"] == 0
    # one chain's failure must not poison batchmates / later calls
    with pytest.raises(ValueError):
        svc.prefill_export(prompt_ids=[VOCAB + 5])
    assert svc.prefill_export(
        prompt_ids=_ids(40, seed=400))["n_blocks"] == 5


def test_export_cached_pages_ships_spilled_chains(stack):
    """A demoted chain is still exportable: export-only promotes it
    (checksum-verified) and ships it — the peer re-warm path works
    even when the donor itself spilled the prefix."""
    model, params = stack
    svc = GenerationService.from_model(model, params, prefix_cache={
        "enabled": True, "block_tokens": BLOCK, "pool_blocks": 18,
        "host_spill_blocks": 64})
    hot = _ids(40, seed=500)
    svc.generate(prompt_ids=hot, max_new_tokens=4, seed=0)
    # push the hot chain out of the device pool entirely
    for s in range(501, 508):
        svc.generate(prompt_ids=_ids(40, seed=s), max_new_tokens=4,
                     seed=0)
    pf = svc._prefix
    assert any(tuple(hot[:(i + 1) * BLOCK]) in pf.spill
               for i in range(5)), "setup failed: nothing spilled"
    payload = svc.export_cached_pages(prompt_ids=hot)
    assert payload["n_blocks"] == 5
    # and the shipped chain decodes token-identically on a peer
    peer = GenerationService.from_model(model, params, prefix_cache={
        "enabled": True, "block_tokens": BLOCK, "pool_blocks": 64})
    receipt = peer.import_remote_pages(payload)
    assert receipt["imported_blocks"] > 0
    cold = GenerationService.from_model(model, params)
    assert peer.generate(prompt_ids=hot, max_new_tokens=6,
                         seed=0)["ids"] == \
        cold.generate(prompt_ids=hot, max_new_tokens=6,
                      seed=0)["ids"]


# ---------------------------------------------------------------------------
# fleet: re-warm plan extraction + manager pull machinery (HTTP mocked)
# ---------------------------------------------------------------------------


def test_fleet_radix_replica_prefixes_deepest_hottest_first():
    radix = FleetRadix(block_tokens=4)
    a = list(range(1, 13))              # 3 blocks
    b = list(range(20, 28))             # 2 blocks
    radix.record(a, "r0")
    radix.record(b, "r0")
    radix.record(a, "r1")
    radix.record(b[:4], "r1")
    plans = radix.replica_prefixes("r0", top_k=8)
    assert sorted(map(tuple, plans)) == sorted([tuple(a), tuple(b)])
    # hottest first: b recorded after a, then a touched again by r1's
    # record... use an explicit re-record to pin recency
    radix.record(a, "r0")
    assert radix.replica_prefixes("r0", top_k=1) == [a]
    # deepest-only: r1 holds a fully and b only one block deep
    plans1 = radix.replica_prefixes("r1", top_k=8)
    assert tuple(a) in set(map(tuple, plans1))
    assert [20, 21, 22, 23] in plans1
    assert radix.replica_prefixes("ghost") == []


def _mk_manager(tmp_path, **kw):
    from pytorch_distributed_template_tpu.fleet.replicas import (
        FleetManager, Replica,
    )

    reps = [Replica("r0", url="http://127.0.0.1:1"),
            Replica("r1", url="http://127.0.0.1:2")]
    mgr = FleetManager(reps, run_dir=tmp_path, poll_s=0.05,
                       eject_after=2, readmit_after=1, **kw)
    for r in reps:
        r.state = "healthy"
    return mgr, reps


def test_maybe_peer_pull_picks_deepest_peer(tmp_path, monkeypatch):
    mgr, (r0, r1) = _mk_manager(tmp_path, peer_pull=True,
                                peer_pull_min_tokens=8)
    ids = list(range(1, 65))
    mgr.radix.record(ids, "r1")
    calls = []

    def fake_pull(src, dst, pids, t):
        calls.append((src.rid, dst.rid))
        mgr.record_placement(pids, dst.rid)   # what the real pull does
        return {"blocks": 3, "bytes": 300}

    monkeypatch.setattr(mgr, "_pull_pages", fake_pull)
    res = mgr.maybe_peer_pull(ids, r0)
    assert res is not None and res["src"] == "r1"
    assert calls == [("r1", "r0")]
    assert mgr.stats["peer_pulls_total"] == 1
    assert mgr.stats["peer_pull_blocks_total"] == 3
    # the landed pull records the placement: r0 now matches too, and
    # a second pull finds nothing deeper elsewhere
    assert mgr.maybe_peer_pull(ids, r0) is None
    # disabled manager never pulls
    mgr2, (q0, q1) = _mk_manager(tmp_path / "b")
    mgr2.radix.record(ids, "q1")
    assert mgr2.maybe_peer_pull(ids, q0) is None


def test_peer_pull_timeout_fault_degrades_cold(tmp_path):
    mgr, (r0, r1) = _mk_manager(tmp_path, peer_pull=True,
                                peer_pull_min_tokens=8)
    ids = list(range(1, 65))
    mgr.radix.record(ids, "r1")
    faults.configure("peer_pull_timeout@pull:1:10ms")
    assert mgr.maybe_peer_pull(ids, r0) is None
    assert mgr.stats["peer_pull_timeouts_total"] == 1
    assert mgr.stats["peer_pulls_total"] == 0


def test_rewarm_plan_captured_and_readmission_waits(tmp_path,
                                                    monkeypatch):
    from pytorch_distributed_template_tpu.fleet import replicas as rmod

    mgr, (r0, r1) = _mk_manager(tmp_path, rewarm=True, rewarm_top_k=4)
    ids_a = list(range(1, 65))           # 2 full radix blocks
    ids_b = list(range(100, 164))        # 2 full radix blocks
    for ids in (ids_a, ids_b):
        mgr.radix.record(ids, "r0")
        mgr.radix.record(ids, "r1")
    healthy_poll = {"queue_depth": 0, "live_slots": 0, "slots": 4,
                    "scheduler_progress_total": 1}
    polled = {"r0": healthy_poll, "r1": healthy_poll}

    def fake_http_json(url, timeout_s=5.0):
        for rid, rep in (("r0", r0), ("r1", r1)):
            if rep.url in url:
                out = polled[rid]
                if out is None:
                    raise OSError("down")
                return dict(out)
        raise OSError("unknown url")

    monkeypatch.setattr(rmod, "http_json", fake_http_json)
    pulls = []

    def fake_pull(src, dst, pids, t):
        pulls.append(tuple(pids))
        mgr.record_placement(pids, dst.rid)   # what the real pull does
        return {"blocks": len(pids) // 32, "bytes": 10}

    monkeypatch.setattr(mgr, "_pull_pages", fake_pull)
    # r0 dies: two failed polls eject it, capturing the re-warm plan
    polled["r0"] = None
    mgr.poll_once()
    mgr.poll_once()
    assert r0.state == "ejected"
    assert sorted(map(tuple, r0.rewarm_prefixes)) == sorted(
        [tuple(ids_a), tuple(ids_b)])
    assert r0.rewarm_state == "pending"
    # r1 survives the drop: its claims still route
    assert mgr.radix.match(ids_a).get("r1")
    # r0 comes back: the FIRST healthy poll launches the re-warm and
    # readmission WAITS for it
    polled["r0"] = healthy_poll
    mgr.poll_once()
    deadline = time.monotonic() + 10.0
    while r0.state != "healthy" and time.monotonic() < deadline:
        mgr.poll_once()
        time.sleep(0.02)
    assert r0.state == "healthy"
    assert sorted(pulls) == sorted([tuple(ids_a), tuple(ids_b)])
    assert mgr.stats["rewarm_events_total"] == 1
    assert mgr.stats["rewarm_pulls_total"] == 2
    # the re-warmed pages route back to r0
    assert mgr.radix.match(ids_a).get("r0")
    # bookkeeping reset: a second ejection re-captures
    assert r0.rewarm_state is None and r0.rewarm_prefixes == []


def test_rewarm_off_keeps_classic_readmission(tmp_path, monkeypatch):
    from pytorch_distributed_template_tpu.fleet import replicas as rmod

    mgr, (r0, r1) = _mk_manager(tmp_path)
    mgr.radix.record(list(range(1, 65)), "r0")
    healthy_poll = {"queue_depth": 0, "live_slots": 0, "slots": 4,
                    "scheduler_progress_total": 1}
    polled = {"r0": healthy_poll, "r1": healthy_poll}

    def fake_http_json(url, timeout_s=5.0):
        for rid, rep in (("r0", r0), ("r1", r1)):
            if rep.url in url:
                if polled[rid] is None:
                    raise OSError("down")
                return dict(polled[rid])
        raise OSError("unknown url")

    monkeypatch.setattr(rmod, "http_json", fake_http_json)
    polled["r0"] = None
    mgr.poll_once()
    mgr.poll_once()
    assert r0.state == "ejected" and r0.rewarm_prefixes == []
    polled["r0"] = healthy_poll
    mgr.poll_once()
    assert r0.state == "healthy"
    assert mgr.stats["rewarm_events_total"] == 0
