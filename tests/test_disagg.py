"""Disaggregated prefill/decode serving (ISSUE 12).

Bottom-up: page serialization + export/import round-trips (token-
identical decode vs never-shipped pages, TP-sharded pools, refcount/
eviction invariants on the receiving pool), then the role gates and
the continuous-engine handoff, the DP×TP facade, the fleet layer's
role-filtered routing + two-queue admission + handoff accounting, the
``page_ship`` attribution segment, the loadgen bimodal knobs, and the
offline analyzer section. The live wire path (serve.py /prefill +
/admit_pages through the router's two-stage proxy) against subprocess
replicas has no test yet (ROADMAP D11).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pytorch_distributed_template_tpu.models  # noqa: F401
from pytorch_distributed_template_tpu.config.registry import MODELS
from pytorch_distributed_template_tpu.engine.continuous import (
    ContinuousBatchingService,
)
from pytorch_distributed_template_tpu.engine.kvcache import (
    PAGE_MAGIC, PrefixCache, deserialize_pages, serialize_pages,
    ship_pages,
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
    return model, params


def _ids(n, seed=0):
    rng = np.random.default_rng(seed)
    return [int(x) for x in rng.integers(1, VOCAB, n)]


def _svc(model, params, role="both", pool_blocks=48, paged=True):
    return GenerationService.from_model(
        model, params, role=role,
        prefix_cache={"enabled": True, "block_tokens": BLOCK,
                      "pool_blocks": pool_blocks, "paged": paged})


# ---------------------------------------------------------------------------
# serialization
# ---------------------------------------------------------------------------


def test_serialize_roundtrip_and_refusals(stack):
    model, params = stack
    src = _svc(model, params)
    ids = _ids(40, seed=1)
    src.generate(prompt_ids=ids, max_new_tokens=4)
    payload = src._prefix.export_pages(ids)
    assert payload["n_blocks"] == 5          # 40 tokens / block 8
    assert payload["tp_geometry"]["tp"] == 1
    blob = serialize_pages(payload)
    assert blob.startswith(PAGE_MAGIC)
    back = deserialize_pages(blob)
    assert back["token_ids"] == payload["token_ids"]
    assert back["n_blocks"] == payload["n_blocks"]
    for ps, arr in payload["leaves"].items():
        # export gathers power-of-two padded chains (fixed device
        # shapes); serialize trims to the real block count
        nb = payload["n_blocks"]
        assert back["leaves"][ps].shape[0] == nb
        np.testing.assert_array_equal(np.asarray(arr)[:nb],
                                      back["leaves"][ps])
    with pytest.raises(ValueError):
        deserialize_pages(b"NOPE" + blob)
    with pytest.raises(ValueError):
        deserialize_pages(blob[: len(blob) // 2])   # torn payload


# ---------------------------------------------------------------------------
# export/import round trip
# ---------------------------------------------------------------------------


def test_import_token_identical_greedy_and_sampled(stack):
    model, params = stack
    src = _svc(model, params)
    ids = _ids(48, seed=2)
    greedy = src.generate(prompt_ids=ids, max_new_tokens=6,
                          seed=3)["ids"]
    sampled = src.generate(prompt_ids=ids, max_new_tokens=6,
                           temperature=0.9, top_k=8, seed=3)["ids"]
    dst = _svc(model, params)
    receipt = dst.import_remote_pages(
        serialize_pages(src._prefix.export_pages(ids)))
    # export has no proper-prefix cap: all 6 full blocks of the
    # 48-token prompt ship (the receiver's own admission lookup
    # re-applies the cap)
    assert receipt["imported_blocks"] == 6
    assert dst.generate(prompt_ids=ids, max_new_tokens=6,
                        seed=3)["ids"] == greedy
    assert dst.generate(prompt_ids=ids, max_new_tokens=6,
                        temperature=0.9, top_k=8,
                        seed=3)["ids"] == sampled
    # honest accounting: the ONLY warm-admit copies a decode pool pays
    # are the genuine page transfers
    snap = dst._prefix.stats_snapshot()
    assert snap["warm_admit_copy_bytes"] == snap["page_ship_in_bytes"]
    assert snap["page_ship_in_bytes"] == \
        receipt["imported_blocks"] * dst._prefix.page_bytes


def test_reimport_dedups_already_cached_blocks(stack):
    model, params = stack
    src = _svc(model, params)
    ids = _ids(32, seed=4)
    src.generate(prompt_ids=ids, max_new_tokens=2)
    payload = src._prefix.export_pages(ids)
    dst = _svc(model, params)
    first = dst.import_remote_pages(payload)
    assert first["imported_blocks"] == 4
    again = dst.import_remote_pages(payload)
    assert again["imported_blocks"] == 0     # already cached: no copy
    assert again["cached_tokens"] == 32


def test_import_geometry_refusals(stack):
    model, params = stack
    src = _svc(model, params)
    ids = _ids(24, seed=5)
    src.generate(prompt_ids=ids, max_new_tokens=2)
    payload = src._prefix.export_pages(ids)
    wrong_block = dict(payload, block_tokens=BLOCK * 2)
    dst = _svc(model, params)
    with pytest.raises(ValueError):
        dst.import_remote_pages(wrong_block)
    missing = dict(payload, leaves={})
    with pytest.raises(ValueError):
        dst.import_remote_pages(missing)


def test_inflight_import_pages_are_not_evictable(stack):
    """Private pages (what an in-flight import holds before adoption)
    are invisible to LRU eviction by construction: evict_lru only
    walks radix leaves."""
    model, params = stack
    pf = PrefixCache(model, params, block_tokens=BLOCK, pool_blocks=8)
    got = pf.alloc_chain(7)                   # every allocatable page
    assert got is not None and len(got) == 7
    assert pf.index.evict_lru() is None       # nothing evictable
    assert pf.alloc_chain(1) is None          # pool honestly dry
    pf.free_blocks(got)


def test_import_under_eviction_pressure_token_identical(stack):
    """An import into a pool under pressure LRU-evicts unreferenced
    radix leaves for its chain but never loses its own pages — decode
    through the imported chain stays token-identical."""
    model, params = stack
    src = _svc(model, params)
    ids = _ids(48, seed=6)
    ref = src.generate(prompt_ids=ids, max_new_tokens=6)["ids"]
    payload = src._prefix.export_pages(ids)
    # small receiving pool, pre-filled to the brim with sacrificial
    # content so the import's allocation must evict
    dst = _svc(model, params, pool_blocks=20)
    for s in range(4):
        dst.generate(prompt_ids=_ids(40, seed=100 + s),
                     max_new_tokens=2)
    ev0 = dst._prefix.counter("prefix_evictions")
    receipt = dst.import_remote_pages(payload)
    assert receipt["imported_blocks"] > 0
    assert dst._prefix.counter("prefix_evictions") > ev0
    assert dst.generate(prompt_ids=ids, max_new_tokens=6)["ids"] == ref


def test_import_dropped_on_dry_pool_decodes_cold(stack):
    model, params = stack
    src = _svc(model, params)
    ids = _ids(48, seed=7)
    ref = src.generate(prompt_ids=ids, max_new_tokens=4)["ids"]
    payload = src._prefix.export_pages(ids)
    # a pool too small for paged mode falls back to scatter; pin its
    # few pages so the import cannot allocate at all
    dst = _svc(model, params, pool_blocks=4, paged=False)
    held = dst._prefix.alloc_chain(3)
    receipt = dst.import_remote_pages(payload)
    assert receipt.get("dropped") and receipt["imported_blocks"] == 0
    dst._prefix.free_blocks(held)
    # shipping is an optimization, never a correctness dependency
    assert dst.generate(prompt_ids=ids, max_new_tokens=4)["ids"] == ref


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs 2 devices for a tp=2 pool")
def test_tp_sharded_export_imports_into_tp1_pool(stack):
    """Pages shard on the KV-head axis under TP but their CONTENT is
    the logical tensor — a tp=2 export (header keyed with the
    exporter's tp_geometry) lands in a tp=1 pool token-identically."""
    from pytorch_distributed_template_tpu.parallel.tp import (
        serving_mesh, shard_serving_params,
    )

    model, params = stack
    solo = _svc(model, params)
    ids = _ids(48, seed=8)
    ref = solo.generate(prompt_ids=ids, max_new_tokens=6)["ids"]

    mesh = serving_mesh(2)
    model2 = MODELS.get("Llama")(vocab_size=VOCAB, n_layer=2, n_head=4,
                                 n_kv_head=2, d_model=32, max_len=128,
                                 mesh=mesh)
    params2 = shard_serving_params(model2, params, mesh)
    src = _svc(model2, params2)
    src.generate(prompt_ids=ids, max_new_tokens=2)
    payload = src._prefix.export_pages(ids)
    assert payload["tp_geometry"]["tp"] == 2
    dst = _svc(model, params)
    receipt = dst.import_remote_pages(
        deserialize_pages(serialize_pages(payload)))
    assert receipt["imported_blocks"] > 0
    assert dst.generate(prompt_ids=ids, max_new_tokens=6)["ids"] == ref


def test_ship_pages_device_arm(stack):
    model, params = stack
    src = _svc(model, params)
    ids = _ids(40, seed=9)
    ref = src.generate(prompt_ids=ids, max_new_tokens=5)["ids"]
    dst = _svc(model, params)
    receipt = ship_pages(src._prefix, dst._prefix, ids)
    assert receipt["imported_blocks"] == 5
    assert dst.generate(prompt_ids=ids, max_new_tokens=5)["ids"] == ref


# ---------------------------------------------------------------------------
# roles + the continuous-engine handoff
# ---------------------------------------------------------------------------


def test_prefill_role_refuses_decode_budgets(stack):
    model, params = stack
    pre = _svc(model, params, role="prefill")
    with pytest.raises(ValueError, match="prefill-role"):
        pre.generate(prompt_ids=_ids(16), max_new_tokens=8)
    with pytest.raises(ValueError, match="prefill-role"):
        pre.validate_request({"prompt_ids": _ids(16),
                              "max_new_tokens": 8})
    # a 1-token generate (prefill + first sample) still serves
    assert len(pre.generate(prompt_ids=_ids(16),
                            max_new_tokens=1)["ids"]) <= 1


def test_role_requires_prefix_cache(stack):
    model, params = stack
    with pytest.raises(ValueError, match="prefix cache"):
        GenerationService.from_model(model, params, role="prefill")
    with pytest.raises(ValueError, match="unknown serving role"):
        GenerationService.from_model(model, params, role="wat")


def test_prefill_export_short_prompt_ships_nothing(stack):
    model, params = stack
    pre = _svc(model, params, role="prefill")
    payload = pre.prefill_export(prompt_ids=_ids(BLOCK - 1))
    assert payload["n_blocks"] == 0 and payload["leaves"] == {}


def test_continuous_engine_handoff_token_identical(stack):
    """The real engine pair: a prefill-role continuous engine exports,
    a decode-role continuous engine imports, and the shipped prompt's
    decode — batched through the slot scheduler — matches a colocated
    engine token for token, greedy and sampled."""
    model, params = stack

    def cont(role):
        return ContinuousBatchingService.from_model(
            model, params, slots=2, chunk=4, window_ms=2.0, role=role,
            prefix_cache={"enabled": True, "block_tokens": BLOCK,
                          "pool_blocks": 64})

    colo = cont("both")
    pre = cont("prefill")
    dec = cont("decode")
    for i in range(2):
        ids = _ids(40 + BLOCK * i, seed=20 + i)
        g_ref = colo.generate(prompt_ids=ids, max_new_tokens=6,
                              seed=i)["ids"]
        s_ref = colo.generate(prompt_ids=ids, max_new_tokens=6,
                              temperature=0.8, top_k=8, seed=i)["ids"]
        payload = pre.prefill_export(prompt_ids=ids)
        assert payload["n_blocks"] > 0
        receipt = dec.import_remote_pages(
            serialize_pages(payload))
        assert receipt["imported_blocks"] > 0
        g = dec.generate(prompt_ids=ids, max_new_tokens=6, seed=i)
        assert g["ids"] == g_ref
        # provenance (ISSUE 18): the decode's fingerprint records
        # that its warm pages arrived via the disagg handoff
        assert "ship" in str(g["serve_path"]).split("_"), g
        assert dec.generate(prompt_ids=ids, max_new_tokens=6,
                            temperature=0.8, top_k=8,
                            seed=i)["ids"] == s_ref
    assert dec.stats["remote_admits"] == 2
    assert pre.stats["prefill_exports"] == 2
    snap = dec.prefix_cache_stats()
    assert snap["warm_admit_copy_bytes"] == snap["page_ship_in_bytes"]


# ---------------------------------------------------------------------------
# DP×TP facade
# ---------------------------------------------------------------------------


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs 2 devices for dp=2")
def test_dp_facade_parity_affinity_and_metrics(stack):
    from pytorch_distributed_template_tpu.engine.dp import (
        DataParallelService,
    )
    from pytorch_distributed_template_tpu.models.base import inject_mesh

    model, params = stack
    kw = dict(vocab_size=VOCAB, n_layer=2, n_head=4, n_kv_head=2,
              d_model=32, max_len=128)
    pcfg = {"enabled": True, "block_tokens": BLOCK, "pool_blocks": 48}
    solo = ContinuousBatchingService.from_model(
        model, params, slots=2, chunk=4, prefix_cache=dict(pcfg))
    svc = DataParallelService.from_model_factory(
        lambda mesh: inject_mesh(MODELS.get("Llama")(**kw), mesh),
        params, dp=2, tp=1, service_cls=ContinuousBatchingService,
        service_kw=dict(slots=2, chunk=4, prefix_cache=dict(pcfg)))
    for i in range(3):
        ids = _ids(24 + 8 * i, seed=30 + i)
        assert svc.generate(prompt_ids=ids, max_new_tokens=5,
                            seed=i)["ids"] == \
            solo.generate(prompt_ids=ids, max_new_tokens=5,
                          seed=i)["ids"]
    # group-1 params are really pinned to device 1 (dp, not N
    # schedulers sharing chip 0)
    leaf = jax.tree_util.tree_leaves(svc._engines[1].params)[0]
    assert leaf.devices() == {jax.devices()[1]}
    # an import's landing group is its own affinity record: the
    # follow-up generate routes to it through the radix probe
    src = _svc(model, params)
    ids = _ids(40, seed=40)
    ref = src.generate(prompt_ids=ids, max_new_tokens=5)["ids"]
    receipt = svc.import_remote_pages(src._prefix.export_pages(ids))
    g = receipt["dp_group"]
    hits0 = svc._engines[g]._prefix.counter("prefix_hit_requests")
    assert svc.generate(prompt_ids=ids, max_new_tokens=5)["ids"] == ref
    assert svc._engines[g]._prefix.counter(
        "prefix_hit_requests") > hits0
    # merged surfaces
    assert svc.stats["dp_groups"] == 2
    assert svc.prefix_cache_stats()["pages_imported"] == \
        receipt["imported_blocks"] and receipt["imported_blocks"] > 0
    assert svc.queue_depth() == 0
    s = svc.stats
    s["deadline_expired"] = s.get("deadline_expired", 0) + 1
    assert svc.stats.get("deadline_expired", 0) >= 1   # write-through


def test_dp_geometry_validation():
    from pytorch_distributed_template_tpu.parallel.tp import (
        validate_dp_geometry,
    )

    with pytest.raises(ValueError):
        validate_dp_geometry(0, 1)
    with pytest.raises(ValueError):
        validate_dp_geometry(jax.device_count() + 1, 1)
    validate_dp_geometry(1, 1)


# ---------------------------------------------------------------------------
# fleet layer: roles, two queues, handoff accounting
# ---------------------------------------------------------------------------


def test_role_serves_matrix():
    from pytorch_distributed_template_tpu.fleet.placement import (
        role_serves,
    )

    assert role_serves("both", None) and role_serves("prefill", None)
    assert role_serves("both", "prefill") and role_serves("both",
                                                          "decode")
    assert role_serves("prefill", "prefill")
    assert not role_serves("prefill", "decode")
    assert role_serves("decode", "decode")
    assert not role_serves("decode", "prefill")
    assert role_serves("", "decode")          # unset role = both


def _fake_manager(tmp_path, roles):
    from pytorch_distributed_template_tpu.fleet.replicas import (
        HEALTHY, FleetManager, Replica,
    )

    reps = []
    for i, role in enumerate(roles):
        r = Replica(f"r{i}", url=f"http://127.0.0.1:{4000 + i}",
                    role=role)
        r.state = HEALTHY
        r.polled = {"slots": 2, "queue_depth": 0}
        reps.append(r)
    return FleetManager(reps, run_dir=tmp_path, block_tokens=4,
                        snapshot_every=0)


def test_manager_role_filtered_routing_and_capacity(tmp_path):
    m = _fake_manager(tmp_path, ["prefill", "decode", "both"])
    ids = list(range(16))
    for _ in range(4):
        rep, _ = m.route(ids, role="prefill")
        assert rep.role in ("prefill", "both")
        rep, _ = m.route(ids, role="decode")
        assert rep.role in ("decode", "both")
    # capacity splits by stage (queue_factor default 2.0, slots 2)
    assert m.capacity(role="prefill") == 8    # prefill + both
    assert m.capacity(role="decode") == 8     # decode + both
    assert m.capacity() == 12                 # everyone
    assert m.disaggregated()
    m.events.close()


def test_disaggregated_needs_a_dedicated_prefill_replica(tmp_path):
    m = _fake_manager(tmp_path, ["both", "both"])
    assert not m.disaggregated()   # all-colocated fleet: classic path
    m.events.close()
    m2 = _fake_manager(tmp_path / "b", ["prefill"])
    assert not m2.disaggregated()  # nothing can decode
    m2.events.close()


def test_note_handoff_counters_and_snapshot(tmp_path):
    m = _fake_manager(tmp_path, ["prefill", "decode"])
    m.note_handoff(5, 4096, 0.02)
    m.note_handoff(3, 2048, 0.04)
    m.note_handoff(0, 0, 0.0, fallback=True)
    snap = m.snapshot_counters()
    assert snap["handoffs_total"] == 2
    assert snap["pages_shipped_total"] == 8
    assert snap["page_ship_bytes_total"] == 6144
    assert snap["handoff_fallbacks_total"] == 1
    assert snap["handoff_seconds"]["count"] == 2
    assert snap["replicas_prefill_healthy"] == 1
    assert snap["replicas_decode_healthy"] == 1
    m.events.close()


def test_staged_gates_have_independent_clocks():
    from pytorch_distributed_template_tpu.fleet.admission import (
        ADMITTED, staged_gates,
    )

    decode_gate, prefill_gate = staged_gates(
        lambda: 1, prefill_capacity_fn=lambda: 1, max_waiting=4,
        queue_timeout_s=0.05)
    assert prefill_gate is not None
    # fill the decode gate: the prefill gate must still admit
    # instantly — separate clocks, separate heaps
    assert decode_gate.submit("t") == ADMITTED
    assert prefill_gate.submit("t") == ADMITTED
    prefill_gate.release()
    # a SECOND decode submit times out (capacity 1) while prefill
    # admission stays open
    assert decode_gate.submit("t", timeout_s=0.05) == "shed_timeout"
    assert prefill_gate.submit("t") == ADMITTED
    prefill_gate.release()
    decode_gate.release()
    # no prefill capacity fn = no prefill gate (classic fleet)
    only, none = staged_gates(lambda: 1)
    assert none is None


# ---------------------------------------------------------------------------
# page_ship attribution segment
# ---------------------------------------------------------------------------


def test_page_ship_segment_is_non_overlapping():
    from pytorch_distributed_template_tpu.observability.reqtrace import (
        stitch_spans,
    )

    t0 = 1000.0

    def rec(name, proc, t, dur_s, **attrs):
        return {"rid": "rq1", "name": name, "proc": proc,
                "pid": 1 if proc == "router" else 2,
                "t": t, "dur_ms": dur_s * 1e3, "attrs": attrs}

    spans = [
        rec("request", "router", t0, 1.0),
        rec("admission_wait", "router", t0 + 0.01, 0.01),
        # page_ship: prefill dispatch -> decode dispatch
        rec("page_ship", "router", t0 + 0.03, 0.4, bytes=4096,
            blocks=4),
        rec("proxy", "router", t0 + 0.02, 0.2, kind="prefill"),
        rec("proxy", "router", t0 + 0.43, 0.55, kind="decode"),
        rec("http", "serve", t0 + 0.44, 0.5),
        rec("queue_wait", "serve", t0 + 0.45, 0.02),
        rec("first_token", "serve", t0 + 0.55, 0.0),
        rec("complete", "serve", t0 + 0.9, 0.0, tokens=8),
    ]
    rep = stitch_spans(spans, client_e2e_by_rid={"rq1": 1.0})
    row = rep["requests"][0]
    seg = row["segments"]
    assert "page_ship" in seg
    assert abs(seg["page_ship"] - 0.4) < 1e-6
    # route covers only the slice BEFORE the handoff; the proxy pair
    # anchors on the decode hop — no double counting
    assert abs(seg["route"] - 0.01) < 1e-6
    assert row["coverage"] > 0.9
    assert row["residual_s"] < 0.12


# ---------------------------------------------------------------------------
# loadgen bimodal mixture knobs
# ---------------------------------------------------------------------------


def test_loadgen_knobs_off_is_byte_identical():
    from pytorch_distributed_template_tpu.fleet.loadgen import (
        build_trace,
    )

    a = build_trace(16, seed=3, prefix_groups=3)
    b = build_trace(16, seed=3, prefix_groups=3, long_prefix_len=0,
                    long_groups=0, group_prompt_lens=None,
                    group_max_new=None, group_weights=None,
                    group_stream=None)
    assert a == b


def test_loadgen_bimodal_and_per_group_knobs():
    from pytorch_distributed_template_tpu.fleet.loadgen import (
        build_trace,
    )

    tr = build_trace(
        64, seed=5, prefix_groups=4, suffix_len=8, prefix_len=16,
        long_prefix_len=64, long_groups=2,
        group_max_new=[4, 4, 32, 32],
        group_stream=[False, False, True, True])
    lens = {}
    for item in tr:
        g = int(item["group"][1:])
        lens.setdefault(g, len(item["prompt_ids"]))
        if g < 2:
            assert len(item["prompt_ids"]) == 64 + 8
            assert item["max_new_tokens"] == 4 and not item["stream"]
        else:
            assert len(item["prompt_ids"]) == 16 + 8
            assert item["max_new_tokens"] == 32 and item["stream"]
    # deterministic under the seed contract
    assert tr == build_trace(
        64, seed=5, prefix_groups=4, suffix_len=8, prefix_len=16,
        long_prefix_len=64, long_groups=2,
        group_max_new=[4, 4, 32, 32],
        group_stream=[False, False, True, True])


def test_loadgen_group_weights_and_prompt_lens():
    from pytorch_distributed_template_tpu.fleet.loadgen import (
        build_trace,
    )

    tr = build_trace(
        48, seed=6, prefix_groups=3, suffix_len=8,
        group_prompt_lens=[72, 24, 24],
        group_weights=[0.0, 1.0, 1.0])
    groups = {item["group"] for item in tr}
    assert "g0" not in groups          # zero weight never draws
    assert all(len(item["prompt_ids"]) == 24 for item in tr)


# ---------------------------------------------------------------------------
# offline analyzer section
# ---------------------------------------------------------------------------


def test_analyze_disagg_section(tmp_path):
    import sys

    sys.path.insert(0, str(
        __import__("pathlib").Path(__file__).parent.parent / "scripts"))
    from telemetry_report import analyze_disagg

    path = tmp_path / "router.jsonl"
    recs = [
        {"t": 100.0, "event": "start"},
        {"t": 110.0, "event": "snapshot", "handoffs_total": 4,
         "pages_shipped_total": 20, "page_ship_bytes_total": 81920,
         "handoff_fallbacks_total": 1, "replicas_prefill_healthy": 1,
         "replicas_decode_healthy": 2, "handoff_p50_s": 0.02,
         "handoff_p99_s": 0.05},
    ]
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    out = analyze_disagg(path)
    assert out["handoffs_total"] == 4
    assert out["pages_shipped_total"] == 20
    assert out["handoff_success_frac"] == 0.8
    assert out["transfer_bytes_per_s"] == 8192.0
    # a fleet that never disaggregated renders no section
    empty = tmp_path / "empty.jsonl"
    empty.write_text(json.dumps({"t": 1.0, "event": "snapshot"}) + "\n")
    assert analyze_disagg(empty) == {}
