"""Serving-path chaos primitives (ISSUE 9): the serving fault kinds,
deadline propagation, the brownout ladder, and the artifact checksum
manifest.

The fleet-level composition (router deadline shed, hedging, wedged-
replica detection) lives in test_fleet.py next to the router tests;
the end-to-end walk of the whole fault grammar against a live fleet has
no test yet (ROADMAP D11). Here
each primitive is pinned in isolation:

- grammar: every new kind parses, validates its duration arg, fires
  exactly once, and honors attempt gating;
- hooks: ``slow_decode`` delays in place, ``hang`` blocks the calling
  thread forever (in a scratch thread!), ``pool_exhaust`` hands its
  spec back, the req/load ordinals hit exact targets;
- ``Deadline``: relative-ms wire form, monotonic accounting, clamped
  parsing, remaining-budget forwarding (satellite: clock-skew-free
  deadline arithmetic);
- ``BrownoutController``: enter/exit hysteresis with dwell, cliff
  jumps, validation;
- continuous engine: an expired deadline cancels a queued request
  and truncates a decoding one (``stop_reason: "deadline"``), the
  engine stays healthy after; brownout pressure engages under a
  flood and level 1 strips speculative decode;
- artifact manifest: save writes it, verify passes clean, REFUSES on
  real tampering, and the ``ckpt_corrupt`` fault proves the refusal
  path without touching the artifact bytes.
"""
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import pytorch_distributed_template_tpu.models  # noqa: F401
from pytorch_distributed_template_tpu.checkpoint.manager import (
    ArtifactCorrupt, restore_serving_params, save_serving_params,
    verify_artifact_manifest,
)
from pytorch_distributed_template_tpu.config.registry import MODELS
from pytorch_distributed_template_tpu.engine.continuous import (
    ContinuousBatchingService,
)
from pytorch_distributed_template_tpu.engine.serving import (
    DeadlineExceeded, GenerationService,
)
from pytorch_distributed_template_tpu.observability.reqtrace import (
    Deadline, SloWatcher,
)
from pytorch_distributed_template_tpu.resilience import faults
from pytorch_distributed_template_tpu.resilience.faults import FaultPlan
from pytorch_distributed_template_tpu.utils.brownout import (
    BrownoutController,
)

VOCAB = 64


@pytest.fixture(autouse=True)
def _clean_plan(monkeypatch):
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def stack():
    model = MODELS.get("Llama")(vocab_size=VOCAB, n_layer=2, n_head=4,
                                n_kv_head=2, d_model=32, max_len=128)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


# ---------------------------------------------------------------------------
# grammar + hooks
# ---------------------------------------------------------------------------


SERVE_PLAN = ("slow_decode@tick:5:50ms;hang@tick:9;"
              "pool_exhaust@tick:3:2s;stall_stream@req:2;"
              "proxy_latency@req:4:40ms;proxy_blackhole@req:6;"
              "ckpt_corrupt@load:2")


def test_serving_kinds_parse_and_round_trip():
    plan = FaultPlan.parse(SERVE_PLAN)
    assert [s.describe() for s in plan.specs] == SERVE_PLAN.split(";")
    assert {s.unit for s in plan.specs} == {"tick", "req", "load"}


def test_duration_args_validate_at_parse_time():
    with pytest.raises(ValueError):
        FaultPlan.parse("slow_decode@tick:5:quick")
    with pytest.raises(ValueError):
        FaultPlan.parse("proxy_latency@req:1:2x")
    with pytest.raises(ValueError):
        FaultPlan.parse("slow_decode@step:5")   # wrong unit


def test_slow_decode_sleeps_once_at_its_tick():
    faults.configure("slow_decode@tick:3:80ms")
    t0 = time.monotonic()
    assert faults.on_serve_tick(2) is None
    assert time.monotonic() - t0 < 0.05
    faults.on_serve_tick(3)
    assert time.monotonic() - t0 >= 0.08
    t1 = time.monotonic()
    faults.on_serve_tick(3)             # once per process
    assert time.monotonic() - t1 < 0.05


def test_pool_exhaust_spec_returned_once_with_duration():
    faults.configure("pool_exhaust@tick:2:1500ms")
    assert faults.on_serve_tick(1) is None
    spec = faults.on_serve_tick(2)
    assert spec is not None and spec.kind == "pool_exhaust"
    assert spec.duration_s == pytest.approx(1.5)
    assert faults.on_serve_tick(2) is None      # one-shot


def test_hang_blocks_the_calling_thread_forever():
    faults.configure("hang@tick:1")
    returned = threading.Event()

    def run():
        faults.on_serve_tick(1)
        returned.set()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert not returned.wait(0.3), "hang@tick returned — not a wedge"
    assert t.is_alive()


def test_request_and_proxy_ordinals_hit_exact_targets():
    faults.configure("stall_stream@req:2;proxy_blackhole@req:3;"
                     "proxy_latency@req:2:30ms")
    assert faults.on_serve_request(1) is None
    spec = faults.on_serve_request(2)
    assert spec is not None and spec.kind == "stall_stream"
    assert faults.on_serve_request(2) is None
    assert faults.on_proxy_request(1) is None
    t0 = time.monotonic()
    assert faults.on_proxy_request(2) is None   # latency fires inline
    assert time.monotonic() - t0 >= 0.03
    bh = faults.on_proxy_request(3)
    assert bh is not None and bh.kind == "proxy_blackhole"


def test_serving_kinds_are_attempt_gated():
    faults.configure("slow_decode@tick:1:80ms;stall_stream@req:1",
                     attempt=2)
    t0 = time.monotonic()
    assert faults.on_serve_tick(1) is None
    assert time.monotonic() - t0 < 0.05
    assert faults.on_serve_request(1) is None


# ---------------------------------------------------------------------------
# Deadline: monotonic, relative, clamped (satellite)
# ---------------------------------------------------------------------------


def test_deadline_parse_and_clamp():
    assert Deadline.from_header(None) is None
    assert Deadline.from_header("   ") is None
    d = Deadline.from_header("250")
    assert d.budget_s == pytest.approx(0.25)
    # clamped to [1ms, 1h]
    assert Deadline.from_header(str(10 ** 9)).budget_s \
        == pytest.approx(3600.0)
    for bad in ("abc", "1.5.2", "0", "-5"):
        with pytest.raises(ValueError):
            Deadline.from_header(bad)


def test_deadline_monotonic_accounting_and_forwarding():
    # explicit anchors: no sleeps, no wall clock anywhere
    d = Deadline(1.0, t0=100.0)
    assert d.remaining_s(now=100.4) == pytest.approx(0.6)
    assert not d.expired(now=100.999)
    assert d.expired(now=101.0)
    # the forwarded header is the REMAINING budget in ms
    assert d.header_value(now=100.4) == "600"
    # floor 1ms: a forwarded deadline of 0 would be malformed
    assert d.header_value(now=101.5) == "1"
    assert d.deadline_at() == pytest.approx(101.0)


def test_slo_watcher_exempts_deadline_and_cancelled():
    slo = SloWatcher(e2e_s=0.001)
    assert slo.observe("r1", e2e_s=5.0, stop_reason="deadline") == []
    assert slo.observe("r2", e2e_s=5.0, stop_reason="cancelled") == []
    assert slo.observe("r3", e2e_s=5.0, stop_reason="length") \
        == ["e2e"]
    assert slo.stats()["slo_breach_total"] == 1


# ---------------------------------------------------------------------------
# brownout ladder hysteresis
# ---------------------------------------------------------------------------


def test_brownout_hysteresis_enter_exit_dwell():
    t = {"v": 0.0}
    seen = []
    bc = BrownoutController(
        dwell_s=2.0, time_fn=lambda: t["v"],
        on_change=lambda old, new, p: seen.append((old, new)))
    assert bc.update(0.5) == 0
    assert bc.update(1.0) == 1          # enter level 1 at >= 1.0
    assert bc.update(0.9) == 1          # inside the hysteresis band
    assert bc.update(0.4) == 1          # below exit but dwell unmet
    t["v"] = 3.0
    assert bc.update(0.4) == 0          # dwell elapsed -> step down
    assert bc.update(4.5) == 4          # a cliff jumps multiple levels
    t["v"] = 6.0
    assert bc.update(1.7) == 3          # one step per dwell window
    assert bc.update(1.7) == 3          # next step needs fresh dwell
    t["v"] = 9.0
    assert bc.update(1.4) == 2
    assert seen[0] == (0, 1) and (0, 4) in seen
    s = bc.stats()
    assert s["brownout_peak_level"] == 4
    assert s["brownout_transitions_total"] == len(seen)


def test_brownout_threshold_validation():
    with pytest.raises(ValueError):
        BrownoutController(enter=(1.0,), exit=(1.0,))   # no band
    with pytest.raises(ValueError):
        BrownoutController(enter=(2.0, 1.0), exit=(0.5, 0.4))


# ---------------------------------------------------------------------------
# continuous engine: deadlines as engine-raised cancels
# ---------------------------------------------------------------------------


def test_engine_drops_queued_request_with_expired_deadline(stack):
    model, params = stack
    service = ContinuousBatchingService.from_model(
        model, params, slots=2, chunk=2, window_ms=5.0)
    out = service.generate(prompt_ids=[1, 2, 3], max_new_tokens=8,
                           deadline=Deadline(1e-4))
    assert out["stop_reason"] == "deadline"
    assert out["ids"] == []
    assert service.stats["deadline_expired"] >= 1


def test_engine_truncates_mid_decode_at_deadline(stack):
    model, params = stack
    service = ContinuousBatchingService.from_model(
        model, params, slots=2, chunk=2, window_ms=5.0)
    # warm the executables so the deadline measures DECODE, not compile
    service.generate(prompt_ids=[5, 6, 7], max_new_tokens=4)
    t0 = time.monotonic()
    out = service.generate(prompt_ids=[1, 2, 3], max_new_tokens=100,
                           deadline=Deadline(0.15))
    took = time.monotonic() - t0
    if out["stop_reason"] == "deadline":
        # truncated: partial tokens, slot freed long before the 100-
        # token budget, and the engine stays healthy afterwards
        assert 0 < len(out["ids"]) < 100
        assert service.stats["deadline_expired"] >= 1
    else:
        # a fast host may decode all 100 inside the budget — then the
        # request must have completed WITHIN it (no silent overrun)
        assert out["stop_reason"] == "length" and took < 1.0
    follow = service.generate(prompt_ids=[9, 9], max_new_tokens=4)
    assert follow["stop_reason"] == "length"
    assert len(follow["ids"]) == 4


def test_plain_service_rejects_expired_deadline(stack):
    model, params = stack
    service = GenerationService.from_model(model, params)
    with pytest.raises(DeadlineExceeded):
        service.generate(prompt_ids=[1, 2, 3], max_new_tokens=4,
                         deadline=Deadline(1e-6))


def test_engine_brownout_engages_under_flood_and_strips_spec(stack):
    model, params = stack
    service = ContinuousBatchingService.from_model(
        model, params, slots=1, chunk=1, window_ms=5.0,
        brownout={"enabled": True, "queue_norm": 0.25,
                  "dwell_s": 0.05})
    assert service.brownout_level == 0
    done = []

    def call(i):
        done.append(service.generate(prompt_ids=[i + 1, i + 2],
                                     max_new_tokens=6))

    threads = [threading.Thread(target=call, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert len(done) == 6
    # the flood (queue of ~5 over 1 slot, norm 0.25) must have pushed
    # pressure past 1.0 at least once — the gauge may have cleared by
    # now, so the peak is the honest assertion
    assert service.brownout_stats()["brownout_peak_level"] >= 1
    # level 1 (no_spec): speculative requests are served WITHOUT the
    # speculative machinery — no spec stats block in the response
    service._brownout.level = 1
    out = service.generate(prompt_ids=[3, 4, 5], max_new_tokens=4,
                           speculative=4)
    assert "speculative" not in out
    assert len(out["ids"]) == 4


def test_pool_exhaust_window_defers_then_recovers(stack):
    model, params = stack
    service = ContinuousBatchingService.from_model(
        model, params, slots=2, chunk=2, window_ms=5.0,
        prefix_cache={"enabled": True, "block_tokens": 8,
                      "pool_blocks": 32})
    # the fault window makes the pool read dry: paged admissions defer
    # (deferred_admissions counts) but requests still complete
    service._pool_dry_until = time.monotonic() + 0.5
    out = service.generate(prompt_ids=list(range(1, 20)),
                           max_new_tokens=4)
    assert len(out["ids"]) == 4
    if service._paged:
        assert service.stats["deferred_admissions"] >= 1
    # window over: the pool serves again
    assert not service._pool_dry()
    out2 = service.generate(prompt_ids=list(range(1, 20)),
                            max_new_tokens=4)
    assert out2["ids"] == out["ids"]


# ---------------------------------------------------------------------------
# artifact checksum manifest + ckpt_corrupt (satellite)
# ---------------------------------------------------------------------------


def test_artifact_manifest_written_verified_and_refuses_tampering(
        tmp_path):
    params = {"w": jnp.ones((4, 4), jnp.float32),
              "b": jnp.zeros((4,), jnp.float32)}
    path = save_serving_params(tmp_path / "model", params,
                               meta={"arch": "test"})
    mpath = tmp_path / "model.manifest.json"
    assert mpath.exists()
    manifest = json.loads(mpath.read_text())
    assert manifest["files"], "empty manifest"
    assert verify_artifact_manifest(path) is True
    # restore verifies too (clean round trip)
    template = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    restored = restore_serving_params(path, template)
    assert jnp.allclose(restored["w"], params["w"])
    # REAL tampering: flip bytes in one payload file
    victim = next(p for p in sorted(path.rglob("*"))
                  if p.is_file() and p.stat().st_size > 0)
    victim.write_bytes(victim.read_bytes()[:-1] + b"\x00")
    with pytest.raises(ArtifactCorrupt):
        verify_artifact_manifest(path)
    with pytest.raises(ArtifactCorrupt):
        restore_serving_params(path, template)


def test_ckpt_corrupt_fault_proves_the_refusal_path(tmp_path):
    params = {"w": jnp.ones((2, 2), jnp.float32)}
    path = save_serving_params(tmp_path / "model", params,
                               meta={"arch": "test"})
    faults.configure("ckpt_corrupt@load:1")
    with pytest.raises(ArtifactCorrupt):
        verify_artifact_manifest(path)
    # one-shot: the next load (ordinal 2) verifies clean — exactly the
    # supervisor-restart story (attempt 2 sails past)
    assert verify_artifact_manifest(path) is True


def test_missing_manifest_stays_loadable(tmp_path):
    # pre-manifest artifacts (older rounds) must not start refusing
    params = {"w": jnp.ones((2, 2), jnp.float32)}
    path = save_serving_params(tmp_path / "model", params,
                               meta={"arch": "test"})
    (tmp_path / "model.manifest.json").unlink()
    assert verify_artifact_manifest(path) is False
