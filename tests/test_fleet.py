"""Fleet front door (pytorch_distributed_template_tpu/fleet): routing,
admission control, health lifecycle, load harness.

Fast tier drives the REAL router HTTP stack against fake in-process
replicas (stdlib HTTP servers speaking serve.py's /metrics + /generate
wire format — no jax, no subprocesses): placement affinity, least-
loaded fallback, watermark shedding, tenant fairness, ejection /
re-admission, SSE passthrough. The slow tier runs the whole thing for
real: scripts/serve_fleet.py over two serve.py replicas on a random-
init artifact — loadgen traffic, an injected SIGKILL, supervised
recovery, and a clean SIGTERM fleet drain with no orphans.
"""
import json
import os
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path

import pytest

from pytorch_distributed_template_tpu.fleet.admission import (
    ADMITTED, SHED_WATERMARK, FairAdmission,
)
from pytorch_distributed_template_tpu.fleet.loadgen import (
    _percentile, build_trace, replay, summarize,
)
from pytorch_distributed_template_tpu.fleet.placement import (
    FleetRadix, affinity_ids, choose_replica,
)
from pytorch_distributed_template_tpu.fleet.replicas import (
    EJECTED, HEALTHY, FleetManager, Replica, http_json,
)
from pytorch_distributed_template_tpu.fleet.router import (
    HedgePolicy, RouterStats, build_router, prometheus_text,
    router_metrics,
)
from pytorch_distributed_template_tpu.resilience import faults

REPO = Path(__file__).parent.parent


# ---------------------------------------------------------------------------
# placement: the fleet radix + the chooser
# ---------------------------------------------------------------------------


def test_radix_match_is_block_granular_and_proper():
    rx = FleetRadix(block_tokens=4)
    ids = list(range(12))
    assert rx.match(ids) == {}
    rx.record(ids, "r0")
    # a strict extension matches every full block...
    assert rx.match(ids + [99]) == {"r0": 12}
    # ...the identical prompt only a PROPER prefix (final token is
    # never served from cache — mirrors PrefixCache.lookup)
    assert rx.match(ids) == {"r0": 8}
    # divergence mid-block shares nothing for that block
    assert rx.match(ids[:7] + [99, 100]) == {"r0": 4}
    # sub-block prompts can't match anything
    assert rx.match(ids[:3]) == {}


def test_radix_multi_replica_and_drop():
    rx = FleetRadix(block_tokens=4)
    ids = list(range(8))
    rx.record(ids, "r0")
    rx.record(ids, "r1")
    assert rx.match(ids + [9]) == {"r0": 8, "r1": 8}
    rx.drop_replica("r0")
    assert rx.match(ids + [9]) == {"r1": 8}
    rx.drop_replica("r1")           # replica-less chains are pruned
    assert rx.nodes == 0


def test_radix_bounded_lru_eviction():
    rx = FleetRadix(block_tokens=2, max_nodes=3)
    rx.record([1, 2, 3, 4], "r0")        # 2 nodes
    rx.record([5, 6, 7, 8], "r0")        # +2 -> evicts the LRU leaf
    assert rx.nodes <= 3
    # the most recent chain survives whole
    assert rx.match([5, 6, 7, 8, 9]) == {"r0": 4}


def test_affinity_ids_wire_forms():
    assert affinity_ids({"prompt_ids": [1, 2, 3]}) == [1, 2, 3]
    assert affinity_ids({"prompt": "ab"}) == [97, 98]
    assert affinity_ids({}) == []
    assert affinity_ids({"prompt_ids": "oops"}) == []


def test_choose_replica_policies():
    cands = [("r0", 0.0), ("r1", 3.0)]
    # deep match within the load spread wins
    assert choose_replica(cands, {"r1": 64}) == ("r1", "prefix")
    # ...but not past it (hot prefix must not become a hotspot)
    assert choose_replica([("r0", 0.0), ("r1", 9.0)], {"r1": 64},
                          load_spread=4.0) == ("r0", "least_loaded")
    # no match falls back to least loaded; equal loads rotate
    assert choose_replica(cands, {}) == ("r0", "least_loaded")
    both_idle = [("r0", 0.0), ("r1", 0.0)]
    picks = {choose_replica(both_idle, {}, rr_counter=i)[0]
             for i in range(2)}
    assert picks == {"r0", "r1"}
    # explicit policies
    assert choose_replica(cands, {"r1": 64},
                          policy="least_loaded") == ("r0",
                                                     "least_loaded")
    assert choose_replica(cands, {}, policy="round_robin",
                          rr_counter=3) == ("r1", "round_robin")
    assert choose_replica([], {}) is None


# ---------------------------------------------------------------------------
# admission: WFQ + watermark
# ---------------------------------------------------------------------------


def test_admission_inline_grant_and_release():
    adm = FairAdmission(lambda: 2)
    assert adm.submit("a") == ADMITTED
    assert adm.submit("a") == ADMITTED
    assert adm.depths() == {"inflight": 2, "waiting": 0, "capacity": 2}
    adm.release()
    assert adm.depths()["inflight"] == 1


def test_admission_watermark_shed_and_counters():
    adm = FairAdmission(lambda: 0, max_waiting=0)
    assert adm.submit("a") == SHED_WATERMARK
    st = adm.stats()
    assert st["shed_total"] == 1
    assert st["tenants"]["a"][SHED_WATERMARK] == 1


def test_admission_per_tenant_slice():
    adm = FairAdmission(lambda: 0, max_waiting=10,
                        max_waiting_per_tenant=0)
    assert adm.submit("a") == "shed_tenant"


def test_admission_timeout_sheds():
    adm = FairAdmission(lambda: 0, max_waiting=4, queue_timeout_s=0.1)
    t0 = time.monotonic()
    assert adm.submit("a") == "shed_timeout"
    assert time.monotonic() - t0 < 2.0


def test_admission_wfq_prefers_light_tenant():
    """With capacity 1 and a flood from the heavy tenant queued, the
    light tenant's first request tags just past the global virtual
    clock and admits ahead of the flood's BACKLOG (it cannot jump the
    head-of-line request, which carries the same tag and an earlier
    arrival — that is the fairness bound, not a defect)."""
    adm = FairAdmission(lambda: 1, weights={"heavy": 1.0, "light": 1.0})
    assert adm.submit("heavy") == ADMITTED       # occupies the slot
    grants = []

    def waiter(tenant):
        if adm.submit(tenant) == ADMITTED:
            grants.append(tenant)
            time.sleep(0.01)
            adm.release()

    heavies = [threading.Thread(target=waiter, args=("heavy",))
               for _ in range(3)]
    for t in heavies:
        t.start()
    time.sleep(0.05)                 # heavy backlog tags 1, 2, 3
    light = threading.Thread(target=waiter, args=("light",))
    light.start()
    time.sleep(0.05)
    adm.release()                    # free the slot: grants drain
    for t in heavies + [light]:
        t.join(timeout=5)
    assert grants.index("light") <= 1, grants
    assert grants.count("heavy") == 3


def test_admission_timeout_refunds_virtual_clock():
    """Requests that shed on timeout did no work: their virtual-clock
    charge is refunded, so a tenant whose spike timed out is not
    starved behind fresher tenants after the overload clears."""
    adm = FairAdmission(lambda: 0, max_waiting=8, queue_timeout_s=0.05)
    for _ in range(3):
        assert adm.submit("a") == "shed_timeout"
    # the clock shows no residue from requests that never ran
    assert adm._tenant_tag.get("a", 0.0) < 1e-6


def test_admission_retry_after_tracks_backlog_and_clamps():
    adm = FairAdmission(lambda: 1)
    assert adm.retry_after_s() >= 1          # empty: still >= 1
    assert adm.submit("a") == ADMITTED
    adm.observe_service_s(7.0)               # slow service -> bigger hint
    assert adm.retry_after_s() >= 2
    adm.observe_service_s(10_000.0)
    assert adm.retry_after_s() == 60         # clamped: don't lose clients


# ---------------------------------------------------------------------------
# fake replicas: serve.py's wire shape, no jax
# ---------------------------------------------------------------------------


class FakeReplica:
    """A stdlib HTTP server speaking serve.py's /metrics + /generate
    formats: configurable slots/queue_depth gauges, request recording,
    optional per-request delay, SSE when asked."""

    def __init__(self, slots=4, delay_s=0.0, sse_deltas=2, port=0,
                 sse_delay_s=0.01, error_code=None, sse_die_after=0,
                 serve_path=None):
        self.slots = slots
        # ISSUE 18 provenance: stamped as X-Serve-Path on buffered
        # responses and as the done event's serve_path key on SSE
        self.serve_path = serve_path
        self.delay_s = delay_s
        self.sse_deltas = sse_deltas
        self.sse_delay_s = sse_delay_s
        self.error_code = error_code          # answer every POST with it
        self.sse_die_after = sse_die_after    # RST after N SSE frames
        self.broken_pipes = 0
        self.queue_depth = 0
        # ISSUE 9 gauges: the wedge detector reads progress + pending
        # work, the fleet brownout gauge reads brownout_level
        self.progress = 0
        self.live_slots = 0
        self.brownout_level = 0
        self.requests = []
        self.counters = {"requests_total": 0,
                         "prefix_hit_tokens_total": 0}
        self._lock = threading.Lock()
        fake = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _json(self, code, payload, headers=()):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in headers:
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):  # noqa: N802
                if self.path.startswith("/metrics"):
                    with fake._lock:
                        payload = dict(fake.counters)
                    payload.update(
                        slots=fake.slots,
                        queue_depth=fake.queue_depth,
                        live_slots=fake.live_slots,
                        scheduler_progress_total=fake.progress,
                        brownout_level=fake.brownout_level)
                    return self._json(200, payload)
                self._json(200, {"status": "ok"})

            def do_POST(self):  # noqa: N802
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n) or b"{}")
                with fake._lock:
                    fake.requests.append(
                        {"body": body,
                         "tenant": self.headers.get("X-Tenant"),
                         "rid": self.headers.get("X-Request-Id"),
                         "deadline_ms": self.headers.get(
                             "X-Deadline-Ms")})
                    fake.counters["requests_total"] += 1
                if fake.delay_s:
                    time.sleep(fake.delay_s)
                if fake.error_code:
                    return self._json(fake.error_code,
                                      {"error": "synthetic"})
                ids = list(range(body.get("max_new_tokens", 4)))
                if body.get("stream"):
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/event-stream")
                    self.end_headers()
                    per = max(len(ids) // fake.sse_deltas, 1)
                    sent = 0
                    try:
                        for i in range(0, len(ids), per):
                            chunk = json.dumps({"ids": ids[i:i + per]})
                            self.wfile.write(
                                b"data: " + chunk.encode() + b"\n\n")
                            self.wfile.flush()
                            sent += 1
                            if (fake.sse_die_after
                                    and sent >= fake.sse_die_after):
                                # simulate a replica crash mid-stream:
                                # SO_LINGER 0 turns close() into a TCP
                                # RST, so the router's readline raises
                                # instead of seeing a clean EOF
                                self.connection.setsockopt(
                                    socket.SOL_SOCKET,
                                    socket.SO_LINGER,
                                    struct.pack("ii", 1, 0))
                                self.connection.close()
                                return
                            time.sleep(fake.sse_delay_s)
                        done = {"ids": ids, "done": True}
                        if fake.serve_path:
                            done["serve_path"] = fake.serve_path
                        fin = json.dumps(done)
                        self.wfile.write(
                            b"data: " + fin.encode() + b"\n\n")
                    except (BrokenPipeError, ConnectionError,
                            OSError):
                        with fake._lock:
                            fake.broken_pipes += 1
                else:
                    self._json(200, {"ids": ids, "stop_reason":
                                     "length"},
                               headers=([("X-Serve-Path",
                                          fake.serve_path)]
                                        if fake.serve_path else ()))

        self.server = ThreadingHTTPServer(("127.0.0.1", port), Handler)
        self.port = self.server.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self.thread = threading.Thread(
            target=self.server.serve_forever, daemon=True)
        self.thread.start()

    def stop(self):
        self.server.shutdown()
        self.server.server_close()


def _mk_fleet(tmp_path, fakes, **kw):
    replicas = [Replica(f"r{i}", url=f.url)
                for i, f in enumerate(fakes)]
    kw.setdefault("readmit_after", 1)
    kw.setdefault("eject_after", 2)
    kw.setdefault("block_tokens", 4)
    kw.setdefault("min_match_tokens", 4)
    kw.setdefault("snapshot_every", 0)
    manager = FleetManager(replicas, run_dir=tmp_path, **kw)
    manager.poll_once()              # readmit_after=1 -> all healthy
    return manager


def _router(manager, admission=None, **kw):
    admission = admission or FairAdmission(manager.capacity)
    server = build_router(manager, admission, port=0, **kw)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    return server, admission, url


def _post(url, body, headers=None, timeout=30):
    req = urllib.request.Request(
        url + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _get_json(url, path, timeout=10):
    return http_json(url + path, timeout)


def _wait_for_span(tracer, path, rid, name, timeout=10.0):
    """The router records a request's closing span after it has sent
    the response, so a client that has its answer polls for the span."""
    deadline = time.monotonic() + timeout
    while True:
        tracer.flush()
        for line in path.read_text().splitlines():
            rec = json.loads(line)
            if rec.get("rid") == rid and rec.get("name") == name:
                return rec
        assert time.monotonic() < deadline, f"no {name} span for {rid}"
        time.sleep(0.02)


# ---------------------------------------------------------------------------
# router behavior over fake replicas
# ---------------------------------------------------------------------------


def test_router_prefix_affinity_and_spread(tmp_path):
    fakes = [FakeReplica(), FakeReplica()]
    manager = _mk_fleet(tmp_path, fakes)
    server, _, url = _router(manager)
    try:
        shared = list(range(100, 112))        # 3 blocks of 4
        for _ in range(3):
            code, _ = _post(url, {"prompt_ids": shared,
                                  "max_new_tokens": 2})
            assert code == 200
        # all three shared-prefix requests landed on ONE replica
        counts = sorted(len(f.requests) for f in fakes)
        assert counts == [0, 3], counts
        assert manager.stats["routed_prefix_total"] == 2
        # distinct prefixes spread over the idle fleet
        for i in range(2):
            _post(url, {"prompt_ids": [200 + 16 * i + j
                                       for j in range(12)],
                        "max_new_tokens": 2})
        assert all(f.requests for f in fakes)
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_router_least_loaded_fallback_past_spread(tmp_path):
    fakes = [FakeReplica(), FakeReplica()]
    manager = _mk_fleet(tmp_path, fakes, load_spread=2.0)
    server, _, url = _router(manager)
    try:
        shared = list(range(50, 62))
        _post(url, {"prompt_ids": shared, "max_new_tokens": 2})
        holder = next(i for i, f in enumerate(fakes) if f.requests)
        # the prefix holder reports a deep internal queue
        fakes[holder].queue_depth = 10
        manager.poll_once()
        _post(url, {"prompt_ids": shared + [7], "max_new_tokens": 2})
        other = 1 - holder
        assert len(fakes[other].requests) == 1
        assert manager.stats["routed_least_loaded_total"] >= 1
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_router_round_robin_policy_header(tmp_path):
    fakes = [FakeReplica(), FakeReplica()]
    manager = _mk_fleet(tmp_path, fakes)
    server, _, url = _router(manager)
    try:
        shared = list(range(60, 72))
        for _ in range(4):
            _post(url, {"prompt_ids": shared, "max_new_tokens": 2},
                  headers={"X-Fleet-Policy": "round_robin"})
        # round robin ignores affinity: both replicas saw traffic
        assert all(len(f.requests) == 2 for f in fakes)
        code = None
        try:
            _post(url, {"prompt_ids": shared},
                  headers={"X-Fleet-Policy": "nope"})
        except urllib.error.HTTPError as e:
            code = e.code
        assert code == 400
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_router_sheds_429_with_retry_after(tmp_path):
    fakes = [FakeReplica(slots=1, delay_s=0.5)]
    manager = _mk_fleet(tmp_path, fakes, queue_factor=1.0)
    admission = FairAdmission(manager.capacity, max_waiting=0)
    server, _, url = _router(manager, admission)
    try:
        results = []

        def call(i):
            try:
                results.append(_post(url, {"prompt_ids": [i] * 8,
                                           "max_new_tokens": 2})[0])
            except urllib.error.HTTPError as e:
                results.append(
                    (e.code, e.headers.get("Retry-After")))
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=20)
        sheds = [r for r in results if isinstance(r, tuple)
                 and r[0] == 429]
        assert sheds, results
        assert all(int(ra) >= 1 for _, ra in sheds)
        assert 200 in results          # and real work still flowed
        m = router_metrics(manager, admission, RouterStats())
        assert m["shed_total"] == len(sheds)
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_router_tenant_fairness_under_contention(tmp_path):
    """Heavy tenant floods a capacity-1 fleet; the light tenant's
    request admits ahead of the flood's backlog."""
    fakes = [FakeReplica(slots=1, delay_s=0.15)]
    manager = _mk_fleet(tmp_path, fakes, queue_factor=1.0)
    admission = FairAdmission(manager.capacity, max_waiting=16)
    server, _, url = _router(manager, admission)
    try:
        done = []

        def call(tenant, i):
            _post(url, {"prompt_ids": [i] * 8, "max_new_tokens": 2},
                  headers={"X-Tenant": tenant}, timeout=60)
            done.append(tenant)

        heavies = [threading.Thread(target=call, args=("heavy", i))
                   for i in range(5)]
        for t in heavies:
            t.start()
        time.sleep(0.3)              # flood queued behind the slot
        light = threading.Thread(target=call, args=("light", 99))
        light.start()
        light.join(timeout=30)
        for t in heavies:
            t.join(timeout=30)
        # light arrived LAST; FIFO would finish it LAST. WFQ tags it
        # just past the advancing virtual clock, so it overtakes the
        # tail of the flood's backlog (how much depends on how many
        # heavies drained before it arrived — assert the invariant,
        # not the timing)
        assert done.index("light") <= len(done) - 2, done
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_router_ejection_and_readmission(tmp_path):
    fakes = [FakeReplica(), FakeReplica()]
    manager = _mk_fleet(tmp_path, fakes, eject_after=2)
    server, _, url = _router(manager)
    try:
        port = fakes[0].port
        fakes[0].stop()
        manager.poll_once()
        manager.poll_once()
        assert manager.replicas["r0"].state == EJECTED
        assert manager.stats["ejections_total"] == 1
        # traffic keeps flowing, on the survivor only
        for i in range(3):
            code, _ = _post(url, {"prompt_ids": [i] * 8,
                                  "max_new_tokens": 2})
            assert code == 200
        assert len(fakes[1].requests) == 3
        # resurrect on the SAME port -> re-admitted, traffic rebalances
        revived = FakeReplica(port=port)
        try:
            manager.poll_once()
            assert manager.replicas["r0"].state == HEALTHY
            assert manager.stats["readmissions_total"] == 1
            assert manager.recoveries_s
            snap = manager.snapshot()
            assert snap["status"] == "ok"
        finally:
            revived.stop()
    finally:
        server.shutdown()
        fakes[1].stop()


def test_router_503_when_no_healthy_replica(tmp_path):
    manager = FleetManager(
        [Replica("r0", url="http://127.0.0.1:1")],
        run_dir=tmp_path, snapshot_every=0)
    server, _, url = _router(manager)
    try:
        code = None
        try:
            _post(url, {"prompt_ids": [1, 2, 3]})
        except urllib.error.HTTPError as e:
            code = e.code
        assert code == 503
    finally:
        server.shutdown()


def test_router_sse_passthrough(tmp_path):
    fakes = [FakeReplica(sse_deltas=3)]
    manager = _mk_fleet(tmp_path, fakes)
    server, _, url = _router(manager)
    try:
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"prompt_ids": [1] * 8,
                             "max_new_tokens": 6,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        events = []
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers["Content-Type"].startswith(
                "text/event-stream")
            for line in resp:
                if line.startswith(b"data: "):
                    events.append(json.loads(line[6:]))
        assert events[-1].get("done") is True
        deltas = [e["ids"] for e in events[:-1]]
        assert sum(len(d) for d in deltas) == 6
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_router_metrics_and_admin_gating(tmp_path):
    fakes = [FakeReplica()]
    manager = _mk_fleet(tmp_path, fakes)
    server, admission, url = _router(manager)
    try:
        _post(url, {"prompt_ids": [1] * 8, "max_new_tokens": 2})
        manager.poll_once()          # absorb replica counters
        m = _get_json(url, "/metrics?format=json")
        for key in ("requests_total", "shed_total",
                    "fleet_requests_total", "replicas_healthy",
                    "routed_least_loaded_total", "capacity"):
            assert key in m, key
        assert m["fleet_requests_total"] >= 1
        text = urllib.request.urlopen(url + "/metrics").read().decode()
        assert "# TYPE pdt_fleet_requests_total counter" in text
        assert "pdt_fleet_replicas_healthy" in text
        hz = _get_json(url, "/healthz")
        assert hz["status"] == "ok" and hz["replicas"][0]["url"]
        # admin is OFF by default
        code = None
        try:
            req = urllib.request.Request(
                url + "/admin/kill?replica=r0", data=b"", method="POST")
            urllib.request.urlopen(req, timeout=5)
        except urllib.error.HTTPError as e:
            code = e.code
        assert code == 403
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_replica_counter_reset_correction():
    r = Replica("r0", url="http://x")
    r.absorb_counters({"requests_total": 10})
    r.absorb_counters({"requests_total": 14})
    assert r.cum["requests_total"] == 14
    # restart: the counter dropped — the new value IS the delta
    r.absorb_counters({"requests_total": 3})
    assert r.cum["requests_total"] == 17


def test_prometheus_text_fleet_prefix():
    text = prometheus_text({"a_total": 3, "b": 1.5,
                            "nested": {"p50": 0.1}}, prefix="pdt_fleet")
    assert "# TYPE pdt_fleet_a_total counter" in text
    assert "pdt_fleet_b 1.5" in text
    assert "pdt_fleet_nested_p50 0.1" in text


# ---------------------------------------------------------------------------
# loadgen
# ---------------------------------------------------------------------------


def test_loadgen_trace_deterministic_and_shaped():
    a = build_trace(24, seed=3, arrival="poisson", cancel_frac=0.2)
    b = build_trace(24, seed=3, arrival="poisson", cancel_frac=0.2)
    assert a == b
    assert all(a[i]["t"] <= a[i + 1]["t"] for i in range(len(a) - 1))
    groups = {r["group"] for r in a}
    assert 1 < len(groups) <= 4
    # shared prefix inside a group, unique suffixes
    by_group = {}
    for r in a:
        by_group.setdefault(r["group"], []).append(r["prompt_ids"])
    for ids_list in by_group.values():
        if len(ids_list) > 1:
            assert ids_list[0][:64] == ids_list[1][:64]
            assert ids_list[0][64:] != ids_list[1][64:]
    # different group TAG shares no prefixes (arm isolation)
    c = build_trace(8, seed=3, group_tag="x")
    assert c[0]["prompt_ids"][:64] not in [
        r["prompt_ids"][:64] for r in a]
    bursty = build_trace(50, seed=1, arrival="bursty",
                         burst_period_s=1.0, burst_duty=0.25)
    assert all(
        (r["t"] % 1.0) < 0.25 + 1e-6 for r in bursty)


def test_loadgen_percentile():
    assert _percentile([], 0.5) is None
    assert _percentile([2.0], 0.99) == 2.0
    assert _percentile([1.0, 2.0, 3.0], 0.5) == 2.0
    assert abs(_percentile([1.0, 2.0], 0.99) - 1.99) < 1e-9


def test_loadgen_replay_against_fake_replica():
    fake = FakeReplica()
    try:
        trace = build_trace(8, seed=5, rate_rps=50.0, stream_frac=0.5,
                            prefix_len=8, suffix_len=4,
                            max_new_tokens=4)
        summary = summarize(replay(fake.url, trace, timeout_s=30),
                            trace)
        assert summary["requests"] == 8
        assert summary["ok"] == 8, summary
        assert summary["errors"] == 0
        assert summary["tokens_out"] == 8 * 4
        assert summary["prompt_tokens"] == 8 * 12
        # the streaming half produced TTFT numbers
        assert summary["ttft_p50_s"] is not None
        assert summary["per_tenant"]
    finally:
        fake.stop()


def test_loadgen_cancellation_propagates_through_router(tmp_path):
    """A cancel_after_s streaming request hangs up mid-stream; the
    router propagates the disconnect upstream (the replica's next
    write breaks — what serve.py turns into a slot-engine cancel)."""
    fakes = [FakeReplica(sse_deltas=20, sse_delay_s=0.1)]
    manager = _mk_fleet(tmp_path, fakes)
    server, _, url = _router(manager)
    try:
        trace = build_trace(2, seed=9, rate_rps=50.0, stream_frac=1.0,
                            cancel_frac=1.0, cancel_after_s=0.3,
                            prefix_len=8, suffix_len=4,
                            max_new_tokens=40)
        summary = summarize(replay(url, trace, timeout_s=30), trace)
        assert summary["cancelled"] == 2, summary
        assert summary["errors"] == 0, summary
        deadline = time.time() + 10
        while fakes[0].broken_pipes < 2 and time.time() < deadline:
            time.sleep(0.1)
        assert fakes[0].broken_pipes == 2   # the replica FELT it
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_telemetry_report_fleet_section(tmp_path):
    """``telemetry_report --fleet router.jsonl`` folds the router's
    lifecycle log (the schema FleetManager.events emits) into the
    fleet section — JSON mode so the fields are assertable."""
    events = [
        {"v": 1, "t": 1.0, "event": "start", "replicas": 2,
         "policy": "cache_aware"},
        {"v": 1, "t": 2.0, "event": "ready", "replica": "r0"},
        {"v": 1, "t": 5.0, "event": "kill", "replica": "r1", "sig": 9},
        {"v": 1, "t": 5.5, "event": "eject", "replica": "r1"},
        {"v": 1, "t": 19.7, "event": "readmit", "replica": "r1",
         "recovery_s": 14.2},
        {"v": 1, "t": 20.0, "event": "snapshot", "replicas": 2,
         "replicas_healthy": 2, "routed_prefix_total": 31,
         "routed_least_loaded_total": 12,
         "routed_round_robin_total": 0, "fleet_requests_total": 43,
         "fleet_prefix_hit_tokens_total": 1920},
        {"v": 1, "t": 31.0, "event": "stopped", "orphans": 0},
    ]
    path = tmp_path / "router.jsonl"
    path.write_text("".join(json.dumps(e) + "\n" for e in events))
    proc = subprocess.run(
        [sys.executable,
         str(REPO / "scripts" / "telemetry_report.py"),
         "--fleet", str(path), "--json"],
        capture_output=True, text=True, timeout=60, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    fleet = json.loads(proc.stdout)["fleet"]
    assert fleet["ejections"] == 1 and fleet["readmissions"] == 1
    assert fleet["kills"] == 1
    assert fleet["drained_clean"] is True
    assert fleet["recovery_s_mean"] == 14.2
    assert fleet["fleet_prefix_hit_tokens_total"] == 1920
    assert abs(fleet["prefix_routed_frac"] - 31 / 43) < 0.01


# ---------------------------------------------------------------------------
# request-scoped tracing through the router (ISSUE 8)
# ---------------------------------------------------------------------------


def test_router_request_id_round_trip_spans_and_slo(tmp_path):
    """The tracing contract at the front door: a client-supplied
    X-Request-Id is honored, propagated to the replica, echoed on the
    response, and keys the router's admission_wait/proxy/request spans
    in its spans.jsonl; an absent/hostile id gets a minted one. The
    sub-latency SLO threshold proves the breach path (counter + dump),
    and the router's own latency histograms fill."""
    from pytorch_distributed_template_tpu.observability.reqtrace import (
        RequestTracer, SloWatcher,
    )

    fakes = [FakeReplica()]
    manager = _mk_fleet(tmp_path, fakes)
    tracer = RequestTracer(tmp_path / "spans.jsonl", process="router")
    slo = SloWatcher(e2e_s=1e-9, dump_dir=tmp_path / "dumps",
                     tracer=tracer, cooldown_s=0.0)
    server, _, url = _router(manager, tracer=tracer, slo=slo)
    try:
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"prompt_ids": [1] * 8,
                             "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "cli-42", "X-Tenant": "acme"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.status == 200
            assert resp.headers["X-Request-Id"] == "cli-42"  # echoed
        assert fakes[0].requests[-1]["rid"] == "cli-42"   # propagated
        # hostile id: replaced by a minted one (still echoed)
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"prompt_ids": [2] * 8,
                             "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "../../etc/passwd"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            minted = resp.headers["X-Request-Id"]
        assert minted and minted != "../../etc/passwd"
        assert fakes[0].requests[-1]["rid"] == minted
        tracer.flush()
        recs = [json.loads(l) for l in
                (tmp_path / "spans.jsonl").read_text().splitlines()]
        spans_42 = [r for r in recs if r.get("rid") == "cli-42"]
        names = {r["name"] for r in spans_42}
        assert {"admission_wait", "proxy", "request"} <= names
        by_name = {r["name"]: r for r in spans_42}
        assert by_name["proxy"]["attrs"]["replica"] == "r0"
        assert by_name["request"]["attrs"]["tenant"] == "acme"
        assert by_name["request"]["attrs"]["outcome"] == "proxied"
        # SLO: the 1 ns threshold breached on both requests, counters
        # scrape via /metrics and the bounded dump carries a timeline
        m = _get_json(url, "/metrics?format=json")
        for _ in range(50):     # the second request's stamp follows its
            if m["slo_breach_total"] == 2:      # last byte to the client
                break
            time.sleep(0.1)
            m = _get_json(url, "/metrics?format=json")
        assert m["slo_breach_total"] == 2
        assert m["slo_dumps_written"] >= 1
        assert list((tmp_path / "dumps").glob("slow_request_*.json"))
        # the router's e2e histogram filled (aggregable buckets, not
        # a percentile gauge) and renders as a proper prom histogram
        assert m["router_e2e_seconds"]["count"] == 2
        assert m["admission_wait_seconds"]["count"] == 2
        text = prometheus_text(m, prefix="pdt_fleet")
        assert 'pdt_fleet_router_e2e_seconds_bucket{le="+Inf"} 2' \
            in text
        assert "# TYPE pdt_fleet_router_e2e_seconds histogram" in text
    finally:
        server.shutdown()
        tracer.close()
        for f in fakes:
            f.stop()


def test_router_unserved_requests_stay_out_of_latency_slo(tmp_path):
    """A request that never reached a replica (dead fleet -> 502/503
    after admission) must NOT land in router_e2e_seconds or breach an
    SLO — an outage would otherwise drag fleet p50 DOWN and dump
    never-served requests as 'slow' — and its request span carries
    the real outcome, not 'proxied'."""
    from pytorch_distributed_template_tpu.observability.reqtrace import (
        RequestTracer, SloWatcher,
    )

    fakes = [FakeReplica()]
    manager = _mk_fleet(tmp_path, fakes)
    fakes[0].stop()          # dies AFTER the health poll: still HEALTHY
    tracer = RequestTracer(tmp_path / "spans.jsonl", process="router")
    slo = SloWatcher(e2e_s=1e-9, dump_dir=tmp_path / "dumps",
                     tracer=tracer)
    server, _, url = _router(manager, tracer=tracer, slo=slo)
    try:
        code = None
        try:
            _post(url, {"prompt_ids": [1] * 8, "max_new_tokens": 2},
                  headers={"X-Request-Id": "dead-1"}, timeout=30)
        except urllib.error.HTTPError as e:
            code = e.code
        assert code in (502, 503)
        # the closing span is the last of the request's bookkeeping:
        # once it is there, the counters below are final
        req_span = _wait_for_span(tracer, tmp_path / "spans.jsonl",
                                  "dead-1", "request")
        m = _get_json(url, "/metrics?format=json")
        assert m["router_e2e_seconds"]["count"] == 0
        assert m["slo_breach_total"] == 0
        assert req_span["attrs"]["outcome"] in ("unroutable",
                                                "unreachable")
    finally:
        server.shutdown()
        tracer.close()


def test_router_replica_timeout_is_proxy_failed_not_served(tmp_path):
    """A request that DISPATCHED but came back as a synthesized 504
    (replica read timeout) is an in-flight casualty, not a served
    request: out of the e2e histogram and the SLO, and its request
    span says proxy_failed."""
    from pytorch_distributed_template_tpu.observability.reqtrace import (
        RequestTracer, SloWatcher,
    )

    fakes = [FakeReplica(delay_s=3.0)]
    manager = _mk_fleet(tmp_path, fakes)
    tracer = RequestTracer(tmp_path / "spans.jsonl", process="router")
    slo = SloWatcher(e2e_s=1e-9, dump_dir=tmp_path / "dumps",
                     tracer=tracer)
    server, _, url = _router(manager, tracer=tracer, slo=slo,
                             read_timeout_s=0.5)
    try:
        code = None
        try:
            _post(url, {"prompt_ids": [1] * 8, "max_new_tokens": 2},
                  headers={"X-Request-Id": "late-1"}, timeout=30)
        except urllib.error.HTTPError as e:
            code = e.code
        assert code == 504
        m = _get_json(url, "/metrics?format=json")
        assert m["proxy_timeouts_total"] == 1
        assert m["router_e2e_seconds"]["count"] == 0
        assert m["slo_breach_total"] == 0
        req_span = _wait_for_span(tracer, tmp_path / "spans.jsonl",
                                  "late-1", "request")
        assert req_span["attrs"]["outcome"] == "proxy_failed"
    finally:
        server.shutdown()
        tracer.close()
        for f in fakes:
            f.stop()


def test_router_upstream_error_is_relayed_but_not_served(tmp_path):
    """A replica's own 4xx relays verbatim (status + rid echo) but is
    NOT a served request: a flood of ~1 ms 429/400 turnarounds must
    not collapse the router's e2e p50 or trip the SLO — the replica
    already excludes them from its own histogram."""
    from pytorch_distributed_template_tpu.observability.reqtrace import (
        RequestTracer, SloWatcher,
    )

    fakes = [FakeReplica(error_code=429)]
    manager = _mk_fleet(tmp_path, fakes)
    tracer = RequestTracer(tmp_path / "spans.jsonl", process="router")
    slo = SloWatcher(e2e_s=1e-9, dump_dir=tmp_path / "dumps",
                     tracer=tracer)
    server, _, url = _router(manager, tracer=tracer, slo=slo)
    try:
        code, echoed = None, None
        try:
            _post(url, {"prompt_ids": [1] * 8, "max_new_tokens": 2},
                  headers={"X-Request-Id": "flood-1"})
        except urllib.error.HTTPError as e:
            code = e.code
            echoed = e.headers.get("X-Request-Id")
        assert code == 429
        assert echoed == "flood-1"
        m = _get_json(url, "/metrics?format=json")
        assert m["router_e2e_seconds"]["count"] == 0
        assert m["slo_breach_total"] == 0
        req_span = _wait_for_span(tracer, tmp_path / "spans.jsonl",
                                  "flood-1", "request")
        assert req_span["attrs"]["outcome"] == "upstream_error"
    finally:
        server.shutdown()
        tracer.close()
        for f in fakes:
            f.stop()


def test_router_replica_death_mid_sse_is_not_served(tmp_path):
    """A replica that RSTs mid-stream is an in-flight casualty — same
    carve-out as the non-stream 504/502 paths: the truncated request
    stays out of the e2e histogram and the SLO even though its first
    token (and so a real TTFT) was relayed."""
    from pytorch_distributed_template_tpu.observability.reqtrace import (
        RequestTracer, SloWatcher,
    )

    fakes = [FakeReplica(sse_deltas=4, sse_die_after=1,
                         sse_delay_s=0.05)]
    manager = _mk_fleet(tmp_path, fakes)
    tracer = RequestTracer(tmp_path / "spans.jsonl", process="router")
    slo = SloWatcher(e2e_s=1e-9, dump_dir=tmp_path / "dumps",
                     tracer=tracer)
    server, _, url = _router(manager, tracer=tracer, slo=slo)
    try:
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"prompt_ids": [1] * 8,
                             "max_new_tokens": 8,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "dead-sse-1"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.headers.get("X-Request-Id") == "dead-sse-1"
            resp.read()   # drain until the router truncates
        m = _get_json(url, "/metrics?format=json")
        assert m["proxy_errors_total"] == 1
        assert m["router_e2e_seconds"]["count"] == 0
        assert m["slo_breach_total"] == 0
        # the first frame DID reach the client before the crash, so
        # the router-observed TTFT is real and stays
        assert m["router_ttft_seconds"]["count"] == 1
        req_span = _wait_for_span(tracer, tmp_path / "spans.jsonl",
                                  "dead-sse-1", "request")
        assert req_span["attrs"]["outcome"] == "proxy_failed"
    finally:
        server.shutdown()
        tracer.close()
        for f in fakes:
            f.stop()


def test_router_stamps_ttft_on_sse_and_loadgen_rids_join(tmp_path):
    """Streamed requests: the router's TTFT histogram stamps on the
    first relayed SSE payload, and loadgen's deterministic rids ride
    X-Request-Id end to end — the join key for the stitcher."""
    from pytorch_distributed_template_tpu.observability.reqtrace import (
        RequestTracer,
    )

    fakes = [FakeReplica(sse_deltas=2)]
    manager = _mk_fleet(tmp_path, fakes)
    tracer = RequestTracer(tmp_path / "spans.jsonl", process="router")
    server, _, url = _router(manager, tracer=tracer)
    try:
        trace = build_trace(3, seed=5, prefix_groups=1, group_tag="t",
                            prefix_len=8, suffix_len=4,
                            max_new_tokens=4, stream_frac=1.0,
                            rate_rps=50.0)
        assert [t["rid"] for t in trace] == \
            ["lg-t-5-0000", "lg-t-5-0001", "lg-t-5-0002"]
        summary = summarize(replay(url, trace, timeout_s=30), trace)
        assert summary["errors"] == 0
        # the summary's by_request rows carry the SAME rids the
        # replica saw — client measurements join server spans
        assert {r["rid"] for r in summary["by_request"]} == \
            {t["rid"] for t in trace}
        assert all(r["total_s"] is not None
                   for r in summary["by_request"])
        assert {r["rid"] for r in fakes[0].requests} == \
            {t["rid"] for t in trace}
        m = _get_json(url, "/metrics?format=json")
        assert m["router_ttft_seconds"]["count"] == 3   # SSE stamped
        # streams the replica completed ARE served requests (the
        # mid-stream-death carve-out must not leak into the happy path).
        # The router stamps e2e after the client has its last byte: on a
        # loaded machine the third stamp can land after this scrape
        for _ in range(50):
            if m["router_e2e_seconds"]["count"] == 3:
                break
            time.sleep(0.1)
            m = _get_json(url, "/metrics?format=json")
        assert m["router_e2e_seconds"]["count"] == 3
        tracer.flush()
        recs = [json.loads(l) for l in
                (tmp_path / "spans.jsonl").read_text().splitlines()]
        assert {r.get("rid") for r in recs if r.get("name") ==
                "request"} == {t["rid"] for t in trace}
    finally:
        server.shutdown()
        tracer.close()
        for f in fakes:
            f.stop()


# ---------------------------------------------------------------------------
# ISSUE 9: wedged-replica detection, deadlines, hedging, brownout
# ---------------------------------------------------------------------------


@pytest.fixture()
def _clean_faults(monkeypatch):
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    faults.reset()
    yield
    faults.reset()


def test_wedged_replica_ejected_not_readmitted_until_it_moves(
        tmp_path):
    """The satellite regression: frozen scheduler progress + pending
    work + a perfectly healthy /healthz must eject — and a still-
    frozen process must NOT readmit on its next healthy-looking
    scrape."""
    fakes = [FakeReplica(), FakeReplica()]
    manager = _mk_fleet(tmp_path, fakes, eject_after=2, wedge_after=2)
    r0 = manager.replicas["r0"]
    try:
        fakes[0].progress = 5
        manager.poll_once()              # progress=5 recorded, idle
        fakes[0].progress = 6
        manager.poll_once()              # advanced: liveness ARMS
        assert r0.state == HEALTHY
        fakes[0].queue_depth = 3         # work appears, progress frozen
        manager.poll_once()              # stuck streak 1
        assert r0.state == HEALTHY
        manager.poll_once()              # stuck streak 2 -> WEDGED
        assert r0.state == EJECTED and r0.wedged
        assert manager.stats["wedged_ejections_total"] == 1
        assert manager.stats["ejections_total"] == 1
        # the OTHER idle replica (frozen progress, no work) is fine
        assert manager.replicas["r1"].state == HEALTHY
        # a healthy scrape of the SAME frozen process must not readmit
        manager.poll_once()
        manager.poll_once()
        assert r0.state == EJECTED
        # "restart": progress moves (counters reset) and queue drains
        fakes[0].progress = 0
        fakes[0].queue_depth = 0
        manager.poll_once()              # readmit_after=1
        assert r0.state == HEALTHY and not r0.wedged
        assert manager.stats["readmissions_total"] == 1
        assert manager.recoveries_s     # time-to-recovery recorded
        ev = [json.loads(line) for line in
              (tmp_path / "router.jsonl").read_text().splitlines()]
        eject = next(e for e in ev if e.get("event") == "eject")
        assert eject["reason"] == "wedged"
        assert eject["stuck_polls"] == 2
    finally:
        for f in fakes:
            f.stop()


def test_idle_frozen_replica_stays_healthy(tmp_path):
    fakes = [FakeReplica()]
    manager = _mk_fleet(tmp_path, fakes)
    try:
        for _ in range(6):               # frozen progress, zero work
            manager.poll_once()
        assert manager.replicas["r0"].state == HEALTHY
        assert manager.stats["wedged_ejections_total"] == 0
    finally:
        fakes[0].stop()


def test_wedge_window_defaults_to_the_time_grace(tmp_path):
    """Without an explicit wedge_after, the window derives from
    wedge_grace_s / poll_s: mid-life XLA compiles (new bucket shapes)
    freeze the progress counter for seconds and must never read as a
    wedge at the default cadence."""
    fakes = [FakeReplica()]
    try:
        m = _mk_fleet(tmp_path, fakes)            # poll_s 1.0
        assert m.wedge_after == 60
        m2 = FleetManager([Replica("x", url=fakes[0].url)],
                          run_dir=tmp_path / "m2", poll_s=0.3,
                          wedge_grace_s=6.0)
        assert m2.wedge_after == 20
        m2.events.close()
    finally:
        fakes[0].stop()


def test_cold_start_compile_stall_is_not_a_wedge(tmp_path):
    """Startup grace (k8s startupProbe semantics): a replica that has
    NEVER advanced — its first arrival wave frozen behind cold XLA
    compiles with requests already queued — must not be ejected;
    liveness arms only after the first observed advance, and a
    counter reset (restart) re-disarms it."""
    fakes = [FakeReplica()]
    manager = _mk_fleet(tmp_path, fakes, eject_after=2, wedge_after=2)
    r0 = manager.replicas["r0"]
    try:
        fakes[0].queue_depth = 4         # traffic queued, progress 0
        for _ in range(6):               # way past wedge_after
            manager.poll_once()
        assert r0.state == HEALTHY
        assert manager.stats["wedged_ejections_total"] == 0
        fakes[0].progress = 9            # compile done, work flows
        manager.poll_once()
        fakes[0].progress = 2            # counter RESET = restart
        manager.poll_once()
        fakes[0].queue_depth = 4         # post-restart compile stall
        for _ in range(6):
            manager.poll_once()
        assert r0.state == HEALTHY
        assert manager.stats["wedged_ejections_total"] == 0
    finally:
        fakes[0].stop()


def test_router_deadline_forwarded_and_expiry_is_504(tmp_path):
    """Deadline propagation e2e at the router: the remaining budget
    is forwarded on the hop; a replica slower than the budget costs
    the client its deadline (504 + marker), never the 600 s read
    budget — and the dead request stays OUT of the served e2e
    histogram."""
    # the replica's delay stands ten budgets off, so that a loaded
    # machine's slow 504 is still told from a wait for the replica
    fakes = [FakeReplica(delay_s=3.0)]
    manager = _mk_fleet(tmp_path, fakes)
    server, _, url = _router(manager)
    try:
        t0 = time.monotonic()
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(url, {"prompt_ids": [1] * 8, "max_new_tokens": 2},
                  headers={"X-Deadline-Ms": "300"})
        took = time.monotonic() - t0
        assert e.value.code == 504
        assert e.value.headers.get("X-Deadline-Expired") == "1"
        assert took < 2.5                # deadline, not delay_s
        # the hop carried the REMAINING budget
        assert fakes[0].requests
        fwd = int(fakes[0].requests[0]["deadline_ms"])
        assert 0 < fwd <= 300
        # the handler counts after it has answered: on a loaded machine
        # the scrape can come first (PR 45's whole run read 0 once)
        until = time.monotonic() + 5.0
        while True:
            m = _get_json(url, "/metrics?format=json")
            if m["deadline_expired_total"] or time.monotonic() > until:
                break
            time.sleep(0.05)
        assert m["deadline_expired_total"] == 1
        assert m["router_e2e_seconds"]["count"] == 0   # out of SLO
        # malformed header is the client's error
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(url, {"prompt_ids": [2] * 8, "max_new_tokens": 2},
                  headers={"X-Deadline-Ms": "soon"})
        assert e.value.code == 400
    finally:
        server.shutdown()
        fakes[0].stop()


def test_sse_drip_feed_cannot_outlive_the_deadline(tmp_path):
    """The relay's deadline bound is WALL-CLOCK, not per-read: a
    replica that keeps emitting deltas (each inside the socket
    timeout) must still be truncated at the deadline — otherwise a
    deadline-ignoring replica holds the client for deltas x budget."""
    fakes = [FakeReplica(sse_deltas=16, sse_delay_s=0.25)]
    manager = _mk_fleet(tmp_path, fakes)
    server, _, url = _router(manager)
    try:
        t0 = time.monotonic()
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"prompt_ids": [1] * 8,
                             "max_new_tokens": 16,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Deadline-Ms": "600"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            body = resp.read()      # truncated stream ends at close
        took = time.monotonic() - t0
        # 16 deltas x 0.25s = 4s of drip; the budget is 0.6s
        assert took < 2.0, f"drip-feed outlived the deadline: {took}"
        assert b"done" not in body   # truncated, not completed
        m = _get_json(url, "/metrics?format=json")
        assert m["deadline_expired_total"] == 1
    finally:
        server.shutdown()
        fakes[0].stop()


def test_retry_never_fires_into_an_expired_deadline(
        tmp_path, _clean_faults):
    """Satellite: the retry-once path checks the remaining budget. A
    proxy_latency fault burns the deadline before the hop; the first
    attempt's connect failure must answer 504-deadline instead of
    spending another replica on a dead request."""
    faults.configure("proxy_latency@req:1:300ms")
    fakes = [FakeReplica()]
    manager = _mk_fleet(tmp_path, fakes)
    # r0 -> a dead port; r1 -> the live fake (would serve a retry)
    dead = Replica("rdead", url="http://127.0.0.1:9")
    dead.state = HEALTHY
    manager.replicas["rdead"] = dead
    manager.replicas["r0"].state = EJECTED   # force the dead pick
    server, _, url = _router(manager)
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(url, {"prompt_ids": [1] * 8, "max_new_tokens": 2},
                  headers={"X-Deadline-Ms": "150"})
        assert e.value.code == 504
        assert e.value.headers.get("X-Deadline-Expired") == "1"
        m = _get_json(url, "/metrics?format=json")
        assert m["proxy_retries_total"] == 0
        assert len(fakes[0].requests) == 0
    finally:
        server.shutdown()
        fakes[0].stop()


def test_hedge_fires_after_delay_and_respects_budget(tmp_path):
    fakes = [FakeReplica(delay_s=0.5), FakeReplica(delay_s=0.5)]
    manager = _mk_fleet(tmp_path, fakes)
    server, _, url = _router(
        manager, hedge=HedgePolicy(enabled=True, frac=1.0,
                                   delay_ms=60))
    try:
        code, body = _post(url, {"prompt_ids": [1] * 8,
                                 "max_new_tokens": 2})
        assert code == 200 and body["ids"]
        m = _get_json(url, "/metrics?format=json")
        assert m["hedge_fired_total"] == 1
        # both replicas ran it (that IS hedging); exactly one response
        # reached the client and the loser was cancelled
        assert m["hedge_cancelled_total"] == 1
        assert len(fakes[0].requests) + len(fakes[1].requests) == 2
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_hedge_budget_caps_fraction(tmp_path):
    fakes = [FakeReplica(delay_s=0.3), FakeReplica(delay_s=0.3)]
    manager = _mk_fleet(tmp_path, fakes)
    server, _, url = _router(
        manager, hedge=HedgePolicy(enabled=True, frac=0.05,
                                   delay_ms=30))
    try:
        for i in range(4):
            _post(url, {"prompt_ids": [i + 1] * 8,
                        "max_new_tokens": 2})
        m = _get_json(url, "/metrics?format=json")
        # 5% of 4 requests -> the budget never allows a hedge
        assert m["hedge_fired_total"] == 0
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_hedge_no_double_execution_under_proxy_blackhole(
        tmp_path, _clean_faults):
    """Satellite: the blackholed primary attempt reaches NO replica;
    the hedge serves the request. Exactly ONE replica executed it —
    the no-double-execution proof."""
    faults.configure("proxy_blackhole@req:1")
    fakes = [FakeReplica(), FakeReplica()]
    manager = _mk_fleet(tmp_path, fakes)
    server, _, url = _router(
        manager, hedge=HedgePolicy(enabled=True, frac=1.0,
                                   delay_ms=50))
    try:
        code, body = _post(url, {"prompt_ids": [1] * 8,
                                 "max_new_tokens": 2})
        assert code == 200 and body["ids"]
        assert len(fakes[0].requests) + len(fakes[1].requests) == 1
        m = _get_json(url, "/metrics?format=json")
        assert m["hedge_fired_total"] == 1
        assert m["hedge_won_total"] == 1
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_streaming_requests_never_hedge(tmp_path):
    fakes = [FakeReplica(delay_s=0.3), FakeReplica(delay_s=0.3)]
    manager = _mk_fleet(tmp_path, fakes)
    server, _, url = _router(
        manager, hedge=HedgePolicy(enabled=True, frac=1.0,
                                   delay_ms=20))
    try:
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"prompt_ids": [1] * 8,
                             "max_new_tokens": 4,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            resp.read()
        m = _get_json(url, "/metrics?format=json")
        assert m["hedge_fired_total"] == 0
        assert len(fakes[0].requests) + len(fakes[1].requests) == 1
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_hedge_auto_delay_needs_histogram_samples():
    hp = HedgePolicy(enabled=True)       # delay_ms=0 -> p95-derived
    from pytorch_distributed_template_tpu.utils.promtext import (
        LatencyHistogram,
    )

    hist = LatencyHistogram()
    assert hp.delay_s(hist) is None      # empty histogram: no hedging
    for _ in range(30):
        hist.observe(0.2)
    d = hp.delay_s(hist)
    assert d is not None and d >= 0.02   # p95-based once warmed
    assert HedgePolicy(enabled=False).delay_s(hist) is None


# ---------------------------------------------------------------------------
# serve-path provenance through the router (ISSUE 18)
# ---------------------------------------------------------------------------


def test_router_relays_serve_path_header_round_trip(tmp_path):
    """Path provenance satellite: the replica's X-Serve-Path
    fingerprint relays through the buffered proxy to the client, and a
    replica that stamps none relays none — the router never invents
    provenance."""
    for want in ("paged_ring_wrap", None):
        fake = FakeReplica(serve_path=want)
        run_dir = tmp_path / (want or "bare")
        run_dir.mkdir()
        manager = _mk_fleet(run_dir, [fake])
        server, _, url = _router(manager)
        try:
            req = urllib.request.Request(
                url + "/generate",
                data=json.dumps({"prompt_ids": [1] * 8,
                                 "max_new_tokens": 2}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.status == 200
                assert resp.headers.get("X-Serve-Path") == want
            assert len(fake.requests) == 1
        finally:
            server.shutdown()
            fake.stop()


def test_hedge_winner_relays_its_own_serve_path(
        tmp_path, _clean_faults):
    """Whichever attempt wins the hedging race relays its OWN
    replica's fingerprint. The primary attempt is blackholed so
    exactly one replica executes — the hedge — and the client's
    X-Serve-Path must be that replica's, not the primary target's."""
    faults.configure("proxy_blackhole@req:1")
    fakes = [FakeReplica(serve_path="warm_adopt"),
             FakeReplica(serve_path="paged_ship")]
    manager = _mk_fleet(tmp_path, fakes)
    server, _, url = _router(
        manager, hedge=HedgePolicy(enabled=True, frac=1.0,
                                   delay_ms=50))
    try:
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"prompt_ids": [1] * 8,
                             "max_new_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as resp:
            assert resp.status == 200
            got = resp.headers.get("X-Serve-Path")
        ran = [f for f in fakes if f.requests]
        assert len(ran) == 1          # blackhole: only the hedge ran
        assert got == ran[0].serve_path
        m = _get_json(url, "/metrics?format=json")
        assert m["hedge_fired_total"] == 1
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


def test_loadgen_by_path_joins_router_relayed_fingerprints(tmp_path):
    """Disagg-flavoured round trip: a decode replica stamping the
    shipped-import fingerprint relays through the router on BOTH wire
    forms — response header on buffered JSON, done-event key on SSE —
    and loadgen's per-path summary joins them into one row."""
    fakes = [FakeReplica(serve_path="paged_ship")]
    manager = _mk_fleet(tmp_path, fakes)
    server, _, url = _router(manager)
    try:
        trace = build_trace(6, seed=7, rate_rps=100.0,
                            stream_frac=0.5, prefix_len=8,
                            suffix_len=4, max_new_tokens=4)
        summary = summarize(replay(url, trace, timeout_s=30), trace)
        assert summary["ok"] == 6, summary
        bp = summary["by_path"]
        assert set(bp) == {"paged_ship"}
        assert bp["paged_ship"]["requests"] == 6
        assert bp["paged_ship"]["errors"] == 0
        assert bp["paged_ship"]["latency_p50_s"] is not None
    finally:
        server.shutdown()
        fakes[0].stop()


def test_admission_brownout_level4_tightens_tenant_slice():
    adm = FairAdmission(lambda: 0, max_waiting=16,
                        max_waiting_per_tenant=8,
                        queue_timeout_s=0.2)
    adm.set_brownout_level(4)            # slice: 8 -> 2
    waiters = [threading.Thread(
        target=lambda: adm.submit("heavy", timeout_s=1.0))
        for _ in range(2)]
    for w in waiters:
        w.start()
    time.sleep(0.2)                      # both queued (capacity 0)
    assert adm.submit("heavy", timeout_s=0.0) == "shed_tenant"
    assert adm.submit("light", timeout_s=0.0) == "shed_timeout"
    s = adm.stats()
    assert s["brownout_shed_total"] == 1
    for w in waiters:
        w.join(timeout=3)


def test_fleet_brownout_gauge_tracks_worst_replica(tmp_path):
    fakes = [FakeReplica(), FakeReplica()]
    fakes[1].brownout_level = 3
    manager = _mk_fleet(tmp_path, fakes)
    server, admission, url = _router(manager)
    try:
        assert manager.brownout_level() == 3
        m = _get_json(url, "/metrics?format=json")
        assert m["brownout_level"] == 3
        assert m["fleet_brownout_level"] == 3
    finally:
        server.shutdown()
        for f in fakes:
            f.stop()


# ---------------------------------------------------------------------------
# slow tier: the real thing, end to end
# ---------------------------------------------------------------------------


def _wait_ready(log: Path, proc, deadline_s: float = 300.0) -> str:
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        text = log.read_text() if log.exists() else ""
        for line in text.splitlines():
            if line.startswith("READY "):
                return line.split()[1].strip()
        if proc.poll() is not None:
            raise AssertionError(
                "process exited early:\n" + text[-3000:])
        time.sleep(0.5)
    raise AssertionError("never READY:\n"
                         + (log.read_text()[-3000:] if log.exists()
                            else "<no log>"))


def _healthy_count(url: str) -> int:
    try:
        hz = _get_json(url, "/healthz", timeout=5)
    except (OSError, ValueError):
        return -1
    return sum(1 for r in hz["replicas"] if r["state"] == "healthy")


@pytest.mark.slow
def test_fleet_end_to_end_kill_drain_recover(tmp_path):
    """The acceptance path: artifact -> 2-replica fleet -> loadgen
    traffic (prefix routing observable on replica counters) -> SIGKILL
    one replica (supervised crash restart, re-admission) -> SIGTERM
    the fleet (clean preemption-path drain, no orphans)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    art = tmp_path / "artifact"
    subprocess.run(
        [sys.executable, str(REPO / "scripts" /
                             "make_serving_artifact.py"),
         "-o", str(art), "--max-len", "256", "--block-tokens", "16",
         "--compile-cache-dir", str(tmp_path / "xla-cache")],
        check=True, env=env, timeout=600, cwd=REPO)
    run_dir = tmp_path / "fleet"
    log = tmp_path / "fleet.log"
    with open(log, "w") as log_f:     # the child holds its own dup
        proc = subprocess.Popen(
            [sys.executable, str(REPO / "scripts" / "serve_fleet.py"),
             "-r", str(art / "model"), "--replicas", "2", "--port",
             "0", "--run-dir", str(run_dir), "--admin",
             "--poll-s", "0.3", "--readmit-after", "1",
             "--restart-delay", "0.5", "--block-tokens", "16",
             "--", "--max-batch", "2", "--decode-chunk", "4"],
            stdout=log_f, stderr=subprocess.STDOUT, env=env, cwd=REPO)
    try:
        url = _wait_ready(log, proc)
        deadline = time.time() + 420
        while _healthy_count(url) != 2 and time.time() < deadline:
            time.sleep(1.0)
        assert _healthy_count(url) == 2, log.read_text()[-3000:]

        # traffic: small shared-prefix trace through the router
        trace = build_trace(10, seed=7, rate_rps=2.0,
                            prefix_groups=2, prefix_len=32,
                            suffix_len=8, max_new_tokens=4,
                            stream_frac=0.5)
        summary = summarize(replay(url, trace, timeout_s=120), trace)
        assert summary["errors"] == 0, summary
        assert summary["ok"] == 10, summary
        time.sleep(1.5)              # let the poller absorb counters
        m = _get_json(url, "/metrics?format=json")
        assert m["fleet_requests_total"] >= 10
        assert m["routed_prefix_total"] >= 1, m
        assert m["fleet_prefix_hit_tokens_total"] > 0, m

        # chaos: SIGKILL r0's child through the admin endpoint
        req = urllib.request.Request(url + "/admin/kill?replica=r0",
                                     data=b"", method="POST")
        assert json.loads(urllib.request.urlopen(
            req, timeout=10).read())["killed"] is True
        t_kill = time.monotonic()
        deadline = time.time() + 300
        saw_down = False
        while time.time() < deadline:
            n = _healthy_count(url)
            if n < 2:
                saw_down = True
            if saw_down and n == 2:
                break
            time.sleep(0.5)
        assert saw_down, "kill never observed on /healthz"
        assert _healthy_count(url) == 2, log.read_text()[-3000:]
        recovery_s = time.monotonic() - t_kill
        # recovered replica takes traffic again
        code, _ = _post(url, {"prompt_ids": [5] * 33,
                              "max_new_tokens": 2}, timeout=120)
        assert code == 200
        sup = (run_dir / "r0" / "supervisor.jsonl").read_text()
        assert '"cause": "crash"' in sup, sup

        # drain: SIGTERM the fleet -> rc 0, replicas exit via the
        # preemption path, no orphan processes
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        assert rc == 0, log.read_text()[-3000:]
        assert "DRAINED" in log.read_text()
        pids = []
        for rid in ("r0", "r1"):
            for line in (run_dir / rid /
                         "supervisor.jsonl").read_text().splitlines():
                rec = json.loads(line)
                if rec.get("event") == "spawn":
                    pids.append(rec["pid"])
        time.sleep(1.0)
        for pid in pids:
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                continue
            raise AssertionError(f"orphan replica pid {pid}")
        print(f"fleet e2e ok: recovery {recovery_s:.1f}s")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)
