"""The unified telemetry subsystem (ISSUE 1 tentpole): flight-recorder
JSONL schema round-trip, ring-buffer eviction, span nesting/exception
safety, watchdog stall dumps, and serve.py's /metrics endpoint."""
import json
import logging
import sys
import threading
import time
from pathlib import Path

import pytest

from pytorch_distributed_template_tpu.observability.telemetry import (
    FlightRecorder, host_rss_bytes, read_jsonl,
)
from pytorch_distributed_template_tpu.observability.trace import (
    SpanRecorder,
)
from pytorch_distributed_template_tpu.utils.watchdog import StepWatchdog

sys.path.insert(0, str(Path(__file__).parent.parent))


# ---------------------------------------------------------------------------
# FlightRecorder
# ---------------------------------------------------------------------------


def test_recorder_jsonl_schema_roundtrip(tmp_path):
    rec = FlightRecorder(run_dir=tmp_path, capacity=8, memory_every=1)
    rec.record(0, wall_ms=100.0, data_wait_ms=5.0, loss=2.5,
               lr=3e-4, tokens=1024, examples=8)
    rec.record(1, wall_ms=90.0, tokens=1024, examples=8)
    rec.close()

    records = read_jsonl(tmp_path / "telemetry.jsonl")
    assert len(records) == 2
    r0 = records[0]
    assert r0["v"] == 1 and r0["step"] == 0
    assert r0["wall_ms"] == 100.0 and r0["loss"] == 2.5
    assert r0["tokens"] == 1024
    assert "t" in r0
    # memory_every=1 attaches host RSS on linux (guarded: the probe can
    # legitimately return None on exotic platforms)
    if host_rss_bytes() is not None:
        assert r0["host_rss_mb"] > 0
    # every line is standalone strict JSON (the file parses line-wise,
    # no trailing commas / NaN literals)
    for line in (tmp_path / "telemetry.jsonl").read_text().splitlines():
        json.loads(line)


def test_recorder_nulls_nonfinite_and_drops_none(tmp_path):
    rec = FlightRecorder(run_dir=tmp_path, capacity=8, memory_every=0)
    rec.record(0, loss=float("nan"), grad_norm=float("inf"), mfu=None)
    rec.close()
    (r,) = read_jsonl(tmp_path / "telemetry.jsonl")
    assert r["loss"] is None and r["grad_norm"] is None
    assert "mfu" not in r


def test_recorder_ring_eviction():
    rec = FlightRecorder(run_dir=None, capacity=4, memory_every=0)
    for i in range(10):
        rec.record(i, wall_ms=10.0)
    last = rec.last()
    assert len(last) == 4
    assert [r["step"] for r in last] == [6, 7, 8, 9]
    assert [r["step"] for r in rec.last(2)] == [8, 9]


def test_recorder_aggregates_from_records():
    rec = FlightRecorder(run_dir=None, capacity=64, memory_every=0)
    for i in range(10):
        rec.record(i, wall_ms=100.0, tokens=500, examples=5)
    agg = rec.aggregates()
    assert agg["steps"] == 10
    assert agg["steps_per_sec"] == pytest.approx(10.0, rel=1e-6)
    assert agg["tokens_per_sec"] == pytest.approx(5000.0, rel=1e-3)
    assert agg["examples_per_sec"] == pytest.approx(50.0, rel=1e-3)


def test_recorder_thread_safe_no_file():
    rec = FlightRecorder(run_dir=None, capacity=128, memory_every=0)

    def worker(base):
        for i in range(50):
            rec.record(base + i, wall_ms=1.0)

    threads = [threading.Thread(target=worker, args=(k * 100,))
               for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(rec.last()) == 128  # full ring, no crash


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------


def test_span_nesting_records_both_levels():
    sr = SpanRecorder()
    with sr.span("outer"):
        with sr.span("inner"):
            time.sleep(0.01)
    events = sr.snapshot()
    names = [e["name"] for e in events]
    assert names == ["inner", "outer"]  # inner closes first
    inner, outer = events
    assert outer["dur"] >= inner["dur"]
    # inner nests inside outer on the trace timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e3


def test_span_exception_safety():
    sr = SpanRecorder()
    with pytest.raises(ValueError):
        with sr.span("boom", step=3):
            raise ValueError("x")
    (e,) = sr.snapshot()
    assert e["name"] == "boom"
    assert e["args"]["error"] is True and e["args"]["step"] == 3
    assert sr.active_spans() == []  # the open-span stack unwound


def test_active_spans_visible_mid_flight():
    sr = SpanRecorder()
    with sr.span("outer"):
        with sr.span("inner"):
            active = sr.active_spans()
    assert [s["name"] for s in active] == ["outer", "inner"]
    assert all(s["elapsed_ms"] >= 0 for s in active)
    assert sr.active_spans() == []


def test_span_chrome_trace_dump_loads(tmp_path):
    sr = SpanRecorder()
    with sr.span("a", k=1):
        pass
    path = sr.dump(tmp_path / "trace.json")
    trace = json.loads(Path(path).read_text())
    (e,) = trace["traceEvents"]
    assert e["ph"] == "X" and e["name"] == "a"
    assert set(e) >= {"ts", "dur", "pid", "tid"}


def test_span_ring_bounded():
    sr = SpanRecorder(capacity=8)
    for i in range(20):
        with sr.span(f"s{i}"):
            pass
    assert len(sr.snapshot()) == 8


# ---------------------------------------------------------------------------
# watchdog stall dump
# ---------------------------------------------------------------------------


def test_watchdog_stall_dump_contents(tmp_path, caplog):
    rec = FlightRecorder(run_dir=None, capacity=8, memory_every=0)
    for i in range(5):
        rec.record(i, wall_ms=10.0, loss=1.0)
    sr = SpanRecorder()
    dump_path = tmp_path / "stall_dump.json"
    wd = StepWatchdog(timeout_s=0.2, dump_stacks=False, recorder=rec,
                      spans=sr, dump_path=dump_path, dump_last_n=3)
    wd.start()
    try:
        with caplog.at_level(logging.ERROR):
            with sr.span("train/step", step=5):
                time.sleep(0.7)  # stall inside an open span
    finally:
        wd.stop()
    assert wd.alarms >= 1
    report = json.loads(dump_path.read_text())
    assert report["stalled_s"] >= 0.2
    assert [s["name"] for s in report["active_spans"]] == ["train/step"]
    assert len(report["last_records"]) == 3
    assert report["last_records"][-1]["step"] == 4
    assert any("stall report" in r.message for r in caplog.records)


def test_watchdog_report_without_sinks():
    wd = StepWatchdog(timeout_s=0)  # legacy construction still works
    assert wd.stall_report(1.0)["stalled_s"] == 1.0


# ---------------------------------------------------------------------------
# serve.py /metrics
# ---------------------------------------------------------------------------


class _FakeQueue:
    def qsize(self):
        return 3


class _FakeContinuousService:
    stats = {"requests": 7, "completed": 5, "chunks": 11,
             "admissions": 6, "eras": 2, "max_active": 4,
             "tokens_generated": 320, "cancelled": 1}
    _slots = 8
    _queue = _FakeQueue()

    def queue_depth(self):
        return 3

    def live_slots(self):
        return 2

    def latency_percentiles(self):
        return {"p50_s": 0.5, "p95_s": 1.0, "n": 5}


def test_service_metrics_snapshot():
    import serve

    m = serve.service_metrics(_FakeContinuousService())
    assert m["requests_total"] == 7
    assert m["requests_completed"] == 5
    assert m["tokens_generated_total"] == 320
    assert m["cancelled_total"] == 1
    assert m["queue_depth"] == 3
    assert m["live_slots"] == 2
    assert m["slots"] == 8
    assert m["latency"]["p95_s"] == 1.0


def test_prometheus_text_exposition():
    import serve

    text = serve.prometheus_text(
        serve.service_metrics(_FakeContinuousService()))
    assert "# TYPE pdt_serve_tokens_generated_total counter" in text
    assert "pdt_serve_tokens_generated_total 320" in text
    assert "# TYPE pdt_serve_queue_depth gauge" in text
    assert "pdt_serve_queue_depth 3" in text
    assert "pdt_serve_latency_p95_s 1.0" in text
    # non-numeric fields stay out: the scheduler CLASS-NAME string is
    # never exported (the numeric scheduler_progress_total counter —
    # the fleet's wedge-detection signal, ISSUE 9 — legitimately is)
    assert "pdt_serve_scheduler " not in text
    assert "ContinuousBatchingService" not in text
    assert "pdt_serve_scheduler_progress_total" in text


def test_metrics_endpoint_http(tmp_path):
    """GET /metrics end-to-end over a real socket: Prometheus text by
    default, JSON with ?format=json."""
    import http.client

    from http.server import ThreadingHTTPServer

    import serve

    server = ThreadingHTTPServer(
        ("127.0.0.1", 0), serve.make_handler(_FakeContinuousService()))
    port = server.server_address[1]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = resp.read().decode()
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        assert "pdt_serve_queue_depth 3" in body
        assert "pdt_serve_tokens_generated_total 320" in body

        conn.request("GET", "/metrics?format=json")
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        assert resp.status == 200
        assert payload["queue_depth"] == 3
        assert payload["tokens_generated_total"] == 320
        assert payload["cancelled_total"] == 1
    finally:
        server.shutdown()
        server.server_close()
