"""Request-scoped distributed tracing (observability/reqtrace.py).

The cross-process contract (ISSUE 8): every hop keys its spans on one
X-Request-Id, the per-process ``spans.jsonl`` files stitch into
per-request timelines whose segments explain the measured e2e (clock
skew aligned causally, orphans reported — never silently dropped),
and the SLO watcher turns thresholds into counters + BOUNDED forensic
dumps. Fast tier: synthetic span files plus one tiny in-process
continuous engine; the real fleet round-trip lives in
test_fleet.py/test_serve.py.
"""
import json
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent.parent / "scripts"))

from pytorch_distributed_template_tpu.observability import reqtrace
from pytorch_distributed_template_tpu.observability.reqtrace import (
    RequestTracer,
    SloWatcher,
    mint_request_id,
    sanitize_request_id,
)
from pytorch_distributed_template_tpu.utils import promtext

# ---------------------------------------------------------------------------
# request ids
# ---------------------------------------------------------------------------


def test_mint_and_sanitize_request_ids():
    a, b = mint_request_id(), mint_request_id()
    assert a != b and sanitize_request_id(a) == a
    assert sanitize_request_id("lg-a-11-0042") == "lg-a-11-0042"
    # hostile / malformed ids are rejected (they land in filenames)
    for bad in (None, "", 7, "a" * 65, "../etc/passwd", "x y",
                "nul\x00byte"):
        assert sanitize_request_id(bad) is None


# ---------------------------------------------------------------------------
# the tracer
# ---------------------------------------------------------------------------


def test_tracer_appends_anchor_then_request_keyed_records(tmp_path):
    path = tmp_path / "spans.jsonl"
    tr = RequestTracer(path, process="router")
    t0 = 100.0
    tr.add("r1", "proxy", t0, t0 + 0.25, replica="r0")
    tr.event("r1", "first_token", ttft_s=0.1)
    with pytest.raises(RuntimeError):
        with tr.span("r1", "boom"):
            raise RuntimeError("x")
    tr.close()
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    assert recs[0]["anchor"] == 1 and recs[0]["proc"] == "router"
    proxy = recs[1]
    assert proxy["rid"] == "r1" and proxy["dur_ms"] == 250.0
    assert proxy["attrs"] == {"replica": "r0"}
    # the span context manager records even when the body raises
    assert recs[3]["name"] == "boom" and recs[3]["attrs"]["error"]
    # wall-clock anchoring: epoch-scale timestamps, not monotonic-scale
    assert proxy["t"] > 1e9


def test_tracer_ring_serves_per_request_timelines(tmp_path):
    tr = RequestTracer(tmp_path / "spans.jsonl", ring=4)
    for i in range(6):
        tr.event(f"r{i % 2}", "e", i=i)
    tl = tr.timeline("r1")
    assert [r["attrs"]["i"] for r in tl] == [3, 5]   # ring bounded
    tr.close()


# ---------------------------------------------------------------------------
# SLO watcher
# ---------------------------------------------------------------------------


def test_slo_watcher_counts_breaches_and_bounds_dumps(tmp_path):
    tr = RequestTracer(tmp_path / "spans.jsonl")
    slo = SloWatcher(ttft_s=0.1, e2e_s=1.0, dump_dir=tmp_path,
                     tracer=tr, max_dumps=2, cooldown_s=0.0)
    assert slo.observe("ok", ttft_s=0.05, e2e_s=0.5) == []
    tr.event("slow1", "first_token", ttft_s=0.4)
    assert slo.observe("slow1", ttft_s=0.4, e2e_s=2.0) == \
        ["ttft", "e2e"]
    assert slo.observe("slow2", e2e_s=3.0) == ["e2e"]
    assert slo.observe("slow3", e2e_s=3.0) == ["e2e"]   # over max_dumps
    s = slo.stats()
    assert s["slo_breach_total"] == 3
    assert s["slo_ttft_breach_total"] == 1
    assert s["slo_e2e_breach_total"] == 3
    dumps = sorted(tmp_path.glob("slow_request_*.json"))
    assert len(dumps) == 2 == s["slo_dumps_written"]   # bounded
    d = json.loads((tmp_path / "slow_request_slow1.json").read_text())
    assert d["reasons"] == ["ttft", "e2e"]
    # the dump carries the request's own span timeline from the ring
    assert [r["name"] for r in d["timeline"]] == ["first_token"]
    tr.close()


def test_slo_watcher_cooldown_spaces_dumps(tmp_path):
    slo = SloWatcher(e2e_s=1.0, dump_dir=tmp_path, max_dumps=8,
                     cooldown_s=3600.0)
    slo.observe("a", e2e_s=2.0)
    slo.observe("b", e2e_s=2.0)    # inside cooldown: counted, no dump
    assert slo.stats()["slo_breach_total"] == 2
    assert slo.stats()["slo_dumps_written"] == 1


# ---------------------------------------------------------------------------
# stitching: synthetic multi-process span sets
# ---------------------------------------------------------------------------

T0 = 1_700_000_000.0   # epoch-scale base


def _request_spans(rid, t0=T0, skew=0.0, with_router=True,
                   with_replica=True):
    """One realistic request: 200 ms e2e through router + replica.
    ``skew`` shifts the REPLICA clock (negative = behind)."""
    spans = []
    if with_router:
        spans += [
            {"rid": rid, "name": "request", "proc": "router",
             "pid": 1, "t": t0, "dur_ms": 200.0},
            {"rid": rid, "name": "admission_wait", "proc": "router",
             "pid": 1, "t": t0 + 0.002, "dur_ms": 30.0},
            {"rid": rid, "name": "proxy", "proc": "router", "pid": 1,
             "t": t0 + 0.034, "dur_ms": 160.0,
             "attrs": {"replica": "r0"}},
        ]
    if with_replica:
        s = skew
        spans += [
            {"rid": rid, "name": "http", "proc": "serve", "pid": 2,
             "t": t0 + 0.036 + s, "dur_ms": 155.0},
            {"rid": rid, "name": "queue_wait", "proc": "serve",
             "pid": 2, "t": t0 + 0.038 + s, "dur_ms": 20.0},
            {"rid": rid, "name": "admit", "proc": "serve", "pid": 2,
             "t": t0 + 0.058 + s, "dur_ms": 40.0,
             "attrs": {"mode": "warm", "prefix_hit_tokens": 32}},
            {"rid": rid, "name": "first_token", "proc": "serve",
             "pid": 2, "t": t0 + 0.108 + s, "dur_ms": 0.0,
             "attrs": {"ttft_s": 0.108}},
            {"rid": rid, "name": "complete", "proc": "serve",
             "pid": 2, "t": t0 + 0.180 + s, "dur_ms": 0.0,
             "attrs": {"tokens": 16, "e2e_s": 0.144}},
        ]
    return spans


def test_stitch_decomposes_e2e_into_segments():
    report = reqtrace.stitch_spans(_request_spans("r1"))
    assert report["counts"] == {"requests": 1, "stitched": 1,
                                "partial": 0}
    row = report["requests"][0]
    assert row["stitched"] and row["procs"] == ["router", "serve"]
    seg = row["segments"]
    assert seg["admission_wait"] == pytest.approx(0.030)
    assert seg["scheduler_queue"] == pytest.approx(0.020)
    assert seg["decode"] == pytest.approx(0.072)
    # non-overlapping segments reconstruct the router-observed e2e
    assert row["attributed_s"] == pytest.approx(0.200, abs=1e-6)
    assert row["e2e_source"] == "router"
    assert row["coverage"] == pytest.approx(1.0, abs=1e-3)
    assert row["ttft_s"] == pytest.approx(0.108)
    assert row["tokens"] == 16


def test_stitch_joins_client_e2e_and_reports_residual():
    report = reqtrace.stitch_spans(
        _request_spans("r1"), client_e2e_by_rid={"r1": 0.21})
    row = report["requests"][0]
    assert row["e2e_source"] == "client"
    assert row["residual_s"] == pytest.approx(0.01, abs=1e-6)
    assert row["coverage"] == pytest.approx(0.2 / 0.21, abs=1e-3)


def test_stitch_aligns_skewed_replica_clock():
    # replica clock 5 s BEHIND: its spans appear to start before the
    # router dispatched them — causally impossible, so the stitcher
    # shifts that process forward by the median violation
    spans = []
    for i in range(3):
        spans += _request_spans(f"r{i}", t0=T0 + i, skew=-5.0)
    report = reqtrace.stitch_spans(spans)
    assert report["offsets"] == {"serve:2": pytest.approx(4.998)}
    for row in report["requests"]:
        assert row["stitched"]
        assert all(v >= 0 for v in row["segments"].values())
        assert row["attributed_s"] == pytest.approx(0.2, abs=5e-3)
    # an already-causal set is NOT "aligned" (genuine queueing delay
    # must survive): positive skew = replica clock ahead = no shift
    ahead = reqtrace.stitch_spans(_request_spans("r9", skew=0.004))
    assert ahead["offsets"] == {}


def test_stitch_anchors_on_the_last_proxy_attempt():
    """A router retry records one proxy span per attempt under the
    same rid; attribution and flow linkage must anchor on the LAST
    (served) attempt, not the dead first one."""
    spans = _request_spans("r1")
    spans.append({"rid": "r1", "name": "proxy", "proc": "router",
                  "pid": 1, "t": T0 + 0.004, "dur_ms": 25.0,
                  "attrs": {"replica": "r9", "reason": "affinity"}})
    report = reqtrace.stitch_spans(spans)
    seg = report["requests"][0]["segments"]
    # anchored on the failed attempt this would read 0.032
    assert seg["proxy_send"] == pytest.approx(0.002, abs=1e-6)
    assert seg["proxy_return"] == pytest.approx(0.003, abs=1e-6)
    trace = reqtrace.to_perfetto(spans)
    flow_s = next(e for e in trace["traceEvents"] if e["ph"] == "s")
    # the flow departs from the served attempt's start (t0 + 0.034),
    # not the dead attempt's (t0 + 0.004)
    assert flow_s["ts"] == pytest.approx(0.034 * 1e6, abs=200)


def test_stitch_reports_orphan_spans_as_partial():
    spans = (_request_spans("full")
             + _request_spans("router_only", with_replica=False)
             + _request_spans("replica_only", with_router=False))
    report = reqtrace.stitch_spans(spans)
    assert report["counts"] == {"requests": 3, "stitched": 1,
                                "partial": 2}
    by_rid = {r["rid"]: r for r in report["requests"]}
    assert not by_rid["router_only"]["stitched"]
    # orphans still decompose what they can — replica-side segments
    # exist without any router span
    assert "scheduler_queue" in by_rid["replica_only"]["segments"]


def test_attribution_names_the_p99_request():
    spans = []
    for i in range(20):
        spans += _request_spans(f"r{i:02d}", t0=T0 + i)
    # one outlier: +1 s of admission wait dominates its e2e
    slow = _request_spans("slowboi", t0=T0 + 50)
    slow[0]["dur_ms"] = 1200.0                    # request
    slow[1]["dur_ms"] = 1030.0                    # admission_wait
    for rec in slow[2:]:
        rec["t"] += 1.0
    report = reqtrace.stitch_spans(spans + slow)
    att = reqtrace.attribution(report)
    assert att["attributed_requests"] == 21
    assert att["p99_request"]["rid"] == "slowboi"
    worst_seg = max(att["p99_request"]["segments"].items(),
                    key=lambda kv: kv[1])
    assert worst_seg[0] == "admission_wait"       # the "240 ms of it
    assert worst_seg[1] == pytest.approx(1.03)    # is WFQ wait" row
    # linear-interpolation p99 over twenty 0.03 s waits + one 1.03 s
    # outlier: 0.03 + 0.8 * (1.03 - 0.03)
    assert att["seg_admission_wait_p99_s"] == pytest.approx(0.83)
    assert att["coverage_p50"] == pytest.approx(1.0, abs=1e-3)


def test_perfetto_trace_links_processes_with_flow_events():
    trace = reqtrace.to_perfetto(_request_spans("r1"))
    events = trace["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    assert {e["args"]["name"] for e in meta} == \
        {"router (pid 1)", "serve (pid 2)"}
    flows = [e for e in events if e["ph"] in ("s", "f")]
    assert [e["ph"] for e in flows] == ["s", "f"]
    assert flows[0]["id"] == flows[1]["id"]       # linked pair
    assert flows[0]["pid"] != flows[1]["pid"]     # across processes
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["args"]["rid"] for e in xs} == {"r1"}
    assert all(e["dur"] >= 1 for e in xs)         # visible in the UI


def test_load_spans_skips_torn_tail_lines(tmp_path):
    path = tmp_path / "spans.jsonl"
    lines = [json.dumps(s) for s in _request_spans("r1")]
    path.write_text("\n".join(lines) + '\n{"rid": "torn", "na')
    spans = reqtrace.load_spans([path])
    assert len(spans) == len(lines)               # torn tail skipped


# ---------------------------------------------------------------------------
# the CLI + run-dir discovery (scripts/trace_stitch.py)
# ---------------------------------------------------------------------------


def _fleet_run_dir(tmp_path, n=3):
    """A fleet-shaped run dir: router spans at the top, replica spans
    under its save dir — exactly what serve_fleet leaves behind."""
    run = tmp_path / "fleet"
    (run / "r0" / "save").mkdir(parents=True)
    router_f = run / "spans.jsonl"
    serve_f = run / "r0" / "save" / "spans.jsonl"
    router, serve = [], []
    for i in range(n):
        spans = _request_spans(f"r{i}", t0=T0 + i)
        router += [s for s in spans if s["proc"] == "router"]
        serve += [s for s in spans if s["proc"] == "serve"]
    router_f.write_text("\n".join(json.dumps(s) for s in router) + "\n")
    serve_f.write_text("\n".join(json.dumps(s) for s in serve) + "\n")
    return run


def test_stitch_run_discovers_and_attributes(tmp_path):
    report = reqtrace.stitch_run(_fleet_run_dir(tmp_path))
    assert report["counts"]["stitched"] == 3
    assert report["attribution"]["coverage_p50"] == \
        pytest.approx(1.0, abs=1e-3)


def test_trace_stitch_cli_gates_and_outputs(tmp_path, capsys):
    import trace_stitch

    run = _fleet_run_dir(tmp_path)
    client = tmp_path / "loadgen.json"
    client.write_text(json.dumps({"by_request": [
        {"rid": "r0", "total_s": 0.21, "ok": True},
        {"rid": "r1", "total_s": 0.21, "ok": True},
        {"rid": "nope", "total_s": 0.1, "ok": False},   # filtered
    ]}))
    perfetto = tmp_path / "merged.json"
    rc = trace_stitch.main([
        "--run-dir", str(run), "--client", str(client),
        "--perfetto", str(perfetto), "--json",
        "--require-stitched", "3", "--min-coverage", "0.9"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["counts"]["stitched"] == 3
    by_rid = {r["rid"]: r for r in report["requests"]}
    assert by_rid["r0"]["e2e_source"] == "client"
    assert by_rid["r2"]["e2e_source"] == "router"   # no client row
    trace = json.loads(perfetto.read_text())
    assert any(e["ph"] == "s" for e in trace["traceEvents"])
    # the markdown rendering carries the attribution table
    assert trace_stitch.main(["--run-dir", str(run)]) == 0
    md = capsys.readouterr().out
    assert "Tail-latency attribution" in md and "admission_wait" in md
    # gates fail loudly
    assert trace_stitch.main(
        ["--run-dir", str(run), "--require-stitched", "99"]) == 1
    capsys.readouterr()
    assert trace_stitch.main(["--run-dir", str(tmp_path / "nope")]) == 2


def test_telemetry_report_renders_reqtrace_section(tmp_path, capsys):
    import telemetry_report

    run = _fleet_run_dir(tmp_path)
    section = telemetry_report.analyze_reqtrace(run_dir=run)
    assert section["stitched"] == 3 and section["span_files"] == 2
    # explicit --spans overlapping --run-dir discovery dedupes on the
    # resolved path — an overlap must not double-load span records
    overlap = telemetry_report.analyze_reqtrace(
        run_dir=run, span_files=[str(run / "spans.jsonl")])
    assert overlap["span_files"] == 2
    assert overlap["stitched"] == 3
    assert section["coverage_p50"] == pytest.approx(1.0, abs=1e-3)
    assert section["slow_request_dumps"] == 0
    assert telemetry_report.analyze_reqtrace(
        run_dir=tmp_path / "empty") == {}
    rc = telemetry_report.main(["--run-dir", str(run)])
    assert rc == 0
    assert "Request tracing" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# latency histograms (utils/promtext) — the aggregable form
# ---------------------------------------------------------------------------


def test_latency_histogram_snapshot_quantile_and_prom_render():
    h = promtext.LatencyHistogram()
    for s in (0.003, 0.02, 0.02, 0.2, 3.0):
        h.observe(s)
    snap = h.snapshot()
    assert promtext.is_histogram(snap)
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(3.243)
    assert snap["buckets"]["0.005"] == 1          # cumulative
    assert snap["buckets"]["0.025"] == 3
    assert snap["buckets"]["+Inf"] == 5
    q50 = promtext.histogram_quantile(snap, 0.5)
    assert 0.01 <= q50 <= 0.025                   # in the right bucket
    assert promtext.histogram_quantile(
        promtext.zero_histogram(), 0.5) is None
    text = promtext.prometheus_text(
        {"ttft_seconds": snap, "requests_total": 5}, prefix="pdt_x")
    assert "# TYPE pdt_x_ttft_seconds histogram" in text
    assert 'pdt_x_ttft_seconds_bucket{le="+Inf"} 5' in text
    assert "pdt_x_ttft_seconds_count 5" in text


def test_histograms_aggregate_by_bucket_sums():
    a, b = promtext.LatencyHistogram(), promtext.LatencyHistogram()
    a.observe(0.01)
    b.observe(1.5)
    b.observe(0.01)
    merged = promtext.add_histograms(
        promtext.add_histograms(promtext.zero_histogram(),
                                a.snapshot()), b.snapshot())
    assert merged["count"] == 3
    assert merged["buckets"]["0.01"] == 2
    # scale=-1 subtracts: the reset-correction delta
    delta = promtext.add_histograms(
        promtext.add_histograms(promtext.zero_histogram(),
                                merged), a.snapshot(), scale=-1.0)
    assert delta["count"] == 2 and delta["buckets"]["2.5"] == 2


def test_replica_histogram_fold_survives_restart():
    """fleet/replicas.Replica folds per-replica histogram snapshots
    reset-corrected: a count DROP means the replica restarted and the
    new snapshot IS the delta (same contract as the scalar counters)."""
    from pytorch_distributed_template_tpu.fleet.replicas import Replica

    r = Replica("r0", url="http://127.0.0.1:1")
    h = promtext.LatencyHistogram()
    h.observe(0.02)
    r.absorb_counters({"e2e_seconds": h.snapshot()})
    h.observe(0.02)
    r.absorb_counters({"e2e_seconds": h.snapshot()})
    assert r.cum_hist["e2e_seconds"]["count"] == 2
    fresh = promtext.LatencyHistogram()          # restart: counts drop
    fresh.observe(5.0)
    r.absorb_counters({"e2e_seconds": fresh.snapshot()})
    cum = r.cum_hist["e2e_seconds"]
    assert cum["count"] == 3                     # nothing double/lost
    assert cum["buckets"]["0.025"] == 2 and cum["buckets"]["5"] == 3


# ---------------------------------------------------------------------------
# the continuous engine records request-keyed spans + server-side TTFT
# ---------------------------------------------------------------------------


def test_continuous_engine_traces_requests_and_ttft(tmp_path):
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_template_tpu.config.registry import MODELS
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.engine.continuous import (
        ContinuousBatchingService,
    )

    model = MODELS.get("Llama")(vocab_size=64, n_layer=2, n_head=4,
                                n_kv_head=2, d_model=32, max_len=128)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    tracer = RequestTracer(tmp_path / "spans.jsonl", process="serve")
    slo = SloWatcher(e2e_s=1e-9, dump_dir=tmp_path, tracer=tracer,
                     cooldown_s=0.0)
    service = ContinuousBatchingService.from_model(
        model, params, slots=2, chunk=4, window_ms=10.0,
        tracer=tracer, slo=slo)
    try:
        out = service.generate(prompt_ids=[1, 2, 3, 4, 5],
                               max_new_tokens=6, request_id="eng-1")
        assert len(out["ids"]) == 6
        # the worker finalizes SLO/trace bookkeeping a hair AFTER the
        # caller's event fires, and the dump exists before it is
        # written: wait until it parses, don't race it
        dump = None
        deadline = time.monotonic() + 10
        while dump is None:
            try:
                dump = json.loads(
                    (tmp_path / "slow_request_eng-1.json").read_text())
            except (OSError, ValueError):
                assert time.monotonic() < deadline, "no SLO dump"
                time.sleep(0.02)
        tracer.flush()
        recs = [json.loads(l) for l in
                (tmp_path / "spans.jsonl").read_text().splitlines()]
        names = [r["name"] for r in recs if r.get("rid") == "eng-1"]
        # the engine-side lifecycle: queue wait -> admit (annotated)
        # -> first token -> completion
        for expected in ("queue_wait", "admit", "first_token",
                         "complete"):
            assert expected in names, (expected, names)
        admit = next(r for r in recs if r.get("rid") == "eng-1"
                     and r["name"] == "admit")
        assert admit["attrs"]["mode"] in ("cold", "warm", "paged")
        assert "prefix_hit_tokens" in admit["attrs"]
        done = next(r for r in recs if r.get("rid") == "eng-1"
                    and r["name"] == "complete")
        assert done["attrs"]["tokens"] == 6
        # server-side TTFT (ISSUE 8 satellite): percentiles + the
        # aggregable histograms both fill from the same stamp
        lat = service.latency_percentiles()
        assert lat["ttft_p50_s"] <= lat["p50_s"]
        assert service.hist["ttft_seconds"].snapshot()["count"] == 1
        assert service.hist["e2e_seconds"].snapshot()["count"] == 1
        # the 1 ns SLO breached and dumped, carrying the timeline
        assert service.slo_stats()["slo_breach_total"] == 1
        assert {r["name"] for r in dump["timeline"]} >= \
            {"queue_wait", "admit", "complete"}
        # an untraced request (no rid) must not throw or record
        service.generate(prompt_ids=[1, 2, 3], max_new_tokens=2)
    finally:
        tracer.close()
