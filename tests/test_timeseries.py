"""Fleet observability substrate (ISSUE 14): time-series ring,
service-time models, goodput accounting, dashboard, drift gate."""
import copy
import json
import threading
import urllib.request

import pytest

from pytorch_distributed_template_tpu.fleet.admission import (
    FairAdmission,
)
from pytorch_distributed_template_tpu.fleet.replicas import (
    FleetManager, Replica,
)
from pytorch_distributed_template_tpu.fleet.router import (
    RouterStats, build_router,
)
from pytorch_distributed_template_tpu.observability import (
    servicedist,
)
from pytorch_distributed_template_tpu.observability.timeseries import (
    TimeSeriesStore, load_timeseries, rate_name, set_default_store,
)


# ---------------------------------------------------------------------------
# TimeSeriesStore: ring bounds / delta / reset correction
# ---------------------------------------------------------------------------


class TestTimeSeriesStore:
    def test_counter_deltas_become_rates(self, tmp_path):
        s = TimeSeriesStore(tmp_path / "ts.jsonl", interval_s=1.0)
        s.observe(counters={"tokens_generated_total": 0}, t=100.0)
        s.observe(counters={"tokens_generated_total": 50}, t=100.5)
        s.observe(counters={"tokens_generated_total": 80}, t=101.2)
        s.flush(t=102.0)
        pts = s.points()
        assert len(pts) == 2
        # bucket 100: delta 50 over 0.5 s covered span
        assert pts[0]["tokens_generated_per_s"] == pytest.approx(100.0)
        # bucket 101: delta 30 over 0.7 s
        assert pts[1]["tokens_generated_per_s"] == pytest.approx(
            30 / 0.7, rel=1e-3)
        s.close()

    def test_reset_correction(self, tmp_path):
        """A counter DROP means the source restarted: the new value
        IS the delta (fleet/replicas discipline) — the rate must not
        go negative or spike."""
        s = TimeSeriesStore(None, interval_s=1.0)
        s.observe(counters={"c_total": 100}, t=10.0)
        s.observe(counters={"c_total": 200}, t=10.9)
        s.observe(counters={"c_total": 7}, t=11.9)   # restart
        s.flush(t=13.0)
        pts = s.points()
        assert pts[1]["c_per_s"] == pytest.approx(7.0, rel=1e-3)
        assert all(p.get("c_per_s", 0) >= 0 for p in pts)

    def test_ring_bounded(self):
        s = TimeSeriesStore(None, interval_s=1.0, window=4)
        for i in range(10):
            s.observe(counters={"c_total": i}, gauges={"g": i},
                      t=100.0 + i)
        s.flush(t=200.0)
        assert len(s.points()) == 4
        # the oldest points fell off; the newest survives
        assert s.points()[-1]["g"] == 9.0

    def test_gauges_sample_last_write(self):
        s = TimeSeriesStore(None, interval_s=1.0)
        s.observe(gauges={"queue_depth": 3}, t=50.1)
        s.observe(gauges={"queue_depth": 9}, t=50.8)
        s.flush(t=51.5)
        assert s.points()[0]["queue_depth"] == 9.0

    def test_first_bucket_emits_no_rate(self):
        """A single first-ever observation covers no span — emitting
        a rate from it would report the whole counter history as one
        interval's throughput."""
        s = TimeSeriesStore(None, interval_s=1.0)
        s.observe(counters={"c_total": 10_000},
                  gauges={"g": 1}, t=100.0)
        s.flush(t=101.0)
        (p,) = s.points()
        assert "c_per_s" not in p and p["g"] == 1.0

    def test_jsonl_roundtrip_and_query(self, tmp_path):
        path = tmp_path / "ts.jsonl"
        s = TimeSeriesStore(path, interval_s=1.0)
        for i in range(5):
            s.observe(counters={"c_total": i * 10},
                      gauges={"g": i}, t=100.0 + i)
        s.flush(t=110.0)
        loaded = load_timeseries(path)
        assert loaded == s.points()
        assert s.quantile("g", 0.5) == 2.0
        assert s.latest("g") == 4.0
        assert "c_per_s" in s.series_names()
        assert s.summary()["g"]["p50"] == 2.0
        s.close()

    def test_observe_flat_classifies_by_suffix(self):
        s = TimeSeriesStore(None, interval_s=1.0)
        s.observe_flat({"x_total": 5, "depth": 2, "name": "nope",
                        "hist": {"buckets": {}}, "flag": True},
                       t=10.0)
        s.observe_flat({"x_total": 9, "depth": 4}, t=10.5)
        s.flush(t=12.0)
        (p,) = s.points()
        assert p["x_per_s"] == pytest.approx(8.0)
        assert p["depth"] == 4.0
        assert "name" not in p and "flag" not in p

    def test_rate_name(self):
        assert rate_name("tokens_total") == "tokens_per_s"
        assert rate_name("chunks") == "chunks_per_s"


# ---------------------------------------------------------------------------
# servicedist: quantile extraction from known synthetic spans
# ---------------------------------------------------------------------------


def _synthetic_spans(n=10, admit_ms=200.0, queue_ms=100.0):
    """n cross-process request timelines with EXACTLY known segment
    durations (admit = admit_ms, scheduler_queue = queue_ms)."""
    spans = []
    for i in range(n):
        rid, t = f"req{i:03d}", 100.0 + i * 5
        spans += [
            {"rid": rid, "name": "request", "proc": "router",
             "pid": 1, "t": t, "dur_ms": 1000.0,
             "attrs": {"stream": False}},
            {"rid": rid, "name": "admission_wait", "proc": "router",
             "pid": 1, "t": t + 0.01, "dur_ms": 40.0},
            {"rid": rid, "name": "proxy", "proc": "router", "pid": 1,
             "t": t + 0.06, "dur_ms": 900.0},
            {"rid": rid, "name": "http", "proc": "serve", "pid": 2,
             "t": t + 0.07, "dur_ms": 880.0,
             "attrs": {"stream": bool(i % 2)}},
            {"rid": rid, "name": "queue_wait", "proc": "serve",
             "pid": 2, "t": t + 0.08, "dur_ms": queue_ms},
            {"rid": rid, "name": "admit", "proc": "serve", "pid": 2,
             "t": t + 0.08 + queue_ms / 1e3, "dur_ms": admit_ms,
             "attrs": {"mode": "warm" if i % 2 else "cold",
                       "bucket": 64}},
            {"rid": rid, "name": "first_token", "proc": "serve",
             "pid": 2, "t": t + 0.08 + (queue_ms + admit_ms) / 1e3,
             "dur_ms": 0.0, "attrs": {"ttft_s": 0.3}},
            {"rid": rid, "name": "complete", "proc": "serve",
             "pid": 2, "t": t + 0.9, "dur_ms": 0.0,
             "attrs": {"tokens": 16, "stop_reason": "length"}},
        ]
    return spans


class TestServiceModel:
    def test_quantiles_match_known_segments(self):
        model = servicedist.build_service_model(_synthetic_spans())
        admit = model["segments"]["admit"]
        # every synthetic admit is exactly 200 ms: p50 == p99 == 0.2
        assert admit["count"] == 10
        assert admit["p50_s"] == pytest.approx(0.2, abs=1e-6)
        assert admit["p99_s"] == pytest.approx(0.2, abs=1e-6)
        sq = model["segments"]["scheduler_queue"]
        assert sq["p50_s"] == pytest.approx(0.1, abs=1e-6)
        assert model["version"] == servicedist.SERVICE_MODEL_VERSION
        assert model["coverage"]["frac"] >= 0.99

    def test_route_classes_split_warm_cold_and_stream(self):
        model = servicedist.build_service_model(_synthetic_spans())
        classes = model["segments"]["admit"]["classes"]
        assert "warm|stream|b64" in classes
        assert "cold|unary|b64" in classes
        assert sum(c["count"] for c in classes.values()) == 10

    def test_histogram_counts_align_to_edges(self):
        vals = [0.2] * 5
        counts = servicedist.hist_counts(vals)
        assert sum(counts) == 5
        import bisect

        assert counts[bisect.bisect_left(
            servicedist.LOG_EDGES_S, 0.2)] == 5

    def test_model_roundtrip(self, tmp_path):
        model = servicedist.build_service_model(_synthetic_spans())
        path = servicedist.write_service_model(
            model, tmp_path / "service_model.json")
        loaded = servicedist.load_service_model(path)
        assert loaded == json.loads(json.dumps(model))

    def test_route_class_bucket_falls_back_to_queue_wait(self):
        recs = [
            {"name": "queue_wait", "attrs": {"bucket": 128}},
            {"name": "admit", "attrs": {"mode": "paged"}},
            {"name": "http", "attrs": {"stream": True}},
        ]
        assert servicedist.route_class(recs) == "paged|stream|b128"

    def test_prompt_len_bucket(self):
        assert servicedist.prompt_len_bucket(0) == 0
        assert servicedist.prompt_len_bucket(1) == 32
        assert servicedist.prompt_len_bucket(33) == 64
        assert servicedist.prompt_len_bucket(64) == 64
        assert servicedist.prompt_len_bucket(65) == 128


# ---------------------------------------------------------------------------
# drift gate: pass/fail both directions
# ---------------------------------------------------------------------------


class TestDrift:
    def _model(self):
        return servicedist.build_service_model(_synthetic_spans())

    def test_self_compare_passes_at_tolerance_zero(self):
        m = self._model()
        out = servicedist.drift_report(m, m, tolerance=0.0)
        assert out["shifts"] == []
        assert out["compared"]          # it actually compared things

    def test_slower_segment_fails(self):
        base = self._model()
        cur = copy.deepcopy(base)
        cur["segments"]["admit"]["p99_s"] = round(
            base["segments"]["admit"]["p99_s"] * 2.0, 6)
        out = servicedist.drift_report(cur, base, tolerance=0.25)
        assert any(s["segment"] == "admit" for s in out["shifts"])

    def test_faster_segment_also_fails(self):
        """A segment getting 10x FASTER is a behavior change too
        (usually a broken measurement) — both directions gate."""
        base = self._model()
        cur = copy.deepcopy(base)
        cur["segments"]["admit"]["p50_s"] = round(
            base["segments"]["admit"]["p50_s"] / 10.0, 6)
        out = servicedist.drift_report(cur, base, tolerance=0.25)
        assert any(s["segment"] == "admit" for s in out["shifts"])

    def test_within_tolerance_passes(self):
        base = self._model()
        cur = copy.deepcopy(base)
        cur["segments"]["admit"]["p99_s"] = round(
            base["segments"]["admit"]["p99_s"] * 1.1, 6)
        out = servicedist.drift_report(cur, base, tolerance=0.25)
        assert out["shifts"] == []

    def test_missing_segment_is_a_shift(self):
        base = self._model()
        cur = copy.deepcopy(base)
        del cur["segments"]["admit"]
        out = servicedist.drift_report(cur, base, tolerance=0.5)
        assert any(s["kind"] == "missing" for s in out["shifts"])

    def test_cli_drift_gate(self, tmp_path):
        """telemetry_report --drift: exit 0 on self-compare at
        tolerance 0, exit 1 on a perturbed copy."""
        import scripts.telemetry_report as tr

        base = self._model()
        a = servicedist.write_service_model(base, tmp_path / "a.json")
        pert = copy.deepcopy(base)
        pert["segments"]["admit"]["p99_s"] = round(
            base["segments"]["admit"]["p99_s"] * 3.0, 6)
        b = servicedist.write_service_model(pert, tmp_path / "b.json")
        assert tr.main(["--drift", str(a), str(a),
                        "--drift-tolerance", "0", "--json"]) == 0
        assert tr.main(["--drift", str(b), str(a),
                        "--drift-tolerance", "0.25", "--json"]) == 1


# ---------------------------------------------------------------------------
# goodput classification
# ---------------------------------------------------------------------------


class TestGoodput:
    def test_excluded_outcomes(self):
        """Deadline / cancelled / error tokens count raw, never
        goodput (the ISSUE 14 classification contract)."""
        g = servicedist.GoodputMeter()
        g.observe(10, outcome="proxied")
        g.observe(7, outcome="deadline")
        g.observe(5, outcome="cancelled")
        g.observe(3, outcome="upstream_error")
        st = g.stats()
        assert st["raw_tokens_total"] == 25
        assert st["served_tokens_total"] == 10
        assert st["goodput_tokens_total"] == 10
        assert st["goodput_tokens_total"] <= st["raw_tokens_total"]

    def test_slo_tier(self):
        g = servicedist.GoodputMeter(ttft_s=0.1, e2e_s=1.0)
        g.observe(10, outcome="proxied", ttft_s=0.05, e2e_s=0.5)
        g.observe(10, outcome="proxied", ttft_s=0.5, e2e_s=0.5)
        g.observe(10, outcome="proxied", ttft_s=0.05, e2e_s=2.0)
        st = g.stats()
        assert st["served_tokens_total"] == 30
        assert st["goodput_tokens_total"] == 10

    def test_deadline_feasible_tier_and_tenants(self):
        g = servicedist.GoodputMeter()
        g.observe(8, outcome="proxied", tenant="a",
                  had_deadline=True)
        g.observe(4, outcome="proxied", tenant="b")
        g.observe(6, outcome="deadline", tenant="b",
                  had_deadline=True)
        st = g.stats()
        assert st["deadline_goodput_tokens_total"] == 8
        tnts = st["goodput_tenants"]
        assert tnts["a"]["goodput_frac"] == 1.0
        assert tnts["b"]["good_tokens"] == 4
        assert tnts["b"]["goodput_frac"] == 0.4

    def test_deadline_tier_is_subset_of_served_not_slo(self):
        """A served deadline-carrying request met its budget even
        when it breached the (separate) SLO — the feasible tier
        follows SERVED, not the SLO tier."""
        g = servicedist.GoodputMeter(e2e_s=0.001)
        g.observe(9, outcome="proxied", e2e_s=5.0,
                  had_deadline=True)      # SLO-breached but served
        st = g.stats()
        assert st["goodput_tokens_total"] == 0
        assert st["deadline_goodput_tokens_total"] == 9

    def test_loadgen_summary_goodput_fields(self):
        from pytorch_distributed_template_tpu.fleet import loadgen

        results = [
            {"i": 0, "rid": "a", "tenant": "t0", "group": "g0",
             "stream": False, "prompt_tokens": 8, "ok": True,
             "shed": False, "cancelled": False, "deadline": False,
             "tokens": 10, "status": 200, "error": None,
             "ttft_s": None, "tpot_s": None, "total_s": 0.5},
            {"i": 1, "rid": "b", "tenant": "t0", "group": "g0",
             "stream": True, "prompt_tokens": 8, "ok": True,
             "shed": False, "cancelled": True, "deadline": False,
             "tokens": 6, "status": 200, "error": None,
             "ttft_s": 0.1, "tpot_s": None, "total_s": 0.4},
            {"i": 2, "rid": "c", "tenant": "t1", "group": "g0",
             "stream": False, "prompt_tokens": 8, "ok": True,
             "shed": False, "cancelled": False, "deadline": True,
             "tokens": 4, "status": 200, "error": None,
             "ttft_s": None, "tpot_s": None, "total_s": 0.3},
        ]
        out = loadgen.summarize({"results": results, "wall_s": 2.0})
        # only request "a" is compliant: cancelled + deadline tokens
        # are excluded from goodput, included in raw
        assert out["slo_compliant_tokens"] == 10
        assert out["slo_compliant_tok_s"] == pytest.approx(5.0)
        assert out["slo_compliant_tok_s"] <= out["agg_tok_s"]
        assert out["per_tenant"]["t0"]["compliance_frac"] == \
            pytest.approx(10 / 16)
        assert out["per_tenant"]["t1"]["compliance_frac"] == 0.0
        # an armed e2e SLO tightens it further
        out2 = loadgen.summarize({"results": results, "wall_s": 2.0},
                                 slo_e2e_s=0.1)
        assert out2["slo_compliant_tokens"] == 0


# ---------------------------------------------------------------------------
# dashboard: 200 + well-formed HTML
# ---------------------------------------------------------------------------


class TestDashboard:
    def _serve(self, tmp_path, tsdb=None):
        mgr = FleetManager(
            [Replica("r0", url="http://127.0.0.1:1")],
            run_dir=tmp_path, tsdb=tsdb)
        adm = FairAdmission(lambda: 4)
        stats = RouterStats()
        srv = build_router(mgr, adm, port=0, stats=stats, tsdb=tsdb)
        threading.Thread(target=srv.serve_forever,
                         daemon=True).start()
        return srv, f"http://127.0.0.1:{srv.server_address[1]}"

    def _assert_well_formed(self, doc: str):
        from html.parser import HTMLParser

        VOID = {"meta", "br", "img", "hr", "link", "input"}

        class Checker(HTMLParser):
            def __init__(self):
                super().__init__(convert_charrefs=True)
                self.stack, self.errors = [], []

            def handle_starttag(self, tag, attrs):
                if tag not in VOID:
                    self.stack.append(tag)

            def handle_startendtag(self, tag, attrs):
                pass                      # self-closing (SVG) is fine

            def handle_endtag(self, tag):
                if not self.stack or self.stack[-1] != tag:
                    self.errors.append((tag, list(self.stack[-3:])))
                else:
                    self.stack.pop()

        c = Checker()
        c.feed(doc)
        assert not c.errors, c.errors
        assert not c.stack, c.stack

    def test_dashboard_200_and_well_formed(self, tmp_path):
        tsdb = TimeSeriesStore(None, interval_s=0.5)
        tsdb.observe(counters={"fleet_tokens_generated_total": 0},
                     gauges={"queue_depth": 1}, t=100.0)
        tsdb.observe(counters={"fleet_tokens_generated_total": 40},
                     gauges={"queue_depth": 3}, t=100.4)
        tsdb.flush(t=101.0)
        srv, url = self._serve(tmp_path, tsdb=tsdb)
        try:
            resp = urllib.request.urlopen(url + "/dashboard",
                                          timeout=10)
            assert resp.status == 200
            assert resp.getheader("Content-Type", "").startswith(
                "text/html")
            doc = resp.read().decode("utf-8")
        finally:
            srv.shutdown()
        assert "<html" in doc and "Replicas" in doc
        assert "svg" in doc              # sparklines rendered
        assert "r0" in doc
        self._assert_well_formed(doc)

    def test_dashboard_degrades_without_store(self, tmp_path):
        srv, url = self._serve(tmp_path, tsdb=None)
        try:
            resp = urllib.request.urlopen(url + "/dashboard",
                                          timeout=10)
            assert resp.status == 200
            doc = resp.read().decode("utf-8")
        finally:
            srv.shutdown()
        assert "no time-series store" in doc
        assert "anatomy" not in doc
        self._assert_well_formed(doc)


# ---------------------------------------------------------------------------
# dumps carry the trend window
# ---------------------------------------------------------------------------


class TestDumpWindows:
    def test_health_anomaly_dump_carries_window(self, tmp_path):
        from pytorch_distributed_template_tpu.observability.health \
            import HealthMonitor

        store = TimeSeriesStore(None, interval_s=1.0)
        store.observe(counters={"tokens_generated_total": 10},
                      gauges={"queue_depth": 2}, t=50.0)
        store.observe(counters={"tokens_generated_total": 90},
                      gauges={"queue_depth": 7}, t=51.5)
        store.flush(t=53.0)
        set_default_store(store)
        try:
            mon = HealthMonitor(cfg={"enabled": True},
                                log_dir=tmp_path)
            fired = mon.observe(3, {"loss": float("nan")})
            assert fired is not None
            assert fired["timeseries_window"]
            dump = json.loads(
                (tmp_path / "anomaly_3.json").read_text())
            assert dump["timeseries_window"][-1]["queue_depth"] == 7.0
        finally:
            set_default_store(None)

    def test_watchdog_stall_report_carries_window(self):
        from pytorch_distributed_template_tpu.utils.watchdog import (
            StepWatchdog,
        )

        store = TimeSeriesStore(None, interval_s=1.0)
        store.observe(gauges={"live_slots": 3}, t=10.0)
        store.flush(t=12.0)
        set_default_store(store)
        try:
            wd = StepWatchdog(timeout_s=1e9, dump_stacks=False)
            report = wd.stall_report(12.3)
            assert report["timeseries_window"][0]["live_slots"] == 3.0
        finally:
            set_default_store(None)

    def test_no_store_no_window(self):
        from pytorch_distributed_template_tpu.utils.watchdog import (
            StepWatchdog,
        )

        set_default_store(None)
        wd = StepWatchdog(timeout_s=1e9, dump_stacks=False)
        assert "timeseries_window" not in wd.stall_report(1.0)
