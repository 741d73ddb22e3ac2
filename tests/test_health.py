"""Health observability (ISSUE 3 tentpole): numerics forensics
(in-graph summary + EWMA anomaly detector + anomaly dumps), straggler
aggregation, on-demand profiling triggers, the health counters on
serve.py's endpoints, and the offline telemetry analyzer's regression
gate."""
import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

from pytorch_distributed_template_tpu.engine.state import create_train_state
from pytorch_distributed_template_tpu.engine.steps import make_train_step
from pytorch_distributed_template_tpu.observability.crosshost import (
    CrossHostAggregator, aggregate, local_stats_vector,
)
from pytorch_distributed_template_tpu.observability.health import (
    EwmaDetector, HealthMonitor, health_counters, health_layout,
    reset_counters, unpack_health_summary,
)
from pytorch_distributed_template_tpu.observability.profiler import (
    OnDemandProfiler, TraceCapture, install_sigusr2,
)
from pytorch_distributed_template_tpu.observability.telemetry import (
    FlightRecorder,
)

sys.path.insert(0, str(Path(__file__).parent.parent))

from test_e2e_mnist import build_trainer, make_config  # noqa: E402

REPO = Path(__file__).parent.parent


@pytest.fixture(autouse=True)
def _fresh_counters():
    reset_counters()
    yield
    reset_counters()


# ---------------------------------------------------------------------------
# EwmaDetector
# ---------------------------------------------------------------------------


def test_ewma_no_fire_during_warmup():
    det = EwmaDetector(alpha=0.1, warmup=10)
    # wildly varying warmup values (the compile step / init transient)
    for x in [100.0, 1.0, 50.0, 2.0, 80.0, 3.0, 60.0, 4.0, 40.0]:
        assert det.update(x) is None


def test_ewma_fires_on_upward_spike_only():
    det = EwmaDetector(alpha=0.1, warmup=5, floor_frac=0.02)
    for _ in range(30):
        z = det.update(2.0 + np.random.default_rng(0).normal() * 0.0)
        assert z is None or z < 1.0
    # downward move never fires (one-sided: improvement isn't anomalous)
    assert det.update(0.5) == 0.0
    # big upward spike fires hard
    assert det.update(20.0) > 8.0


def test_ewma_tracks_decreasing_series_silently():
    """A healthy training loss (steady decrease) must never z-fire."""
    det = EwmaDetector(alpha=0.05, warmup=10)
    zs = [det.update(x) for x in np.linspace(6.0, 0.5, 200)]
    fired = [z for z in zs if z is not None and z > 8.0]
    assert not fired


def test_ewma_skips_nonfinite():
    det = EwmaDetector(alpha=0.1, warmup=2)
    det.update(1.0), det.update(1.0), det.update(1.0)
    n_before = det.n
    assert det.update(float("nan")) is None
    assert det.update(float("inf")) is None
    assert det.n == n_before  # non-finite values don't pollute the EWMA


# ---------------------------------------------------------------------------
# HealthMonitor
# ---------------------------------------------------------------------------


def _clean(loss=1.0):
    return {"loss": loss, "grad_norm": 0.5, "update_norm": 0.01,
            "nonfinite_grads": 0.0, "nonfinite_params": 0.0}


def test_monitor_hard_trigger_writes_anomaly_dump(tmp_path):
    rec = FlightRecorder(run_dir=None, capacity=16, memory_every=0)
    for i in range(6):
        rec.record(i, wall_ms=10.0, loss=1.0)
    mon = HealthMonitor({"dump_last_n": 4}, recorder=rec,
                        log_dir=tmp_path)
    for i in range(6):
        assert mon.observe(i, _clean()) is None
    bad = _clean(loss=float("nan"))
    bad["nonfinite_grads"] = 128.0
    bad["nonfinite/layer_3"] = 128.0
    anomaly = mon.observe(6, bad, meta={"epoch": 1, "batch_idx": 6})
    assert anomaly is not None
    kinds = {r["kind"] for r in anomaly["reasons"]}
    assert {"nonfinite_loss", "nonfinite_grads"} <= kinds
    path = tmp_path / "anomaly_6.json"
    assert path.exists()
    dump = json.loads(path.read_text())
    assert dump["step"] == 6 and dump["epoch"] == 1
    assert dump["summary"]["nonfinite_grads"] == 128.0
    assert dump["summary"]["nonfinite/layer_3"] == 128.0
    assert len(dump["last_records"]) == 4
    # the anomaly landed on the recorder timeline too
    assert rec.last(1)[0]["event"] == "anomaly"
    assert health_counters()["anomaly_total"] == 1
    assert health_counters()["last_anomaly_step"] == 6


def test_monitor_hard_trigger_on_nonfinite_norms():
    """An f32-overflowing global norm (finite elements, inf norm) makes
    grad clipping zero every update while loss stays finite and counts
    stay 0 — the non-finite NORM itself must hard-trigger, since the
    EWMA detector deliberately skips non-finite inputs."""
    mon = HealthMonitor({})
    bad = _clean()
    bad["grad_norm"] = float("inf")
    a = mon.observe(0, bad)
    assert a is not None
    assert {"kind": "nonfinite_grad_norm", "value": "inf"} in a["reasons"]
    bad2 = _clean()
    bad2["update_norm"] = float("nan")
    a2 = mon.observe(1, bad2)
    assert any(r["kind"] == "nonfinite_update_norm"
               for r in a2["reasons"])


def test_monitor_dump_cooldown_and_cap(tmp_path):
    mon = HealthMonitor({"cooldown_steps": 10, "max_dumps": 2},
                        log_dir=tmp_path)
    for step in range(40):  # a NaN streak fires every step
        mon.observe(step, _clean(loss=float("nan")))
    files = list(tmp_path.glob("anomaly_*.json"))
    assert len(files) == 2  # cooldown + cap bound the flood
    assert mon.anomalies == 40  # ...but every fire is counted
    assert health_counters()["anomaly_total"] == 40


def test_monitor_disabled_is_inert(tmp_path):
    mon = HealthMonitor({"enabled": False}, log_dir=tmp_path)
    assert mon.observe(0, _clean(loss=float("nan"))) is None
    mon.enqueue(1, {"health": jnp.zeros(4)})
    mon.drain()
    assert not list(tmp_path.glob("anomaly_*.json"))
    assert mon.promotion_allowed()


def test_monitor_promotion_pause_epoch_scoped():
    mon = HealthMonitor({"pause_best_promotion": True})
    assert mon.promotion_allowed()
    mon.observe(3, _clean(loss=float("inf")))
    assert not mon.promotion_allowed()
    mon.epoch_start()  # next epoch starts clean
    assert mon.promotion_allowed()


# ---------------------------------------------------------------------------
# in-graph summary through a real train step
# ---------------------------------------------------------------------------


class _Tiny(nn.Module):
    @nn.compact
    def __call__(self, x, train=False):
        return nn.Dense(4)(x)


def _sq_err(output, target):
    return jnp.sum((output - target[:, None].astype(output.dtype)) ** 2,
                   axis=-1)


def _batch(poison=False):
    x = np.ones((8, 3), np.float32)
    if poison:
        x[3, 1] = np.inf
    return {"image": jnp.asarray(x),
            "label": jnp.zeros((8,), jnp.int32),
            "mask": jnp.ones((8,), bool)}


def _health_step(skip_nonfinite=True):
    model = _Tiny()
    tx = optax.sgd(0.05)
    state = create_train_state(model, tx, jnp.ones((1, 3), jnp.float32),
                               seed=0)
    step = jax.jit(make_train_step(
        model, tx, _sq_err, skip_nonfinite=skip_nonfinite, health=True,
    ))
    return state, step


def test_health_summary_clean_step():
    state, step = _health_step()
    layout = health_layout(state.params)
    state, m = step(state, _batch())
    s = unpack_health_summary(jax.device_get(m["health"]), layout)
    assert s["nonfinite_grads"] == 0.0
    assert s["nonfinite_params"] == 0.0
    assert np.isfinite(s["loss"]) and s["loss"] > 0
    assert s["grad_norm"] > 0 and s["update_norm"] > 0


def test_health_summary_poisoned_step_reports_counts():
    """The whole acceptance path at the step level: a poisoned batch
    under skip_nonfinite leaves the weights intact AND the health
    vector reports the non-finite loss + per-group grad counts (the
    skip guard zeroes the ordinary metrics — the health fields must
    survive it)."""
    state, step = _health_step(skip_nonfinite=True)
    layout = health_layout(state.params)
    before = jax.tree.map(np.asarray, state.params)
    state, m = step(state, _batch(poison=True))
    s = unpack_health_summary(jax.device_get(m["health"]), layout)
    assert not np.isfinite(s["loss"])      # raw loss, not the zeroed sum
    assert s["nonfinite_grads"] > 0
    group_counts = {k: v for k, v in s.items()
                    if k.startswith("nonfinite/")}
    assert sum(group_counts.values()) == s["nonfinite_grads"]
    assert any(v > 0 for v in group_counts.values())
    assert s["nonfinite_params"] == 0.0    # guard kept the weights clean
    for a, b in zip(jax.tree.leaves(before),
                    jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_nan_injection_end_to_end(tmp_path):
    """ISSUE 3 acceptance: a NaN injected mid-run produces
    anomaly_<step>.json with last-N records + non-finite counts,
    without crashing the run (skip_nonfinite), and pauses best-model
    promotion when configured."""
    config = make_config(
        tmp_path, run_id="health-nan",
        **{"trainer;epochs": 1,
           "trainer;skip_nonfinite": True,
           "trainer;health": {"enabled": True,
                              "pause_best_promotion": True},
           "train_loader;args;shuffle": False},
    )
    t = build_trainer(config)
    # poison exactly batch 3 (samples 128..191 of the unshuffled set)
    t.train_loader.arrays["image"][128:192] = np.inf
    log = t.train()                      # must not raise
    assert log["skipped"] > 0            # the guard ate the bad batch
    dumps = sorted(config.save_dir.glob("anomaly_*.json"))
    assert dumps, "no anomaly dump written"
    a = json.loads(dumps[0].read_text())
    kinds = {r["kind"] for r in a["reasons"]}
    assert "nonfinite_grads" in kinds
    assert a["last_records"], "dump missing flight-recorder tail"
    assert a["summary"]["nonfinite_grads"] > 0
    assert health_counters()["anomaly_total"] >= 1
    # promotion pause: the poisoned epoch must not crown model_best
    assert not (config.save_dir / "model_best").exists()
    # the anomaly also rides the JSONL timeline
    lines = (config.save_dir / "telemetry.jsonl").read_text().splitlines()
    assert any('"anomaly"' in ln for ln in lines)


# ---------------------------------------------------------------------------
# cross-host aggregation (single-process half; two-process lives in
# test_multihost.py::test_two_process_straggler_detection)
# ---------------------------------------------------------------------------


def test_local_stats_vector_from_records():
    recs = [{"step": i, "wall_ms": 100.0, "data_wait_ms": 4.0}
            for i in range(10)]
    vec = local_stats_vector(recs)
    assert vec.shape == (4,)
    assert vec[0] == pytest.approx(100.0)
    assert vec[1] == pytest.approx(4.0)


def test_aggregate_flags_straggler():
    out = aggregate(np.array([[100.0, 1.0, 0, 0],
                              [104.0, 1.0, 0, 0],
                              [260.0, 9.0, 0, 0]]), threshold=1.25)
    assert out["straggler"] is True
    assert out["straggler_hosts"] == [2]
    assert out["hosts"]["2"]["wall_ms"] == 260.0
    assert out["wall_spread"] == pytest.approx(260.0 / 104.0, rel=1e-3)


def test_local_stats_vector_excludes_compile_records():
    """The first multi-host window is asymmetric (process 0 defers its
    log-step records; peers record the compile step immediately) — a
    30s compile in one host's mean but not another's must not read as
    a straggler, so compile-carrying records stay out of the vector."""
    recs = [{"step": 0, "wall_ms": 30000.0,
             "compile_events": [{"event": "backend_compile"}]}] + [
        {"step": i, "wall_ms": 100.0} for i in range(1, 10)
    ]
    assert local_stats_vector(recs)[0] == pytest.approx(100.0)


def test_aggregate_skips_hosts_with_empty_windows():
    """A host whose records were all compile-filtered (wall 0) must not
    drag the median down and flag its healthy peers."""
    out = aggregate(np.array([[0.0, 0, 0, 0],
                              [100.0, 1.0, 0, 0]]), threshold=1.25)
    assert "straggler" not in out


def test_aggregate_no_false_flag_within_threshold():
    out = aggregate(np.array([[100.0, 1.0, 0, 0],
                              [118.0, 1.0, 0, 0]]), threshold=1.25)
    assert "straggler" not in out
    assert len(out["hosts"]) == 2


def test_crosshost_single_host_exchange():
    agg = CrossHostAggregator({"enabled": True, "threshold": 1.25})
    out = agg.exchange([{"step": 0, "wall_ms": 50.0}])
    assert out is not None
    assert list(out["hosts"]) == ["0"]
    assert "straggler" not in out
    # default (auto) config on a single host: disabled, no exchange
    assert not CrossHostAggregator().enabled


# ---------------------------------------------------------------------------
# on-demand profiling
# ---------------------------------------------------------------------------


def test_trace_capture_request_arms_runtime_window(tmp_path):
    rec = FlightRecorder(run_dir=None, capacity=8, memory_every=0)
    tc = TraceCapture(tmp_path, num_steps=0)  # nothing scheduled
    tc.attach_recorder(rec)
    tc.before_step(0)
    assert not tc._active  # disabled config: no capture
    tc.request(2)
    x = jnp.ones((4,))
    for step in range(1, 5):
        tc.before_step(step)
        x = x + 1
        tc.after_step(step, sync=x)
    assert tc.captures == 1
    assert Path(tc.dir).exists()
    assert health_counters()["profile_captures_total"] == 1
    last = rec.last(1)[0]
    assert last["event"] == "profile_capture"
    assert last["profile_steps"] == 2


@pytest.mark.skipif(not hasattr(signal, "SIGUSR2"),
                    reason="no SIGUSR2 on this platform")
def test_sigusr2_triggers_capture(tmp_path):
    """The train.py wiring: SIGUSR2 arms the next-N-steps capture and a
    trace directory appears."""
    tc = TraceCapture(tmp_path, num_steps=0)
    assert install_sigusr2(tc, default_steps=1)
    try:
        os.kill(os.getpid(), signal.SIGUSR2)
        x = jnp.ones((4,))
        for step in range(3):
            tc.before_step(step)
            x = x + 1
            tc.after_step(step, sync=x)
        assert tc.captures == 1
        assert Path(tc.dir).exists()
    finally:
        signal.signal(signal.SIGUSR2, signal.SIG_DFL)


def test_ondemand_profiler_progress_window(tmp_path):
    prof = OnDemandProfiler(tmp_path)
    ticks = {"n": 0}

    def progress():
        ticks["n"] += 1
        return ticks["n"]

    out = prof.capture(steps=3, progress_fn=progress, timeout_s=5.0,
                       poll_s=0.001)
    assert "error" not in out
    assert out["steps_observed"] >= 3 and not out["timed_out"]
    assert Path(out["profile_dir"]).exists()
    assert health_counters()["profile_captures_total"] == 1
    # an idle server times out instead of pinning the request thread
    out2 = prof.capture(steps=5, progress_fn=lambda: 0, timeout_s=0.05,
                        poll_s=0.01)
    assert out2["timed_out"] is True


# ---------------------------------------------------------------------------
# serve.py surface: POST /profile + health counters on /metrics,/healthz
# ---------------------------------------------------------------------------


class _FakeService:
    arch, vocab, tokenizer = "TinyLM", 64, None
    stats = {"requests": 2, "completed": 2, "chunks": 5,
             "tokens_generated": 64}
    _slots = 4


def _serve_server(tmp_path):
    from http.server import ThreadingHTTPServer

    import serve

    profiler = OnDemandProfiler(tmp_path)
    server = ThreadingHTTPServer(
        ("127.0.0.1", 0),
        serve.make_handler(_FakeService(), profiler=profiler))
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, server.server_address[1]


def test_serve_profile_endpoint_and_counters(tmp_path):
    import http.client

    from pytorch_distributed_template_tpu.observability.health import (
        note_anomaly,
    )

    note_anomaly(41)
    server, port = _serve_server(tmp_path)
    try:
        # generous timeout: the process's FIRST jax.profiler
        # start/stop pays ~10s of one-time backend initialization on a
        # loaded CPU host
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=90)
        # steps=0: immediate start/stop capture (no traffic needed)
        conn.request("POST", "/profile?steps=0")
        resp = conn.getresponse()
        payload = json.loads(resp.read())
        assert resp.status == 200, payload
        assert Path(payload["profile_dir"]).exists()
        assert payload["captures_total"] == 1

        conn.request("GET", "/metrics?format=json")
        m = json.loads(conn.getresponse().read())
        assert m["profile_captures_total"] == 1
        assert m["anomaly_total"] == 1
        assert m["straggler_windows_total"] == 0

        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode()
        assert "# TYPE pdt_serve_anomaly_total counter" in text
        assert "pdt_serve_profile_captures_total 1" in text

        conn.request("GET", "/healthz")
        h = json.loads(conn.getresponse().read())
        assert h["last_anomaly_step"] == 41
    finally:
        server.shutdown()
        server.server_close()


def test_serve_profile_no_progress_counter_is_503(tmp_path):
    """A scheduler with no usable monotonic counter (empty stats) gets
    503 for a windowed capture instead of silently burning the whole
    timeout holding the profiler lock; steps=0 still works."""
    import http.client

    class _Bare(_FakeService):
        stats = {}

    from http.server import ThreadingHTTPServer

    import serve

    server = ThreadingHTTPServer(
        ("127.0.0.1", 0),
        serve.make_handler(_Bare(), profiler=OnDemandProfiler(tmp_path)))
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=90)
        conn.request("POST", "/profile?steps=4")
        assert conn.getresponse().status == 503
        conn.request("POST", "/profile?steps=0")
        resp = conn.getresponse()
        assert resp.status == 200
        assert Path(json.loads(resp.read())["profile_dir"]).exists()
    finally:
        server.shutdown()
        server.server_close()


def test_serve_profile_tokens_progress_fallback(tmp_path):
    """The plain serialized service only counts tokens_generated; a
    windowed capture uses it as the progress counter instead of
    spinning to timeout under active traffic."""
    import http.client

    class _Plain(_FakeService):
        def __init__(self):
            self.stats = {"tokens_generated": 0}

    from http.server import ThreadingHTTPServer

    import serve

    svc = _Plain()
    server = ThreadingHTTPServer(
        ("127.0.0.1", 0),
        serve.make_handler(svc, profiler=OnDemandProfiler(tmp_path)))
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()

    def traffic():
        for _ in range(200):
            svc.stats["tokens_generated"] += 1
            time.sleep(0.005)

    threading.Thread(target=traffic, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=90)
        conn.request("POST", "/profile?steps=5&timeout_s=30")
        resp = conn.getresponse()
        d = json.loads(resp.read())
        assert resp.status == 200, d
        assert d["steps_observed"] >= 5 and not d["timed_out"]
    finally:
        server.shutdown()
        server.server_close()


def test_serve_profile_not_configured():
    import http.client

    from http.server import ThreadingHTTPServer

    import serve

    server = ThreadingHTTPServer(
        ("127.0.0.1", 0), serve.make_handler(_FakeService()))
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
        conn.request("POST", "/profile?steps=1")
        assert conn.getresponse().status == 503
    finally:
        server.shutdown()
        server.server_close()


# ---------------------------------------------------------------------------
# watchdog + recorder satellites
# ---------------------------------------------------------------------------


def test_watchdog_stall_report_includes_memory():
    from pytorch_distributed_template_tpu.utils.watchdog import (
        StepWatchdog,
    )

    wd = StepWatchdog(timeout_s=0)
    report = wd.stall_report(3.0)
    # host RSS is a /proc read on linux; guarded like the recorder's
    if os.path.exists("/proc/self/status"):
        assert report["host_rss_mb"] > 0


def test_watchdog_stall_path_flushes_recorder(tmp_path):
    from pytorch_distributed_template_tpu.utils.watchdog import (
        StepWatchdog,
    )

    rec = FlightRecorder(run_dir=tmp_path, capacity=8, memory_every=0)
    rec.record(0, wall_ms=5.0)
    flushed = []
    orig = rec.flush
    rec.flush = lambda: (flushed.append(1), orig())[1]
    wd = StepWatchdog(timeout_s=5, dump_stacks=False, recorder=rec,
                      dump_path=tmp_path / "stall.json")
    wd._dump_telemetry(7.0)
    assert flushed, "stall path did not flush the recorder tail"
    rec.close()


def test_recorder_registers_atexit_flush(tmp_path):
    from pytorch_distributed_template_tpu.observability import telemetry

    rec = FlightRecorder(run_dir=tmp_path, capacity=4, memory_every=0)
    assert rec in telemetry._live_recorders
    rec.record(0, wall_ms=1.0)
    telemetry._flush_live_recorders()  # must not raise; forces fsync
    rec.close()
    telemetry._flush_live_recorders()  # closed recorder: still safe


# ---------------------------------------------------------------------------
# scripts/telemetry_report.py (subprocess: the CI entry surface)
# ---------------------------------------------------------------------------

REPORT = REPO / "scripts" / "telemetry_report.py"


def _run_report(*args):
    return subprocess.run(
        [sys.executable, str(REPORT), *args],
        capture_output=True, text=True, timeout=60, cwd=REPO,
    )


def test_report_analyzes_run_dir(tmp_path):
    tel = tmp_path / "telemetry.jsonl"
    records = [
        {"v": 1, "step": 0, "t": 0, "wall_ms": 500.0,
         "compile_events": [{"event": "backend_compile",
                             "dur_ms": 400.0},
                            {"event": ".../cache_misses"}]},
    ] + [
        {"v": 1, "step": i, "t": i, "wall_ms": 100.0,
         "data_wait_ms": 10.0, "tokens": 1000, "examples": 8}
        for i in range(1, 11)
    ] + [
        {"v": 1, "step": 11, "t": 11, "event": "anomaly",
         "reasons": "[\"nonfinite_grads\"]"},
        {"v": 1, "step": 12, "t": 12, "wall_ms": 100.0, "straggler": True,
         "wall_spread": 1.8, "hosts": {"0": {}, "1": {}}},
    ]
    tel.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    (tmp_path / "trace.json").write_text(json.dumps({
        "traceEvents": [{"name": "train/step", "ph": "X", "ts": 0,
                         "dur": 5000.0, "pid": 1, "tid": 1}]}))
    (tmp_path / "anomaly_11.json").write_text(json.dumps({
        "step": 11, "reasons": [{"kind": "nonfinite_grads"}]}))
    r = _run_report("--run-dir", str(tmp_path), "--json")
    assert r.returncode == 0, r.stderr
    report = json.loads(r.stdout)
    tel_r = report["telemetry"]
    # the compile record (timed[0], carrying compile_events) is
    # excluded from steady state: 10 clean steps + the straggler-window
    # record at 100ms -> 10 steps/s, not dragged down by the 500ms
    # compile step
    assert tel_r["steady_steps"] == 11
    assert tel_r["steady_steps_per_sec"] == pytest.approx(10.0, rel=0.01)
    # 10 x 10ms waits over 1.1s of steady wall
    assert tel_r["data_wait_frac"] == pytest.approx(0.1 / 1.1, rel=0.01)
    assert tel_r["anomalies"] == 1
    assert tel_r["straggler_windows"] == 1
    assert tel_r["host_wall_spread_max"] == 1.8
    assert tel_r["compile_cache_hit_rate"] == 0.0
    assert report["anomalies"]["dump_count"] == 1
    assert report["trace"]["top_spans"][0]["name"] == "train/step"
    # markdown mode renders without crashing and mentions the gate data
    r2 = _run_report("--run-dir", str(tmp_path))
    assert r2.returncode == 0 and "Telemetry report" in r2.stdout
