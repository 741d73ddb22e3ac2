"""Profiler tier (observability/profiler.py): throughput, MFU, trace capture.

SURVEY.md §5 "Tracing / profiling": the reference only had a steps_per_sec
scalar; the TPU-native framework adds compiled-FLOPs MFU and jax.profiler
trace windows. CPU backend: peak FLOPs is unknown -> mfu None, but the
mechanics (cost analysis, meters, capture files) are all testable.
"""
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_template_tpu.observability.profiler import (
    OnDemandProfiler, ThroughputMeter, TraceCapture, compiled_flops,
    install_sigusr2, mfu, peak_flops_per_device,
)


def test_throughput_meter_rates():
    m = ThroughputMeter()
    for _ in range(5):
        m.update(32)
    time.sleep(0.05)
    r = m.rate()
    assert r["steps_per_sec"] > 0
    assert abs(r["examples_per_sec"] / r["steps_per_sec"] - 32) < 1e-6
    # window reset: immediate second call sees zero steps
    r2 = m.rate()
    assert r2["steps_per_sec"] == 0


def test_compiled_flops_reports_matmul():
    @jax.jit
    def f(a, b):
        return a @ b

    a = jnp.ones((128, 128), jnp.float32)
    flops = compiled_flops(f, a, a)
    # XLA:CPU reports flops; a 128^3 matmul is ~4.2 MFLOPs (2*n^3)
    if flops is not None:
        assert flops >= 2 * 128**3 * 0.5


def test_mfu_math():
    # flops_per_step is per-device (SPMD cost analysis is the partitioned
    # module), so peak is NOT scaled by device count
    assert mfu(1e12, 2.0, peak_per_device=4e12) == 0.5
    assert mfu(None, 2.0) is None
    assert mfu(1e12, 0.0) is None


@pytest.mark.parametrize("kind, peak", [
    ("TPU v5 lite", 197e12),   # what the v5e reports; "v5" alone is the v5p's
    ("TPU v5e", 197e12),
    ("TPU v5p", 459e12),
    ("TPU v4", 275e12),
    ("TPU v6 lite", 918e12),
])
def test_peak_flops_by_device_kind(kind, peak):
    """The table is searched by substring, first match wins: the lite
    kinds must come before the generation they share a prefix with."""
    device = types.SimpleNamespace(device_kind=kind)
    assert peak_flops_per_device(device) == peak


def test_peak_flops_cpu_unknown():
    # tests run on the CPU backend: no table entry
    assert peak_flops_per_device(jax.devices()[0]) is None


def test_trace_capture_window(tmp_path):
    cap = TraceCapture(tmp_path, start_step=2, num_steps=2)
    x = jnp.ones((64, 64))
    for step in range(6):
        cap.before_step(step)
        jax.block_until_ready(x @ x)
        cap.after_step(step)
    cap.close()
    assert cap._done and not cap._active
    prof_dir = tmp_path / "profile"
    assert prof_dir.is_dir()
    assert any(prof_dir.rglob("*"))  # trace events written


def test_trace_capture_disabled(tmp_path):
    cap = TraceCapture(tmp_path, start_step=0, num_steps=0)
    cap.before_step(0)
    cap.after_step(0)
    cap.close()
    assert not (tmp_path / "profile").exists()


def test_trace_capture_request_rearms_consumed_window(tmp_path):
    """request() must re-arm even after the config-scheduled window
    was consumed (or never existed): the SIGUSR2 path on a long-lived
    run profiles on demand, not once."""
    cap = TraceCapture(tmp_path, num_steps=0)   # nothing scheduled
    x = jnp.ones((32, 32))
    cap.before_step(0)
    cap.after_step(0)
    assert cap.captures == 0
    cap.request(1)
    cap.before_step(1)
    assert cap._active
    jax.block_until_ready(x @ x)
    cap.after_step(1)
    assert cap.captures == 1 and cap._done and not cap._active


def test_trace_capture_request_coalesces_while_active(tmp_path):
    """A second request() while a capture is in flight is DROPPED —
    two SIGUSR2s during one slow capture must not latch a surprise
    extra trace for after it closes."""
    cap = TraceCapture(tmp_path, num_steps=0)
    x = jnp.ones((32, 32))
    cap.request(2)
    cap.before_step(0)
    assert cap._active
    cap.request(5)                      # the second signal, mid-flight
    assert cap._requested is None       # coalesced away, not queued
    jax.block_until_ready(x @ x)
    cap.after_step(0)
    assert cap._active                  # window is 2 steps
    cap.after_step(1)
    assert not cap._active and cap.captures == 1
    # and nothing re-arms on the next step
    cap.before_step(2)
    assert not cap._active
    cap.after_step(2)
    assert cap.captures == 1


def test_install_sigusr2_requests_capture(tmp_path, monkeypatch):
    """kill -USR2: the handler arms a capture sized by
    PDT_PROFILE_STEPS (bad values fall back to the default)."""
    import os
    import signal

    cap = TraceCapture(tmp_path, num_steps=0)
    old = signal.getsignal(signal.SIGUSR2)
    try:
        assert install_sigusr2(cap, default_steps=5) is True
        monkeypatch.setenv("PDT_PROFILE_STEPS", "3")
        os.kill(os.getpid(), signal.SIGUSR2)
        assert cap._requested == 3
        cap._requested = None
        monkeypatch.setenv("PDT_PROFILE_STEPS", "not-a-number")
        os.kill(os.getpid(), signal.SIGUSR2)
        assert cap._requested == 5      # default_steps fallback
    finally:
        signal.signal(signal.SIGUSR2, old)


def test_install_sigusr2_refused_off_main_thread(tmp_path):
    import threading

    cap = TraceCapture(tmp_path, num_steps=0)
    out = []
    t = threading.Thread(
        target=lambda: out.append(install_sigusr2(cap)))
    t.start()
    t.join(timeout=10)
    assert out == [False]


def test_on_demand_profiler_idle_timeout(tmp_path):
    """An idle server (progress never advances) must release the
    request thread at timeout_s and say so, not pin it forever."""
    prof = OnDemandProfiler(tmp_path)
    t0 = time.monotonic()
    out = prof.capture(steps=5, progress_fn=lambda: 0,
                       timeout_s=0.2, poll_s=0.01)
    assert out["timed_out"] is True
    assert out["steps_observed"] == 0
    assert out["steps_requested"] == 5
    assert 0.2 <= time.monotonic() - t0 < 10
    assert out["captures_total"] == 1


def test_on_demand_profiler_busy_second_caller(tmp_path):
    """One capture at a time: a concurrent caller gets {'busy': True}
    immediately instead of queueing behind the in-flight trace."""
    import threading

    prof = OnDemandProfiler(tmp_path)
    started = threading.Event()
    release = threading.Event()
    first: dict = {}

    def progress():
        started.set()
        return 1 if release.is_set() else 0

    def run_first():
        first.update(prof.capture(steps=1, progress_fn=progress,
                                  timeout_s=30.0, poll_s=0.01))

    t = threading.Thread(target=run_first)
    t.start()
    assert started.wait(timeout=10)
    busy = prof.capture(steps=1)
    assert busy.get("busy") is True and "error" in busy
    release.set()
    t.join(timeout=30)
    assert not t.is_alive()
    assert first.get("timed_out") is False
    assert first.get("steps_observed", 0) >= 1
    # the busy bounce did not count as a capture
    assert prof.captures == 1


def test_trainer_profiler_integration(tmp_path, monkeypatch):
    """Profiler-enabled training run: mfu/examples_per_sec paths execute,
    the step is lowered and compiled ahead of time exactly once for the
    FLOPs probe, and with ``peak_flops_per_device`` set (the table knows
    no CPU) the epoch's log carries ``mfu``."""
    import json
    from pathlib import Path

    from pytorch_distributed_template_tpu.config import (
        ConfigParser, LOADERS, LOSSES, METRICS, MODELS,
    )
    import pytorch_distributed_template_tpu.data  # noqa: F401
    import pytorch_distributed_template_tpu.models  # noqa: F401
    import pytorch_distributed_template_tpu.engine  # noqa: F401
    from pytorch_distributed_template_tpu.engine import Trainer
    from pytorch_distributed_template_tpu.engine import trainer as trainer_mod
    from pytorch_distributed_template_tpu.parallel import mesh_from_config

    probes = []

    def counting_probe(jitted_fn, *args, **kwargs):
        probes.append(jitted_fn)
        return compiled_flops(jitted_fn, *args, **kwargs)

    monkeypatch.setattr(trainer_mod, "compiled_flops", counting_probe)

    cfg = json.loads(
        (Path(__file__).parent.parent / "configs" / "mnist_debug.json")
        .read_text()
    )
    cfg["trainer"]["save_dir"] = str(tmp_path)
    cfg["trainer"]["epochs"] = 1
    cfg["trainer"]["profiler"] = {
        "enabled": True, "trace_start_step": 1, "trace_steps": 1,
        "peak_flops_per_device": 1e12,
    }
    config = ConfigParser(cfg, run_id="prof")
    model = config.init_obj("arch", MODELS)
    trainer = Trainer(
        model, LOSSES.get(config["loss"]),
        [METRICS.get(m) for m in config["metrics"]], config=config,
        train_loader=config.init_obj("train_loader", LOADERS),
        mesh=mesh_from_config(config),
    )
    log = trainer.train()
    assert np.isfinite(log["loss"])
    # trace window wrote events into the run's log dir
    assert (config.log_dir / "profile").is_dir()
    assert len(probes) == 1 and probes[0] is trainer._train_step
    assert log["mfu"] > 0
