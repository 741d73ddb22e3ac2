"""The host's seconds by name (ISSUE 38): set-up's phases as spans and
on the first flight record, the wait for the warmed step, compile
events that say which function, and an iteration that accounts for
itself and, when it stalls, says what the host was doing."""
import gc
import json
import logging
import threading
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_template_tpu.engine.steps import instrument_step
from pytorch_distributed_template_tpu.engine.warmup import StepWarmup
from pytorch_distributed_template_tpu.observability import telemetry
from pytorch_distributed_template_tpu.observability.telemetry import (
    ITERATION_PARTS, FlightRecorder, IterationAccount, drain_compile_events,
    read_jsonl,
)
from pytorch_distributed_template_tpu.observability.trace import (
    SpanRecorder, get_recorder,
)
from pytorch_distributed_template_tpu.resilience import faults

STAGES = ("trace", "lower", "compile")


def _make_step():
    def f(state, batch):
        s = jnp.sum(batch["x"]) * 1.5
        return state + s, {"loss_sum": s}

    return jax.jit(f)


def _args():
    return jnp.float32(0), {"x": jax.ShapeDtypeStruct((4,), jnp.float32)}


def _events(mark: float, suffix: str = "") -> list:
    return [e for e in get_recorder().since(mark)
            if e["name"].endswith(suffix)]


# -- the warm-up's stages and the wait for them ------------------------------


def test_the_warmup_runs_in_three_spans_on_its_own_thread():
    mark = time.perf_counter()
    w = StepWarmup()
    w.add("toy_step", _make_step(), *_args())
    w.start()
    assert w.result("toy_step", timeout=60) is not None
    w._thread.join(timeout=60)
    assert not w._thread.is_alive()
    spans = [e for e in get_recorder().since(mark)
             if e["name"].startswith("warmup/toy_step/")]
    assert [e["name"] for e in spans] == [
        f"warmup/toy_step/{s}" for s in STAGES]
    # on the warm-up's thread, one after the other, inside nothing
    assert {e["tid"] for e in spans} == {w._thread.ident}
    assert w._thread.ident != threading.get_ident()
    for a, b in zip(spans, spans[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 0.2     # rounding, in us
    others = [e for e in get_recorder().since(mark)
              if e["tid"] == w._thread.ident and e not in spans]
    assert not [e for e in others if e["dur"] > 0]


def test_the_staged_warmup_builds_the_one_call_forms_executable():
    jitted = _make_step()
    w = StepWarmup()
    w.add("toy_step", jitted, *_args())
    staged = w.start().result("toy_step", timeout=60)
    direct = jitted.lower(*_args()).compile()
    assert staged.as_text() == direct.as_text()
    x = {"x": jnp.arange(4, dtype=jnp.float32)}
    assert float(staged(jnp.float32(2), x)[0]) == float(
        direct(jnp.float32(2), x)[0])


def test_the_wait_for_a_finished_warmup_is_a_span_of_no_length():
    jitted = _make_step()
    w = StepWarmup()
    w.add("toy_step", jitted, *_args())
    assert w.start().result("toy_step", timeout=60) is not None
    step = instrument_step(jitted, "toy_step", warmup=w)
    mark = time.perf_counter()
    step(jnp.float32(0), {"x": jnp.ones((4,), jnp.float32)})
    step(jnp.float32(0), {"x": jnp.ones((4,), jnp.float32)})
    (wait,) = _events(mark, "/await_warmup")       # once, at the first call
    assert wait["name"] == "toy_step/await_warmup"
    assert wait["tid"] == threading.get_ident()
    assert wait["dur"] < 20e3                      # us: nothing to wait for
    names = [e["name"] for e in _events(mark)]
    assert names.index("toy_step/await_warmup") < names.index(
        "toy_step/dispatch")


def test_the_wait_covers_what_the_warmup_had_left(monkeypatch):
    w = StepWarmup()
    jitted = _make_step()
    w.add("toy_step", jitted, *_args())
    real = w.result

    def slow(name, timeout=None):
        time.sleep(0.15)
        return real(name, timeout)

    monkeypatch.setattr(w, "result", slow)
    w.start()
    mark = time.perf_counter()
    instrument_step(jitted, "toy_step", warmup=w)(
        jnp.float32(0), {"x": jnp.ones((4,), jnp.float32)})
    (wait,) = _events(mark, "/await_warmup")
    assert wait["dur"] >= 0.15e6


def test_the_lazy_path_waits_for_nothing():
    mark = time.perf_counter()
    instrument_step(_make_step(), "lazy_step")(
        jnp.float32(0), {"x": jnp.ones((4,), jnp.float32)})
    names = [e["name"] for e in _events(mark)]
    assert "lazy_step/compile+execute" in names
    assert not [n for n in names if n.endswith("/await_warmup")]


def test_since_returns_what_began_after_the_mark():
    rec = SpanRecorder()
    with rec.span("before"):
        pass
    with rec.span("outer") as frame:
        with rec.span("inner"):
            pass
    assert [e["name"] for e in rec.since(frame["t0"])] == ["inner", "outer"]
    assert rec.since(time.perf_counter()) == []


# -- compile events say which function ---------------------------------------


def test_compile_events_name_their_function_and_trace_once_a_function():
    FlightRecorder(run_dir=None)            # installs the listener
    drain_compile_events()

    def inner(x):
        return x * 2.0 + 1.0

    @jax.jit
    def named_program(x):
        # two nested jits: their own trace events are sub-jaxprs'
        return jax.jit(inner)(x) + jax.jit(inner)(x + 1.0)

    named_program(jnp.ones((3,)))
    named_program(jnp.ones((5,)))           # traced again: summed
    events = drain_compile_events()
    timed = [e for e in events if "dur_ms" in e]
    assert timed and all(e.get("fun_name") for e in timed
                         if "/jax/core/compile/" in e["event"])
    traces = [e for e in timed
              if e["event"].endswith("jaxpr_trace_duration")]
    names = [e["fun_name"] for e in traces]
    assert names.count("named_program") == 1 and "inner" not in names
    assert len(set(names)) == len(names)
    compiles = [e for e in timed
                if e["event"].endswith("backend_compile_duration")]
    assert [e["fun_name"] for e in compiles].count("named_program") == 2
    assert not [e for e in events if e["event"].endswith("time_saved_sec")]
    assert drain_compile_events() == []


# -- an iteration accounts for itself ----------------------------------------


def _rec(wall, **parts):
    return {"wall_ms": wall, "data_wait_ms": 1.0, "dispatch_ms": 2.0, **parts}


def test_the_account_closes_the_sum_and_judges_after_eight():
    account = IterationAccount()
    first = _rec(5000.0, dispatch_ms=4990.0)
    assert account.settle(first, first=True) is None
    assert first["unattributed_ms"] == pytest.approx(9.0)
    for k in range(8):
        rec = _rec(1000.0 if k == 5 else 10.0, health_fetch_ms=6.0)
        assert account.settle(rec) is None      # fewer than 8 before it
        assert rec["unattributed_ms"] == pytest.approx(
            rec["wall_ms"] - 9.0)
    assert account.settle(_rec(20.0, health_fetch_ms=16.0)) is None
    late_device = _rec(50.0, health_fetch_ms=45.0, log_flush_ms=1.0)
    stall = account.settle(late_device)
    assert stall is late_device["stall"]
    assert stall["in"] == "health_fetch_ms"
    assert stall["over_ms"] == pytest.approx(40.0)
    assert set(stall) == {"over_ms", "in", "gc_ms", "gc_gen2", "nvcsw",
                          "nivcsw", "majflt", "threads"}
    late_host = _rec(50.0, health_fetch_ms=6.0)
    assert account.settle(late_host)["in"] == "unattributed"


def test_a_captures_start_and_stop_are_a_part_of_their_own():
    account = IterationAccount()
    for _ in range(8):
        account.settle(_rec(10.0))
    stopped = _rec(1200.0, profile_ms=1180.0)
    assert account.settle(stopped)["in"] == "profile_ms"
    assert stopped["unattributed_ms"] == pytest.approx(17.0)


def test_a_stall_names_the_other_threads_open_spans():
    spans = SpanRecorder()
    account = IterationAccount(spans=spans)
    for _ in range(8):
        account.settle(_rec(10.0))
    inside, leave = threading.Event(), threading.Event()

    def other():
        with spans.span("data/host_gather"):
            inside.set()
            leave.wait(10)

    t = threading.Thread(target=other)
    t.start()
    try:
        assert inside.wait(10)
        with spans.span("train/mine"):
            stall = account.settle(_rec(100.0))
    finally:
        leave.set()
        t.join(timeout=10)
    assert not t.is_alive()
    assert stall["threads"] == ["data/host_gather"]


def test_a_collection_shows_in_the_counters():
    FlightRecorder(run_dir=None)            # installs the callback
    before = telemetry.host_counters()
    junk = [[] for _ in range(50_000)]
    for a, b in zip(junk, junk[1:]):
        a.append(b)
    del junk, a, b
    gc.collect()
    after = telemetry.host_counters()
    assert after[0] > before[0] and after[1] >= before[1] + 1
    assert all(y >= x for x, y in zip(before[2:], after[2:]))


# -- the trainer's own records ------------------------------------------------


def _trainer(tmp_path, run_id, **trainer_keys):
    from pytorch_distributed_template_tpu.config import (
        ConfigParser, LOADERS, LOSSES, METRICS, MODELS,
    )
    from pytorch_distributed_template_tpu.engine import Trainer
    from pytorch_distributed_template_tpu.parallel import mesh_from_config

    cfg = json.loads((Path(__file__).parent.parent / "configs"
                      / "mnist_debug.json").read_text())
    cfg["trainer"].update(save_dir=str(tmp_path), save_period=10 ** 6,
                          **trainer_keys)
    config = ConfigParser(cfg, run_id=run_id, training=True)
    trainer = Trainer(
        config.init_obj("arch", MODELS), LOSSES.get(config["loss"]),
        [METRICS.get(m) for m in config["metrics"]], config=config,
        train_loader=config.init_obj("train_loader", LOADERS),
        valid_loader=None, mesh=mesh_from_config(config), seed=0)
    return trainer, config


def _records(config) -> list:
    return [r for r in read_jsonl(Path(config.save_dir) / "telemetry.jsonl")
            if "wall_ms" in r]


@pytest.fixture
def clean_faults(monkeypatch):
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def two_runs(tmp_path_factory):
    """Two trainers built and run one after the other in this process."""
    out = []
    for run_id in ("first", "second"):
        mark = time.perf_counter()
        trainer, config = _trainer(tmp_path_factory.mktemp(run_id), run_id,
                                   epochs=1)
        built = time.perf_counter() - mark
        trainer.train()
        out.append((_records(config), built))
    return out


def test_each_trainers_first_record_carries_its_own_setup(two_runs):
    for records, built in two_runs:
        (first,) = [r for r in records if "setup" in r]
        assert first["step"] == 0
        assert set(first["step_program"]) == {"batch_devices",
                                              "param_devices"}
        setup, at = first["setup"], first["setup_at"]
        assert {"setup/trainer_init", "setup/state_init",
                "train_step/await_warmup", "first_iteration_s"} | {
            f"warmup/train_step/{s}" for s in STAGES} <= set(setup)
        assert set(at) == set(setup) - {"first_iteration_s"}
        # this trainer's, not the process's total: its own init is in
        # it once, and no longer than the caller saw it take
        assert 0 < setup["setup/state_init"] < setup[
            "setup/trainer_init"] <= built
        assert at["setup/trainer_init"] == 0.0
        assert 0 < at["setup/state_init"] < at["warmup/train_step/trace"]
        assert (at["warmup/train_step/trace"]
                < at["warmup/train_step/lower"]
                < at["warmup/train_step/compile"])
        # the wait ends when the compile does, or had nothing to wait for
        wait_end = at["train_step/await_warmup"] + setup[
            "train_step/await_warmup"]
        compile_end = at["warmup/train_step/compile"] + setup[
            "warmup/train_step/compile"]
        assert wait_end >= compile_end - 1e-3
        assert setup["first_iteration_s"] >= setup[
            "train_step/await_warmup"]


def test_the_trainers_compile_events_name_the_step(two_runs):
    records, _ = two_runs[0]
    events = [e for r in records for e in r.get("compile_events", ())]
    by_stage = {e["event"].rsplit("/", 1)[-1]: e for e in events
                if e.get("fun_name") == "train_step"}
    assert {"jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
            "backend_compile_duration"} <= set(by_stage)
    for r in records:
        traced = [e["fun_name"] for e in r.get("compile_events", ())
                  if e["event"].endswith("jaxpr_trace_duration")]
        assert len(set(traced)) == len(traced)


def test_every_record_closes_its_sum(two_runs):
    for records, _ in two_runs:
        assert len(records) == 8
        for r in records:
            named = sum(r.get(k, 0.0) for k in ITERATION_PARTS)
            assert r["unattributed_ms"] + named == pytest.approx(
                r["wall_ms"], abs=5e-3)
            assert r["unattributed_ms"] > -5e-3
        # the recorder's own write, on the record after it
        assert "record_ms" not in records[0]
        assert sum("record_ms" in r for r in records) >= len(records) - 2
        assert all(0 <= r["record_ms"] <= r["wall_ms"]
                   for r in records if "record_ms" in r)


def test_the_iterations_that_hold_a_captures_ends_are_marked(tmp_path):
    trainer, config = _trainer(
        tmp_path, "captured", epochs=1,
        profiler={"trace_start_step": 3, "trace_steps": 2})
    trainer.train()
    records = _records(config)
    assert [r["step"] for r in records if "profile_ms" in r] == [3, 4]
    for r in records:
        named = sum(r.get(k, 0.0) for k in ITERATION_PARTS)
        assert r["unattributed_ms"] + named == pytest.approx(
            r["wall_ms"], abs=5e-3)
    assert (config.log_dir / "profile").is_dir()


def test_a_stalled_iteration_says_what_the_host_was_doing(
        tmp_path, clean_faults):
    """A slow host (the fault hook sleeps), a late device (the health
    fetch waits) and a collection, each in an iteration of its own."""
    trainer, config = _trainer(tmp_path, "stalls", epochs=4,
                               faults="slow_host@step:12:300ms")
    enqueue = trainer.health.enqueue

    def slowed(step, *args, **kwargs):
        if step == 18:
            time.sleep(0.3)
        elif step == 24:
            junk = [[] for _ in range(400_000)]
            for a, b in zip(junk, junk[1:]):
                a.append(b)
            del junk, a, b
            gc.collect()
        return enqueue(step, *args, **kwargs)

    trainer.health.enqueue = slowed
    said = []
    handler = logging.Handler(level=logging.WARNING)
    handler.emit = lambda record: said.append(record.getMessage())
    trainer.logger.addHandler(handler)
    try:
        trainer.train()
    finally:
        trainer.logger.removeHandler(handler)
    by_step = {r["step"]: r for r in _records(config)}
    assert len(by_step) == 32
    host, device, collected = (by_step[s]["stall"] for s in (12, 18, 24))
    assert host["in"] == "unattributed" and host["over_ms"] >= 250
    assert device["in"] == "health_fetch_ms" and device["over_ms"] >= 250
    assert collected["in"] == "health_fetch_ms"
    assert collected["gc_gen2"] >= 1 and collected["gc_ms"] > 1.0
    assert host["gc_gen2"] == 0 or host["gc_ms"] < host["over_ms"] / 2
    for stall in (host, device, collected):
        assert set(stall) == {"over_ms", "in", "gc_ms", "gc_gen2", "nvcsw",
                              "nivcsw", "majflt", "threads"}
        assert isinstance(stall["threads"], list)
    assert "stall" not in by_step[0]        # set-up accounts for that one
    assert any("step 12" in s and "unattributed" in s for s in said)
    assert any("step 18" in s and "health_fetch_ms" in s for s in said)


# -- the operator's reading ----------------------------------------------------


def test_the_report_prints_setup_by_phase_and_the_stalls():
    import sys

    sys.path.insert(0, str(Path(__file__).parent.parent / "scripts"))
    import telemetry_report

    setup = {"setup/trainer_init": 12.5, "setup/state_init": 9.0,
             "train_step/await_warmup": 55.0, "first_iteration_s": 60.0}

    def stall(where, over, gc_ms=0.0):
        return {"over_ms": over, "in": where, "gc_ms": gc_ms, "gc_gen2": 0,
                "nvcsw": 3, "nivcsw": 0, "majflt": 0, "threads": []}

    records = [{"step": 0, "wall_ms": 60000.0, "setup": setup}] + [
        {"step": k, "wall_ms": 250.0} for k in range(1, 20)] + [
        {"step": 20, "wall_ms": 900.0, "stall": stall("unattributed", 650.0)},
        {"step": 21, "wall_ms": 4900.0,
         "stall": stall("unattributed", 4650.0, gc_ms=4400.0)},
        {"step": 22, "wall_ms": 700.0,
         "stall": stall("health_fetch_ms", 450.0)}]
    report = telemetry_report.analyze_telemetry(records)
    assert report["setup"] == setup
    assert report["stalls"] == {
        "count": 3, "worst_over_ms": 4650.0, "worst_step": 21,
        "worst_gc_ms": 4400.0, "in unattributed": 2, "in health_fetch_ms": 1}
    text = telemetry_report.to_markdown({"telemetry": report})
    assert "| train_step/await_warmup | 55.0 |" in text
    assert "## Stalled iterations" in text
    assert "| in unattributed | 2 |" in text
    quiet = telemetry_report.analyze_telemetry(records[1:20])
    assert "setup" not in quiet and "stalls" not in quiet
