"""The readings PR 25 added, on a trace recorded on the chip in PR 32
(call 1: `mistral7b_l2.seq8k`, seed 2147500001, the change's tree) and
cut to three steps by `benchmarks/tools/cut_xplane.py`: each device
operation's scope from the capture's metadata, the dispatching thread's
spans, and the two reducers over them. The numbers asserted are the ones
the traced run itself printed over its eight steps (PERF.md section 5),
and hold for the cut to the tolerances given."""
import argparse
import dataclasses
import gzip
import json
import re
from pathlib import Path

import pytest

from benchmarks import reducers, run, xplane
from benchmarks import trace_reduce as tr
from benchmarks.tools import cut_xplane

BENCH = Path(__file__).resolve().parents[2] / "benchmarks"
FIXTURE = BENCH / "fixtures" / "trace_chip_pr32_mistral7b_l2_seq8k.xplane.pb.gz"
SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
PARTITION = ("fwd_ms_per_step", "remat_ms_per_step", "bwd_ms_per_step",
             "optimizer_ms_per_step", "unscoped_ms_per_step")
IDLE = ("idle_log_flush_ms", "idle_health_fetch_ms", "idle_data_wait_ms",
        "idle_unnamed_ms")
NEW = PARTITION + IDLE + ("head_loss_ms_per_step",)


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """The fixture unpacked, as a run's profiler would have left it."""
    path = tmp_path_factory.mktemp("bench") / "chip.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return path


@pytest.fixture()
def facts(capture):
    trace = tr.Trace(tr.load_xplane(capture), "train_step")
    return run.Facts(sizes={"window": 4096}, peak={}, setup_records=[],
                     window_records=[], compile_events=[], trace=trace,
                     capture=capture)


def read(facts, metric):
    spec = json.loads((BENCH / "layer_metrics" / f"{metric}.json").read_text())
    return reducers.get(spec["reducer"])(facts, **spec["args"])


def test_wire_reading_and_writing_agree():
    inner = cut_xplane.put(2, 2, b"/device:TPU:0") + cut_xplane.put(1, 0, 300)
    message = cut_xplane.put(1, 2, inner) + cut_xplane.put(9, 1, b"12345678")
    (n1, w1, v1), (n2, w2, v2) = xplane.fields(message)
    assert (n1, w1, bytes(v1)) == (1, 2, inner)
    assert (n2, w2, bytes(v2)) == (9, 1, b"12345678")
    assert [(n, v if w == 0 else bytes(v)) for n, w, v in xplane.fields(v1)] \
        == [(2, b"/device:TPU:0"), (1, 300)]     # 300 takes two bytes


def test_the_reducers_read_the_capture_the_run_names(facts, capture):
    """Not the newest on disk: two runs at once (the tests' rehearsals)
    each leave a capture."""
    later = capture.with_name("later.xplane.pb")
    later.write_bytes(b"")
    try:
        assert read(facts, "optimizer_ms_per_step") > 0
        assert read(facts, "idle_log_flush_ms") > 0
        nowhere = dataclasses.replace(facts, capture=later)
        with pytest.raises(ValueError, match="holds no plane"):
            read(nowhere, "optimizer_ms_per_step")
    finally:
        later.unlink()


def test_every_flash_kernel_event_carries_its_kernels_name(facts, capture):
    scopes = xplane.op_scopes(capture, facts.trace.device)
    kernels = {n for n, _, _ in facts.trace.ops if "tpu_custom_call" in n}
    # two layers: a forward, a dkv and a dq each; since PR 27 the forward's
    # output and lse are kept, so none is run again
    assert len(kernels) == 6
    by_kernel = {}
    for name in kernels:
        (kernel,) = re.findall(r"^%(flash_fwd|flash_dkv|flash_dq)\.\d+ = ",
                               name)
        assert f"/self_attn/{kernel}/pallas_call" in scopes[name]
        by_kernel.setdefault(kernel, []).append(scopes[name])
    assert {k: len(v) for k, v in by_kernel.items()} == {
        "flash_fwd": 2, "flash_dkv": 2, "flash_dq": 2}
    assert not any("rematted_computation" in s
                   for s in by_kernel["flash_fwd"])
    assert all("transpose(" in s
               for s in by_kernel["flash_dkv"] + by_kernel["flash_dq"])


def test_scopes_partition_the_busy_time(facts):
    got = {m: read(facts, m) for m in PARTITION + ("head_loss_ms_per_step",)}
    busy = read(facts, "step_busy_ms")
    assert sum(got[m] for m in PARTITION) == pytest.approx(busy, rel=1e-9)
    # the run's line over eight steps (PERF.md section 5, PR 32): forward
    # 75.44, recomputation 13.29, backward 150.33, optimizer 40.26, the
    # rest 2.05 of 281.37 ms a step; head and loss 52.09 over all phases
    assert got["fwd_ms_per_step"] == pytest.approx(75.44135, rel=1e-3)
    assert got["remat_ms_per_step"] == pytest.approx(13.28749, rel=1e-3)
    assert got["bwd_ms_per_step"] == pytest.approx(150.32541, rel=1e-3)
    assert got["optimizer_ms_per_step"] == pytest.approx(40.26489, rel=1e-3)
    assert got["unscoped_ms_per_step"] == pytest.approx(2.04869, rel=5e-3)
    assert got["unscoped_ms_per_step"] < 0.05 * busy
    assert got["head_loss_ms_per_step"] == pytest.approx(52.0946, rel=2e-3)


def test_flash_time_falls_into_the_phases_by_hand(facts, capture):
    """Per step and layer: one forward of 4.26 ms, no recomputation, a
    dkv of 6.19 ms and a dq of 4.74 ms (PERF.md section 5, PR 31)."""
    scopes = xplane.op_scopes(capture, facts.trace.device)
    trace = facts.trace
    took = {"fwd": 0.0, "remat": 0.0, "bwd": 0.0}
    for name, t in tr.self_times(trace.ops, trace.lo, trace.hi).items():
        if "tpu_custom_call" in name:
            scope = scopes[name]
            phase = ("remat" if "rematted_computation" in scope else
                     "bwd" if "transpose(" in scope else "fwd")
            took[phase] += t / 1e6 / trace.steps
    assert took["fwd"] == pytest.approx(2 * 4.26, rel=0.01)
    assert took["remat"] == 0.0
    assert took["bwd"] == pytest.approx(2 * (6.19 + 4.74), rel=0.01)
    assert sum(took.values()) == pytest.approx(
        read(facts, "flash_ms_per_step"), rel=1e-6)


def test_the_dispatching_thread_is_told_from_its_namesake(capture):
    """Both Python threads' lines are called `python3` in this capture;
    the loader's thread holds `data/host_gather` and no step."""
    events = xplane.thread_events(capture, "train_step")
    names = {n for n, _, _ in events}
    assert {"train_step/dispatch", "train/health_fetch", "train/log",
            "train/log_fetch", "train/log_lr", "data/next_batch",
            "data/device_put"} <= names
    assert "data/host_gather" not in names
    merged = tr.load_xplane(capture)["/host:CPU"]["python3"]
    assert any(n == "data/host_gather" for n, _, _ in merged)
    # which fetch waits: the log's device_get returns at once, the
    # schedule's float() takes the step (280 ms), the health fetch under 1
    took = {}
    for n, _, d in events:
        took.setdefault(n, []).append(d / 1e6)
    assert max(took["train/log_fetch"]) < 1.0
    assert max(took["train/health_fetch"]) < 1.0
    assert min(took["train/log_lr"]) > 280.0


def test_idle_time_by_span(facts):
    got = {m: read(facts, m) for m in IDLE}
    trace = facts.trace
    idle = (trace.window_s - trace.busy_s) * 1e3 / trace.steps
    # two gaps in three steps, 4.17 and 4.25 ms: the device stops,
    # float() returns and train/log closes, the flight record is written,
    # data/next_batch and data/device_put run, and the gap ends inside
    # train_step/dispatch, a span that none of the four readings takes
    assert idle == pytest.approx(2.8386, rel=1e-3)
    assert got["idle_log_flush_ms"] == pytest.approx(1.4590, rel=2e-3)
    assert got["idle_data_wait_ms"] == pytest.approx(0.8948, rel=2e-3)
    assert got["idle_unnamed_ms"] == pytest.approx(0.2839, rel=5e-3)
    assert got["idle_health_fetch_ms"] == pytest.approx(0.0, abs=1e-4)
    dispatch = reducers.get("idle_by_span")(
        facts, thread="train_step", within="^train_step/")
    assert dispatch == pytest.approx(0.2009, rel=5e-3)
    assert sum(got.values()) + dispatch == pytest.approx(idle, rel=1e-5)
    assert got["idle_unnamed_ms"] < 0.11 * idle


def test_a_program_without_spans_or_scopes_reads_zero_not_nothing(
        facts, monkeypatch):
    """The parent commit's traces: the harness fails a run whose reducer
    returns None, so time inside what is not there is 0.0 and the
    unnamed remainder is everything."""
    real = xplane.thread_events
    monkeypatch.setattr(
        xplane, "thread_events", lambda path, pattern: [
            e for e in real(path, pattern)
            if not re.match(r"(train|train_step|data)/", e[0])])
    monkeypatch.setattr(xplane, "op_scopes", lambda path, plane: {})
    trace = facts.trace
    idle = (trace.window_s - trace.busy_s) * 1e3 / trace.steps
    assert read(facts, "idle_log_flush_ms") == 0.0
    assert read(facts, "idle_data_wait_ms") == 0.0
    assert read(facts, "idle_unnamed_ms") == pytest.approx(idle, rel=1e-9)
    assert read(facts, "optimizer_ms_per_step") == 0.0
    assert read(facts, "unscoped_ms_per_step") == pytest.approx(
        read(facts, "step_busy_ms"), rel=1e-9)
    # no thread that dispatches the step at all: nothing to read
    monkeypatch.setattr(xplane, "thread_events", lambda path, pattern: [])
    assert read(facts, "idle_unnamed_ms") is None


def test_cutting_keeps_whole_steps_and_their_metadata(capture, tmp_path):
    out = tmp_path / "cut.xplane.pb.gz"
    cut_xplane.main([str(capture), str(out), "--first", "1",
                     "--steps", "2"])
    path = tmp_path / "cut.xplane.pb"
    path.write_bytes(gzip.decompress(out.read_bytes()))
    trace = tr.Trace(tr.load_xplane(path), "train_step")
    assert trace.steps == 2
    assert trace.busy_s * 1e3 / 2 == pytest.approx(281.4, rel=1e-3)
    assert len(xplane.op_scopes(str(path), trace.device)) == 270
    with pytest.raises(SystemExit):
        cut_xplane.main([str(capture), str(out), "--first", "2",
                         "--steps", "2"])


GRANITE = (BENCH / "fixtures"
           / "trace_chip_pr40_granite4_h_micro_l10_seq8k.xplane.pb.gz")
# what the traced run printed over its eight steps (my chip run, PR 40:
# `granite4_h_micro_l10.seq8k`, seed 2147496001, this PR's tree)
GRANITE_LINE = {
    "flash_ms_per_step": 18.89671025, "flash_roofline_pct": 33.23172842980275,
    "step_busy_ms": 438.220851625, "ssm_scan_ms_per_step": 80.932042875,
    "ssm_intra_ms_per_step": 39.298901875,
    "ssm_state_ms_per_step": 24.846685375,
    "ssm_conv_ms_per_step": 14.4888355,
    "ssm_conv_bwd_roofline": 42.238451449386005}


def test_the_convolutions_readings_on_a_hybrid_steps_chip_trace(
        tmp_path_factory):
    """One step of the eight traced, cut by `cut_xplane.py --first 2
    --steps 1`: the convolution's scope, its backward kernel's share of
    the bytes roofline, and the scan's whole scope above its parts."""
    path = tmp_path_factory.mktemp("granite") / "chip.xplane.pb"
    path.write_bytes(gzip.decompress(GRANITE.read_bytes()))
    trace = tr.Trace(tr.load_xplane(path), "train_step")
    assert (trace.device, trace.steps) == ("/device:TPU:0", 1)
    peaks = json.loads((BENCH / "peaks.json").read_text())
    sizes = json.loads((BENCH / "configs" / "granite4_h_micro_l10.json")
                       .read_text())["sizes"]
    facts = run.Facts(sizes=sizes, peak=peaks["TPU v5 lite"],
                      setup_records=[], window_records=[], compile_events=[],
                      trace=trace, capture=path)
    got = {m: read(facts, m) for m in GRANITE_LINE}
    for m, printed in GRANITE_LINE.items():
        assert got[m] == pytest.approx(printed, rel=5e-4), m
    # nine layers' kernels, each [1, 4352, 8192] with four taps: 0.2614 ms
    # of bytes against 0.619 ms a call
    kernels = [e for e in trace.ops if e[0].startswith("%ssm_conv_bwd")]
    assert len(kernels) == 9
    assert all(" = (bf16[1,4352,8192]" in n and " f32[4352,4]{" in n
               for n, _, _ in kernels)
    assert sum(d for _, _, d in kernels) / 9e6 == pytest.approx(0.619, rel=2e-3)
    assert got["ssm_conv_bwd_roofline"] == pytest.approx(
        100 * 0.2614 / 0.619, rel=2e-3) and got["ssm_conv_bwd_roofline"] < 105
    # the kernel is 5.6 of the scope's 14.5 ms; the scope lies in the scan's
    assert 9 * 0.619 < got["ssm_conv_ms_per_step"] < 15
    parts = sum(got[m] for m in ("ssm_intra_ms_per_step",
                                 "ssm_state_ms_per_step",
                                 "ssm_conv_ms_per_step"))
    assert parts < got["ssm_scan_ms_per_step"] < parts + 5
    # the flash kernels beside it, head 64 at 8192 positions without a band
    assert "window" not in facts.sizes
    assert 33 < got["flash_roofline_pct"] < 33.5


@pytest.mark.parametrize("workload", [w["name"] for w in SPEC["workloads"]])
def test_a_traced_rehearsal_reports_every_new_reading(workload):
    args = argparse.Namespace(workload=workload, seed=2**31 + 25,
                              seconds=1.5, trace=1, rehearse=True)
    text, code = run.run(args, lambda s: None)
    line = json.loads(text)
    assert code == run.EXIT_REHEARSED and line["correct"] is True
    assert set(NEW) <= set(line["metrics"])
    assert set(NEW) <= set(run.expected_metrics(SPEC, workload, True))
    values = {m: line["metrics"][m]["value"] for m in NEW}
    assert all(v >= 0 for v in values.values())
    # the CPU backend keeps no scope in its capture: all of it unscoped
    assert sum(values[m] for m in PARTITION) == pytest.approx(
        line["metrics"]["step_busy_ms"]["value"], rel=1e-6)
