"""The readings PR 25 added, on a trace recorded on the chip in that PR
(call 2: `mistral7b_l2.seq8k`, seed 2147483711, the change's tree) and
cut to three steps by `benchmarks/tools/cut_xplane.py`: each device
operation's scope from the capture's metadata, the dispatching thread's
spans, and the two reducers over them. The numbers asserted were read
from the full eight-step trace by hand (the span listing and the
per-class sums in PERF.md section 5) and hold for the cut to the
tolerances given."""
import argparse
import gzip
import json
import re
from pathlib import Path

import pytest

from benchmarks import reducers, run, xplane
from benchmarks import trace_reduce as tr
from benchmarks.tools import cut_xplane

BENCH = Path(__file__).resolve().parents[2] / "benchmarks"
FIXTURE = BENCH / "fixtures" / "trace_chip_pr25_mistral7b_l2_seq8k.xplane.pb.gz"
SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
PARTITION = ("fwd_ms_per_step", "remat_ms_per_step", "bwd_ms_per_step",
             "optimizer_ms_per_step", "unscoped_ms_per_step")
IDLE = ("idle_log_flush_ms", "idle_health_fetch_ms", "idle_data_wait_ms",
        "idle_unnamed_ms")
NEW = PARTITION + IDLE + ("head_loss_ms_per_step",)


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    """The fixture unpacked where a run would have left it."""
    root = tmp_path_factory.mktemp("bench")
    path = root / "cell" / "plugins" / "profile" / "t" / "chip.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    return root, path


@pytest.fixture()
def facts(capture, monkeypatch):
    root, path = capture
    monkeypatch.setattr(xplane, "CAPTURES", root)
    trace = tr.Trace(tr.load_xplane(path), "train_step")
    return run.Facts(sizes={"window": 4096}, peak={}, setup_records=[],
                     window_records=[], compile_events=[], trace=trace)


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


def test_newest_capture_is_found_by_time(capture, tmp_path):
    root, path = capture
    assert xplane.newest_capture(root) == path
    with pytest.raises(FileNotFoundError):
        xplane.newest_capture(tmp_path)


def test_every_flash_kernel_event_carries_its_kernels_name(facts, capture):
    scopes = xplane.op_scopes(str(capture[1]), facts.trace.device)
    kernels = {n for n, _, _ in facts.trace.ops if "tpu_custom_call" in n}
    # two layers: a forward and its recomputation, one dkv, one dq each
    assert len(kernels) == 8
    by_kernel = {}
    for name in kernels:
        (kernel,) = re.findall(r"^%(flash_fwd|flash_dkv|flash_dq)\.\d+ = ",
                               name)
        assert f"/self_attn/{kernel}/pallas_call" in scopes[name]
        by_kernel.setdefault(kernel, []).append(scopes[name])
    assert {k: len(v) for k, v in by_kernel.items()} == {
        "flash_fwd": 4, "flash_dkv": 2, "flash_dq": 2}
    # of the four forward calls two are under the remat marker
    assert sum("rematted_computation" in s
               for s in by_kernel["flash_fwd"]) == 2
    assert all("transpose(" in s for s in by_kernel["flash_dkv"])


def test_scopes_partition_the_busy_time(facts):
    got = {m: read(facts, m) for m in PARTITION + ("head_loss_ms_per_step",)}
    busy = read(facts, "step_busy_ms")
    assert sum(got[m] for m in PARTITION) == pytest.approx(busy, rel=1e-9)
    # read from the full trace (PERF.md section 5, PR 25): forward 80.2,
    # recomputation 61.7, backward 168.0, optimizer 40.3, the rest 1.8
    # of 352.0 ms a step; head and loss 67.4 over all phases
    assert got["fwd_ms_per_step"] == pytest.approx(80.24, rel=1e-3)
    assert got["remat_ms_per_step"] == pytest.approx(61.68, rel=1e-3)
    assert got["bwd_ms_per_step"] == pytest.approx(167.95, rel=1e-3)
    assert got["optimizer_ms_per_step"] == pytest.approx(40.29, rel=1e-3)
    assert got["unscoped_ms_per_step"] == pytest.approx(1.806, rel=5e-3)
    assert got["unscoped_ms_per_step"] < 0.05 * busy
    assert got["head_loss_ms_per_step"] == pytest.approx(67.43, rel=1e-3)


def test_flash_time_falls_into_the_phases_by_hand(facts, capture):
    """Per step and layer: one forward of 7.8 ms, its recomputation, a
    dkv of 7.86 ms and a dq of 5.6 ms (PERF.md section 5, PR 24)."""
    scopes = xplane.op_scopes(str(capture[1]), facts.trace.device)
    trace = facts.trace
    took = {"fwd": 0.0, "remat": 0.0, "bwd": 0.0}
    for name, t in tr.self_times(trace.ops, trace.lo, trace.hi).items():
        if "tpu_custom_call" in name:
            scope = scopes[name]
            phase = ("remat" if "rematted_computation" in scope else
                     "bwd" if "transpose(" in scope else "fwd")
            took[phase] += t / 1e6 / trace.steps
    assert took["fwd"] == pytest.approx(2 * 7.8, rel=0.01)
    assert took["remat"] == pytest.approx(2 * 7.8, rel=0.01)
    assert took["bwd"] == pytest.approx(2 * (7.86 + 5.6), rel=0.01)
    assert sum(took.values()) == pytest.approx(
        read(facts, "flash_ms_per_step"), rel=1e-6)


def test_the_dispatching_thread_is_told_from_its_namesake(capture):
    """Both Python threads' lines are called `python3` in this capture;
    the loader's thread holds `data/host_gather` and no step."""
    events = xplane.thread_events(capture[1], "train_step")
    names = {n for n, _, _ in events}
    assert {"train_step/dispatch", "train/health_fetch", "train/log",
            "train/log_fetch", "train/log_lr", "data/next_batch",
            "data/device_put"} <= names
    assert "data/host_gather" not in names
    merged = tr.load_xplane(capture[1])["/host:CPU"]["python3"]
    assert any(n == "data/host_gather" for n, _, _ in merged)
    # which fetch waits: the log's device_get returns at once, the
    # schedule's float() takes the step (351 ms), the health fetch under 1
    took = {}
    for n, _, d in events:
        took.setdefault(n, []).append(d / 1e6)
    assert max(took["train/log_fetch"]) < 1.0
    assert max(took["train/health_fetch"]) < 1.0
    assert min(took["train/log_lr"]) > 350.0


def test_idle_time_by_span(facts):
    got = {m: read(facts, m) for m in IDLE}
    trace = facts.trace
    idle = (trace.window_s - trace.busy_s) * 1e3 / trace.steps
    # two gaps in three steps, 4.07 and 4.61 ms. By hand, the first: the
    # device stops at 0; float() returns at 2.63 and train/log closes at
    # 2.83; the flight record is written; data/next_batch opens at 3.19
    # and the first operation of the next batch's transfer ends the gap
    # at 4.07, before train_step/dispatch opens at 4.51
    assert idle == pytest.approx(2.923, rel=1e-3)
    assert got["idle_log_flush_ms"] == pytest.approx(1.935, rel=2e-3)
    assert got["idle_data_wait_ms"] == pytest.approx(0.725, rel=2e-3)
    assert got["idle_unnamed_ms"] == pytest.approx(0.263, rel=5e-3)
    assert got["idle_health_fetch_ms"] == pytest.approx(0.0, abs=1e-4)
    # nothing but a few nanoseconds between operations idles under
    # train_step/dispatch here, so these four add up
    assert sum(got.values()) == pytest.approx(idle, rel=1e-5)
    assert got["idle_unnamed_ms"] < 0.1 * idle


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
    cut_xplane.main([str(capture[1]), str(out), "--first", "1",
                     "--steps", "2"])
    path = tmp_path / "cut.xplane.pb"
    path.write_bytes(gzip.decompress(out.read_bytes()))
    trace = tr.Trace(tr.load_xplane(path), "train_step")
    assert trace.steps == 2
    assert trace.busy_s * 1e3 / 2 == pytest.approx(352.0, rel=1e-3)
    assert len(xplane.op_scopes(str(path), trace.device)) == 300
    with pytest.raises(SystemExit):
        cut_xplane.main([str(capture[1]), str(out), "--first", "2",
                         "--steps", "2"])


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
