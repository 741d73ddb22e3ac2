"""benchmarks/trace_reduce.py on its two fixtures: a hand-made event
list whose numbers are worked out here, and a trace recorded on the chip
in PR 24's first call, cut to three steps, with the numbers read from it
by hand."""
import gzip
import json
from pathlib import Path

import pytest

from benchmarks import trace_reduce as tr

FIXTURES = Path(__file__).resolve().parents[2] / "benchmarks" / "fixtures"


def planes_of(raw: dict) -> dict:
    return {p: {line: [tuple(e) for e in events]
                for line, events in lines.items()}
            for p, lines in raw.items()}


@pytest.fixture(scope="module")
def synthetic():
    fx = json.loads((FIXTURES / "trace_synthetic.json").read_text())
    return planes_of(fx["planes"]), fx["expect"]


def test_merged_and_clipped():
    assert tr.merged([(5, 9), (0, 3), (2, 4), (9, 9)]) == [[0, 4], [5, 9]]
    events = [("a", 0, 10), ("b", 8, 10), ("c", 30, 5)]
    assert tr.clipped(events, 5, 32) == [(5, 10), (8, 18), (30, 32)]
    assert tr.union_ns(events, 5, 32) == 13 + 2


def test_window_leaves_out_the_step_in_flight(synthetic):
    planes, want = synthetic
    mods = planes["/device:TPU:0"][tr.MODULES_LINE]
    lo, hi, steps = tr.step_window(mods, "train_step")
    # the first event lasts 30 against 100: tracing began inside it
    assert (lo, hi, steps) == (100, 420, want["steps"])
    with pytest.raises(ValueError, match="no module event"):
        tr.step_window(mods, "eval_step")


def test_busy_is_a_union_on_one_line_of_one_device(synthetic):
    planes, want = synthetic
    trace = tr.Trace(planes, "train_step")
    assert trace.device == want["device"] and trace.n_devices == 2
    assert trace.steps == want["steps"]
    assert trace.hi - trace.lo == want["window_ns"]
    # by hand: step 1 [100,190] + [192,200] = 98, step 2 [210,240] +
    # [245,280] + [290,310] = 85, step 3 = 100; the loop's children, the operation
    # straddling the start and the modules and steps lines add nothing
    assert trace.busy_ns == want["busy_ns"] == 98 + 85 + 100
    assert 0 < trace.busy_s <= trace.window_s
    summed = sum(d for _, s, d in trace.ops if 100 <= s < 420)
    assert summed > want["window_ns"], "a plain sum would pass the window"


def test_self_time_charges_the_innermost_operation(synthetic):
    planes, want = synthetic
    ops = planes["/device:TPU:0"][tr.OPS_LINE]
    got = tr.self_times(ops, 100, 420)
    assert got == want["self_ns"]
    assert sum(got.values()) == want["busy_ns"]


def test_exposed_collective_time(synthetic):
    planes, want = synthetic
    ops = planes["/device:TPU:0"][tr.OPS_LINE]
    total, alone = tr.exposed_ns(ops, "all-reduce", 100, 420)
    # [170,185] inside its loop and [192,200] run alone; of [245,265]
    # only [245,250] does, the rest lies behind fusion.3
    assert total == want["allreduce_ns"] == 15 + 8 + 20
    assert alone == want["allreduce_exposed_ns"] == 15 + 8 + 5


def test_idle_gaps_and_their_names(synthetic):
    planes, want = synthetic
    trace = tr.Trace(planes, "train_step")
    gaps = tr.idle_gaps(trace.ops, trace.lo, trace.hi)
    assert sorted(gaps) == sorted(tuple(g) for g in want["gaps_ns"])
    assert sum(b - a for a, b in gaps) == want["window_ns"] - want["busy_ns"]
    bd = trace.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", 170e-9]
    assert [round(s * 1e9) for _, s in bd["idle_gaps"]] == [10, 10, 10, 5, 2]
    names = dict((round(s * 1e9, 3), n) for n, s in bd["idle_gaps"][-1:])
    assert names[2] == "after while.1, before all-reduce.2"
    # the thread that dispatches the step was in data/next_batch; the
    # prefetch thread's long wait and the run-long span say nothing
    assert bd["idle_gaps"][0][0] == "data/next_batch"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_short_names_keep_what_identifies_an_operation():
    long = ('%self_attn.12 = bf16[32,8192,128]{2,1,0:T(8,128)(2,1)} '
            'custom-call(bf16[32,8192,128]{2,1,0} %custom-call.57), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    assert tr.short_name(long) == \
        "self_attn.12 custom-call bf16[32,8192,128] tpu_custom_call"
    fused = ('%fusion.96 = (f32[4096]{0:T(1024)}, bf16[8192,4096]{1,0}) '
             'fusion(bf16[1,8192,4096]{2,1,0} %copy-done.34), kind=kOutput')
    assert tr.short_name(fused) == "fusion.96 fusion f32[4096]"
    assert tr.short_name("fusion.1") == "fusion.1"


@pytest.fixture(scope="module")
def chip():
    """Recorded on a v5e in PR 24's first chip call (mistral7b_l2.seq8k,
    traced run, seed 101), cut by benchmarks/tools/trace_look.py to the
    first whole steps: the device's operations and modules lines and the
    host events longer than 0.2 ms."""
    with gzip.open(FIXTURES / "trace_chip_mistral7b_l2_seq8k.json.gz",
                   "rt") as f:
        return planes_of(json.load(f))


def test_chip_trace_reads_as_it_did_by_hand(chip):
    trace = tr.Trace(chip, "train_step")
    assert trace.device == "/device:TPU:0" and trace.steps == 4
    # read by hand from the full trace (tools/trace_look.py, PR 24):
    # 2852.6 ms and 2819.0 ms busy over 8 steps, 352.4 ms a step with a
    # 4.3-4.8 ms gap between steps; the cut holds 4 steps and 3 gaps
    assert trace.window_s == pytest.approx(1.42331, rel=1e-4)
    assert trace.busy_s == pytest.approx(1.40951, rel=1e-4)
    assert trace.busy_s / trace.steps == pytest.approx(0.3524, rel=2e-3)
    gaps = sorted(b - a for a, b in
                  tr.idle_gaps(trace.ops, trace.lo, trace.hi))[-3:]
    assert all(4.2e6 < g < 4.9e6 for g in gaps)
    # the operations line nests (loops hold their bodies): a plain sum
    # of durations passes the window, the union does not
    inside = [e for e in trace.ops if trace.lo <= e[1] < trace.hi]
    assert sum(d for _, _, d in inside) > trace.hi - trace.lo
    assert sum(tr.self_times(trace.ops, trace.lo, trace.hi).values()) == \
        pytest.approx(trace.busy_ns, rel=1e-9)


def test_chip_trace_union_against_a_raster(chip):
    """The union again by another road: mark every microsecond that an
    operation covers."""
    import numpy as np

    trace = tr.Trace(chip, "train_step")
    busy = np.zeros(int((trace.hi - trace.lo) / 1e3) + 1, bool)
    for a, b in tr.clipped(trace.ops, trace.lo, trace.hi):
        busy[int((a - trace.lo) / 1e3):int(np.ceil((b - trace.lo) / 1e3))] = True
    assert busy.sum() * 1e3 == pytest.approx(trace.busy_ns, rel=2e-3)


def test_chip_trace_kernels_and_gap_names(chip):
    from benchmarks import reducers
    from benchmarks.run import Facts

    trace = tr.Trace(chip, "train_step")
    peaks = json.loads((FIXTURES.parent / "peaks.json").read_text())
    facts = Facts(sizes={"window": 4096}, peak=peaks["TPU v5 lite"],
                  setup_records=[], window_records=[], compile_events=[],
                  trace=trace)

    def read(metric):
        spec = json.loads(
            (FIXTURES.parent / "layer_metrics" / f"{metric}.json").read_text())
        return reducers.get(spec["reducer"])(facts, **spec["args"])

    # by hand: per step 4 forward calls of 7.7-7.8 ms (two layers, run
    # again by remat), 2 dkv of 7.86 ms, 2 dq of 5.6 ms = 58.1 ms
    assert read("flash_ms_per_step") == pytest.approx(58.05, rel=2e-3)
    # forward: 4 x 128 x 32 x 8192 x 3072.25 = 4.12e11 operations, 2.09 ms
    # at 197e12/s against 7.8 ms; with dkv (twice the operations in
    # 7.86 ms) and dq (1.5 times in 5.6 ms): 23.0 ms of 58.05
    assert read("flash_roofline_pct") == pytest.approx(39.66, rel=2e-3)
    assert 0 < read("flash_roofline_pct") <= 100
    assert read("step_busy_ms") == pytest.approx(352.38, rel=1e-3)
    assert read("device_idle_pct") == pytest.approx(0.969, rel=5e-3)
    assert read("allreduce_ms_per_step") is None      # one chip
    top = trace.breakdown()
    assert top["device_ops"][0][0].startswith("convolution_add_fusion.8")
    assert top["idle_gaps"][0][0] == (
        "$trainer.py:1039 _flush_log_entry > $array.py:631 _value")


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        tr.Trace({"/host:CPU": {"python": [("x", 0, 1)]}}, "train_step")
    with pytest.raises(FileNotFoundError):
        tr.newest_xplane(FIXTURES)
