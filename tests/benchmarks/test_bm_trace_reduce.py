"""benchmarks/trace_reduce.py on its two fixtures: a hand-made event
list whose numbers are worked out here, and a trace recorded on the chip
in PR 32 (the kernels carry their names in it), cut to three steps, with
the numbers the traced run itself printed over all eight. Then the
readings by name, on made-up steps."""
import gzip
import json
from pathlib import Path

import pytest

from benchmarks import trace_reduce as tr

FIXTURES = Path(__file__).resolve().parents[2] / "benchmarks" / "fixtures"
SPEC = json.loads((FIXTURES.parents[1] / "BENCHMARK.json").read_text())


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


# -- readings by name (PR 32): a made-up step with the three flash kernels,
# a fourth kernel of another family, fusions that read a kernel's or a
# crossing's result, and a bare all-reduce. Names in the form the chip's
# traces print them (the whole HLO instruction).
STEP_NS = 300e6
FLASH = {      # kind: (duration ns, the instruction)
    "fwd": (4.26e6, '%flash_fwd.4 = (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, '
            'f32[32,8192,1]{2,1,0:T(8,128)}) custom-call(bf16[32,8192,128]'
            '{2,1,0:T(8,128)(2,1)S(1)} %fusion.172, bf16[32,8192,128]{2,1,0} '
            '%bitcast.639), custom_call_target="tpu_custom_call"'),
    "dkv": (6.19e6, '%flash_dkv.2 = (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, '
            'bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}) custom-call(bf16[32,8192,'
            '128]{2,1,0:T(8,128)(2,1)S(1)} %custom-call.57, bf16[32,8192,128]'
            '{2,1,0} %get-tuple-element.506), '
            'custom_call_target="tpu_custom_call"'),
    "dq": (4.76e6, '%flash_dq.2 = bf16[32,8192,128]{2,1,0:T(8,128)(2,1)} '
           'custom-call(bf16[32,8192,128]{2,1,0:T(8,128)(2,1)S(1)} '
           '%custom-call.57, f32[32,8192,1]{2,1,0:T(8,128)} %pallas_call.28), '
           'custom_call_target="tpu_custom_call"'),
}
OTHERS = [     # (duration ns, the instruction): none of them a flash kernel
    (9e6, '%ssd_scan.3 = bf16[8,8192,1024]{2,1,0} custom-call(f32[8,64,128]'
     '{2,1,0} %fusion.9, bf16[8,8192,1024]{2,1,0} %fusion.10), '
     'custom_call_target="tpu_custom_call"'),
    (1e6, '%fusion.138 = f32[1,8192,32,128]{3,1,2,0:T(8,128)} fusion(bf16[32,'
     '8192,128]{2,1,0:T(8,128)(2,1)} %flash_dq.2, f32[8192,128]{1,0} '
     '%custom-call.53), kind=kLoop, calls=%fused_computation.316'),
]
ALL_REDUCE = (2e6, '%all-reduce.5 = f32[4096,4096]{1,0:T(8,128)} all-reduce('
              'f32[4096,4096]{1,0:T(8,128)} %fusion.7), channel_id=5, '
              'replica_groups={{0,1,2,3}}, use_global_device_ids=true, '
              'to_apply=%add.1')
READS_ONE = (3e6, '%fusion.12 = bf16[4096,4096]{1,0:T(8,128)(2,1)} fusion('
             'f32[4096,4096]{1,0:T(8,128)} %all-reduce.5), kind=kLoop, '
             'calls=%fused_computation.12')


def made_up_facts(extra=()):
    """Three steps of `STEP_NS`; in each the three flash kernels, then
    `extra` (duration, name) one after another; the rest of the step is
    one long fusion."""
    from benchmarks.run import Facts

    modules, ops = [], []
    for k in range(3):
        at = k * STEP_NS
        modules.append(("jit_train_step(7)", at, STEP_NS))
        for ns, name in list(FLASH.values()) + list(extra):
            ops.append((name, at, ns))
            at += ns
        ops.append(("%fusion.1 = bf16[8192,4096]{1,0} fusion(bf16[8192,4096]"
                    "{1,0} %param.1), kind=kOutput", at, (k + 1) * STEP_NS - at))
    planes = {"/device:TPU:0": {tr.MODULES_LINE: modules, tr.OPS_LINE: ops}}
    peaks = json.loads((FIXTURES.parent / "peaks.json").read_text())
    return Facts(sizes={"window": 4096}, peak=peaks["TPU v5 lite"],
                 setup_records=[], window_records=[], compile_events=[],
                 trace=tr.Trace(planes, "train_step"))


def own_name(event_name: str) -> str:
    return event_name.partition(" = ")[0]


def read_metric(facts, metric, **override):
    from benchmarks import reducers

    spec = json.loads(
        (FIXTURES.parent / "layer_metrics" / f"{metric}.json").read_text())
    return reducers.get(spec["reducer"])(facts, **{**spec["args"], **override})


CHIP = FIXTURES / "trace_chip_pr32_mistral7b_l2_seq8k.xplane.pb.gz"
# what the traced run printed over its eight steps (my chip run, PR 32:
# `mistral7b_l2.seq8k`, seed 2147500001, this PR's tree)
RUN_LINE = {"flash_ms_per_step": 30.37781325,
            "flash_roofline_pct": 62.01349541031605,
            "step_busy_ms": 281.367832}


@pytest.fixture(scope="module")
def chip(tmp_path_factory):
    """Recorded on a v5e in PR 32's first chip call and cut by
    benchmarks/tools/cut_xplane.py to steps 2 to 4 of the eight traced:
    the device plane's events inside them, the host events that overlap
    them, every plane's metadata."""
    path = tmp_path_factory.mktemp("chip") / "chip.xplane.pb"
    path.write_bytes(gzip.decompress(CHIP.read_bytes()))
    return tr.load_xplane(path)


def test_chip_trace_reads_as_the_run_read_it(chip):
    trace = tr.Trace(chip, "train_step")
    assert trace.device == "/device:TPU:0" and trace.steps == 3
    # the full trace: 2281.9 ms and 2250.9 ms busy over 8 steps, 281.4 ms
    # a step with a 4.2-5.1 ms gap between steps; the cut holds 3 steps
    # and 2 gaps
    assert trace.window_s == pytest.approx(0.852752, rel=1e-5)
    assert trace.busy_s == pytest.approx(0.844236, rel=1e-5)
    assert trace.busy_s / trace.steps == pytest.approx(0.28137, rel=1e-3)
    gaps = sorted(b - a for a, b in
                  tr.idle_gaps(trace.ops, trace.lo, trace.hi))[-2:]
    assert all(4.1e6 < g < 4.3e6 for g in gaps)
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
    from benchmarks.run import Facts

    trace = tr.Trace(chip, "train_step")
    peaks = json.loads((FIXTURES.parent / "peaks.json").read_text())
    facts = Facts(sizes={"window": 4096}, peak=peaks["TPU v5 lite"],
                  setup_records=[], window_records=[], compile_events=[],
                  trace=trace)
    # per step one forward a layer of 4.26 ms (its output is kept, so
    # none is run again), a dkv of 6.19 ms, a dq of 4.74 ms = 30.38 ms,
    # all six found by their own names
    named = {own_name(n) for n, _, _ in trace.ops if "tpu_custom_call" in n}
    assert named == {"%flash_fwd.2", "%flash_fwd.3", "%flash_dkv.2",
                     "%flash_dkv.3", "%flash_dq.2", "%flash_dq.3"}
    assert read_metric(facts, "flash_ms_per_step") == pytest.approx(
        RUN_LINE["flash_ms_per_step"], rel=1e-4)
    # forward: 4 x 128 x 32 x 8192 x 3072.25 = 4.12e11 operations, 2.09 ms
    # at 197e12/s against 4.26 ms; with dkv (twice the operations in
    # 6.19 ms) and dq (1.5 times in 4.74 ms): 18.84 ms of 30.38
    assert read_metric(facts, "flash_roofline_pct") == pytest.approx(
        RUN_LINE["flash_roofline_pct"], rel=1e-4)
    assert 0 < read_metric(facts, "flash_roofline_pct") <= 100
    # to the last bit what the parent's reader (one head size, PR 39) made
    # of these three steps
    assert read_metric(facts, "flash_ms_per_step") == 30.378231
    assert read_metric(facts, "flash_roofline_pct") == 62.01264262406572
    # the readings PR 31's ledger line holds, made by what a call returns
    assert read_metric(
        facts, "flash_ms_per_step",
        pattern='custom_call_target="tpu_custom_call"') == \
        read_metric(facts, "flash_ms_per_step")
    assert read_metric(facts, "step_busy_ms") == pytest.approx(
        RUN_LINE["step_busy_ms"], rel=1e-3)
    # two gaps in three steps; the run's eight steps had seven (1.356%)
    assert read_metric(facts, "device_idle_pct") == pytest.approx(
        0.99862, rel=1e-4)
    assert read_metric(facts, "allreduce_ms_per_step") is None      # one chip
    top = trace.breakdown()
    assert top["device_ops"][0][0].startswith(
        "bitcast_dynamic-update-slice_fusion.2 fusion bf16[4,2048,4096]")
    assert top["idle_gaps"][0][0] == (
        "$trainer.py:1050 _flush_log_entry > $<unknown> acquire")


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError, match="no device plane"):
        tr.Trace({"/host:CPU": {"python": [("x", 0, 1)]}}, "train_step")
    with pytest.raises(FileNotFoundError):
        tr.newest_xplane(FIXTURES)


# -- the readings by name, on the made-up steps above --------------------


def test_another_kernel_in_the_trace_changes_neither_flash_reading():
    from benchmarks import flops

    alone, beside = made_up_facts(), made_up_facts(OTHERS)
    assert beside.trace.steps == 3
    ms = read_metric(alone, "flash_ms_per_step")
    assert ms == pytest.approx((4.26 + 6.19 + 4.76), rel=1e-12)
    assert read_metric(beside, "flash_ms_per_step") == ms
    shape = dict(batch=1, seq_len=8192, n_head=32, head_dim=128)
    least = sum(flops.roofline_seconds(
        flops.flash_call_flops(kind, window=4096, **shape),
        flops.flash_call_bytes(kind, **shape), alone.peak)[0]
        for kind in FLASH)
    share = read_metric(alone, "flash_roofline_pct")
    assert share == pytest.approx(100 * least / (ms / 1e3), rel=1e-9)
    assert 55 < share < 70          # section 5's kernels, about 62%
    assert read_metric(beside, "flash_roofline_pct") == share
    # without flash kernels there is nothing to read, never 0
    bare = made_up_facts()
    bare.trace.ops = [e for e in bare.trace.ops if "flash" not in e[0]]
    assert read_metric(bare, "flash_ms_per_step") is None
    assert read_metric(bare, "flash_roofline_pct") is None


def test_a_step_without_flash_kernels_reports_what_it_has_and_no_more():
    """The flash metrics carry a `workloads` list (PR 40). A later cell
    whose step calls no flash kernel (a stack of scans, or attention
    kernels under names of their own) is not on it: it neither expects
    nor reports them, whatever its trace holds. A listed cell reports
    both; and a listed cell whose trace holds no `%flash_*` event still
    finds nothing to read and leaves them out of the line: no failed run,
    never a 0."""
    from benchmarks import lastline, run

    kept = ("flash_ms_per_step", "flash_roofline_pct", "step_busy_ms")
    spec = json.loads(json.dumps(SPEC))
    spec["per_layer"] = [m for m in spec["per_layer"] if m["name"] in kept]
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert "workloads" not in by_name["step_busy_ms"]
    on_the_list = by_name["flash_ms_per_step"]["workloads"]
    assert on_the_list == by_name["flash_roofline_pct"]["workloads"]
    # the six cells PR 39's ledger lines carry both readings in
    assert {"mistral7b_l2.seq8k", "gpt2_large.seq1k", "mistral7b_l2.seq8k_dp4",
            "nemotron3_super_l11.seq8k", "mistral7b_l2.seq4k",
            "granite4_h_micro_l10.seq8k"} <= set(on_the_list)
    listed = on_the_list[0]
    facts, said = made_up_facts(OTHERS), []
    assert set(run.expected_metrics(spec, "scans_l8.seq8k", True)) == {
        "step_busy_ms"}
    values = run.layer_values(spec, "scans_l8.seq8k", facts, said.append)
    assert set(values) == {"step_busy_ms"} and not said
    assert set(run.expected_metrics(spec, listed, True)) == set(kept)
    values = run.layer_values(spec, listed, facts, said.append)
    assert set(values) == set(kept) and not said
    facts.trace.ops = [e for e in facts.trace.ops if "%flash_" not in e[0]]
    values = run.layer_values(spec, listed, facts, said.append)
    assert set(values) == {"step_busy_ms"} and values["step_busy_ms"] > 0
    assert said == [f"nothing to read for {n}: left out of the line"
                    for n in kept[:2]]
    expected = {n: u for n, u in run.expected_metrics(
        spec, listed, True).items() if n in values}
    line = json.loads(lastline.build(
        correct=True, attempted=3, failed=0, values=values,
        expected=expected, trace=True, device={
            "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
            "memory_peak_bytes": 1, "busy_s": facts.trace.busy_s,
            "window_s": facts.trace.window_s}))
    assert set(line["metrics"]) == {"step_busy_ms"}


# a flash-family call whose value head size is another than its query-key
# one, under a name of its own: q and k at 192, v and what it returns at 128
TWO_SIZES = (7e6, '%mla_fwd.3 = (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, '
             'f32[32,8192,1]{2,1,0:T(8,128)}) custom-call(bf16[32,8192,192]'
             '{2,1,0:T(8,128)(2,1)S(1)} %fusion.172, bf16[32,8192,192]{2,1,0} '
             '%bitcast.639, bf16[32,8192,128]{2,1,0:T(8,128)(2,1)} '
             '%bitcast.640), custom_call_target="tpu_custom_call"')
VALUE_OPERAND = (r"custom-call\((?:bf16\[[\d,]+\]\S* %\S+, ){2}"
                 r"bf16\[\d+,\d+,(\d+)\]")


def test_the_roofline_reads_a_second_head_size_where_it_is_told_to():
    from benchmarks import flops

    facts = made_up_facts([TWO_SIZES])
    facts.sizes = {}                    # full causal
    mla = {"kernels": {"fwd": r"^%mla_fwd(\.\d+)? = "}}
    shape = dict(batch=1, seq_len=8192, n_head=32, head_dim=192)
    least = {v: flops.roofline_seconds(
        flops.flash_call_flops("fwd", v_head_dim=v, **shape),
        flops.flash_call_bytes("fwd", v_head_dim=v, **shape),
        facts.peak)[0] for v in (None, 128)}
    two = read_metric(facts, "flash_roofline_pct", **mla,
                      value_operand=VALUE_OPERAND)
    assert two == pytest.approx(100 * least[128] / 7e-3, rel=1e-12)
    assert 45 < two < 55
    # with the first operand's size for both, a fifth over the true share
    one = read_metric(facts, "flash_roofline_pct", **mla)
    assert one == pytest.approx(100 * least[None] / 7e-3, rel=1e-12)
    assert one / two == pytest.approx(1.2, rel=1e-9)
    # the three flash kernels beside it read as without it
    flash = read_metric(made_up_facts(), "flash_roofline_pct")
    assert read_metric(made_up_facts([TWO_SIZES]),
                       "flash_roofline_pct") == flash
    with pytest.raises(ValueError, match="finds no value head size"):
        read_metric(made_up_facts(), "flash_roofline_pct",
                    value_operand=VALUE_OPERAND)    # two operands printed


# the convolution's backward as the chip's traces print it (my chip run,
# PR 40, granite's step): the projection three times, the cotangent twice,
# taps and bias
CONV_BWD = (0.619e6, '%ssm_conv_bwd.9 = (bf16[1,4352,8192]{2,1,0:T(8,128)'
            '(2,1)}, f32[1,4352,128]{2,1,0:T(8,128)}) custom-call(bf16[1,8512,'
            '8192]{2,1,0:T(8,128)(2,1)} %bitcast.5323, bf16[1,8512,8192]{2,1,0'
            ':T(8,128)(2,1)} %bitcast.5324, bf16[1,8512,8192]{2,1,0:T(8,128)'
            '(2,1)} %bitcast.5325, bf16[1,4352,8192]{2,1,0:T(8,128)(2,1)} '
            '%maximum_bitcast_fusion, bf16[1,4352,8192]{2,1,0:T(8,128)(2,1)} '
            '%maximum_bitcast_fusion, f32[4352,4]{1,0:T(8,128)S(1)} '
            '%bitcast.5686, f32[4352,1]{1,0:T(8,128)S(1)} '
            '%broadcast_in_dim.165), custom_call_target="tpu_custom_call"')


def test_the_convolutions_kernel_reads_its_share_of_the_bytes_roofline():
    facts = made_up_facts([CONV_BWD] * 9)
    share = read_metric(facts, "ssm_conv_bwd_roofline")
    # 214.08 MB at 819 GB/s is 0.2614 ms, of 0.619: PERF.md's 42%
    assert share == pytest.approx(100 * 214_083_584 / 819e9 / 0.619e-3,
                                  rel=1e-12)
    assert 42 < share < 42.5
    # another kernel's events change nothing; none of its own, nothing
    assert read_metric(made_up_facts([CONV_BWD] * 9 + OTHERS),
                       "ssm_conv_bwd_roofline") == share
    assert read_metric(made_up_facts(OTHERS), "ssm_conv_bwd_roofline") is None
    assert read_metric(facts, "flash_roofline_pct") == \
        read_metric(made_up_facts(), "flash_roofline_pct")
    with pytest.raises(ValueError, match="finds no taps a channel") as err:
        read_metric(facts, "ssm_conv_bwd_roofline", taps=r"f32\[(\d+)\]\{")
    assert "%ssm_conv_bwd.9 = (bf16[1,4352,8192]" in str(err.value)


def test_an_event_the_roofline_cannot_read_is_named_in_the_error():
    facts = made_up_facts(OTHERS)
    with pytest.raises(ValueError) as err:
        read_metric(facts, "flash_roofline_pct",
                    kernels={"dq": r"^%ssd_scan(\.\d+)? = "})
    text = str(err.value)
    assert "%ssd_scan.3 = bf16[8,8192,1024]" in text and "'dq'" in text
    assert "finds no batch x heads" in text


def test_a_crossing_is_read_by_its_own_name():
    """`%all-reduce.5` counts; the fusion whose operand it is does not
    (an unanchored search counted both: PR 28)."""
    facts = made_up_facts([ALL_REDUCE, READS_ONE])
    assert read_metric(facts, "allreduce_ms_per_step") == pytest.approx(2.0)
    assert read_metric(facts, "allreduce_exposed_ms") == pytest.approx(2.0)
    assert read_metric(facts, "allreduce_ms_per_step",
                       pattern="all-reduce") == pytest.approx(5.0)
    only_the_reader = made_up_facts([READS_ONE])
    assert read_metric(only_the_reader, "allreduce_ms_per_step") is None
    assert read_metric(only_the_reader, "allreduce_exposed_ms") is None


DP4 = FIXTURES / "trace_chip_pr32_mistral7b_l2_seq8k_dp4.xplane.pb.gz"


@pytest.fixture(scope="module")
def dp4(tmp_path_factory):
    """Recorded on four v5e chips in PR 32's second chip call
    (`mistral7b_l2.seq8k_dp4`, seed 2147502001, this PR's tree) and cut
    by `cut_xplane.py --first 2 --steps 2 --device /device:TPU:3`: the
    plane the run reduced, the busiest of the four."""
    path = tmp_path_factory.mktemp("dp4") / "chip.xplane.pb"
    path.write_bytes(gzip.decompress(DP4.read_bytes()))
    return path


def test_four_chip_trace_reads_the_bare_all_reduces_only(dp4):
    from benchmarks.run import Facts

    trace = tr.Trace(tr.load_xplane(dp4), "train_step")
    assert (trace.device, trace.n_devices, trace.steps) == (
        "/device:TPU:3", 1, 2)
    peaks = json.loads((FIXTURES.parent / "peaks.json").read_text())
    facts = Facts(sizes={"window": 4096}, peak=peaks["TPU v5 lite"],
                  setup_records=[], window_records=[], compile_events=[],
                  trace=trace)
    inside = [n for n, s, _ in trace.ops if trace.lo <= s < trace.hi]
    # seven all-reduces a step under their own names; two carry the
    # time, 2.07 and 4.61 ms (the run's eight steps: 7.52 ms a step)
    bare = {own_name(n) for n in inside if n.startswith("%all-reduce")}
    assert bare == {"%all-reduce.1", "%all-reduce.3", "%all-reduce.202",
                    "%all-reduce.203", "%all-reduce.204", "%all-reduce.205",
                    "%all-reduce.206"}
    assert read_metric(facts, "allreduce_ms_per_step") == pytest.approx(
        7.22344, rel=1e-4)
    assert read_metric(facts, "allreduce_exposed_ms") == pytest.approx(
        7.22344, rel=1e-4)          # nothing else runs beside them
    # what the search of the whole instruction counted besides, up to
    # PR 31 (16.69 here, 16.99 over the run's eight steps): the fusions
    # that read a crossing's result, and zero-length starts
    readers = {own_name(n) for n in inside
               if "all-reduce" in n and not n.startswith("%all-reduce")}
    assert {r for r in readers if "fusion" in r} == {
        "%multiply_reduce_fusion.1", "%multiply_reduce_fusion.3",
        "%multiply_reduce_fusion.8", "%multiply_reduce_fusion.14",
        "%is-finite_reduce_fusion.1"}
    assert read_metric(facts, "allreduce_ms_per_step",
                       pattern="all-reduce") == pytest.approx(16.6859, rel=1e-4)
    # the carried crossings (PR 28) are in neither reading: sixteen
    # async-collective-start a step, 0.1 ms of events, their time inside
    # the fusions that carry them
    carried = {own_name(n) for n in inside
               if n.startswith("%async-collective-start")}
    assert len(carried) == 16
    assert not any("all-reduce" in own_name(n) for n in inside
                   if n.startswith("%async-collective"))
    # the kernels read as on one chip (section 5: 30.64, 61.48)
    assert read_metric(facts, "flash_ms_per_step") == pytest.approx(
        30.64, rel=1e-3)
    assert read_metric(facts, "flash_roofline_pct") == pytest.approx(
        61.48, rel=1e-3)
    assert read_metric(facts, "flash_ms_per_step") == 30.641886
    assert read_metric(facts, "flash_roofline_pct") == 61.47906113071222


def test_cutting_keeps_one_device_of_a_multi_chip_capture(dp4, tmp_path):
    from benchmarks.tools import cut_xplane

    out = tmp_path / "cut.xplane.pb.gz"
    cut_xplane.main([str(dp4), str(out), "--first", "1", "--steps", "1",
                     "--device", "/device:TPU:3"])
    path = tmp_path / "cut.xplane.pb"
    path.write_bytes(gzip.decompress(out.read_bytes()))
    trace = tr.Trace(tr.load_xplane(path), "train_step")
    assert (trace.device, trace.steps) == ("/device:TPU:3", 1)
    assert trace.busy_s * 1e3 == pytest.approx(284.9, rel=2e-3)
    with pytest.raises(SystemExit, match="no device plane /device:TPU:0"):
        cut_xplane.main([str(dp4), str(out), "--device", "/device:TPU:0"])
