"""The benchmark's data files against each other and against what the
driver accepts in BENCHMARK.json."""
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    return json.loads(Path(path).read_text())


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "benchmarks/run.py"]
    assert SPEC["paths"] == ["benchmarks", "tests/benchmarks"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["workloads"]) <= 24
    assert 1 <= len(SPEC["end_to_end"]) <= 16
    assert 1 <= len(SPEC["per_layer"]) <= 128
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    # a full check of 24 cells has to fit 43200 s
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def check_configs(spec, root):
    """Every configuration is used by a cell, and its file under `root`
    says what its entry says."""
    assert {c["name"] for c in spec["configs"]} == \
        {w["config"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"])
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        held = load(Path(root) / c["file"])
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"] and len(c["reduced"]) <= 16
        assert set(held["reduced_why"]) == set(c["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|d_model|d_ff|hidden|head_dim)$",
                                 key), "a width may never be reduced"
        assert (ROOT / held["reference"]).is_file()
        args = held["experiment"]["arch"]["args"]
        for key, value in held["sizes"].items():
            if key in args:     # GPT-2 names its sizes by `size`
                assert args[key] == value, (c["name"], key)


def test_configs_name_files_that_hold_what_is_run():
    check_configs(SPEC, ROOT)


def four_chip_cells_fit(spec) -> bool:
    """At most a quarter of the cells, rounded down, may ask for four
    chips, and one always may."""
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    return four <= max(1, len(spec["workloads"]) // 4)


def check_workloads(spec, bench_dir):
    """Every cell has its file under `bench_dir`, which says what its
    entry says; how many cells there are is no rule."""
    bench_dir = Path(bench_dir)
    files = {p.stem for p in (bench_dir / "workloads").glob("*.json")}
    assert files == {w["name"] for w in spec["workloads"]}
    pairs = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        for key in ("name", "config", "traffic"):
            assert NAME.match(w[key]), w[key]
        assert one_line(w["why"]) and w["chips"] in (1, 4)
        held = load(bench_dir / "workloads" / f"{w['name']}.json")
        for key in ("name", "config", "traffic", "chips", "why"):
            assert held[key] == w[key], (w["name"], key)
        assert (bench_dir / "configs" / f"{w['config']}.json").is_file()
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        limits = held["check"]["limits"]
        assert set(limits) == {"loss_rel_gap", "grad_norm_gap",
                               "update_norm_gap"}
        # a step that returns its state unchanged reads 1.0
        assert 0 < limits["update_norm_gap"] < 1
        assert held["check"]["limits_from"]
        assert held["warmup_iterations"] >= 5 and held["trace_steps"] >= 4
    assert four_chip_cells_fit(spec)


def test_every_workload_file_matches_its_entry():
    check_workloads(SPEC, BENCH)


def test_end_to_end_metrics():
    names = [m["name"] for m in SPEC["end_to_end"]]
    assert "setup_s" in names and len(set(names)) == len(names)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1


def check_per_layer(spec, metric_dir):
    """The driver's rules for `per_layer`, and the harness's: an entry
    and its file under `metric_dir` agree key for key, the `workloads`
    list included, and whether a metric has one is the metric's to say."""
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in spec["end_to_end"]}
    files = {p.stem for p in Path(metric_dir).glob("*.json")}
    assert files == {m["name"] for m in spec["per_layer"]}
    all_names = [m["name"] for m in spec["per_layer"]] + list(e2e)
    assert len(set(all_names)) == len(all_names)
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert one_line(m["layer"])
        held = load(Path(metric_dir) / f"{m['name']}.json")
        for key in m:
            assert held[key] == m[key], (m["name"], key)
        assert ("workloads" in held) == ("workloads" in m), m["name"]
        assert (BENCH / "reducers" / f"{held['reducer']}.py").is_file()
        assert isinstance(held["args"], dict)
        reported_in = set(m.get("workloads", cells))
        assert reported_in <= cells and reported_in
        assert reported_in <= e2e[m["moves"]], m["name"]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        assert any(cell in m.get("workloads", cells)
                   for m in spec["per_layer"])


def test_per_layer_metrics_have_a_reader_and_an_arrow():
    check_per_layer(SPEC, BENCH / "layer_metrics")


def named_once_in_order(spec, names) -> list:
    """The `per_layer` entries of `names`, looked up by name: each there
    once, and in `names`' order among themselves. Where in the list they
    stand is no rule: a later PR puts its entries at the end."""
    names = list(names)
    found = [m for m in spec["per_layer"] if m["name"] in names]
    assert [m["name"] for m in found] == names
    return found


def _strings(value):
    if isinstance(value, str):
        yield value
    elif isinstance(value, dict):
        for v in value.values():
            yield from _strings(v)
    elif isinstance(value, list):
        for v in value:
            yield from _strings(v)


def reads_a_kernels_own_events(held: dict) -> bool:
    """Whether a metric's file reads the events of a kernel by its own
    name: its layer is `kernels`, or its reducer is `trace_ops` or a
    `trace_roofline*` over a pattern anchored at `%name`, the form in
    which only the chip's traces print an instruction (the all-reduce
    readings' `^%?all-reduce` finds the CPU's too)."""
    if held["layer"] == "kernels":
        return True
    return (held["reducer"] == "trace_ops"
            or held["reducer"].startswith("trace_roofline")) and any(
        re.match(r"\^%\w", text) for text in _strings(held["args"]))


def may_lack_on_the_cpu(names, metric_dir=BENCH / "layer_metrics") -> set:
    """Those of the metrics `names` that a CPU rehearsal's line may lack:
    a rehearsal runs no TPU kernel, so what reads a kernel's own events
    finds nothing there. Every other metric a cell expects has to be in
    its rehearsal's line: the rest of the per-layer ones, and the
    end-to-end ones, which have no reader's file."""
    files = {n: Path(metric_dir) / f"{n}.json" for n in names}
    return {n for n, path in files.items()
            if path.is_file() and reads_a_kernels_own_events(load(path))}


def test_only_what_reads_a_kernel_may_be_absent_from_a_rehearsal():
    may = may_lack_on_the_cpu(m["name"] for m in SPEC["per_layer"])
    assert {"flash_ms_per_step", "flash_roofline_pct",
            "ssm_conv_bwd_roofline"} <= may
    assert not {"ssm_conv_ms_per_step", "allreduce_ms_per_step",
                "step_busy_ms", "iter_ms_max"} & may
    # by what the file reads, not by its name
    scope = load(BENCH / "layer_metrics" / "ssm_conv_ms_per_step.json")
    assert not reads_a_kernels_own_events(scope)
    assert reads_a_kernels_own_events({**scope, "layer": "kernels"})
    assert reads_a_kernels_own_events({
        **scope, "reducer": "trace_ops",
        "args": {"pattern": "^%ssd_scan(\\.\\d+)? = "}})
    assert not reads_a_kernels_own_events({
        **scope, "reducer": "trace_ops", "args": {"pattern": "^%?all-gather"}})


def test_a_later_metric_with_a_list_of_its_own_needs_no_edit(tmp_path):
    """What a `model_config` PR brings for a new kernel: a cell, a metric
    listed for that cell alone, and the metric's file. Nothing that is
    there changes, and the rules above hold of the whole."""
    import shutil

    from benchmarks import run

    metric_dir = tmp_path / "layer_metrics"
    shutil.copytree(BENCH / "layer_metrics", metric_dir)
    entry = {"name": "ssd_scan_roofline", "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "kernels",
             "moves": "mfu_pct", "workloads": ["hybrid_l6.seq8k"]}
    (metric_dir / "ssd_scan_roofline.json").write_text(json.dumps({
        **entry, "reducer": "trace_roofline",
        "args": {"kernels": {}, "operand": ""}}))
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({
        "name": "hybrid_l6.seq8k", "config": "hybrid_l6", "traffic": "seq8k",
        "chips": 1, "why": "a made-up fourth cell"})
    spec["per_layer"].append(entry)
    check_per_layer(spec, metric_dir)
    assert "ssd_scan_roofline" in run.expected_metrics(
        spec, "hybrid_l6.seq8k", True)
    for w in SPEC["workloads"]:
        assert "ssd_scan_roofline" not in run.expected_metrics(
            spec, w["name"], True)
        assert run.expected_metrics(spec, w["name"], True) == \
            run.expected_metrics(SPEC, w["name"], True)


def test_files_under_paths_are_named_from_the_allowed_characters():
    for base in SPEC["paths"]:
        for p in (ROOT / base).rglob("*"):
            if "__pycache__" in p.parts or p.suffix == ".pyc":
                continue
            rel = str(p.relative_to(ROOT))
            assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", rel), rel


def test_peaks_table_names_the_chip_and_its_source():
    peaks = load(BENCH / "peaks.json")
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert all(p["source"] for p in peaks.values())


def test_traffic_generator_is_seeded_and_rows_differ():
    import numpy as np

    from benchmarks.data import batch_rows, make_tokens

    a = make_tokens(2**31 + 11, 32, 64, 50257)
    b = make_tokens(2**31 + 11, 32, 64, 50257)
    c = make_tokens(2**31 + 12, 32, 64, 50257)
    assert a.dtype == np.int32 and (a == b).all() and (a != c).any()
    assert a.min() >= 0 and a.max() < 50257
    assert len({row.tobytes() for row in a}) == 32
    assert (batch_rows(a, 3, 8) == a[24:32]).all()
    assert (batch_rows(a, 4, 8) == a[0:8]).all()      # wraps round
    # skewed towards low ids, as a frequency-sorted vocabulary is
    assert np.median(a) < 50257 / 3
