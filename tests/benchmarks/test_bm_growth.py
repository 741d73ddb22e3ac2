"""The benchmark takes what later PRs bring, by files and entries at the
end of their lists and no edit to what is there (ISSUE 40): two cells
with a configuration of their own, a metric listed for one of them alone
and a kernel's roofline share listed for both. The rules
the other tests hold BENCHMARK.json to are run against a copy grown so,
and the three tests that once pinned the benchmark at six cells, at PR
38's ten last metrics and at flash metrics without a list are run on it
as they stand."""
import json
import shutil

import pytest

from benchmarks import run

from test_bm_data import (
    BENCH, SPEC, check_configs, check_per_layer, check_workloads,
    four_chip_cells_fit, may_lack_on_the_cpu, named_once_in_order,
)

CONFIG, CELL, SECOND = "latent_l5", "latent_l5.seq8k", "latent_l5.seq32k"
SCOPE = {"name": "stream_mix_ms_per_step", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "compiled step",
         "moves": "mfu_pct", "workloads": [CELL]}
KERNEL = {"name": "mla_fwd_roofline", "unit": "%", "better": "higher",
          "source": "device_trace", "layer": "kernels", "moves": "mfu_pct",
          "workloads": [CELL, SECOND]}


def put(path, held):
    path.write_text(json.dumps(held, indent=2) + "\n")


def cell_entry(name):
    return {"name": name, "config": CONFIG, "traffic": name.split(".")[1],
            "chips": 1, "why": "a made-up cell on one chip"}


@pytest.fixture()
def grown(tmp_path):
    """(the spec with its lists grown at their ends, the root its files
    lie under): copies of the three directories of data files, and beside
    them what a `model_config` PR would add."""
    root = tmp_path
    for part in ("layer_metrics", "workloads", "configs"):
        shutil.copytree(BENCH / part, root / "benchmarks" / part)
    bench = root / "benchmarks"
    config = json.loads(
        (bench / "configs" / "granite4_h_micro_l10.json").read_text())
    config["source"] = "https://example.org/latent-attention/config.json"
    put(bench / "configs" / f"{CONFIG}.json", config)
    cell = json.loads(
        (bench / "workloads" / "granite4_h_micro_l10.seq8k.json").read_text())
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({
        "name": CONFIG, "source": config["source"],
        "file": f"benchmarks/configs/{CONFIG}.json",
        "reduced": config["reduced"], "why": "a made-up fifth configuration"})
    for entry in (cell_entry(CELL), cell_entry(SECOND)):
        spec["workloads"].append(entry)
        put(bench / "workloads" / f"{entry['name']}.json", {**cell, **entry})
    put(bench / "layer_metrics" / f"{SCOPE['name']}.json", {
        **SCOPE, "reducer": "trace_scopes", "args": {"within": "stream_mix"}})
    put(bench / "layer_metrics" / f"{KERNEL['name']}.json", {
        **KERNEL, "reducer": "trace_roofline", "args": {
            "kernels": {"fwd": "^%mla_fwd(\\.\\d+)? = "},
            "operand": "custom-call\\(bf16\\[(\\d+),(\\d+),(\\d+)\\]",
            "value_operand": "custom-call\\((?:bf16\\[[\\d,]+\\]\\S* %\\S+, )"
                             "{2}bf16\\[\\d+,\\d+,(\\d+)\\]"}})
    spec["per_layer"] += [SCOPE, KERNEL]
    return spec, root


def test_the_rules_hold_of_a_benchmark_grown_at_the_end_of_its_lists(grown):
    spec, root = grown
    assert len(spec["workloads"]) == len(SPEC["workloads"]) + 2
    assert spec["per_layer"][-2:] == [SCOPE, KERNEL]
    check_configs(spec, root)
    check_workloads(spec, root / "benchmarks")
    check_per_layer(spec, root / "benchmarks" / "layer_metrics")


@pytest.mark.parametrize("cells,on_four,fits", [
    (1, 1, True), (6, 1, True), (6, 2, False), (7, 2, False), (8, 2, True),
    (8, 3, False), (24, 6, True), (24, 7, False)])
def test_a_second_four_chip_cell_waits_for_the_eighth_cell(cells, on_four,
                                                           fits):
    """The share rule and no count: one cell always may take four chips,
    and of the cells a quarter, rounded down."""
    spec = {"workloads": [{"chips": 4 if k < on_four else 1}
                          for k in range(cells)]}
    assert four_chip_cells_fit(spec) is fits


def test_old_cells_expect_what_they_did_and_new_ones_their_own(grown):
    spec, root = grown
    for w in SPEC["workloads"]:
        for trace in (False, True):
            assert run.expected_metrics(spec, w["name"], trace) == \
                run.expected_metrics(SPEC, w["name"], trace)
    unlisted = {m["name"] for m in SPEC["per_layer"] if "workloads" not in m}
    assert set(run.expected_metrics(spec, CELL, True)) == \
        unlisted | {SCOPE["name"], KERNEL["name"]}
    assert set(run.expected_metrics(spec, SECOND, True)) == \
        unlisted | {KERNEL["name"]}
    assert not {"flash_ms_per_step", "flash_roofline_pct"} & (
        set(run.expected_metrics(spec, CELL, True)))
    assert run.expected_metrics(spec, CELL, False) == \
        run.expected_metrics(SPEC, SPEC["workloads"][0]["name"], False)
    # what a CPU rehearsal of the new cell may lack: its kernel's reading,
    # by what the metric's file reads
    metric_dir = root / "benchmarks" / "layer_metrics"
    assert may_lack_on_the_cpu(run.expected_metrics(spec, CELL, True),
                               metric_dir) == {KERNEL["name"]}
    names = [m["name"] for m in SPEC["per_layer"]]
    assert may_lack_on_the_cpu(names, metric_dir) == may_lack_on_the_cpu(names)


def test_the_three_tests_that_pinned_the_benchmark_pass_on_it(grown,
                                                              monkeypatch):
    """As they stand, with the grown spec in SPEC's place. At the parent
    commit the first asserts six cells, the second PR 38's ten as the
    last ten entries, the third that the flash metrics have no list."""
    import test_bm_granite_hybrid
    import test_bm_host_metrics
    import test_bm_trace_reduce

    spec, _ = grown
    named_once_in_order(spec, test_bm_host_metrics.HOST_METRICS)
    for module in (test_bm_granite_hybrid, test_bm_host_metrics,
                   test_bm_trace_reduce):
        monkeypatch.setattr(module, "SPEC", spec)
    test_bm_granite_hybrid.test_the_new_metrics_belong_to_the_new_cell_alone()
    test_bm_host_metrics.\
        test_the_ten_come_to_every_cell_and_move_what_the_issue_says()
    test_bm_trace_reduce.\
        test_a_step_without_flash_kernels_reports_what_it_has_and_no_more()
