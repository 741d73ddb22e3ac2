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


def test_configs_name_files_that_hold_what_is_run():
    assert {c["name"] for c in SPEC["configs"]} == \
        {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"])
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        held = load(ROOT / c["file"])
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


def test_every_workload_file_matches_its_entry():
    files = {p.stem for p in (BENCH / "workloads").glob("*.json")}
    assert files == {w["name"] for w in SPEC["workloads"]}
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        for key in ("name", "config", "traffic"):
            assert NAME.match(w[key]), w[key]
        assert one_line(w["why"]) and w["chips"] in (1, 4)
        held = load(BENCH / "workloads" / f"{w['name']}.json")
        for key in ("name", "config", "traffic", "chips", "why"):
            assert held[key] == w[key], (w["name"], key)
        assert (BENCH / "configs" / f"{w['config']}.json").is_file()
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        limits = held["check"]["limits"]
        assert set(limits) == {"loss_rel_gap", "grad_norm_gap",
                               "update_norm_gap"}
        # a step that returns its state unchanged reads 1.0
        assert 0 < limits["update_norm_gap"] < 1
        assert held["check"]["limits_from"]
        assert held["warmup_iterations"] >= 5 and held["trace_steps"] >= 4
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 4)


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


def test_per_layer_metrics_have_a_reader_and_an_arrow():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells))
           for m in SPEC["end_to_end"]}
    files = {p.stem for p in (BENCH / "layer_metrics").glob("*.json")}
    assert files == {m["name"] for m in SPEC["per_layer"]}
    all_names = [m["name"] for m in SPEC["per_layer"]] + list(e2e)
    assert len(set(all_names)) == len(all_names)
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert one_line(m["layer"])
        held = load(BENCH / "layer_metrics" / f"{m['name']}.json")
        for key in m:
            assert held[key] == m[key], (m["name"], key)
        assert (BENCH / "reducers" / f"{held['reducer']}.py").is_file()
        assert isinstance(held["args"], dict)
        reported_in = set(m.get("workloads", cells))
        assert reported_in <= cells and reported_in
        assert reported_in <= e2e[m["moves"]], m["name"]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        assert any(cell in m.get("workloads", cells)
                   for m in SPEC["per_layer"])


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
