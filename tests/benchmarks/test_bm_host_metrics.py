"""The ten readings of the host's timeline (ISSUE 38): the three new
reducers on hand-made facts, every metric's file against what the
program writes, and a traced rehearsal of the three cells that share
`mistral7b_l2` (the four-chip one on four virtual devices) with all ten
in its line. The other three cells: test_bm_host_metrics_cells.py."""
import json

import pytest

from benchmarks import reducers, run
from benchmarks.reducers import (
    compile_events_window, flight_once, flight_worst,
)

from test_bm_data import named_once_in_order

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())
HOST_METRICS = {
    "setup_trainer_init_s": "s", "setup_state_init_s": "s",
    "setup_step_trace_s": "s", "setup_step_lower_s": "s",
    "setup_step_compile_s": "s", "setup_await_step_s": "s",
    "window_compiles": "count", "dispatch_ms_p50": "ms",
    "iter_unattributed_ms_p50": "ms", "iter_ms_max": "ms"}


def facts(setup_records=(), window_records=()):
    return run.Facts(sizes={}, peak={}, setup_records=list(setup_records),
                     window_records=list(window_records), compile_events=[])


def spec_of(name):
    return json.loads(
        (run.BENCH / "layer_metrics" / f"{name}.json").read_text())


def read(name, f):
    spec = spec_of(name)
    return reducers.get(spec["reducer"])(f, **spec["args"])


SETUP = {"setup/trainer_init": 3.5, "setup/state_init": 2.25,
         "warmup/train_step/trace": 1.5, "warmup/train_step/lower": 0.75,
         "warmup/train_step/compile": 4.0, "train_step/await_warmup": 3.0,
         "first_iteration_s": 4.5}


def test_flight_once_reads_a_key_of_the_one_record():
    f = facts(setup_records=[{"wall_ms": 9000.0, "setup": SETUP},
                             {"wall_ms": 300.0}, {"wall_ms": 280.0}])
    assert flight_once.reduce(f, "setup", "setup/state_init") == 2.25
    assert flight_once.reduce(f, "setup", "no/such/span") is None
    assert flight_once.reduce(f, "no_such_field", "setup/state_init") is None


@pytest.mark.parametrize("records", [
    [], [{"wall_ms": 300.0}], [{"wall_ms": 1.0, "setup": "not a table"}],
    [{"setup": SETUP}, {"setup": SETUP}]])
def test_flight_once_finds_nothing_without_the_one_record(records):
    """The parent commit's records, a window's, or two trainers' mixed
    up: nothing to read, and nothing raised."""
    assert flight_once.reduce(facts(setup_records=records), "setup",
                              "setup/state_init") is None
    # what the window holds is not looked at
    assert flight_once.reduce(facts(window_records=[{"setup": SETUP}]),
                              "setup", "setup/state_init") is None


@pytest.mark.parametrize("name,key", [
    ("setup_trainer_init_s", "setup/trainer_init"),
    ("setup_state_init_s", "setup/state_init"),
    ("setup_step_trace_s", "warmup/train_step/trace"),
    ("setup_step_lower_s", "warmup/train_step/lower"),
    ("setup_step_compile_s", "warmup/train_step/compile"),
    ("setup_await_step_s", "train_step/await_warmup")])
def test_each_setup_metric_reads_its_span(name, key):
    f = facts(setup_records=[{"wall_ms": 9000.0, "setup": SETUP}])
    assert read(name, f) == SETUP[key]
    assert read(name, facts(setup_records=[{"wall_ms": 9000.0}])) is None


def compiled(fun_name, ms=12.0):
    return {"event": "/jax/core/compile/backend_compile_duration",
            "fun_name": fun_name, "dur_ms": ms}


def test_compile_events_window_counts_the_windows_compiles():
    quiet = [{"wall_ms": 250.0}, {"wall_ms": 251.0}]
    assert compile_events_window.reduce(facts(window_records=quiet)) == 0
    noisy = quiet + [{"wall_ms": 900.0, "compile_events": [
        {"event": "/jax/core/compile/jaxpr_trace_duration",
         "fun_name": "cosine", "dur_ms": 3.0},
        {"event": "/jax/core/compile/jaxpr_to_mlir_module_duration",
         "fun_name": "cosine", "dur_ms": 4.0},
        compiled("cosine"), {"event": "/jax/compilation_cache/cache_hits"},
        compiled("train_step", 4000.0)]}]
    f = facts(setup_records=[{"compile_events": [compiled("train_step")]}],
              window_records=noisy)
    assert compile_events_window.reduce(f) == 2
    assert read("window_compiles", f) == 2
    # whose they were, from the record alone
    assert [e["fun_name"] for r in noisy
            for e in r.get("compile_events", ())
            if e["event"].endswith("backend_compile_duration")] == [
        "cosine", "train_step"]
    assert compile_events_window.reduce(f, event="cache_hits") == 1


def test_compile_events_window_finds_nothing_without_a_window():
    f = facts(setup_records=[{"compile_events": [compiled("train_step")]}])
    assert compile_events_window.reduce(f) is None


def test_the_loop_metrics_read_their_flight_fields():
    window = [{"wall_ms": 250.0 + k, "dispatch_ms": 1.0 + k,
               "unattributed_ms": 0.25 * (k + 1)} for k in range(5)]
    window.append({"wall_ms": 4692.0, "dispatch_ms": 2.0,
                   "unattributed_ms": 4400.0, "stall": {"in": "unattributed"}})
    # the iteration in which the profiler's capture stopped: not the run's
    window.append({"wall_ms": 5853.0, "dispatch_ms": 7.0, "profile_ms": 5421.0,
                   "unattributed_ms": 1.0, "stall": {"in": "profile_ms"}})
    f = facts(window_records=window)
    assert read("dispatch_ms_p50", f) == 3.0
    assert read("iter_unattributed_ms_p50", f) == 1.0
    assert read("iter_ms_max", f) == 4692.0
    assert flight_worst.reduce(f, "wall_ms", without="no_such_mark") == 5853.0
    assert flight_worst.reduce(f, "no_such_field", without="profile_ms") is None
    # the parent's records: no such field, nothing to read
    old = facts(window_records=[{"wall_ms": 250.0, "dispatch_ms": 1.0}])
    assert read("iter_unattributed_ms_p50", old) is None
    assert read("dispatch_ms_p50", old) == 1.0 == read("dispatch_ms_p50", old)
    assert read("iter_ms_max", facts()) is None


def test_the_ten_come_to_every_cell_and_move_what_the_issue_says():
    ten = named_once_in_order(SPEC, HOST_METRICS)
    for m, (name, unit) in zip(ten, HOST_METRICS.items()):
        assert m["unit"] == unit and "workloads" not in m
        assert m["better"] == "lower"
        assert m["moves"] == ("setup_s" if name.startswith("setup_")
                              else "tokens_per_s")
        assert m["layer"] == ("trainer loop" if name.startswith(
            ("dispatch", "iter_")) else "compile cache and warm-up")
    for w in SPEC["workloads"]:
        assert set(HOST_METRICS) <= set(
            run.expected_metrics(SPEC, w["name"], True))
        assert not set(HOST_METRICS) & set(
            run.expected_metrics(SPEC, w["name"], False))


def traced_rehearsal_holds_the_ten(workload):
    import argparse

    from benchmarks import lastline

    said = []
    args = argparse.Namespace(workload=workload, seed=2**31 + 38,
                              seconds=1.5, trace=1, rehearse=True)
    text, code = run.run(args, said.append)
    line = json.loads(text)
    assert code == run.EXIT_REHEARSED
    got = line["metrics"]
    assert set(HOST_METRICS) <= set(got), [s for s in said if "nothing" in s]
    for name, unit in HOST_METRICS.items():
        assert got[name]["unit"] == unit and got[name]["value"] >= 0
    expected = {n: u for n, u in run.expected_metrics(
        SPEC, workload, True).items() if n in got}
    lastline.validate(line, expected, True)
    # the wait is the exposed part of the stages, and the init is inside
    # the trainer's
    value = {n: got[n]["value"] for n in HOST_METRICS}
    assert value["setup_await_step_s"] <= (
        value["setup_step_trace_s"] + value["setup_step_lower_s"]
        + value["setup_step_compile_s"]) + 0.05
    assert value["setup_state_init_s"] < value["setup_trainer_init_s"]
    assert value["window_compiles"] == 0
    assert value["iter_ms_max"] >= value["dispatch_ms_p50"]
    return line


@pytest.mark.parametrize("workload", [
    "mistral7b_l2.seq8k", "mistral7b_l2.seq4k", "mistral7b_l2.seq8k_dp4"])
def test_a_traced_rehearsal_holds_the_ten(workload):
    line = traced_rehearsal_holds_the_ten(workload)
    chips = next(w["chips"] for w in SPEC["workloads"]
                 if w["name"] == workload)
    assert line["device"]["count"] == chips
