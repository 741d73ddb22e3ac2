"""The last line a run prints, against the contract's shape: one
validator, one case per way PR 23's line could have been malformed, and
the file-descriptor discipline that keeps it last."""
import copy
import json
import subprocess
import sys
from pathlib import Path

import pytest

from benchmarks import lastline

ROOT = Path(__file__).resolve().parents[2]
E2E = {"tokens_per_s": "tokens/s", "setup_s": "s"}
LAYER = {"step_busy_ms": "ms", "device_idle_pct": "%"}
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 12_000_000_000}


def good(trace: bool) -> dict:
    expected = LAYER if trace else E2E
    device = dict(DEVICE, busy_s=1.9, window_s=2.0) if trace else dict(DEVICE)
    line = {"correct": True, "attempted": 70, "failed": 0,
            "metrics": {n: {"value": 1.5, "unit": u}
                        for n, u in expected.items()},
            "device": device}
    if trace:
        line["breakdown"] = {"device_ops": [["fusion.1", 0.5]],
                             "idle_gaps": [["after a, before b", 0.01]]}
    return line


@pytest.mark.parametrize("trace", [False, True])
def test_a_good_line_passes(trace):
    lastline.validate(good(trace), LAYER if trace else E2E, trace)


def _set(path, value):
    def change(line):
        node = line
        for key in path[:-1]:
            node = node[key]
        if value is KeyError:
            del node[path[-1]]
        else:
            node[path[-1]] = value
    return change


SUSPECTS = {
    "nan_value": (False, _set(["metrics", "setup_s", "value"], float("nan")),
                  "no finite value"),
    "infinite_value": (True, _set(["metrics", "step_busy_ms", "value"],
                                  float("inf")), "no finite value"),
    "missing_metric": (True, _set(["metrics", "device_idle_pct"], KeyError),
                       "device_idle_pct is missing"),
    "bare_number": (False, _set(["metrics", "tokens_per_s"], 123.0),
                    "not {value, unit}"),
    "wrong_unit": (False, _set(["metrics", "setup_s", "unit"], "ms"),
                   "declared 's'"),
    "undeclared_metric": (False, _set(["metrics", "extra"],
                                      {"value": 1.0, "unit": "s"}),
                          "not declared"),
    "busy_zero": (True, _set(["device", "busy_s"], 0.0), "0 < busy_s"),
    "busy_above_window": (True, _set(["device", "busy_s"], 2.5),
                          "busy_s <= window_s"),
    "window_absent": (True, _set(["device", "window_s"], KeyError),
                      "device.window_s is missing"),
    "busy_absent": (True, _set(["device", "busy_s"], KeyError),
                    "device.busy_s is missing"),
    "memory_absent": (False, _set(["device", "memory_peak_bytes"], KeyError),
                      "memory_peak_bytes is missing"),
    "memory_zero": (False, _set(["device", "memory_peak_bytes"], 0),
                    "positive whole number"),
    "count_as_text": (False, _set(["device", "count"], "1"),
                      "positive whole number"),
    "correct_as_text": (False, _set(["correct"], "true"), "true or false"),
    "no_device": (False, _set(["device"], KeyError), "'device' is missing"),
    "breakdown_too_long": (True, _set(
        ["breakdown", "device_ops"], [["op", 0.1]] * 11), "at most 10"),
    "breakdown_untraced": (False, _set(["breakdown"], {"device_ops": []}),
                           "traced run"),
}


@pytest.mark.parametrize("name", sorted(SUSPECTS))
def test_each_suspect_is_refused(name):
    trace, change, message = SUSPECTS[name]
    line = copy.deepcopy(good(trace))
    change(line)
    with pytest.raises(lastline.LastLineError, match=message):
        lastline.validate(line, LAYER if trace else E2E, trace)


def test_build_refuses_nan_and_missing_values():
    kw = dict(correct=True, attempted=3, failed=0, expected=E2E,
              device=DEVICE, trace=False)
    text = lastline.build(values={"tokens_per_s": 2.0, "setup_s": 3.0,
                                  "not_declared": 9.0}, **kw)
    assert set(json.loads(text)["metrics"]) == set(E2E)
    with pytest.raises(lastline.LastLineError, match="no finite value"):
        lastline.build(values={"tokens_per_s": float("nan"),
                               "setup_s": 3.0}, **kw)
    with pytest.raises(lastline.LastLineError, match="no value for setup_s"):
        lastline.build(values={"tokens_per_s": 2.0}, **kw)


def test_text_after_the_line_or_nan_in_it_is_not_a_result():
    text = json.dumps(good(False))
    assert lastline.parse_last("note\n" + text + "\n") == good(False)
    with pytest.raises(ValueError):
        lastline.parse_last(text + "\nI0927 profiler shut down\n")
    with pytest.raises(lastline.LastLineError, match="NaN"):
        lastline.parse_last(text.replace("1.5", "NaN", 1))


def test_nothing_is_written_to_stdout_after_the_last_line():
    """A logger on stdout, a print and an exit hook all try: the harness's
    lines are the only ones on standard output, the result is the last."""
    code = (
        "import atexit, logging, sys\n"
        "from benchmarks.lastline import Stdout\n"
        "out = Stdout()\n"
        "logging.basicConfig(stream=sys.stdout, level=logging.INFO)\n"
        "atexit.register(lambda: print('exit hook', flush=True))\n"
        "print('a progress bar'); logging.info('a logger')\n"
        "out.say('check loss = 1 limit 2 ok')\n"
        "out.finish('{\"correct\": true}', 0)\n")
    done = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=60)
    assert done.returncode == 0
    assert done.stdout == 'check loss = 1 limit 2 ok\n{"correct": true}\n'
    assert "a progress bar" in done.stderr and "a logger" in done.stderr
    assert "exit hook" not in done.stdout + done.stderr
