"""Test environment: force an 8-device virtual CPU mesh.

Must run before any jax import (SURVEY.md §4): this is the JAX-idiomatic
"fake backend" — the analogue of running the reference without a launcher,
where every dist helper degrades gracefully (/root/reference/utils/dist.py).
"""
import os
import sys
from pathlib import Path

# Force CPU: tests run on the virtual 8-device CPU mesh wherever they are
# started, and never take a chip (chip_smoke.py is the on-chip check).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

REPO_ROOT = Path(__file__).resolve().parent.parent
if str(REPO_ROOT) not in sys.path:
    sys.path.insert(0, str(REPO_ROOT))

import pytest  # noqa: E402

# Speed tiers: `pytest -m "not slow"` is the <2 min smoke pass
# (measured 102 s round 4: unit-level config/optim/data/dist/
# observability plus the torch-parity oracle); the files below are
# marked slow wholesale (multi-epoch training, subprocess CLIs, big
# compiles — incl. the quant/LoRA/HF-import integration modules, moved
# here r4 when the fast tier crept to 253 s). Heavy outliers inside
# otherwise-fast modules carry explicit @pytest.mark.slow instead.
SLOW_FILES = {
    "test_accum_ema.py",
    "test_checkpoint_retention.py",
    "test_e2e_mnist.py",
    "test_generate.py",
    "test_generate_cli.py",
    "test_hf_import.py",
    "test_llama.py",
    "test_lora.py",
    "test_models.py",
    "test_moe.py",
    "test_multihost.py",
    "test_pipeline.py",
    "test_quant.py",
    "test_serve.py",
    "test_transformer.py",
}


# Parametrized cases too heavy for the smoke tier (full-size model init).
SLOW_PARAMS = {
    "test_config_builds[imagenet_resnet50.json]",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (Path(str(item.fspath)).name in SLOW_FILES
                or item.name in SLOW_PARAMS):
            item.add_marker(pytest.mark.slow)


@pytest.fixture()
def tmp_run_dir(tmp_path):
    return tmp_path


_CACHE_SETTINGS = ("jax_compilation_cache_dir",
                   "jax_persistent_cache_min_compile_time_secs",
                   "jax_persistent_cache_min_entry_size_bytes",
                   "jax_compilation_cache_max_size")
_cache_settings_at_start: dict = {}


@pytest.fixture(autouse=True)
def compile_cache_as_the_process_started():
    """An entry point run inside a test (a benchmark rehearsal, `main` of
    train.py) turns jax's persistent compilation cache on for the whole
    process (utils/compile_cache.py), bounded, which makes every later
    compile of that worker, each primitive of an operation-by-operation
    test too, take the directory's file lock and list the directory:
    tests that took seconds alone took minutes behind one (PR 45: 6 s
    against 368 s). So the settings go back after every test to what the
    process started with; a test that wants the cache turns it on itself.
    (Every worker has imported jax with the test files before its first
    test; a suite that never does pays no import here.)"""
    jax = sys.modules.get("jax")
    if jax is not None and not _cache_settings_at_start:
        _cache_settings_at_start.update(
            (n, getattr(jax.config, n)) for n in _CACHE_SETTINGS)
    yield
    if not _cache_settings_at_start:
        return
    jax = sys.modules["jax"]
    moved = {n: v for n, v in _cache_settings_at_start.items()
             if getattr(jax.config, n) != v}
    if moved:
        from jax.experimental.compilation_cache import compilation_cache

        for n, v in moved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()     # detach from the directory


# ---------------------------------------------------------------------------
# Per-module time budget (VERDICT r2 weak #5: full-suite wall time grew
# ~19 -> ~24 min across rounds with nothing enforcing a ceiling).
# Every run prints the slowest modules; passing --module-budget=SECONDS
# (CI's slow tier and scripts/run_tier1.sh do) turns a module exceeding
# the budget into an end-of-run error so creep is caught at the PR that
# introduces it. The times are summed from the test reports, which under
# xdist reach the controller from every worker, so the table prints under
# `-n 6` as it does without. The modules of tests/benchmarks/ are listed
# and marked `*` but held to no budget: they are the benchmark's.
# ---------------------------------------------------------------------------
import collections

_module_times: dict = collections.defaultdict(float)
_UNBUDGETED = "tests/benchmarks/"


def pytest_addoption(parser):
    parser.addoption(
        "--module-budget", type=float, default=0.0,
        help="fail if any test module's summed runtime exceeds this many "
             "seconds (0 = report only)",
    )


def pytest_runtest_logreport(report):
    # setup, call and teardown each report once; the file is the nodeid
    # up to its first "::"
    _module_times[report.nodeid.split("::", 1)[0]] += report.duration


def _over_budget(budget):
    return sorted(
        (name, secs) for name, secs in _module_times.items()
        if secs > budget > 0 and not name.startswith(_UNBUDGETED)
    )


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    if not _module_times:
        return
    budget = config.getoption("--module-budget")
    top = sorted(_module_times.items(), key=lambda kv: -kv[1])[:20]
    terminalreporter.write_sep("-", "slowest test modules")
    for name, secs in top:
        mark = "*" if name.startswith(_UNBUDGETED) else " "
        terminalreporter.write_line(f"{secs:8.1f}s {mark} {name}")
    total = sum(_module_times.values())
    workers = int(getattr(config.option, "numprocesses", None) or 1)
    # with the longest module, these bound the wall time from below
    terminalreporter.write_line(
        f"{total:8.1f}s   all {len(_module_times)} modules; "
        f"{total / workers:.1f}s over {workers} worker(s); "
        f"* = {_UNBUDGETED}, outside --module-budget"
    )
    for name, secs in _over_budget(budget):
        terminalreporter.write_line(
            f"ERROR: {name} took {secs:.0f}s > --module-budget "
            f"{budget:.0f}s", red=True,
        )


def pytest_sessionfinish(session, exitstatus):
    # Budget enforcement lives here (not in terminal_summary: raising
    # there would abort pluggy's remaining summary impls and discard the
    # failure/durations reports — the diagnostics needed to FIX the slow
    # module). Flipping session.exitstatus after the run keeps every
    # report intact while still failing CI.
    budget = session.config.getoption("--module-budget")
    if exitstatus == 0 and _over_budget(budget):
        session.exitstatus = 1
