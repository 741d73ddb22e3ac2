"""Import smoke test: every module under pytorch_distributed_template_tpu/
imports cleanly, and so does every entry point and script beside it.

A jax API move (e.g. ``shard_map`` leaving ``jax.experimental``) used to
surface as 24 separate test-collection errors, each pointing at a test
file instead of the import that actually broke. This test walks the
package and imports every module, so breakage against the installed jax
shows up as ONE failure naming the offending module.
"""
import importlib
import importlib.util
import pkgutil
from pathlib import Path

import pytest

import pytorch_distributed_template_tpu as pkg

MODULES = sorted(
    m.name for m in pkgutil.walk_packages(pkg.__path__, pkg.__name__ + ".")
)

REPO = Path(__file__).resolve().parent.parent
ENTRY_POINTS = ("train.py", "test.py", "serve.py", "generate.py",
                "chip_smoke.py")
SCRIPTS = sorted([REPO / name for name in ENTRY_POINTS]
                 + list((REPO / "scripts").glob("*.py")))


def test_package_has_expected_surface():
    # guard against the walker silently finding nothing (e.g. a path
    # mishap would make the parametrized test below vacuously pass)
    assert len(MODULES) > 40
    for expected in (
        "pytorch_distributed_template_tpu.engine.trainer",
        "pytorch_distributed_template_tpu.ops.attention",
        "pytorch_distributed_template_tpu.parallel.pipeline",
        "pytorch_distributed_template_tpu.observability.telemetry",
        "pytorch_distributed_template_tpu.observability.trace",
        "pytorch_distributed_template_tpu.utils.compile_cache",
    ):
        assert expected in MODULES


@pytest.mark.parametrize("name", MODULES)
def test_module_imports(name):
    importlib.import_module(name)


def test_scripts_found():
    assert len(SCRIPTS) > len(ENTRY_POINTS) + 10


@pytest.mark.parametrize(
    "path", SCRIPTS, ids=[str(p.relative_to(REPO)) for p in SCRIPTS])
def test_script_imports(path):
    """Each entry point and script loads under a name other than
    ``__main__``: its imports resolve against what the package still
    has, and it does no work before its ``main()`` is called."""
    spec = importlib.util.spec_from_file_location(
        "_script_" + path.stem, path)
    spec.loader.exec_module(importlib.util.module_from_spec(spec))
