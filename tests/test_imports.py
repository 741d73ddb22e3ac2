"""Import smoke test: every module under pytorch_distributed_template_tpu/
imports cleanly.

A jax API move (e.g. ``shard_map`` leaving ``jax.experimental``) used to
surface as 24 separate test-collection errors, each pointing at a test
file instead of the import that actually broke. This test walks the
package and imports every module, so breakage against the installed jax
shows up as ONE failure naming the offending module.
"""
import importlib
import pkgutil

import pytest

import pytorch_distributed_template_tpu as pkg

MODULES = sorted(
    m.name for m in pkgutil.walk_packages(pkg.__path__, pkg.__name__ + ".")
)


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
