"""Tests for the observability tier: MetricTracker, TensorboardWriter."""
import pytest

from pytorch_distributed_template_tpu.observability import (
    MetricTracker,
    TensorboardWriter,
)


class FakeWriter:
    def __init__(self):
        self.scalars = []
        self.step = 0
        self.mode = ""

    def add_scalar(self, key, value):
        self.scalars.append((key, float(value)))


def test_tracker_running_average():
    t = MetricTracker("loss", "acc")
    t.update("loss", 2.0)
    t.update("loss", 4.0)
    assert t.avg("loss") == 3.0
    t.update("acc", 0.5, n=10)
    t.update("acc", 1.0, n=10)
    assert t.avg("acc") == 0.75
    assert t.result() == {"loss": 3.0, "acc": 0.75}
    t.reset()
    assert t.result() == {"loss": 0.0, "acc": 0.0}


def test_tracker_writes_through():
    w = FakeWriter()
    t = MetricTracker("loss", writer=w)
    t.update("loss", 1.5)
    assert w.scalars == [("loss", 1.5)]


def test_tracker_auto_key():
    t = MetricTracker()
    t.update("new_key", 1.0)
    assert t.avg("new_key") == 1.0


def test_tb_writer_disabled_noop(tmp_path):
    import logging

    w = TensorboardWriter(tmp_path, logging.getLogger("t"), enabled=False)
    w.set_step(0)
    w.add_scalar("x", 1.0)  # must not raise
    w.add_image("img", None)
    with pytest.raises(AttributeError):
        w.not_a_tb_method  # fixed vs reference visualization.py:70


def test_tb_writer_steps_per_sec(tmp_path):
    import logging

    w = TensorboardWriter(tmp_path, logging.getLogger("t"), enabled=False)
    seen = []
    w.add_scalar = lambda tag, v: seen.append(tag)
    w.set_step(0)
    w.set_step(1)
    assert "steps_per_sec" in seen


def test_tb_writer_real_backend(tmp_path):
    """tensorboardX is installed in this image: exercise the real path."""
    import logging

    w = TensorboardWriter(tmp_path, logging.getLogger("t"), enabled=True)
    assert w.writer is not None
    w.set_step(0, mode="train")
    w.add_scalar("loss", 0.5)
    w.set_step(1, mode="valid")
    w.add_scalar("loss", 0.4)
    w.close()


def test_maybe_tqdm_gating():
    """Progress bars: off for non-TTY auto mode, on when forced, and a
    transparent pass-through for the iterable's contents either way."""
    from pytorch_distributed_template_tpu.utils.util import maybe_tqdm

    data = [1, 2, 3]
    auto = maybe_tqdm(iter(data))          # stderr is not a TTY in tests
    assert list(auto) == data
    off = maybe_tqdm(iter(data), enable=False)
    assert list(off) == data
    pytest.importorskip("tqdm")            # optional dependency
    forced = maybe_tqdm(iter(data), total=3, desc="t", enable=True)
    assert type(forced).__name__ == "tqdm"
    assert list(forced) == data


def test_slowest_modules_report_sees_xdists_workers(tmp_path):
    """tests/conftest.py sums the modules' times from the test reports,
    which reach xdist's controller from every worker: under the tier-1
    command's `-p xdist -n .. --dist loadfile` the table lists every
    file, with the sum and the sum over the workers beside it."""
    import shutil
    import subprocess
    import sys
    from pathlib import Path

    shutil.copy(Path(__file__).with_name("conftest.py"), tmp_path)
    for name in ("one", "two"):
        (tmp_path / f"test_{name}.py").write_text(
            f"def test_{name}():\n    pass\n")
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "xdist", "-n", "2", "--dist", "loadfile", "-p", "no:randomly",
         # plugins the toy suite has no use for: half of its start-up
         "-p", "no:hypothesispytest", "-p", "no:jaxtyping",
         "-p", "no:typeguard", str(tmp_path)],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "slowest test modules" in out.stdout, out.stdout
    report = out.stdout.split("slowest test modules", 1)[1]
    assert "test_one.py" in report and "test_two.py" in report
    assert "all 2 modules" in report and "over 2 worker(s)" in report
