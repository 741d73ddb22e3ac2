"""benchmarks/run.py driven in this process on the CPU at the cells'
rehearsal sizes: the same code as a chip run after the look for a chip.
Sound runs end in a valid last line in both trace modes (the dp4 cell on
four virtual devices); with the timed path broken underneath, `correct`
comes out false."""
import argparse
import json

import jax
import jax.numpy as jnp
import pytest

from benchmarks import lastline, run

from test_bm_data import may_lack_on_the_cpu

SPEC = json.loads((run.ROOT / "BENCHMARK.json").read_text())


def rehearse(workload, trace, seed=2**31 + 21, seconds=1.5):
    said = []
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, rehearse=True)
    text, code = run.run(args, said.append)
    return json.loads(text), code, said


@pytest.mark.parametrize("workload,trace", [
    ("gpt2_large.seq1k", 0), ("mistral7b_l2.seq8k", 1),
    ("mistral7b_l2.seq8k_dp4", 1)])
def test_a_rehearsed_run_ends_in_a_valid_line(workload, trace):
    line, code, said = rehearse(workload, trace)
    assert code == run.EXIT_REHEARSED != 0
    expected = run.expected_metrics(SPEC, workload, bool(trace))
    # only what reads the TPU's kernels finds nothing on the CPU
    absent = {n for n in expected if n not in line["metrics"]}
    assert absent <= may_lack_on_the_cpu(expected)
    expected = {n: u for n, u in expected.items() if n not in absent}
    lastline.validate(line, expected, bool(trace))
    assert line["device"]["platform"] == "cpu"
    chips = next(w["chips"] for w in SPEC["workloads"]
                 if w["name"] == workload)
    assert line["device"]["count"] == chips
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    checks = [s for s in said if s.startswith("check ")]
    assert len(checks) == 4 and all(" limit " in s for s in checks)
    # the same numbers under a key of their own, the line's last
    assert list(line)[-1] == "checks" and len(line["checks"]) == 4
    assert all(0 <= c["value"] <= c["limit"] for c in line["checks"].values())
    if trace:
        assert "breakdown" in line
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        if chips == 4:
            assert "allreduce_ms_per_step" in expected


def test_a_run_without_a_chip_fails_before_any_work():
    args = argparse.Namespace(workload="mistral7b_l2.seq8k", seed=1,
                              seconds=1.0, trace=0, rehearse=False)
    with pytest.raises(run.NoChip, match="needs 1 TPU chip"):
        run.run(args, lambda s: None)


def test_a_rehearsal_that_fails_leaves_no_directory_behind(monkeypatch):
    class NoTrainer:
        def __init__(self, cell, config, seed, run_dir, *a, **kw):
            assert run_dir.is_dir()
            NoTrainer.run_dir = run_dir
            raise RuntimeError("set-up fell over")

    monkeypatch.setattr(run, "Bench", NoTrainer)
    args = argparse.Namespace(workload="gpt2_large.seq1k", seed=3,
                              seconds=1.0, trace=0, rehearse=True)
    with pytest.raises(RuntimeError, match="set-up fell over"):
        run.run(args, lambda s: None)
    assert ".rehearsal." in NoTrainer.run_dir.name
    assert not NoTrainer.run_dir.exists()


class HalfTheBatch(run.Bench):
    """The step is fed every other row only."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        real = self.trainer._train_step

        def step(state, batch):
            return real(state, {**batch,
                                "mask": batch["mask"].at[::2].set(False)})

        self.trainer._train_step = step


class StateUnchanged(run.Bench):
    """The step returns the parameters it was given."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        real = self.trainer._train_step

        def step(state, batch):
            kept = jax.tree.map(jnp.copy, state.params)
            new, metrics = real(state, batch)
            return new.replace(params=kept), metrics

        self.trainer._train_step = step


@pytest.mark.parametrize("broken,failing", [
    (HalfTheBatch, "loss_rel_gap"), (StateUnchanged, "update_norm_gap")])
def test_a_broken_timed_path_comes_out_not_correct(monkeypatch, broken,
                                                   failing):
    monkeypatch.setattr(run, "Bench", broken)
    line, _, said = rehearse("gpt2_large.seq1k", 0, seconds=1.0)
    assert line["correct"] is False
    assert any(s.startswith(f"check {failing}") and " FAIL " in s
               for s in said), said
    assert any(name.startswith(failing) and c["value"] > c["limit"]
               for name, c in line["checks"].items())


PHASES_AND_IDLE = {
    "fwd_ms_per_step", "remat_ms_per_step", "bwd_ms_per_step",
    "optimizer_ms_per_step", "head_loss_ms_per_step", "unscoped_ms_per_step",
    "idle_data_wait_ms", "idle_health_fetch_ms", "idle_log_flush_ms",
    "idle_unnamed_ms"}


def test_a_new_cell_reports_the_phase_and_idle_metrics_without_an_edit():
    """A later PR adds a cell by an entry and data files: what every
    training step can be read for comes to it unasked, what only some
    cells have (a crossing between chips, a kernel) does not."""
    spec = json.loads(json.dumps(SPEC))
    spec["workloads"].append({
        "name": "hybrid_l6.seq8k", "config": "hybrid_l6", "traffic": "seq8k",
        "chips": 1, "why": "a made-up fourth cell"})
    traced = run.expected_metrics(spec, "hybrid_l6.seq8k", True)
    assert PHASES_AND_IDLE <= set(traced)
    assert {"step_busy_ms", "device_idle_pct"} <= set(traced)
    # nor a kernel's readings: a cell joins their list in a `benchmark` PR,
    # or brings metrics of its own kernels' names
    assert not {"allreduce_ms_per_step", "allreduce_exposed_ms",
                "flash_ms_per_step", "flash_roofline_pct",
                "ssm_conv_bwd_roofline"} & set(traced)
    assert set(run.expected_metrics(spec, "hybrid_l6.seq8k", False)) == {
        "tokens_per_s", "step_ms_p90", "mfu_pct", "setup_s"}
