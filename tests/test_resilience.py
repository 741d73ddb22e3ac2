"""Resilience/debug tier: non-finite guard, preemption, debug modes.

SURVEY.md §5 rows "race detection / sanitizers" and "failure detection":
the reference has neither; these are the TPU-native additions.
"""
import json
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

from pytorch_distributed_template_tpu.engine.state import create_train_state
from pytorch_distributed_template_tpu.engine.steps import make_train_step
from pytorch_distributed_template_tpu.utils import preemption
from pytorch_distributed_template_tpu.utils.debug import configure_debug

from test_e2e_mnist import build_trainer, make_config


class _Tiny(nn.Module):
    @nn.compact
    def __call__(self, x, train=False):
        return nn.Dense(4)(x)


class _TinyBN(nn.Module):
    @nn.compact
    def __call__(self, x, train=False):
        x = nn.BatchNorm(use_running_average=not train)(x)
        return nn.Dense(4)(x)


def _sq_err(output, target):
    return jnp.sum((output - target[:, None].astype(output.dtype)) ** 2,
                   axis=-1)


def _make(skip_nonfinite, ema_decay=0.0, model=None):
    model = model if model is not None else _Tiny()
    tx = optax.sgd(0.05)
    sample = jnp.ones((1, 3), jnp.float32)
    state = create_train_state(model, tx, sample, seed=0,
                               with_ema=ema_decay > 0)
    step = jax.jit(make_train_step(
        model, tx, _sq_err, skip_nonfinite=skip_nonfinite,
        ema_decay=ema_decay,
    ))
    return state, step


def _batch(poison=False):
    x = np.ones((8, 3), np.float32)
    if poison:
        x[3, 1] = np.inf
    return {
        "image": jnp.asarray(x),
        "label": jnp.zeros((8,), jnp.int32),
        "mask": jnp.ones((8,), bool),
    }


def test_skip_nonfinite_suppresses_bad_update():
    state, step = _make(skip_nonfinite=True)
    before = jax.tree.map(np.asarray, state.params)

    state, m = step(state, _batch(poison=True))
    assert float(m["skipped_sum"]) == 8.0
    # contaminated statistics are zeroed out of the epoch aggregates
    assert float(m["count"]) == 0.0
    assert float(m["loss_sum"]) == 0.0
    for a, b in zip(jax.tree.leaves(before),
                    jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(state.step) == 1  # counter still advances

    state, m = step(state, _batch(poison=False))
    assert float(m["skipped_sum"]) == 0.0
    assert float(m["count"]) == 8.0
    assert np.isfinite(float(m["loss_sum"]))
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(before),
                        jax.tree.leaves(state.params))
    )
    assert changed


def test_skip_nonfinite_guards_batch_stats():
    """BatchNorm running statistics must not absorb the poisoned forward
    pass — they feed every later eval and checkpoint."""
    state, step = _make(skip_nonfinite=True, model=_TinyBN())
    stats_before = jax.tree.map(np.asarray, state.batch_stats)
    state, _ = step(state, _batch(poison=True))
    for a, b in zip(jax.tree.leaves(stats_before),
                    jax.tree.leaves(state.batch_stats)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all(
        np.isfinite(np.asarray(s)).all()
        for s in jax.tree.leaves(state.batch_stats)
    )
    # clean step does update the running stats
    state, _ = step(state, _batch(poison=False))
    changed = any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(stats_before),
                        jax.tree.leaves(state.batch_stats))
    )
    assert changed


def test_skip_nonfinite_guards_ema_and_opt_state():
    state, step = _make(skip_nonfinite=True, ema_decay=0.9)
    ema_before = jax.tree.map(np.asarray, state.ema_params)
    opt_before = jax.tree.map(
        np.asarray, jax.tree.leaves(state.opt_state)
    )
    state, _ = step(state, _batch(poison=True))
    for a, b in zip(jax.tree.leaves(ema_before),
                    jax.tree.leaves(state.ema_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(opt_before, jax.tree.leaves(state.opt_state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_without_guard_nan_poisons_params():
    state, step = _make(skip_nonfinite=False)
    state, m = step(state, _batch(poison=True))
    assert "skipped_sum" not in m
    leaves = [np.asarray(p) for p in jax.tree.leaves(state.params)]
    assert any(not np.isfinite(p).all() for p in leaves)


def test_sigterm_sets_flag_and_consensus(monkeypatch):
    preemption.reset()
    # install anew whatever an earlier test of this worker left on SIGTERM
    # (`install` is idempotent by a flag, not by what the signal holds)
    monkeypatch.setattr(preemption, "_installed", False)
    preemption.install()
    assert not preemption.requested()
    assert not preemption.sync_requested()
    os.kill(os.getpid(), signal.SIGTERM)
    assert preemption.requested()
    assert preemption.sync_requested()  # single-host consensus == local
    preemption.reset()


def test_preemption_checkpoints_and_stops(tmp_path):
    """Flag set during epoch 1 -> checkpoint saved even outside save_period,
    loop exits after that epoch."""
    config = make_config(
        tmp_path, run_id="preempt",
        **{"trainer;epochs": 3, "trainer;save_period": 5},
    )
    t = build_trainer(config)
    preemption.reset()
    preemption.set_local()
    try:
        log = t.train()
    finally:
        preemption.reset()
    assert log["epoch"] == 1
    # mid-epoch polling: single-host checks every batch, so the epoch was
    # cut at its first batch and validation was skipped entirely
    assert "val_loss" not in log
    assert (config.save_dir / "checkpoint-epoch1").is_dir()
    assert not (config.save_dir / "checkpoint-epoch2").exists()
    # the forced save is resumable
    meta = json.loads(
        (config.save_dir / "checkpoint-epoch1.meta.json").read_text()
    )
    assert meta["epoch"] == 1


def test_finalize_metrics_zero_count_is_nan_not_false_best():
    from pytorch_distributed_template_tpu.engine.steps import (
        finalize_metrics,
    )

    out = finalize_metrics(
        {"loss_sum": 0.0, "count": 0.0, "skipped_sum": 16.0}
    )
    assert np.isnan(out["loss"])  # NOT 0.0 (unbeatable min-monitor best)
    assert out["skipped"] == 16.0  # raw example count, not a ratio
    # a 'min loss' monitor must treat NaN as not-improved
    assert not (out["loss"] <= 2.0)


def test_configure_debug_flags():
    try:
        configure_debug({"nan_check": True, "disable_jit": True})
        assert jax.config.jax_debug_nans
        assert jax.config.jax_disable_jit
    finally:
        jax.config.update("jax_debug_nans", False)
        jax.config.update("jax_disable_jit", False)


def test_configure_debug_noop():
    configure_debug(None)
    configure_debug({})
    assert not jax.config.jax_debug_nans
    assert not jax.config.jax_disable_jit


def test_resolve_loss_name_and_factory():
    from pytorch_distributed_template_tpu.engine.losses import (
        resolve_loss, smooth_cross_entropy,
    )

    plain = resolve_loss("cross_entropy")
    smooth = resolve_loss(
        {"type": "smooth_cross_entropy", "args": {"smoothing": 0.2}}
    )
    logits = jnp.asarray([[4.0, 0.0, 0.0], [0.0, 4.0, 0.0]])
    y = jnp.asarray([0, 1])
    l_plain = np.asarray(plain(logits, y))
    l_smooth = np.asarray(smooth(logits, y))
    assert l_smooth.shape == l_plain.shape == (2,)
    # smoothing strictly increases the loss on confident-correct logits
    assert (l_smooth > l_plain).all()
    # smoothing=0 factory matches plain CE exactly
    s0 = smooth_cross_entropy(0.0)
    np.testing.assert_allclose(np.asarray(s0(logits, y)), l_plain,
                               rtol=1e-5, atol=1e-6)
    import pytest
    with pytest.raises(ValueError, match="smoothing"):
        smooth_cross_entropy(1.5)


def test_resolve_loss_form_mismatch_errors():
    from pytorch_distributed_template_tpu.engine.losses import resolve_loss

    with pytest.raises(ValueError, match="dict form"):
        resolve_loss("smooth_cross_entropy")
    with pytest.raises(ValueError, match="string form"):
        resolve_loss({"type": "cross_entropy", "args": {}})


@pytest.mark.slow
def test_save_interval_steps(tmp_path):
    """Mid-epoch interval checkpoints: with save_interval_steps=2 and 8
    batches/epoch, saves alternate between the A/B slots WITHOUT blocking
    the step loop (no manager-level wait() inside the epoch), and the
    newest slot is resumable even if the run dies before an epoch edge."""
    import json as _json
    from pathlib import Path

    from pytorch_distributed_template_tpu.config import (
        ConfigParser, LOADERS, LOSSES, METRICS, MODELS,
    )
    from pytorch_distributed_template_tpu.config.parser import (
        find_latest_checkpoint,
    )
    from pytorch_distributed_template_tpu.engine import Trainer
    from pytorch_distributed_template_tpu.parallel import mesh_from_config

    cfg = _json.loads(
        (Path(__file__).parent.parent / "configs" / "mnist_debug.json")
        .read_text()
    )
    cfg["trainer"]["save_dir"] = str(tmp_path)
    cfg["trainer"]["epochs"] = 1
    cfg["trainer"]["save_period"] = 10**6      # periodic saves off
    cfg["trainer"]["save_interval_steps"] = 2  # ...but interval saves on
    config = ConfigParser(cfg, run_id="interval", training=True)
    model = config.init_obj("arch", MODELS)
    trainer = Trainer(
        model, LOSSES.get(config["loss"]),
        [METRICS.get(m) for m in config["metrics"]], config=config,
        train_loader=config.init_obj("train_loader", LOADERS),
        valid_loader=None, mesh=mesh_from_config(config), seed=0,
    )
    # The hot loop must never call the blocking manager-level wait();
    # train() calls it exactly once, in the end-of-training finally.
    waits = []
    orig_wait = trainer.ckpt_manager.wait
    trainer.ckpt_manager.wait = lambda: (waits.append(1), orig_wait())[1]
    trainer.train()
    assert len(waits) == 1

    # 8 batches, interval 2 -> saves at steps 2,4,6,8 alternating a,b,a,b
    meta_a = _json.loads(
        (config.save_dir / "checkpoint-interval-a.meta.json").read_text()
    )
    meta_b = _json.loads(
        (config.save_dir / "checkpoint-interval-b.meta.json").read_text()
    )
    assert (config.save_dir / "checkpoint-interval-a").is_dir()
    assert (config.save_dir / "checkpoint-interval-b").is_dir()
    assert meta_a["epoch"] == meta_b["epoch"] == 1
    assert {meta_a["step"], meta_b["step"]} == {6, 8}

    # step-accurate-resume sidecars (resilience subsystem) ride every
    # interval save: next_batch matches the slot's step, and the final
    # slot (all 8 batches done) normalizes past the epoch edge
    from pytorch_distributed_template_tpu.checkpoint.manager import (
        CheckpointManager,
    )

    by_step = {}
    for name in ("checkpoint-interval-a", "checkpoint-interval-b"):
        ds = CheckpointManager.load_data_state(config.save_dir / name)
        assert ds is not None and ds["len_epoch"] == 8
        by_step[ds["global_step"]] = ds
    assert set(by_step) == {6, 8}
    assert (by_step[6]["epoch"], by_step[6]["next_batch"]) == (1, 6)
    assert (by_step[8]["epoch"], by_step[8]["next_batch"]) == (2, 0)

    # auto-resume rediscovery picks an interval slot (no epoch checkpoint
    # exists: save_period never fired) and it restores cleanly
    latest = find_latest_checkpoint(dict(config.config))
    assert latest is not None and latest.name.startswith(
        "checkpoint-interval-"
    )
    resumed = ConfigParser(
        dict(config.config), resume=latest, run_id="interval2",
        training=True,
    )
    t2 = Trainer(
        config.init_obj("arch", MODELS), LOSSES.get(config["loss"]),
        [METRICS.get(m) for m in config["metrics"]], config=resumed,
        train_loader=config.init_obj("train_loader", LOADERS),
        valid_loader=None, mesh=mesh_from_config(config), seed=0,
    )
    assert t2.start_epoch == 2  # meta epoch 1 + 1
