"""The optimizer pass of ``engine/steps.make_train_step`` against a plain
re-statement of the sequence it replaced.

The pass hands ``tx.update`` the summed gradients as the backward left
them, times ONE scalar (``clip_scale / count``), and takes the skip
rule's ``ok`` from the gradients' norm. The sequence before it divided
every leaf by the count, took the norm of that tree, scaled every leaf
by the clip, scanned every leaf for ``ok`` and zeroed every leaf. The two
must agree: bit for bit where the count is a power of two (the division
is exact wherever it sits), to the last place elsewhere.
"""
import contextlib
import functools
import itertools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_template_tpu.engine.optim import build_optimizer
from pytorch_distributed_template_tpu.engine.state import create_train_state
from pytorch_distributed_template_tpu.engine.steps import make_train_step
from pytorch_distributed_template_tpu.observability.health import (
    health_layout, pack_health_summary, unpack_health_summary,
)
from pytorch_distributed_template_tpu.parallel.sharding import path_str

CLIP = 0.5          # under every batch's norm here, so the clip bites
TRAINABLE = ["out/", "norm"]


class _Net(nn.Module):
    """Three groups of leaves. With ``bf16_leaf`` the first kernel is read
    through a bfloat16 cast, so its gradient reaches the pass as a
    bfloat16 sum widened to the parameter's float32: what the benchmark's
    cells' head and embedding hand it."""
    bf16_leaf: bool = False

    @nn.compact
    def __call__(self, x, train=False):
        w = self.param("inp", nn.initializers.lecun_normal(), (3, 16))
        if self.bf16_leaf:
            h = (x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16)
                 ).astype(jnp.float32)
        else:
            h = x @ w
        h = nn.LayerNorm(name="norm")(jnp.tanh(h))
        return nn.Dense(4, name="out")(h)


def _sq_err(output, target):
    return jnp.sum((output - target[:, None].astype(output.dtype)) ** 2,
                   axis=-1)


def _batch(count, turn=0, poison=None):
    """Eight rows of which the first ``count`` are valid; other rows a
    turn."""
    rng = np.random.default_rng(count + 10 * turn)
    x = rng.normal(size=(8, 3)).astype(np.float32)
    if poison is not None:
        x[0, 1] = poison
    return {"image": jnp.asarray(x),
            "label": jnp.asarray(rng.integers(0, 3, (8,)), jnp.int32),
            "mask": jnp.arange(8) < count}


def _optimizer(trainable):
    """AdamW as the cells configure it: decoupled decay with exclusions,
    and the frozen leaves without moments where ``trainable`` is set."""
    args = dict(lr=1e-2, weight_decay=0.1,
                weight_decay_exclude=["bias$", "norm"])
    if trainable:
        args["trainable"] = TRAINABLE
    tx, _, _ = build_optimizer(
        {"optimizer": {"type": "AdamW", "args": args}}, 1)
    return tx


def _old_step(model, tx, criterion, clip, skip, health, trainable):
    """The sequence ``make_train_step`` ran before the pass: divide, norm,
    clip, scan, zero, ``tx.update``, ``apply_updates``, selects."""

    def sum_loss(params, batch):
        out = model.apply({"params": params}, batch["image"], train=True)
        mask = batch["mask"].astype(jnp.float32)
        return jnp.sum(criterion(out, batch["label"]) * mask), mask

    def step(state, batch):
        (loss_sum, mask), grads = jax.value_and_grad(
            sum_loss, has_aux=True)(state.params, batch)
        count = mask.sum()
        metrics = {"loss_sum": loss_sum, "count": count}
        denom = jnp.maximum(count, 1.0)
        grads = jax.tree.map(lambda g: (g / denom).astype(g.dtype), grads)
        if trainable:
            grads = jax.tree_util.tree_map_with_path(
                lambda path, g: g if any(
                    t in path_str(path) for t in TRAINABLE)
                else jnp.zeros_like(g), grads)
        health_grads = grads
        if clip or health:
            gnorm = optax.global_norm(grads)
            metrics["grad_norm_sum"] = gnorm * denom
        if clip:
            scale = jnp.minimum(1.0, clip / (gnorm + 1e-6))
            grads = jax.tree.map(lambda g: g * scale, grads)
        ok = jnp.array(True)
        if skip:
            ok = jnp.isfinite(loss_sum)
            for g in jax.tree.leaves(grads):
                ok = ok & jnp.all(jnp.isfinite(g))
            grads = jax.tree.map(
                lambda g: jnp.where(ok, g, jnp.zeros_like(g)), grads)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        if skip:
            sel = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
            params = jax.tree.map(sel, params, state.params)
            opt_state = jax.tree.map(sel, opt_state, state.opt_state)
            metrics = {k: jnp.where(ok, v, jnp.zeros_like(v))
                       for k, v in metrics.items()}
            metrics["skipped_sum"] = (1.0 - ok.astype(jnp.float32)) * denom
        if health:
            metrics["health"] = pack_health_summary(
                loss=loss_sum / denom, grad_norm=gnorm,
                update_norm=optax.global_norm(updates),
                grads=health_grads, new_params=params)
        return state.replace(step=state.step + 1, params=params,
                             opt_state=opt_state), metrics

    return step


@functools.lru_cache(maxsize=None)
def _steps(clip, skip, health, bf16_leaf, trainable, loss_scale=1.0):
    """(initial state, the program's step, the old sequence), each step
    compiled once for every count: the count is data."""
    model = _Net(bf16_leaf=bf16_leaf)

    def criterion(output, target):
        return loss_scale * _sq_err(output, target)

    tx = _optimizer(trainable)
    state = create_train_state(model, tx, jnp.ones((1, 3), jnp.float32),
                               seed=3)
    new = make_train_step(
        model, tx, criterion, grad_clip_norm=clip, skip_nonfinite=skip,
        health=health, log_grad_norm=bool(clip or health),
        trainable_patterns=TRAINABLE if trainable else None)
    old = _old_step(model, tx, criterion, clip, skip, health, trainable)
    return state, jax.jit(new), jax.jit(old)


def _ulps(a, b):
    """The largest distance between two float32 leaves, in units of the
    last place of the leaf's largest element (an element that a sum has
    all but cancelled is not held to its own last place)."""
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if a.size == 0:
        return 0.0
    spacing = np.spacing(max(np.abs(a).max(), np.abs(b).max()))
    return float(np.max(np.abs(a.astype(np.float64) - b)) / spacing)


def _assert_states_agree(new, old, ulps, squared=None):
    """Leaf by leaf; ``squared`` is the allowance of a second moment,
    whose difference is twice its gradient's and one rounding more."""
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(new)[0],
            jax.tree.leaves(old)):
        name = path_str(path)
        if not jnp.issubdtype(a.dtype, jnp.floating):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
            continue
        allowed = squared if squared and "/nu/" in name else ulps
        assert a.dtype == b.dtype, name
        assert _ulps(a, b) <= allowed, (name, _ulps(a, b))


CASES = list(itertools.product(
    (0.0, CLIP), (False, True), (False, True), (1, 3, 8), (False, True),
    (False, True), (False, True)))


@pytest.mark.parametrize(
    "clip,skip,health,count,bf16_leaf,trainable,jitted", CASES,
    ids=[f"clip{int(bool(c))}-skip{int(s)}-health{int(h)}-count{n}-"
         f"bf16{int(b)}-train{int(t)}-{'jit' if j else 'op_by_op'}"
         for c, s, h, n, b, t, j in CASES])
def test_pass_equals_the_sequence_it_replaced(clip, skip, health, count,
                                              bf16_leaf, trainable, jitted):
    """Two steps from one state through the program's step and through
    the old sequence: parameters, moments, counts, ``grad_norm_sum`` and
    the packed health vector.

    Run operation by operation, they agree bit for bit at the counts 1
    and 8 (a division by a power of two is exact wherever it sits). At 3
    the count's reciprocal and the product with it round where the old
    division rounded once: 2 units of a leaf's last place, and 6 in a
    second moment, which squares the gradient (5 read). Compiled, the two
    programs' loops contract their multiplies and adds differently on the
    CPU, which moves a second moment's last place at any count (1 read
    from a state off zero): 2 units more everywhere."""
    state, new, old = _steps(clip, skip, health, bf16_leaf, trainable)
    ulps = (0 if count in (1, 8) else 2) + (2 if jitted else 0)
    squared = (0 if count in (1, 8) else 6) + (2 if jitted else 0)
    s_new = s_old = state
    for turn in range(2):
        batch = _batch(count, turn)
        with contextlib.nullcontext() if jitted else jax.disable_jit():
            s_new, m_new = new(s_new, batch)
            s_old, m_old = old(s_old, batch)
        assert set(m_new) == set(m_old)
        for key in m_old:
            assert _ulps(m_new[key], m_old[key]) <= ulps, key
        _assert_states_agree(
            (s_new.params, s_new.opt_state, s_new.step),
            (s_old.params, s_old.opt_state, s_old.step), ulps, squared)
    changed = [not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(state.params),
                               jax.tree.leaves(s_new.params))]
    assert any(changed) and (trainable or all(changed))


def _health_of(state, metrics):
    return unpack_health_summary(jax.device_get(metrics["health"]),
                                 health_layout(state.params))


@pytest.mark.parametrize("poison,loss_scale,old_skips", [
    (float("nan"), 1.0, True), (float("inf"), 1.0, True),
    (None, 3e19, False),
], ids=["nan", "inf", "overflow"])
def test_ok_from_the_norm_skips_what_the_scan_skipped_and_an_overflow(
        poison, loss_scale, old_skips):
    """A NaN and an inf in the batch reach the gradients: ``ok`` from the
    norm and the old per-leaf scan both skip. A loss so steep that the
    gradients are finite and their squares overflow float32 has finite
    leaves and an infinite norm: the scan let it through with a clip
    scale of 0 (an update of decay alone), the norm skips it. A skipped
    step leaves parameters and optimizer state bit-identical, and the
    health vector still names the group whose gradients were not
    finite."""
    state, new, old = _steps(CLIP, True, True, False, False, loss_scale)
    state, _ = _steps(CLIP, True, True, False, False)[1](
        state, _batch(8))                     # moments off zero
    batch = _batch(8, poison=poison)
    after, metrics = new(state, batch)
    _, m_old = old(state, batch)
    assert float(m_old["skipped_sum"]) == (8.0 if old_skips else 0.0)
    assert float(metrics["skipped_sum"]) == 8.0
    assert float(metrics["count"]) == 0.0
    assert int(after.step) == int(state.step) + 1
    _assert_states_agree((after.params, after.opt_state),
                         (state.params, state.opt_state), 0)
    health = _health_of(state, metrics)
    assert not np.isfinite(health["grad_norm"])
    assert health["nonfinite_params"] == 0.0
    if old_skips:
        assert health["nonfinite/inp"] > 0      # the leaf that read the row
        assert health == pytest.approx(_health_of(state, m_old), nan_ok=True)
    else:
        # every element finite, their squares' sum not: nothing to count
        assert health["nonfinite_grads"] == 0.0


def test_without_a_norm_the_scan_stays():
    """``skip_nonfinite`` with no clip, no logged norm and no health: no
    norm exists, so every leaf is scanned, as before."""
    state, new, old = _steps(0.0, True, False, False, False)
    after, metrics = new(state, _batch(8, poison=float("nan")))
    assert float(metrics["skipped_sum"]) == 8.0
    _assert_states_agree((after.params, after.opt_state),
                         (state.params, state.opt_state), 0)


def test_pass_says_what_it_is_once():
    """One ``optimizer/pass`` record a distinct choice: leaves,
    parameters, the optimizer by its state, where ``ok`` comes from, what
    rides the scalar, and 28 bytes a float32 AdamW parameter (and the
    optimizer's two counters over so small a model)."""
    from pytorch_distributed_template_tpu.observability import trace

    trace._said.clear()
    trace.get_recorder().clear()
    _steps.cache_clear()
    for clip, skip, health in ((CLIP, True, True), (0.0, True, False),
                               (0.0, False, False)):
        state, new, _ = _steps(clip, skip, health, False, False)
        new(state, _batch(8))
        new(state, _batch(3))
    said = [e["args"] for e in trace.get_recorder().snapshot()
            if e["name"] == "optimizer/pass"]
    assert [(s["ok"], s["scalar"]) for s in said] == [
        ("norm", "clip/count"), ("scan", "1/count"), ("none", "1/count")]
    n = sum(p.size for p in jax.tree.leaves(state.params))
    assert all(s["leaves"] == 5 and s["parameters"] == n
               and "ScaleByAdam" in s["optimizer"]
               and s["bytes_per_parameter"] == round(28 + 16 / n, 2)
               for s in said)


def test_bfloat16_parameters_keep_their_states_dtypes():
    """The scalar is float32 and the leaf it multiplies is handed on at
    its own dtype: a bfloat16 parameter's moments stay bfloat16 over a
    step, so the state a compiled step returns is the state it takes."""

    class Narrow(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            return nn.Dense(4, param_dtype=jnp.bfloat16,
                            dtype=jnp.bfloat16)(x).astype(jnp.float32)

    model, tx = Narrow(), optax.adamw(1e-2)
    state = create_train_state(model, tx, jnp.ones((1, 3)), seed=0)
    step = jax.jit(make_train_step(model, tx, _sq_err, grad_clip_norm=CLIP,
                                   skip_nonfinite=True))
    after, _ = step(state, _batch(8))

    def dtypes(s):
        return jax.tree.map(lambda x: x.dtype, (s.params, s.opt_state))

    assert dtypes(after) == dtypes(state)
    assert not np.array_equal(
        np.asarray(after.params["Dense_0"]["kernel"], np.float32),
        np.asarray(state.params["Dense_0"]["kernel"], np.float32))
