"""The fused head-and-loss slice is reckoned in rows on one device
(engine/losses.slice_positions, step_mesh): the rule's arithmetic, the
loss against the plain logits path at shapes that take several turns and
a padded tail, and the steps that tell the loss their mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import pytorch_distributed_template_tpu.engine  # noqa: F401
import pytorch_distributed_template_tpu.models  # noqa: F401
from pytorch_distributed_template_tpu.config.registry import LOSSES, MODELS
from pytorch_distributed_template_tpu.engine import losses
from pytorch_distributed_template_tpu.engine.losses import (
    SLICE_ROWS, fused_lm_cross_entropy, slice_positions, step_mesh,
)
from pytorch_distributed_template_tpu.engine.state import create_train_state
from pytorch_distributed_template_tpu.engine.steps import (
    make_eval_step, make_train_step,
)
from pytorch_distributed_template_tpu.models.base import inject_mesh
from pytorch_distributed_template_tpu.models.remat_policy import (
    HEADROOM_BYTES, token_shards,
)
from pytorch_distributed_template_tpu.observability import trace
from pytorch_distributed_template_tpu.observability.trace import get_recorder
from pytorch_distributed_template_tpu.parallel import (
    apply_rules, batch_sharding, build_mesh,
)


def _slices_said():
    return [e["args"] for e in get_recorder().snapshot()
            if e["name"] == "head_loss/slice"]


@pytest.fixture
def fresh_record():
    trace._said.clear()
    get_recorder().clear()


# (global batch, devices along `data`, chunk, T, V) -> positions a slice
RULE = {
    # 8 x 256 = 2048 rows already: the program GPT-2-large always ran
    "gpt2-large": ((8, 1, 256, 1024, 50257), 256),
    "mistral-one-chip": ((1, 1, 256, 8192, 32000), 2048),
    # the step traces the global batch of 4; a chip holds one sequence
    "mistral-four-chips": ((4, 4, 256, 8192, 32000), 2048),
    # 683 positions would do: the next multiple of the floor
    "batch-of-3": ((3, 1, 256, 8192, 32000), 768),
    # 2048 rows x 128256 x 4 bytes is 1.05 GB: halved to fit half the headroom
    "vocab-128k": ((1, 1, 256, 8192, 128256), 1024),
    "vocab-256k": ((1, 1, 256, 8192, 262144), 512),
    # the whole (padded) sequence is one slice
    "short-sequence": ((1, 1, 256, 1000, 32000), 1024),
    "shorter-than-the-floor": ((2, 1, 256, 100, 32000), 256),
    # batch 8 in 4 micro-batches: the loss traces 2 sequences
    "micro-batch-under-accumulation": ((2, 1, 256, 1024, 50257), 1024),
    # a batch that the mesh does not divide is reckoned whole
    "batch-not-divisible": ((6, 4, 256, 8192, 32000), 512),
    # never under the floor, whatever the bytes
    "many-sequences": ((64, 1, 256, 1024, 50257), 256),
    "odd-floor": ((1, 1, 100, 8192, 32000), 2100),
    "floor-above-the-target": ((1, 1, 4096, 8192, 32000), 4096),
}


@pytest.mark.parametrize("case,positions", RULE.values(), ids=RULE.keys())
def test_slice_positions(case, positions):
    batch, shards, chunk, seq, vocab = case
    mesh = build_mesh({"data": shards}, devices=jax.devices()[:shards])
    on_device = batch // token_shards(mesh, batch, seq)
    got = slice_positions(on_device, chunk, seq - 1, vocab)
    assert got == positions
    assert got % chunk == 0 and got >= chunk
    padded = -(-(seq - 1) // chunk) * chunk
    assert got <= max(padded, chunk)
    if got > chunk:     # above the floor only inside the bytes and the rows
        assert on_device * got * vocab * 4 <= HEADROOM_BYTES // 2
        assert on_device * (got - chunk) < SLICE_ROWS


# (B, T, chunk, V, headroom or None) -> (positions, turns); every T leaves
# a padded tail in the last slice
SHAPES = {
    "rows-reached-by-the-batch": ((8, 1000, 256, 64, None), (256, 4)),
    "one-long-sequence": ((1, 5000, 256, 64, None), (2048, 3)),
    "batch-of-3": ((3, 2000, 256, 64, None), (768, 3)),
    "one-slice": ((2, 300, 256, 64, None), (512, 1)),
    "capped-by-bytes": ((1, 5000, 256, 64, 2 * 1024 * 64 * 4), (1024, 5)),
    "micro-batch": ((2, 1000, 128, 64, None), (1024, 1)),
}


@pytest.mark.parametrize("shape,want", SHAPES.values(), ids=SHAPES.keys())
def test_loss_and_both_gradients_match_the_plain_path(
        shape, want, monkeypatch, fresh_record):
    b, t, chunk, vocab, headroom = shape
    if headroom is not None:
        monkeypatch.setattr(losses, "HEADROOM_BYTES", headroom)
    rng = np.random.default_rng(b * t)
    h = jnp.asarray(rng.normal(size=(b, t, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, vocab)) / 4, jnp.float32)
    tokens = jnp.asarray(rng.integers(0, vocab, (b, t)), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.5, 1.5, b), jnp.float32)
    plain = LOSSES.get("lm_cross_entropy")
    fused = fused_lm_cross_entropy(chunk=chunk)

    def ref(h, w):
        return jnp.sum(plain(h @ w, tokens) * weights)

    def got(h, w):
        return jnp.sum(fused((h, w), tokens) * weights)

    l1, (dh1, dw1) = jax.value_and_grad(ref, argnums=(0, 1))(h, w)
    l2, (dh2, dw2) = jax.jit(
        jax.value_and_grad(got, argnums=(0, 1)))(h, w)
    np.testing.assert_allclose(float(l2), float(l1), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(dh2), np.asarray(dh1),
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(dw2), np.asarray(dw1),
                               rtol=1e-4, atol=1e-6)
    (said,) = _slices_said()
    positions, turns = want
    assert said == dict(
        rows_per_device=b * positions, positions=positions, turns=turns,
        slice_bytes=b * positions * vocab * 4, floor_positions=chunk,
        gradients="backward")   # a bare per-example gradient


# the summed entrance (`loss.summed`): (B, T, chunk, weights, tied head,
# the scalar the sum is multiplied by). Every T but one leaves a padded
# tail in the last slice.
SUMMED = {
    "batch-of-1-folded": (1, 5000, 256, "ones", False, 1.0),
    "batch-of-2": (2, 1000, 128, "uneven", False, 1.0),
    "batch-of-8": (8, 1000, 256, "uneven", False, 1.0),
    "a-weight-of-zero": (8, 1000, 256, "a-zero", False, 1.0),
    "one-sequence-masked-out": (1, 700, 256, "a-zero", False, 1.0),
    "no-padded-tail": (2, 513, 256, "uneven", False, 1.0),
    "several-turns-and-a-tail": (3, 2000, 256, "uneven", False, 1.0),
    "tied-head": (2, 1000, 256, "uneven", True, 1.0),
    "tied-head-folded": (1, 3000, 256, "ones", True, 1.0),
    "incoming-scalar-3": (2, 1000, 256, "uneven", False, 3.0),
    "incoming-scalar-3-folded": (1, 3000, 256, "ones", False, 3.0),
}


@pytest.mark.parametrize("case", SUMMED.values(), ids=SUMMED.keys())
def test_summed_form_makes_both_gradients_of_the_plain_path(
        case, fresh_record):
    """`loss.summed` under differentiation: value, `d hidden`, `d head_w`
    (through the embedding it is the transpose of, where tied) and
    `d weights` against `lm_cross_entropy` on materialised logits with the
    same weights; `per_example` comes back unweighted; and the record says
    the forward loop made the gradients."""
    b, t, chunk, kind, tied, scalar = case
    vocab, d = 64, 16
    rng = np.random.default_rng(b * t)
    emb = jnp.asarray(rng.normal(size=(vocab, d)) / 4, jnp.float32)
    mix = jnp.asarray(rng.normal(size=(d, d)) / 4, jnp.float32)
    w0 = jnp.asarray(rng.normal(size=(d, vocab)) / 4, jnp.float32)
    tokens = jnp.asarray(rng.integers(0, vocab, (b, t)), jnp.int32)
    weights = {"ones": np.ones(b), "uneven": rng.uniform(0.5, 1.5, b),
               "a-zero": np.r_[0.0, rng.uniform(0.5, 1.5, b - 1)]}[kind]
    weights = jnp.asarray(weights, jnp.float32)
    plain = LOSSES.get("lm_cross_entropy")
    fused = fused_lm_cross_entropy(chunk=chunk)

    def output(emb, w):     # a lookup, so a tied head's gradient joins it
        return jnp.tanh(emb[tokens] @ mix), (emb.T if tied else w)

    def ref(emb, w, weights):
        h, head = output(emb, w)
        per_ex = plain(h @ head, tokens)
        return scalar * jnp.sum(per_ex * weights), per_ex

    def got(emb, w, weights):
        total, per_ex = fused.summed(output(emb, w), tokens, weights)
        return scalar * total, per_ex

    (l1, per1), g1 = jax.value_and_grad(
        ref, argnums=(0, 1, 2), has_aux=True)(emb, w0, weights)
    (l2, per2), g2 = jax.jit(jax.value_and_grad(
        got, argnums=(0, 1, 2), has_aux=True))(emb, w0, weights)
    np.testing.assert_allclose(float(l2), float(l1), rtol=3e-6)
    np.testing.assert_allclose(np.asarray(per2), np.asarray(per1),
                               rtol=3e-6)
    for mine, theirs in zip(g2, g1):
        np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs),
                                   rtol=1e-4, atol=1e-6)
    assert bool(jnp.abs(g2[0]).max() > 0) == bool(weights.sum() > 0)
    (said,) = _slices_said()
    assert said["gradients"] == "forward"
    # not under differentiation: the per-example entrance and a sum
    total, per3 = jax.jit(fused.summed)(output(emb, w0), tokens, weights)
    np.testing.assert_allclose(float(total) * scalar, float(l1), rtol=3e-6)
    np.testing.assert_allclose(np.asarray(per3), np.asarray(per1),
                               rtol=3e-6)
    assert [r["gradients"] for r in _slices_said()] == [
        "forward", "backward"]


def test_hidden_gradient_in_the_compute_dtype_and_the_last_position_zero():
    """bfloat16 operands: `d hidden` and `d head_w` come back in their
    operand's dtype, within bfloat16's rounding of the float32 path, and
    the last position, which predicts nothing, gets none."""
    rng = np.random.default_rng(3)
    h = jnp.asarray(rng.normal(size=(2, 600, 32)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(32, 128)) / 6, jnp.bfloat16)
    tokens = jnp.asarray(rng.integers(0, 128, (2, 600)), jnp.int32)
    weights = jnp.asarray([1.0, 0.5], jnp.float32)
    fused = fused_lm_cross_entropy(chunk=256)
    dh, dw = jax.jit(jax.grad(
        lambda h, w: fused.summed((h, w), tokens, weights)[0],
        argnums=(0, 1)))(h, w)
    rh, rw = jax.grad(
        lambda h, w: jnp.sum(fused((h, w), tokens) * weights),
        argnums=(0, 1))(h.astype(jnp.float32), w.astype(jnp.float32))
    assert dh.dtype == dw.dtype == jnp.bfloat16
    assert not np.asarray(dh[:, -1], np.float32).any()
    for mine, theirs in ((dh, rh), (dw, rw)):
        gap = (np.linalg.norm(np.asarray(mine, np.float32) - theirs)
               / np.linalg.norm(theirs))
        assert gap < 1e-2, gap


def test_choice_is_said_once_a_process_and_distinct_choice(
        fresh_record, caplog):
    h = jnp.zeros((2, 40, 8))
    w = jnp.zeros((8, 32))
    tokens = jnp.zeros((2, 40), jnp.int32)
    with caplog.at_level("INFO", logger=losses.__name__):
        for chunk in (16, 16, 8):
            fused_lm_cross_entropy(chunk=chunk)((h, w), tokens)
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("head_loss/slice")]
    assert len(lines) == 2 and len(_slices_said()) == 2
    assert "96 rows on a device a turn" in lines[0]     # 48 x 2, one turn
    assert "the floor is 16 positions" in lines[0]


def _tiny_lm(mesh=None):
    model = MODELS.get("TinyLM")(vocab_size=64, d_model=32, n_layer=1,
                                 n_head=2, max_len=1025, fused_head=True)
    return model if mesh is None else inject_mesh(model, mesh)


def _batch(n, seq=1025):
    tokens = np.random.default_rng(7).integers(0, 64, (n, seq))
    return {"tokens": jnp.asarray(tokens, jnp.int32),
            "mask": jnp.ones(n, bool)}


def test_sharded_step_reckons_its_slices_a_device_and_matches_one_device(
        fresh_record):
    """`data` 4, batch 4 x 1025: the step traces four sequences and a
    device holds one, so a slice is the whole 1024 positions there where
    the one-device step takes 512 of all four; same loss and gradients."""
    tx = optax.sgd(1.0)     # the step a parameter takes IS its gradient
    crit = fused_lm_cross_entropy(chunk=64)

    def run(mesh):
        model = _tiny_lm(mesh)
        state = create_train_state(model, tx, model.batch_template(1),
                                   seed=0)
        batch = _batch(4)
        if mesh is not None:
            state = jax.device_put(
                state, apply_rules(state, mesh, model.partition_rules()))
            batch = jax.device_put(batch, batch_sharding(mesh))
        step = make_train_step(model, tx, crit, [], input_key="tokens",
                               target_key="tokens")
        new, m = jax.jit(step)(state, batch)
        return float(m["loss_sum"]), jax.tree.map(np.asarray, new.params)

    loss, params = run(build_mesh({"data": 4}, devices=jax.devices()[:4]))
    ref_loss, ref_params = run(None)
    sharded, single = _slices_said()
    assert (sharded["rows_per_device"], sharded["positions"],
            sharded["turns"]) == (1024, 1024, 1)
    assert (single["rows_per_device"], single["positions"],
            single["turns"]) == (2048, 512, 2)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-4, atol=2e-6), params, ref_params)


# (grad_accum_steps, devices along `data` or None, mixup, tied head) ->
# which loop makes the head's gradients
STEPS = {
    "plain": ((1, None, 0.0, True), "forward"),
    "untied-head": ((1, None, 0.0, False), "forward"),
    "accum2": ((2, None, 0.0, True), "forward"),
    "data-mesh-of-4": ((1, 4, 0.0, False), "forward"),
    "accum2-on-the-mesh": ((2, 4, 0.0, True), "forward"),
    # the loss of two targets mixed: the per-example entrance, twice
    "mixup": ((1, None, 0.4, True), "backward"),
}


@pytest.mark.parametrize("case,where", STEPS.values(), ids=STEPS.keys())
def test_train_step_takes_the_summed_form_and_matches_plain_logits(
        fresh_record, case, where):
    """`make_train_step` with the fused criterion (a sequence of the batch
    masked out) against the same step over materialised logits and
    `lm_cross_entropy` on one device: same loss, same parameters after a
    step of plain SGD, whose step IS the gradient."""
    accum, shards, mixup, tied = case
    tx = optax.sgd(1.0)
    batch = dict(_batch(8, seq=300))
    batch["mask"] = batch["mask"].at[3].set(False)

    def run(fused, mesh=None):
        model = MODELS.get("TinyLM")(
            vocab_size=64, d_model=32, n_layer=1, n_head=2, max_len=300,
            fused_head=fused, tie_embeddings=tied)
        if mesh is not None:
            model = inject_mesh(model, mesh)
        state = create_train_state(model, tx, model.batch_template(1),
                                   seed=0)
        fed = batch
        if mesh is not None:
            state = jax.device_put(
                state, apply_rules(state, mesh, model.partition_rules()))
            fed = jax.device_put(batch, batch_sharding(mesh))
        crit = (fused_lm_cross_entropy(chunk=64) if fused
                else LOSSES.get("lm_cross_entropy"))
        step = make_train_step(model, tx, crit, [], input_key="tokens",
                               target_key="tokens", grad_accum_steps=accum,
                               mixup_alpha=mixup)
        new, m = jax.jit(step)(state, fed)
        return (float(m["loss_sum"]), float(m["count"]),
                jax.tree.map(np.asarray, new.params))

    mesh = shards and build_mesh({"data": shards},
                                 devices=jax.devices()[:shards])
    loss, count, params = run(True, mesh or None)
    assert {r["gradients"] for r in _slices_said()} == {where}
    ref_loss, ref_count, ref_params = run(False)
    assert count == ref_count == 7
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-4, atol=2e-6), params, ref_params)


def test_eval_step_gives_what_it_gave_through_the_per_example_entrance(
        fresh_record):
    """`make_eval_step` never sums through the criterion: loss and the
    fused metrics against the plain model's, and nothing says `forward`."""
    from pytorch_distributed_template_tpu.config.registry import METRICS

    batch = dict(_batch(4, seq=300))
    batch["mask"] = batch["mask"].at[1].set(False)

    def run(fused):
        model = MODELS.get("TinyLM")(
            vocab_size=64, d_model=32, n_layer=1, n_head=2, max_len=300,
            fused_head=fused)
        state = create_train_state(model, optax.sgd(1.0),
                                   model.batch_template(1), seed=0)
        crit = (fused_lm_cross_entropy(chunk=64) if fused
                else LOSSES.get("lm_cross_entropy"))
        step = make_eval_step(model, crit, [METRICS.get("lm_nll")],
                              input_key="tokens", target_key="tokens")
        return jax.tree.map(float, jax.jit(step)(state, batch))

    got, want = run(True), run(False)
    assert got.keys() == want.keys()
    for key in want:
        np.testing.assert_allclose(got[key], want[key], rtol=1e-5)
    assert {r["gradients"] for r in _slices_said()} == {"backward"}


@pytest.mark.parametrize("accum,batch,want", [
    (1, 8, (2048, 256, 4)), (4, 8, (2048, 1024, 1)), (2, 2, (1024, 1024, 1)),
], ids=["plain", "accum4", "accum2-one-sequence"])
def test_micro_batch_under_accumulation_is_what_is_reckoned(
        fresh_record, accum, batch, want):
    model = _tiny_lm()
    tx = optax.sgd(0.1)
    state = create_train_state(model, tx, model.batch_template(1), seed=0)
    step = make_train_step(
        model, tx, fused_lm_cross_entropy(chunk=256), [],
        input_key="tokens", target_key="tokens", grad_accum_steps=accum)
    jax.eval_shape(step, state, _batch(batch))
    (said,) = _slices_said()
    assert (said["rows_per_device"], said["positions"],
            said["turns"]) == want


def test_eval_step_and_metrics_are_told_and_a_bare_call_reckons_one_shard(
        fresh_record):
    """The eval step binds the mesh for its criterion and its metrics
    (engine/metrics.lm_nll delegates to the fused loss); the same loss
    called outside any step sees the global batch as one device's."""
    from pytorch_distributed_template_tpu.config.registry import METRICS

    mesh = build_mesh({"data": 4}, devices=jax.devices()[:4])
    model = _tiny_lm(mesh)
    tx = optax.sgd(0.1)
    state = create_train_state(model, tx, model.batch_template(1), seed=0)
    step = make_eval_step(model, fused_lm_cross_entropy(chunk=256),
                          [METRICS.get("lm_nll")], input_key="tokens",
                          target_key="tokens")
    assert step.__name__ == "eval_step"
    jax.eval_shape(step, state, _batch(4))
    (said,) = _slices_said()       # criterion and metric: the same choice
    assert (said["rows_per_device"], said["turns"]) == (1024, 1)

    fresh = jnp.zeros((4, 1025, 32)), jnp.zeros((32, 64))
    fused_lm_cross_entropy(chunk=256)(fresh, _batch(4)["tokens"])
    assert _slices_said()[-1]["rows_per_device"] == 2048
    with step_mesh(mesh):
        pass
    assert losses._step_mesh.get() is None
