"""The two plain references against the program's own models at a tiny
size on the CPU, float32: same seeded weights, same tokens, loss and
every gradient. And the control: the reference in the next-lower
precision (fp8 weight matmuls) does not pass the comparison that the
program's own precision (bfloat16) passes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.data import make_tokens
from benchmarks.reference import common, gpt2, llama
from benchmarks.weights import Weights
from pytorch_distributed_template_tpu import models  # noqa: F401
from pytorch_distributed_template_tpu.config import MODELS
from pytorch_distributed_template_tpu.engine.losses import lm_cross_entropy

GPT2 = dict(n_layer=2, n_head=4, d_model=64, vocab_size=256, max_len=128)
LLAMA = dict(n_layer=2, n_head=4, n_kv_head=2, d_model=64, d_ff=176,
             vocab_size=256, window=16, rope_base=10000.0, rms_eps=1e-6)
HP = dict(lr=2.5e-4, betas=[0.9, 0.95], eps=1e-8, weight_decay=0.1,
          no_decay=["bias$", "ln_", "wpe", "norm"], warmup_steps=100,
          total_steps=100000, grad_clip_norm=1.0)
CASES = {
    "gpt2": (gpt2, GPT2, "TinyLM", dict(
        vocab_size=256, n_layer=2, n_head=4, d_model=64, max_len=128)),
    "llama": (llama, LLAMA, "TinyLlama", dict(
        vocab_size=256, n_layer=2, n_head=4, n_kv_head=2, d_model=64,
        d_ff=176, window=16)),
}


def nested(flat: dict) -> dict:
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        *parents, last = path.split("/")
        for key in parents:
            node = node.setdefault(key, {})
        node[last] = value
    return tree


def flat_of(tree, prefix="") -> dict:
    out = {}
    for key, value in tree.items():
        if isinstance(value, dict):
            out.update(flat_of(value, f"{prefix}{key}/"))
        else:
            out[f"{prefix}{key}"] = value
    return out


@pytest.mark.parametrize("name", sorted(CASES))
def test_reference_matches_the_programs_model(name):
    arch, a, model_name, model_args = CASES[name]
    weights = Weights(arch.param_shapes(a), arch.init_rules(a), 2**31 + 7)
    params = weights.make()
    tokens = make_tokens(3, 4, 32, a["vocab_size"])   # window 16 < 32
    model = MODELS.get(model_name)(**model_args)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 32), jnp.int32)))
    assert {k: v.shape for k, v in flat_of(shapes["params"]).items()} == \
        weights.shapes

    def program_loss(flat):
        logits = model.apply({"params": nested(flat)}, jnp.asarray(tokens))
        return jnp.mean(lm_cross_entropy(logits, jnp.asarray(tokens)))

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(program_loss)(params)
    loss, grads = common.Follower(arch, a).loss_and_grads(params, tokens, 2)
    assert loss == pytest.approx(float(want_loss), rel=2e-6)
    for path in want:
        scale = float(jnp.max(jnp.abs(want[path]))) + 1e-12
        np.testing.assert_allclose(grads[path], want[path], rtol=0,
                                   atol=2e-4 * scale, err_msg=path)


def test_the_band_is_active_and_grouped_queries_share_keys():
    """What makes the Mistral reference differ from plain causal
    attention has to show at the test's size."""
    a = dict(LLAMA)
    weights = Weights(llama.param_shapes(a), llama.init_rules(a), 5)
    params = weights.make()
    tokens = make_tokens(3, 2, 32, 256)
    banded, _ = common.Follower(llama, a).loss_and_grads(params, tokens, 2)
    full, _ = common.Follower(llama, dict(a, window=0)).loss_and_grads(
        params, tokens, 2)
    assert abs(banded - full) > 1e-6
    assert llama.param_shapes(a)["layers_0/self_attn/k_proj/kernel"] == (64, 32)


def follow(arch, a, mode, seed=11):
    weights = Weights(arch.param_shapes(a), arch.init_rules(a), seed)
    tokens = make_tokens(seed, 8, 64, a["vocab_size"])
    return common.follow_steps(
        arch, a, HP, weights.make(), [tokens[:4], tokens[4:]],
        weights, mode=mode, rows_per_block=2)


def test_followed_steps_agree_with_optax():
    import optax
    import re

    weights = Weights(gpt2.param_shapes(GPT2), gpt2.init_rules(GPT2), 11)
    tokens = make_tokens(11, 8, 64, 256)
    got = follow(gpt2, GPT2, "f32")
    p0 = weights.make()
    mask = {k: not any(re.search(q, k) for q in HP["no_decay"]) for k in p0}
    tx = optax.chain(
        optax.clip_by_global_norm(1.0),
        optax.adamw(lambda c: 2.5e-4 * (c + 1) / 100, b1=0.9, b2=0.95,
                    eps=1e-8, weight_decay=0.1, mask=mask))
    state, p = tx.init(p0), dict(p0)
    follower = common.Follower(gpt2, GPT2)
    for batch in (tokens[:4], tokens[4:]):
        _, g = follower.loss_and_grads(p, batch, 2)
        updates, state = tx.update(g, state, p)
        p = optax.apply_updates(p, updates)
    want = {k: float(jnp.linalg.norm(p[k] - p0[k])) for k in p}
    gap, leaf = common.worst_leaf_gap(got["update_norms"], want)
    assert gap < 1e-5, leaf


def test_the_lower_precision_control_fails_where_bfloat16_passes():
    """The limit of the test's size stands between what the program's
    own precision reads and what the control reads, as a cell's limit
    does at the cell's size (PERF.md gives those readings)."""
    want = follow(llama, LLAMA, "f32")
    sound, _ = common.worst_leaf_gap(
        follow(llama, LLAMA, "bf16")["grad_norms"], want["grad_norms"])
    control, _ = common.worst_leaf_gap(
        follow(llama, LLAMA, "fp8")["grad_norms"], want["grad_norms"])
    limit = 3e-3
    assert sound < limit / 1.5, sound
    assert control > 1.5 * limit, control


def test_worst_leaf_gap_uses_the_median_leaf_as_a_floor():
    want = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.1, "b": 2.0, "c": 3e-9}     # c is all but zero
    gap, leaf = common.worst_leaf_gap(got, want)
    assert leaf == "a" and gap == pytest.approx(0.1)


def test_weights_are_seeded_leaf_by_leaf():
    w = Weights({"x/kernel": (4, 8), "x/bias": (8,), "ln/scale": (8,)},
                [("scale$", "ones", 0), ("bias$", "zeros", 0),
                 ("", "normal", 0.5)], 2**31 + 3)
    made = w.make()
    assert (made["ln/scale"] == 1).all() and (made["x/bias"] == 0).all()
    assert (made["x/kernel"] == w.leaf("x/kernel")).all()
    other = Weights(w.shapes, [("", "normal", 0.5)], 2**31 + 4)
    assert not (other.leaf("x/kernel") == made["x/kernel"]).all()
    with pytest.raises(ValueError, match="no init rule"):
        Weights({"x": (2,)}, [("y", "ones", 0)], 0)
