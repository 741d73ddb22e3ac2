"""models/hybrid.py's `GRANITE_HYBRID`: the stack of a mixer and a gated MLP
a layer with the family's four scalars. The program against its plain reference
is tests/benchmarks/test_bm_granite_hybrid.py; here: the attention's scale
on every path the model can take, the tied scaled head through the fused
loss, the scopes the trace reads, what the model says of itself, and a
tiny experiment through the Trainer."""
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import pytorch_distributed_template_tpu.models  # noqa: F401
from pytorch_distributed_template_tpu.config.registry import MODELS
from pytorch_distributed_template_tpu.engine.losses import (
    fused_lm_cross_entropy, lm_cross_entropy,
)
from pytorch_distributed_template_tpu.models.llama import LlamaAttention
from pytorch_distributed_template_tpu.parallel import build_mesh

REPO = Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("impl", ["xla", "flash", "ring", "ring_flash",
                                  "ulysses", "ulysses_flash"])
def test_attention_multiplier_is_the_scores_scale_on_every_path(impl):
    """granite-4.0-h-micro's attention: head 64, scores `q . k / 64`
    where every path and kernel fixes `64 ** -0.5`. The field scales q by
    what is left (0.125), so each path's output is the plain softmax of
    `q . k * 0.015625`; left at 0 it is `q . k / 8`, another function."""
    b, t, d, heads, kv, hd = 2, 64, 96, 4, 2, 64
    mesh = build_mesh({"data": 2, "seq": 4}) if impl != "xla" else None
    x = jax.random.normal(jax.random.key(1), (b, t, d), jnp.float32)
    positions = jnp.arange(t, dtype=jnp.int32)

    def attention(multiplier):
        return LlamaAttention(d, heads, kv, jnp.float32, impl, mesh,
                              rope_base=0.0, head_dim=hd,
                              attention_multiplier=multiplier)

    params = attention(0.015625).init(jax.random.key(2), x, positions, False)
    # weights large enough that the scale moves the softmax
    params = jax.tree.map(lambda w: w * 12.0, params)
    w = {k: v["kernel"] for k, v in params["params"].items()}

    def plain(scale):
        q = (x @ w["q_proj"]).reshape(b, t, heads, hd)
        k, v = (jnp.repeat((x @ w[n]).reshape(b, t, kv, hd), heads // kv, 2)
                for n in ("k_proj", "v_proj"))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return ctx.reshape(b, t, heads * hd) @ w["o_proj"]

    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: attention(0.015625).apply(
            p, x, positions, False))(params)
        default = jax.jit(lambda p: attention(0.0).apply(
            p, x, positions, False))(params)
        want, by_sqrt = plain(1 / 64), plain(1 / 8)
    size = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * size)
    np.testing.assert_allclose(default, by_sqrt, rtol=0, atol=2e-5 * size)
    assert float(jnp.max(jnp.abs(want - by_sqrt))) > 0.05 * size


def _tiny(**kw):
    return MODELS.get("TinyGraniteHybrid")(**kw)


def test_fused_tied_head_is_the_scaled_logits_loss():
    """`fused_head` hands the loss the normed hidden state over
    `logits_scaling` and the embedding's transpose: the fused loss over
    them is the plain loss over the logits, and so is the gradient, the
    embedding's from both its uses. (Each side is one jitted program:
    run operation by operation the two stacks were most of a minute.)"""
    tokens = jax.random.randint(jax.random.key(0), (3, 24), 0, 256)
    plain, fused = _tiny(), _tiny(fused_head=True)
    params = jax.jit(plain.init)(jax.random.key(1), tokens)
    assert "lm_head" not in params["params"]
    loss = fused_lm_cross_entropy(chunk=8)

    def plain_loss(p):
        return jnp.mean(lm_cross_entropy(plain.apply(p, tokens, train=True),
                                         tokens))

    def fused_loss(p):
        return jnp.mean(loss(fused.apply(p, tokens, train=True), tokens))

    hidden, w = jax.jit(fused.apply)(params, tokens)
    logits = jax.jit(plain.apply)(params, tokens)
    np.testing.assert_allclose(hidden @ w, logits, rtol=0, atol=1e-5)
    (lp, gp), (lf, gf) = (jax.jit(jax.value_and_grad(f))(params)
                          for f in (plain_loss, fused_loss))
    assert float(lf) == pytest.approx(float(lp), rel=1e-6)
    for (path, a), c in zip(jax.tree_util.tree_leaves_with_path(gp),
                            jax.tree.leaves(gf)):
        np.testing.assert_allclose(
            c, a, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(a))) + 1e-9,
            err_msg=str(path))


def test_granite_step_carries_its_scopes():
    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )
    from pytorch_distributed_template_tpu.engine.steps import make_train_step

    model = _tiny(remat=True)
    tx = optax.adamw(1e-3)
    state = jax.eval_shape(lambda: create_train_state(
        model, tx, np.zeros((1, 32), np.int32), seed=0))     # shapes alone
    step = make_train_step(model, tx, lm_cross_entropy, [],
                           input_key="tokens", target_key="tokens")
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "mask": jnp.ones((2,), jnp.float32)}
    names = set(re.findall(
        r'op_name="([^"]*)"',
        jax.jit(step).lower(state, batch).compile().as_text()))

    def some(pattern):
        return any(re.search(pattern, n) for n in names)

    for scope in ("ssm_proj", "ssm_scan/ssm_intra", "ssm_scan/ssm_state",
                  "ssm_scan/ssm_conv", "dense_mlp"):
        assert some(rf"jvp\(.*/{scope}/"), scope
        assert some(rf"transpose\(jvp\(.*/{scope}/"), scope
    # both kinds of layer have the MLP; the projections are outside the
    # scan's scope and inside their own
    assert some(r"layers_0/dense_mlp/mlp") and some(r"layers_1/dense_mlp/mlp")
    assert some(r"ssm_proj/in_proj") and some(r"ssm_proj/out_proj")
    assert not some(r"ssm_scan/.*in_proj") and not some(r"dense_mlp/.*mixer")
    assert not some(r"layers_1/.*ssm_")


def test_the_model_says_its_pattern_and_the_scans_mask_once(caplog):
    import logging

    from pytorch_distributed_template_tpu.observability import trace

    trace._said.clear()
    model = _tiny()
    tokens = jnp.zeros((2, 40), jnp.int32)
    with caplog.at_level(logging.INFO):
        params = model.init(jax.random.key(0), tokens)
        model.apply(params, tokens)
        model.apply(params, tokens)
    said = [r.getMessage() for r in caplog.records]
    (pattern,) = [s for s in said if s.startswith("model/pattern")]
    assert "mam (3 layers, each a mixer and a gated MLP of 96)" in pattern
    assert ("embedding 12, residual 0.22, attention 0.015625, logits over 8"
            in pattern)
    assert "over 256 of 256 rows" in pattern
    (chunks,) = [s for s in said if s.startswith("ssm/chunks")]
    # 40 positions in chunks of 16: 3 chunks; 2 rows x 3 x 4 heads x 16^2 x 4
    assert "3 chunks of 16 positions a row, 4 heads in 1 group(s)" in chunks
    assert f"{2 * 3 * 4 * 16 * 16 * 4} bytes" in chunks


def test_the_convolution_says_its_form_once_a_process_and_shape(caplog):
    """`ssm/conv`: one line and one zero-length span for the two mixer
    layers' convolutions of one shape however often they are traced,
    another for another length; it is the mechanism's counter, so a
    model without a mixer says nothing."""
    import logging

    from pytorch_distributed_template_tpu.observability import trace

    trace._said.clear()
    trace.get_recorder().clear()
    model = _tiny()
    with caplog.at_level(logging.INFO):
        params = model.init(jax.random.key(0), jnp.zeros((2, 40), jnp.int32))
        for t in (40, 40, 24):
            model.apply(params, jnp.zeros((2, t), jnp.int32))
        _tiny(layer_types=("attention",)).init(
            jax.random.key(0), jnp.zeros((2, 56), jnp.int32))
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("ssm/conv")]
    spans = [e["args"] for e in trace.get_recorder().snapshot()
             if e["name"] == "ssm/conv"]
    assert len(lines) == len(spans) == 2
    # 64 inner channels and 2 x 1 x 16 of B and C; 2 rows of 40, then 24
    assert [(s["taps"], s["channels"], s["positions"]) for s in spans] == [
        (4, 96, 80), (4, 96, 48)]
    # off the TPU the backward is plain jax.numpy over the whole array,
    # its float32 pre-activation gradient out and back in
    assert spans[0]["backward"] == "xla fusions"
    assert (spans[0]["block_channels"], spans[0]["block_positions"]) == (
        96, 80)
    assert spans[0]["forward_bytes"] == 2 * 80 * 96 * 4
    assert spans[0]["backward_bytes"] == 80 * 96 * (3 * 4 + 8)
    assert "4 taps over 96 channels at 80 positions" in lines[0]
    assert "the backward as xla fusions" in lines[0]


def test_unknown_layer_types_and_decode_are_refused():
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="each one of"):
        _tiny(layer_types=("mamba", "moe")).init(jax.random.key(0), tokens)
    model = _tiny()
    params = model.init(jax.random.key(0), tokens)
    with pytest.raises(NotImplementedError, match="no decode path"):
        model.apply(params, tokens, decode=True)


def test_debug_config_trains_through_the_trainer(tmp_path):
    import pytorch_distributed_template_tpu.data  # noqa: F401
    import pytorch_distributed_template_tpu.engine  # noqa: F401
    from pytorch_distributed_template_tpu.config import (
        ConfigParser, LOADERS, LOSSES, METRICS,
    )
    from pytorch_distributed_template_tpu.engine import Trainer
    from pytorch_distributed_template_tpu.parallel import mesh_from_config

    cfg = json.loads(
        (REPO / "configs" / "granite_hybrid_debug.json").read_text())
    cfg["trainer"].update(save_dir=str(tmp_path), epochs=1, save_period=100,
                          tensorboard=False, monitor="off")
    cfg["train_loader"]["args"].update(n=16 * 12, batch_size=16)
    cfg.pop("valid_loader")
    config = ConfigParser(cfg, run_id="granite")
    trainer = Trainer(
        config.init_obj("arch", MODELS), LOSSES.get(config["loss"]),
        [METRICS.get(m) for m in config["metrics"]], config=config,
        train_loader=config.init_obj("train_loader", LOADERS),
        mesh=mesh_from_config(config))
    trainer._train_epoch(1)
    losses = [r["loss"] for r in trainer.recorder.last() if "loss" in r]
    assert len(losses) >= 2 and losses[-1] < losses[0]
    assert all(np.isfinite(losses))
