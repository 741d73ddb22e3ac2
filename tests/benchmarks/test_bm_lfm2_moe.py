"""The `lfm2_moe` reference (benchmarks/reference/lfm2_moe.py) and the
configuration `lfm2_24b_a2b_l5`: the program against the reference at a
tiny size on the CPU in float32, each kind of mixer alone and a pattern
with its leading dense layer; a bfloat16 router or a convolution with an
activation left in fails the comparison; the share test (what the four
chips' shares of the experts give adds up to the uncut layer, and the
program's layer gives its share); the configuration's file against the
catalog's row; the counts the yardstick takes from the reference, by
hand; and a rehearsal of the new cell."""
import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops, run
from benchmarks.data import make_tokens
from benchmarks.reference import common
from benchmarks.reference import lfm2_moe as ref
from benchmarks.weights import Weights
from pytorch_distributed_template_tpu import models  # noqa: F401
from pytorch_distributed_template_tpu.config import MODELS
from pytorch_distributed_template_tpu.engine.losses import lm_cross_entropy
from pytorch_distributed_template_tpu.models import mixers

from test_bm_data import may_lack_on_the_cpu
from test_bm_reference import flat_of, nested
from test_bm_run import SPEC, rehearse

CONFIG = json.loads(
    (run.BENCH / "configs" / "lfm2_24b_a2b_l5.json").read_text())
CELL = "lfm2_24b_a2b_l5.seq8k"
# every kind of size differs from every other, so that a transposed or
# swapped width cannot pass
TINY = dict(
    layer_types=["conv", "full_attention", "conv", "conv"], d_model=48,
    d_ff=56, n_dense_layers=1, vocab_size=256, n_head=4, n_kv_head=2,
    head_dim=8, conv_taps=3, moe_n_routed=12, moe_held=[3, 5], moe_top_k=4,
    moe_d_ff=28, moe_scale=1.0, rope_base=1e6, rms_eps=1e-5)
# float32 against float32 on the CPU, two schedules of one sum (a mask
# against a top-k, XLA's attention against blocks of query rows):
# rounding alone. The tolerances of the other hybrid families' tests
LOSS_RTOL, GRAD_ATOL = 2e-6, 2e-4
# the catalog's row (guides/model-configs/architectures.jsonl,
# `LFM2-24B-A2B`, its `config`), copied: the tests read nothing outside
# the repository but to see that this copy is the row
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
PUBLISHED_TYPES = (["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 9
                   + ["full_attention", "conv"])
ROW = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": PUBLISHED_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def model_of(a):
    # the rotation's base is the family record's, no size of a call
    return MODELS.get("Lfm2Moe")(
        **{k: v for k, v in a.items() if k != "rope_base"}, max_len=128,
        bfloat16=False, attn_impl="xla", remat=False, fused_head=False)


def program_loss_and_grads(a, params, tokens):
    model = model_of(a)

    def loss(flat):
        logits = model.apply({"params": nested(flat)}, jnp.asarray(tokens),
                             train=True)
        return jnp.mean(lm_cross_entropy(logits, jnp.asarray(tokens)))

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss))(params)


def worst_gap(grads, want):
    return max(float(jnp.max(jnp.abs(grads[p] - want[p])))
               / (float(jnp.max(jnp.abs(want[p]))) + 1e-12) for p in want)


def seeded(a, seed=2**31 + 7):
    """Seeded weights with the norms, the taps' like, and the selection
    biases away from their rules' constants, and 40 tokens a row."""
    weights = Weights(ref.param_shapes(a), ref.init_rules(a), seed)
    params = weights.make()
    key = jax.random.key(seed & 0xFFFF)
    for i, path in enumerate(sorted(params)):
        if path.endswith("norm/weight"):
            params[path] = jax.random.uniform(
                jax.random.fold_in(key, i), params[path].shape, jnp.float32,
                0.5, 1.5)
        elif path.endswith("selection_bias"):
            params[path] = jax.random.uniform(
                jax.random.fold_in(key, i), params[path].shape, jnp.float32,
                -0.1, 0.1)
    return weights, params, make_tokens(3, 4, 40, a["vocab_size"])


PATTERNS = {
    "conv": dict(layer_types=["conv"], n_dense_layers=0),
    "full_attention": dict(layer_types=["full_attention"], n_dense_layers=0),
    "whole": {},
}


@pytest.mark.parametrize("pattern", sorted(PATTERNS))
def test_reference_matches_the_programs_model(pattern):
    """Loss and every leaf's gradient: each mixer alone in front of
    experts, and the pattern whose first layer takes the gated MLP."""
    a = {**TINY, **PATTERNS[pattern]}
    weights, params, tokens = seeded(a)
    shapes = jax.eval_shape(lambda: model_of(a).init(
        jax.random.key(0), jnp.zeros((1, 40), jnp.int32)))
    assert {k: v.shape for k, v in flat_of(shapes["params"]).items()} == \
        weights.shapes
    if pattern == "whole":
        assert "layers_0/mlp/up_proj/kernel" in weights.shapes
        assert "layers_0/experts/router" not in weights.shapes
        assert "layers_1/mixer/q_layernorm/weight" in weights.shapes
        assert "lm_head/kernel" not in weights.shapes
    want_loss, want = program_loss_and_grads(a, params, tokens)
    loss, grads = common.Follower(ref, a).loss_and_grads(params, tokens, 2)
    assert loss == pytest.approx(float(want_loss), rel=LOSS_RTOL)
    for path in want:
        scale = float(jnp.max(jnp.abs(want[path]))) + 1e-12
        np.testing.assert_allclose(grads[path], want[path], rtol=0,
                                   atol=GRAD_ATOL * scale, err_msg=path)
    for path in want:       # no gradient reaches the selection bias
        if path.endswith("selection_bias"):
            assert not np.any(np.asarray(grads[path]))
            assert not np.any(np.asarray(want[path]))


@pytest.mark.parametrize("broken", ["router", "activation"])
def test_a_router_in_bfloat16_or_an_activation_left_in_fails(broken,
                                                             monkeypatch):
    """The router's product in bfloat16, or the convolution followed by
    the SiLU every other convolution of the repository has: the
    comparison above then fails its gradient tolerance ten times over."""
    weights, params, tokens = seeded(TINY)
    _, grads = common.Follower(ref, TINY).loss_and_grads(params, tokens, 2)
    if broken == "router":
        matmul = jnp.matmul

        def rounded(x, w, precision=None):
            if precision is None:
                return matmul(x, w)
            return matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
                          ).astype(jnp.float32)

        monkeypatch.setattr(jnp, "matmul", rounded)
    else:
        plain = mixers.causal_conv
        monkeypatch.setattr(mixers, "causal_conv",
                            lambda x, taps: jax.nn.silu(plain(x, taps)))
    _, got = program_loss_and_grads(TINY, params, tokens)
    assert worst_gap(got, grads) > 10 * GRAD_ATOL


# -- the share test ----------------------------------------------------------


def test_the_four_expert_shares_add_up_to_the_whole_layer():
    """Four chips hold 3 of the 12 routed experts each; there is no
    shared expert, so the shares' sum is the whole layer's. And the
    program's layer gives its share as the reference's does."""
    a = dict(TINY, layer_types=["conv"], n_dense_layers=0, moe_held=[0, 0])
    _, params, _ = seeded(a, seed=11)
    p = common.layer_params(params, "layers_0")
    x = jax.random.normal(jax.random.key(99), (2, 24, a["d_model"]),
                          jnp.float32)
    dot = common.DOTS["f32"]
    names = ("experts_gate", "experts_up", "experts_down")

    def share_of(lo, n):
        return {**p, **{f"experts/{k}": p[f"experts/{k}"][lo:lo + n]
                        for k in names}}

    with jax.default_matmul_precision("highest"):
        whole = ref.experts(a, p, x, dot)
        total = sum(ref.experts(dict(a, moe_held=[lo, 3]), share_of(lo, 3),
                                x, dot) for lo in (0, 3, 6, 9))
    np.testing.assert_allclose(total, whole, rtol=0,
                               atol=4e-6 * float(jnp.max(jnp.abs(whole))))
    from pytorch_distributed_template_tpu.models.moe import ExpertLayer
    layer = ExpertLayer(
        d_model=a["d_model"], d_ff=a["moe_d_ff"], n_routed=12, top_k=4,
        held=(6, 3), selection_bias=True, gated=True)
    share = share_of(6, 3)
    mine = nested({k[len("experts/"):]: v for k, v in share.items()
                   if k.startswith("experts/")})
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda v, y: layer.apply({"params": v}, y))(mine, x)
        want = ref.experts(dict(a, moe_held=[6, 3]), share, x, dot)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=4e-6 * float(jnp.max(jnp.abs(want))))


# -- the counts, by hand -----------------------------------------------------


def test_parameters_and_matmul_weights_by_hand():
    a = CONFIG["sizes"]
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    experts = 2048 * 64 + 64 + 16 * 3 * 2048 * 1536
    mlp = 3 * 2048 * 11776
    assert (conv, attn, experts, mlp) == (16783360, 10485888, 151126080,
                                          72351744)
    layers = [conv + mlp + 4096, attn + experts + 4096] \
        + 3 * [conv + experts + 4096]
    assert layers[:3] == [89139200, 161616064, 167913536]
    assert ref.parameters(a) == sum(layers) + 16384 * 2048 + 2048 \
        == 788052352
    assert "788,052,352 parameters" in CONFIG["deployment"]
    model = MODELS.get("Lfm2Moe")(**CONFIG["experiment"]["arch"]["args"])
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 16), jnp.int32))["params"]
    assert sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes)) \
        == 788052352
    assert {k: v.shape for k, v in flat_of(shapes).items()} == \
        {k: tuple(v) for k, v in ref.param_shapes(a).items()}
    # a held expert is met by 4 of 64 of the tokens
    experts_mm = 2048 * 64 + 16 * 3 * 2048 * 1536 * 4 // 64
    assert ref.matmul_weights(a) == (
        4 * 4 * 2048 * 2048 + (attn - 128) + mlp + 4 * experts_mm
        + 2048 * 16384) == 221773824
    mixer = 12 * 32 * 64 * 4096.5
    assert ref.mixer_flops_per_token(a, 8192) == mixer
    per_token = flops.model_flops_per_token(ref, a, 8192)
    assert per_token == 6 * 221773824 + mixer
    assert 1.431e9 < per_token < 1.432e9


def test_the_file_holds_the_catalogs_keys_and_the_share():
    if CATALOG.is_file():
        rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
        (row,) = [r for r in rows if r["name"] == "LFM2-24B-A2B"]
        assert row["config"] == ROW
        assert row["source_url"] == CONFIG["source"]
    assert set(CONFIG["reduced"]) == set(CONFIG["published"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"}
    for key, value in ROW.items():      # every key of the row, as it is
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value, key
        else:
            assert CONFIG[key] == value, key
    kept = PUBLISHED_TYPES[1:6]
    assert kept == ["conv", "full_attention", "conv", "conv", "conv"]
    assert (CONFIG["num_hidden_layers"], CONFIG["layer_types"],
            CONFIG["num_dense_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (5, kept, 1, 16, 16384)
    s = CONFIG["sizes"]
    assert s["layer_types"] == kept and s["moe_held"] == [0, 16]
    assert (s["d_model"], s["d_ff"], s["moe_d_ff"], s["head_dim"],
            s["n_head"], s["n_kv_head"], s["conv_taps"]) == \
        (2048, 11776, 1536, 64, 32, 8, 3)
    assert (s["moe_n_routed"], s["moe_top_k"], s["n_dense_layers"],
            s["rope_base"], s["rms_eps"]) == (64, 4, 1, 1e6, 1e-5)
    assert "4 chips, one v5e host, share each layer" in CONFIG["deployment"]
    for word in ("tied_embedding", "short_conv", "attention", "experts",
                 "norm_topk_eps", "selection_bias", "optimizer", "compute",
                 "init_rules", "max_len"):
        assert CONFIG["assumed"][word]
    assert CONFIG["distorts"]


def test_init_rules_cover_every_leaf_and_the_exclusions_name_leaves():
    shapes = ref.param_shapes(TINY)
    made = Weights(shapes, ref.init_rules(TINY), 5).make()
    assert set(made) == set(shapes)
    for leaf in ("layers_0/operator_norm/weight", "layers_2/ffn_norm/weight",
                 "layers_1/mixer/q_layernorm/weight",
                 "layers_1/mixer/k_layernorm/weight", "norm/weight"):
        assert np.all(np.asarray(made[leaf]) == 1), leaf
    assert not np.any(np.asarray(made["layers_1/experts/selection_bias"]))
    for leaf in ("embed_tokens/embedding", "layers_0/mixer/in_proj/kernel",
                 "layers_0/mixer/conv_kernel", "layers_0/mlp/up_proj/kernel",
                 "layers_1/mixer/q_proj/kernel", "layers_1/experts/router",
                 "layers_3/experts/experts_gate"):
        assert float(jnp.std(made[leaf])) == \
            pytest.approx(0.02, rel=0.2), leaf
    exclude = CONFIG["experiment"]["optimizer"]["args"][
        "weight_decay_exclude"]
    for pattern in exclude:
        assert any(re.search(pattern, path) for path in shapes), pattern
    outside = {path for path in shapes
               if any(re.search(p, path) for p in exclude)}
    assert {"layers_1/mixer/q_layernorm/weight", "norm/weight",
            "layers_1/experts/selection_bias"} <= outside
    assert "layers_0/mixer/conv_kernel" not in outside     # taps decay


# -- a rehearsal of the new cell ---------------------------------------------

SCOPES = {"short_conv_ms_per_step", "short_conv_proj_ms_per_step",
          "qknorm_attn_ms_per_step"}


def test_the_new_metrics_are_the_new_cells_alone():
    for w in SPEC["workloads"]:
        expected = set(run.expected_metrics(SPEC, w["name"], True))
        assert (SCOPES <= expected) == (w["name"] == CELL)
        if w["name"] != CELL:
            assert not SCOPES & expected
    # none of them reads a kernel's own events: a rehearsal reports all
    assert not may_lack_on_the_cpu(SCOPES)
    # the cell is on no list of the older metrics (a `benchmark` PR's to
    # change): its line carries the list-less ones and its own three
    unlisted = {m["name"] for m in SPEC["per_layer"] if "workloads" not in m}
    assert set(run.expected_metrics(SPEC, CELL, True)) == unlisted | SCOPES
    assert [m["name"] for m in SPEC["per_layer"][-3:]] == [
        "short_conv_ms_per_step", "short_conv_proj_ms_per_step",
        "qknorm_attn_ms_per_step"]
    assert SPEC["workloads"][-1]["name"] == CELL
    assert SPEC["configs"][-1]["name"] == "lfm2_24b_a2b_l5"


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_of_the_new_cell_ends_in_a_valid_line(trace):
    from benchmarks import lastline

    line, code, said = rehearse(CELL, trace)
    assert code == run.EXIT_REHEARSED != 0
    expected = run.expected_metrics(SPEC, CELL, bool(trace))
    assert not may_lack_on_the_cpu(expected)
    assert set(expected) <= set(line["metrics"])
    lastline.validate(line, expected, bool(trace))
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    if trace:
        assert SCOPES <= set(line["metrics"])
        # the leading dense layer has no bias to solve; the two behind it
        (solved,) = [s for s in said if s.startswith("balance: ")]
        assert "layers_0 " not in solved
        assert all(f"layers_{k} " in solved for k in (1, 2))
    else:
        assert not SCOPES & set(line["metrics"])
