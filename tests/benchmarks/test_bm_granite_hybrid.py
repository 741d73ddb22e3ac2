"""The `granitemoehybrid` reference (benchmarks/reference/granite_hybrid.py)
and the configuration `granite4_h_micro_l10`: the program against the
reference at a tiny size on the CPU in float32, each kind of layer alone
and a pattern of both; each of the family's four multipliers shown to
matter; the slice test that ties the chip's share of the tied vocabulary
to the model; the configuration's file against the catalog's row; the
counts the yardstick takes from the reference, by hand; and a rehearsal
of the new cell."""
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
from benchmarks.reference import granite_hybrid as ref
from benchmarks.weights import Weights
from pytorch_distributed_template_tpu import models  # noqa: F401
from pytorch_distributed_template_tpu.config import MODELS
from pytorch_distributed_template_tpu.engine.losses import lm_cross_entropy

from test_bm_data import may_lack_on_the_cpu
from test_bm_reference import flat_of, nested
from test_bm_run import SPEC, rehearse

CONFIG = json.loads(
    (run.BENCH / "configs" / "granite4_h_micro_l10.json").read_text())
CELL = "granite4_h_micro_l10.seq8k"
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")
# every kind of size differs from every other, so that a transposed or
# swapped width cannot pass; the multipliers are the published ones
TINY = dict(
    layer_types=["mamba", "attention", "mamba"], d_model=48, d_ff=56,
    vocab_size=256, n_head=4, n_kv_head=2, head_dim=8, ssm_n_head=6,
    ssm_head_dim=4, ssm_n_group=1, ssm_state=10, ssm_conv=4, ssm_chunk=16,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0, rms_eps=1e-5)
# the catalog's row (guides/model-configs/architectures.jsonl,
# `granite-4.0-h-micro`, its `config`), copied: the tests read nothing
# outside the repository but to see that this copy is the row
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
ROW = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


def model_of(a, **changed):
    return MODELS.get("GraniteHybrid")(
        **{**a, **changed}, max_len=128, bfloat16=False, attn_impl="xla",
        remat=False, fused_head=False)


def program_loss_and_grads(a, params, tokens, **changed):
    model = model_of(a, **changed)

    def loss(flat):
        logits = model.apply({"params": nested(flat)}, jnp.asarray(tokens),
                             train=True)
        return jnp.mean(lm_cross_entropy(logits, jnp.asarray(tokens)))

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params)


def seeded(a, seed=2**31 + 11, rows=4, seq=40):
    weights = Weights(ref.param_shapes(a), ref.init_rules(a), seed)
    return weights, weights.make(), make_tokens(3, rows, seq,
                                                a["vocab_size"])


def over_tolerance(loss, grads, want_loss, want) -> float:
    """The largest of the comparison's gaps, each over its tolerance: the
    loss's relative gap over 2e-6, and per leaf the gradient's largest
    absolute gap over 2e-4 of the leaf's largest entry. Under 1, the
    program is the reference's model."""
    worst = abs(loss - float(want_loss)) / (2e-6 * abs(float(want_loss)))
    for path in want:
        scale = float(jnp.max(jnp.abs(want[path]))) + 1e-12
        gap = float(jnp.max(jnp.abs(grads[path] - want[path])))
        worst = max(worst, gap / (2e-4 * scale))
    return worst


@pytest.mark.parametrize("layer_types", [
    ["mamba"], ["attention"], ["mamba", "attention", "mamba"]],
    ids=["mamba", "attention", "both"])
def test_reference_matches_the_programs_model(layer_types):
    """Loss and every leaf's gradient, 40 tokens a row: the chunk of 16
    does not divide them, and the scan runs over two and a half chunks."""
    a = {**TINY, "layer_types": layer_types}
    weights, params, tokens = seeded(a)
    shapes = jax.eval_shape(lambda: model_of(a).init(
        jax.random.key(0), jnp.zeros((1, 40), jnp.int32)))
    assert {k: v.shape for k, v in flat_of(shapes["params"]).items()} == \
        weights.shapes
    want_loss, want = program_loss_and_grads(a, params, tokens)
    loss, grads = common.Follower(ref, a).loss_and_grads(params, tokens, 2)
    assert set(grads) == set(want)
    assert over_tolerance(loss, grads, want_loss, want) < 1


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_matters(name):
    """The program with one of the four scalars left at 1 is another
    model: the comparison above fails, by ten of its tolerances at least
    (the attention's scale shows in its projections' gradients, the
    others in the loss too)."""
    _, params, tokens = seeded(TINY)
    loss, grads = common.Follower(ref, TINY).loss_and_grads(params, tokens, 2)
    broken = program_loss_and_grads(TINY, params, tokens, **{name: 1.0})
    assert over_tolerance(loss, grads, *broken) > 10


def test_a_vocabulary_slice_of_the_tied_matrix_is_the_models_share():
    """Eight chips share the tied matrix by rows. The chip that holds
    rows `[lo, lo + n)`, fed ids of its slice, computes the uncut model's
    hidden state and those columns of its logits; and the eight slices'
    sums of exponentials add up to the whole softmax's denominator, which
    is all a vocabulary-parallel loss crosses the chips for."""
    a, chips = TINY, 8
    n = a["vocab_size"] // chips
    _, params, _ = seeded(a)
    whole = model_of(a)
    rank = 3
    lo = rank * n
    ids = make_tokens(5, 2, 24, n)                      # ids of the slice
    with jax.default_matmul_precision("highest"):
        logits = whole.apply({"params": nested(params)},
                             jnp.asarray(ids + lo))
        held = dict(params)
        held["embed_tokens/embedding"] = \
            params["embed_tokens/embedding"][lo:lo + n]
        share = model_of(a, vocab_size=n, vocab_published=a["vocab_size"])
        got = share.apply({"params": nested(held)}, jnp.asarray(ids))
        np.testing.assert_allclose(got, logits[..., lo:lo + n], rtol=0,
                                   atol=1e-6)
        hidden, _ = MODELS.get("GraniteHybrid")(
            **a, max_len=128, bfloat16=False, attn_impl="xla", remat=False,
            fused_head=True).apply({"params": nested(params)},
                                   jnp.asarray(ids + lo))
        sums = sum(
            jnp.sum(jnp.exp(hidden @ params["embed_tokens/embedding"]
                            [r * n:(r + 1) * n].T), axis=-1)
            for r in range(chips))
    np.testing.assert_allclose(sums, jnp.sum(jnp.exp(logits), axis=-1),
                               rtol=1e-6)


def test_init_rules_give_the_scan_a_carry_that_matters():
    made = Weights(ref.param_shapes(TINY), ref.init_rules(TINY), 5).make()
    dt = jax.nn.softplus(made["layers_0/mixer/dt_bias"])
    np.testing.assert_allclose(dt, 0.005, rtol=1e-5)
    # what a state is worth a published chunk later, with A = -1
    chunk = CONFIG["mamba_chunk_size"]
    assert 0.25 < float(jnp.exp(-dt[0] * chunk)) < 0.3
    assert not np.any(np.asarray(made["layers_0/mixer/A_log"]))
    for leaf in ("layers_0/mixer/D", "layers_0/mixer/norm_weight",
                 "layers_1/input_layernorm/weight",
                 "layers_1/post_attention_layernorm/weight", "norm/weight"):
        assert np.all(np.asarray(made[leaf]) == 1), leaf
    assert float(jnp.std(made["layers_0/mixer/conv_bias"])) > 0.005
    for leaf in ("embed_tokens/embedding", "layers_0/mixer/in_proj/kernel",
                 "layers_0/mixer/out_proj/kernel",
                 "layers_1/mixer/o_proj/kernel",
                 "layers_2/mlp/gate_proj/kernel",
                 "layers_2/mlp/down_proj/kernel"):
        assert float(jnp.std(made[leaf])) == \
            pytest.approx(0.02, rel=0.1), leaf


# -- the configuration's file -------------------------------------------------

WIDTHS = ("hidden_size", "intermediate_size", "shared_intermediate_size",
          "mamba_d_head", "mamba_d_state", "mamba_expand", "mamba_n_heads",
          "mamba_n_groups", "mamba_d_conv", "mamba_chunk_size",
          "num_attention_heads", "num_key_value_heads")


def test_the_copied_row_is_the_catalogs():
    if not CATALOG.exists():
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(line) for line in CATALOG.read_text().splitlines()]
    (row,) = [r for r in rows if r["name"] == "granite-4.0-h-micro"]
    assert row["config"] == ROW
    assert row["source_url"] == CONFIG["source"]


def test_the_configuration_is_the_catalogs_row_but_for_what_it_lists():
    reduced = ["num_hidden_layers", "layer_types", "vocab_size"]
    assert CONFIG["reduced"] == reduced
    assert sorted(CONFIG["reduced_why"]) == sorted(reduced)
    assert CONFIG["published"] == {k: ROW[k] for k in reduced}
    for key, value in ROW.items():
        if key not in reduced:
            assert CONFIG[key] == value, key
    assert all(CONFIG[k] == ROW[k] for k in WIDTHS)
    assert CONFIG["layer_types"] == ROW["layer_types"][:10]
    assert CONFIG["num_hidden_layers"] == 10 == len(CONFIG["layer_types"])
    assert CONFIG["vocab_size"] * 8 == ROW["vocab_size"]
    entry = next(c for c in SPEC["configs"]
                 if c["name"] == "granite4_h_micro_l10")
    assert entry["reduced"] == reduced
    assert entry["source"] == CONFIG["source"]


def test_sizes_and_experiment_say_what_the_published_keys_say():
    s, args = CONFIG["sizes"], CONFIG["experiment"]["arch"]["args"]
    assert CONFIG["experiment"]["arch"]["type"] == "GraniteHybrid"
    for key, value in s.items():
        assert args[key] == value, key
    assert args["vocab_published"] == ROW["vocab_size"]
    for ours, theirs in (
            ("layer_types", "layer_types"), ("d_model", "hidden_size"),
            ("d_ff", "shared_intermediate_size"),
            ("vocab_size", "vocab_size"), ("n_head", "num_attention_heads"),
            ("n_kv_head", "num_key_value_heads"),
            ("ssm_n_head", "mamba_n_heads"), ("ssm_head_dim", "mamba_d_head"),
            ("ssm_n_group", "mamba_n_groups"), ("ssm_state", "mamba_d_state"),
            ("ssm_conv", "mamba_d_conv"),
            ("embedding_multiplier", "embedding_multiplier"),
            ("residual_multiplier", "residual_multiplier"),
            ("attention_multiplier", "attention_multiplier"),
            ("logits_scaling", "logits_scaling"),
            ("rms_eps", "rms_norm_eps")):
        assert s[ours] == CONFIG[theirs], ours
    assert s["head_dim"] * s["n_head"] == CONFIG["hidden_size"]
    assert s["ssm_n_head"] * s["ssm_head_dim"] == \
        CONFIG["mamba_expand"] * CONFIG["hidden_size"]
    assert s["ssm_chunk"] in (CONFIG["mamba_chunk_size"], 128)
    exclude = CONFIG["experiment"]["optimizer"]["args"]["weight_decay_exclude"]
    outside = {p for p in ref.param_shapes(s)
               if any(re.search(e, p) for e in exclude)}
    assert {p.split("/")[-1] for p in outside} == {
        "weight", "norm_weight", "A_log", "D", "dt_bias", "conv_bias"}


def test_the_counts_the_yardstick_takes_by_hand():
    s = CONFIG["sizes"]
    mamba = (2048 * 8512 + 4 * 4352 + 4352 + 3 * 64 + 4096 + 4096 * 2048
             + 2048 * 16384 + 8192 * 2048 + 2 * 2048)
    attention = (2 * 2048 * 2048 + 2 * 2048 * 512
                 + 2048 * 16384 + 8192 * 2048 + 2 * 2048)
    assert (mamba, attention) == (76_182_976, 60_821_504)
    assert ref.parameters(s) == 9 * mamba + attention + 12544 * 2048 + 2048 \
        == 772_160_448
    assert ref.matmul_weights(s) == 771_883_008
    scan = 3 * (257 * (128 + 4096) + 4 * 4096 * 128)
    attn = 12 * 32 * 64 * 4096.5
    seq = 8192
    assert ref.mixer_flops_per_token({**s, "ssm_chunk": 256}, seq) == \
        9 * scan + attn
    per_token = flops.model_flops_per_token(ref, {**s, "ssm_chunk": 256}, seq)
    assert per_token == 6 * 771_883_008 + 9 * scan + attn
    assert per_token == pytest.approx(4.82e9, rel=1e-3)


# -- a rehearsal of the new cell ---------------------------------------------

NEW_SCOPES = {"ssm_intra_ms_per_step", "ssm_state_ms_per_step",
              "ssm_proj_ms_per_step", "dense_mlp_ms_per_step"}
# since PR 40 in this cell's line too: the scan's whole scope, and the
# convolution's inside it
SHARED_SCOPES = {"ssm_scan_ms_per_step", "ssm_conv_ms_per_step"}


@pytest.mark.parametrize("trace", [0, 1])
def test_a_rehearsal_of_the_new_cell_ends_in_a_valid_line(trace):
    from benchmarks import lastline

    line, code, said = rehearse(CELL, trace)
    assert code == run.EXIT_REHEARSED != 0
    expected = run.expected_metrics(SPEC, CELL, bool(trace))
    absent = {n for n in expected if n not in line["metrics"]}
    assert absent <= may_lack_on_the_cpu(expected)
    lastline.validate(line, {n: u for n, u in expected.items()
                             if n not in absent}, bool(trace))
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    scopes = NEW_SCOPES | SHARED_SCOPES
    assert (scopes & set(line["metrics"])) == (scopes if trace else set())
    if trace:
        # the convolution's kernel runs on the chip alone: expected of the
        # cell, and absent here by the rule, not by its name
        assert "ssm_conv_bwd_roofline" in expected
        assert "ssm_conv_bwd_roofline" in may_lack_on_the_cpu(expected)


def test_the_new_metrics_belong_to_the_new_cell_alone():
    for name in NEW_SCOPES:
        (entry,) = [m for m in SPEC["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "mfu_pct"
        spec = json.loads(
            (run.BENCH / "layer_metrics" / f"{name}.json").read_text())
        assert spec["reducer"] == "trace_scopes"
    (cell,) = [w for w in SPEC["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1


@pytest.mark.parametrize("name,reducer,layer,later", [
    ("ssm_scan_ms_per_step", "trace_scopes", "compiled step", []),
    ("ssm_conv_ms_per_step", "trace_scopes", "compiled step",
     ["solar_open2_l4.seq8k"]),
    ("ssm_conv_bwd_roofline", "trace_roofline_conv", "kernels",
     ["solar_open2_l4.seq8k"])])
def test_the_readings_both_hybrid_cells_share(name, reducer, layer, later):
    """The scan's scope, the convolution's inside it and the convolution's
    backward kernel are in both hybrid steps and in no dense one; the
    convolution's two since PR 44 in the delta-rule cell too, whose nine
    convolutions are the same function."""
    (entry,) = [m for m in SPEC["per_layer"] if m["name"] == name]
    assert entry["workloads"] == ["nemotron3_super_l11.seq8k", CELL] + later
    assert (entry["layer"], entry["moves"]) == (layer, "mfu_pct")
    spec = json.loads(
        (run.BENCH / "layer_metrics" / f"{name}.json").read_text())
    assert spec["reducer"] == reducer
    for w in SPEC["workloads"]:
        assert (name in run.expected_metrics(SPEC, w["name"], True)) == (
            w["name"] in entry["workloads"])
