"""The `solar_open2` reference (benchmarks/reference/solar_open2.py) and the
configuration `solar_open2_l4`: the program against the reference at a
tiny size on the CPU in float32, each kind of layer alone and a whole
period; the share test (what all the chips' shares give adds up to the
uncut layer); a lower precision in the scan's state or the router fails
the comparison; the counts the yardstick takes from the reference, by
hand; and a rehearsal of the new cell."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import flops, run
from benchmarks.data import make_tokens
from benchmarks.reference import common
from benchmarks.reference import solar_open2 as ref
from benchmarks.weights import Weights
from pytorch_distributed_template_tpu import models  # noqa: F401
from pytorch_distributed_template_tpu.config import MODELS
from pytorch_distributed_template_tpu.engine.losses import lm_cross_entropy
from pytorch_distributed_template_tpu.ops import linear_attention

from test_bm_data import may_lack_on_the_cpu
from test_bm_reference import flat_of, nested
from test_bm_run import SPEC, rehearse

CONFIG = json.loads(
    (run.BENCH / "configs" / "solar_open2_l4.json").read_text())
CELL = "solar_open2_l4.seq8k"
# every kind of size differs from every other, so that a transposed or
# swapped width cannot pass
TINY = dict(
    pattern="*KKK", d_model=48, vocab_size=256, n_head=4, n_kv_head=2,
    head_dim=8, kda_n_head=3, kda_head_dim=12, kda_conv=4, kda_chunk=16,
    kda_rank=10, moe_n_routed=12, moe_held=[3, 5], moe_top_k=4, moe_d_ff=28,
    moe_shared_d_ff=36, moe_scale=1.0, rms_eps=1e-5)
# float32 against float32 on the CPU, two schedules of one sum (chunks
# against positions, a mask against a top-k): rounding alone. The same
# tolerances as the nemotron_h tests; a bfloat16 state or router reads
# hundreds of times more (the last tests of this file)
LOSS_RTOL, GRAD_ATOL = 2e-6, 2e-4


def model_of(a):
    return MODELS.get("SolarOpen2")(
        **a, max_len=128, bfloat16=False, attn_impl="xla", remat=False,
        fused_head=False)


def program_loss_and_grads(a, params, tokens):
    model = model_of(a)

    def loss(flat):
        logits = model.apply({"params": nested(flat)}, jnp.asarray(tokens),
                             train=True)
        return jnp.mean(lm_cross_entropy(logits, jnp.asarray(tokens)))

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss)(params)


def worst_gap(grads, want):
    return max(float(jnp.max(jnp.abs(grads[p] - want[p])))
               / (float(jnp.max(jnp.abs(want[p]))) + 1e-12) for p in want)


@pytest.mark.parametrize("pattern", ["K", "*", "*KKK"])
def test_reference_matches_the_programs_model(pattern):
    """Loss and every leaf's gradient, 40 tokens a row: the chunk of 16
    does not divide them, and the scan runs over two and a half chunks."""
    a = {**TINY, "pattern": pattern}
    weights = Weights(ref.param_shapes(a), ref.init_rules(a), 2**31 + 7)
    params = weights.make()
    tokens = make_tokens(3, 4, 40, a["vocab_size"])
    shapes = jax.eval_shape(lambda: model_of(a).init(
        jax.random.key(0), jnp.zeros((1, 40), jnp.int32)))
    assert {k: v.shape for k, v in flat_of(shapes["params"]).items()} == \
        weights.shapes
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


@pytest.mark.parametrize("broken", ["state", "router"])
def test_a_lower_precision_where_float32_is_stated_fails(broken, monkeypatch):
    """The state between chunks or the router's product in bfloat16: the
    comparison above then fails by its gradient tolerance."""
    a = {**TINY, "pattern": "*KKK"}
    weights = Weights(ref.param_shapes(a), ref.init_rules(a), 2**31 + 7)
    params = weights.make()
    tokens = make_tokens(3, 4, 40, a["vocab_size"])
    _, grads = common.Follower(ref, a).loss_and_grads(params, tokens, 2)
    if broken == "state":
        monkeypatch.setattr(linear_attention, "F32", jnp.bfloat16)
    else:
        matmul = jnp.matmul

        def rounded(x, w, precision=None):
            if precision is None:
                return matmul(x, w)
            return matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
                          ).astype(jnp.float32)

        monkeypatch.setattr(jnp, "matmul", rounded)
    _, got = program_loss_and_grads(a, params, tokens)
    assert worst_gap(got, grads) > 20 * GRAD_ATOL


# -- the share test ----------------------------------------------------------

WHOLE = dict(TINY, pattern="K", n_head=4, n_kv_head=2, kda_n_head=4,
             moe_held=[0, 0])


def layer_out(a, p, x):
    with jax.default_matmul_precision("highest"):
        return ref.layer(a, p, x, common.DOTS["f32"])


def one_layer(a, seed=11):
    weights = Weights(ref.param_shapes(a), ref.init_rules(a), seed)
    made = weights.make()
    p = common.layer_params(made, "layers_0")
    # decays, steps and biases away from the constants of the init rules
    key = jax.random.key(seed)
    for i, (name, lo, hi) in enumerate((
            ("mixer/A_log", 0.0, 2.0), ("mixer/dt_bias", -5.0, -1.0),
            ("mixer/g_b_proj/bias", -1.0, 1.0), ("mixer/o_norm", 0.5, 1.5),
            ("experts/selection_bias", -0.2, 0.2))):
        if name in p:
            p[name] = jax.random.uniform(
                jax.random.fold_in(key, i), p[name].shape, jnp.float32,
                lo, hi)
    x = jax.random.normal(jax.random.fold_in(key, 99),
                          (2, 24, a["d_model"]), jnp.float32)
    return p, x


def columns(w, head, size):
    return w[..., head * size:(head + 1) * size]


def test_the_head_shares_of_a_kda_mixer_add_up_to_the_whole():
    a = dict(WHOLE, pattern="K")
    p, x = one_layer(a)
    hp = a["kda_head_dim"]
    u = ref._rms_norm(x, p["input_layernorm/weight"], a["rms_eps"])
    with jax.default_matmul_precision("highest"):
        whole = ref._kda(a, p, u, common.DOTS["f32"])
        total = 0.0
        for h in range(a["kda_n_head"]):
            share = dict(p)
            for name in ("q_proj/kernel", "k_proj/kernel", "v_proj/kernel",
                         "q_conv", "k_conv", "v_conv", "f_b_proj/kernel",
                         "dt_bias", "g_b_proj/kernel", "g_b_proj/bias"):
                share[f"mixer/{name}"] = columns(p[f"mixer/{name}"], h, hp)
            share["mixer/A_log"] = p["mixer/A_log"][h:h + 1]
            share["mixer/b_proj/kernel"] = p["mixer/b_proj/kernel"][:, h:h + 1]
            share["mixer/o_proj/kernel"] = \
                p["mixer/o_proj/kernel"][h * hp:(h + 1) * hp]
            total = total + ref._kda(dict(a, kda_n_head=1), share, u,
                                     common.DOTS["f32"])
    np.testing.assert_allclose(total, whole, rtol=0,
                               atol=2e-6 * float(jnp.max(jnp.abs(whole))))


def test_the_head_shares_of_the_gated_attention_add_up_to_the_whole():
    a = dict(WHOLE, pattern="*")
    p, x = one_layer(a)
    hd, group = a["head_dim"], a["n_head"] // a["n_kv_head"]
    u = ref._rms_norm(x, p["input_layernorm/weight"], a["rms_eps"])
    with jax.default_matmul_precision("highest"):
        whole = ref._attend(a, p, u, common.DOTS["f32"])
        total = 0.0
        for kv in range(a["n_kv_head"]):    # a key-value head and its queries
            share = dict(p)
            for name in ("q_proj/kernel", "g_proj/kernel"):
                share[f"mixer/{name}"] = columns(p[f"mixer/{name}"], kv,
                                                 group * hd)
            for name in ("k_proj/kernel", "v_proj/kernel"):
                share[f"mixer/{name}"] = columns(p[f"mixer/{name}"], kv, hd)
            share["mixer/o_proj/kernel"] = p["mixer/o_proj/kernel"][
                kv * group * hd:(kv + 1) * group * hd]
            total = total + ref._attend(
                dict(a, n_head=group, n_kv_head=1), share, u,
                common.DOTS["f32"])
    np.testing.assert_allclose(total, whole, rtol=0,
                               atol=2e-6 * float(jnp.max(jnp.abs(whole))))


def test_the_expert_shares_add_up_to_the_whole_layer():
    """Three chips hold 4 of the 12 routed experts each; every chip
    computes the shared expert, which is counted once."""
    a = dict(WHOLE, pattern="K")
    p, x = one_layer(a)
    dot = common.DOTS["f32"]
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(a, p, x, dot)
        shared = ref._swiglu(x, p["experts/shared/gate_proj/kernel"],
                             p["experts/shared/up_proj/kernel"],
                             p["experts/shared/down_proj/kernel"], dot)
        total = shared
        for lo in (0, 4, 8):
            share = dict(p)
            for name in ("experts_gate", "experts_up", "experts_down"):
                share[f"experts/{name}"] = p[f"experts/{name}"][lo:lo + 4]
            total = total + ref.experts(dict(a, moe_held=[lo, 4]), share,
                                         x, dot) - shared
    np.testing.assert_allclose(total, whole, rtol=0,
                               atol=4e-6 * float(jnp.max(jnp.abs(whole))))
    # and the program's layer gives its share as the reference's does
    held = dict(a, moe_held=[4, 4])
    share = dict(p)
    for name in ("experts_gate", "experts_up", "experts_down"):
        share[f"experts/{name}"] = p[f"experts/{name}"][4:8]
    from pytorch_distributed_template_tpu.models.moe import ExpertLayer
    layer = ExpertLayer(
        d_model=a["d_model"], d_ff=a["moe_d_ff"], n_routed=12, top_k=4,
        held=(4, 4), shared_d_ff=a["moe_shared_d_ff"], selection_bias=True,
        gated=True)
    mine = nested({k[len("experts/"):]: v for k, v in share.items()
                   if k.startswith("experts/")})
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": mine}, x)
        want = ref.experts(held, share, x, dot)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=4e-6 * float(jnp.max(jnp.abs(want))))


# -- the counts, by hand -----------------------------------------------------


def test_parameters_and_matmul_weights_by_hand():
    a = CONFIG["sizes"]
    kda = (3 * 4096 * 1024 + 3 * 4 * 1024 + 4096 * 128 + 128 * 1024 + 1024
           + 8 + 4096 * 8 + 4096 * 128 + 128 * 1024 + 1024 + 128
           + 1024 * 4096)
    attn = 2 * 4096 * 1024 + 2 * 4096 * 128 + 1024 * 4096
    experts = 4096 * 320 + 320 + 3 * 4096 * 1280 + 8 * 3 * 4096 * 1280
    assert (kda, attn, experts) == (18135176, 13631488, 142868800)
    period = attn + 3 * kda + 4 * (experts + 2 * 4096)
    assert period == 639544984
    assert ref.parameters(a) == period + 2 * 24576 * 4096 + 4096 == 840875672
    assert "840,875,672 parameters" in CONFIG["deployment"]
    kda_mm = 4 * 4096 * 1024 + 2 * (4096 * 128 + 128 * 1024) + 4096 * 8
    experts_mm = 4096 * 320 + 3 * 4096 * 1280 + 8 * 3 * 4096 * 1280 * 8 // 320
    assert ref.matmul_weights(a) == attn + 3 * kda_mm + 4 * experts_mm \
        + 4096 * 24576 == 249397248
    mixers = 12 * 8 * 128 * 4096.5 + 3 * 18 * 8 * 128 * 128
    assert ref.mixer_flops_per_token(a, 8192) == mixers
    per_token = flops.model_flops_per_token(ref, a, 8192)
    assert per_token == 6 * 249397248 + mixers
    assert 1.55e9 < per_token < 1.56e9


def test_the_file_holds_the_published_widths_and_the_share():
    catalog = {"hidden_size": 4096, "head_dim": 128,
               "moe_intermediate_size": 1280, "intermediate_size": 10240,
               "num_experts_per_tok": 8, "n_shared_experts": 1,
               "rms_norm_eps": 1e-05, "first_k_dense_replace": 0,
               "use_rope": False, "use_gqa_gate": True,
               "kda_allow_neg_eigval": True, "kda_use_full_proj": False,
               "tie_word_embeddings": False, "routed_scaling_factor": 1}
    for key, value in catalog.items():
        assert CONFIG[key] == value, key
    assert CONFIG["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 8,
        "num_kv_heads": None}
    assert CONFIG["published"]["linear_attn_config"]["num_heads"] == 64
    assert CONFIG["published"]["gqa_layers"] == list(range(0, 48, 4))
    assert (CONFIG["num_hidden_layers"], CONFIG["gqa_layers"]) == (4, [0])
    assert set(CONFIG["reduced"]) == set(CONFIG["published"])
    s = CONFIG["sizes"]
    assert s["pattern"] == "*KKK" and s["moe_held"] == [0, 8]
    assert (s["d_model"], s["moe_d_ff"], s["moe_shared_d_ff"],
            s["kda_head_dim"], s["head_dim"], s["kda_rank"]) == \
        (4096, 1280, 1280, 128, 128, 128)
    assert (s["moe_n_routed"], s["moe_top_k"]) == (320, 8)
    assert "40 chips share each layer" in CONFIG["deployment"]
    for word in ("kda", "gated_attention", "experts", "init_rules",
                 "optimizer", "selection_bias"):
        assert CONFIG["assumed"][word]


def test_init_rules_cover_every_leaf_and_give_the_scan_a_carry():
    shapes = ref.param_shapes(TINY)
    made = Weights(shapes, ref.init_rules(TINY), 5).make()
    assert set(made) == set(shapes)
    step = jax.nn.softplus(made["layers_1/mixer/dt_bias"])
    np.testing.assert_allclose(step, 0.01, rtol=1e-5)
    # a chunk of 64 positions decays a state to exp(-0.64)
    assert np.exp(-64 * 0.01) == pytest.approx(0.527, abs=1e-3)
    for leaf in ("layers_1/mixer/A_log", "layers_1/mixer/g_b_proj/bias",
                 "layers_0/experts/selection_bias"):
        assert not np.any(np.asarray(made[leaf])), leaf
    for leaf in ("layers_1/mixer/o_norm", "layers_0/input_layernorm/weight",
                 "layers_2/post_attention_layernorm/weight", "norm/weight"):
        assert np.all(np.asarray(made[leaf]) == 1), leaf
    for leaf in ("embed_tokens/embedding", "lm_head/kernel",
                 "layers_1/mixer/q_conv", "layers_1/mixer/f_b_proj/kernel",
                 "layers_0/mixer/g_proj/kernel", "layers_0/experts/router",
                 "layers_3/experts/experts_gate",
                 "layers_3/experts/shared/down_proj/kernel"):
        assert float(jnp.std(made[leaf])) == \
            pytest.approx(0.02, rel=0.15), leaf
    # the optimizer's exclusions name leaves that exist
    import re
    for pattern in CONFIG["experiment"]["optimizer"]["args"][
            "weight_decay_exclude"]:
        assert any(re.search(pattern, path) for path in shapes), pattern


# -- a rehearsal of the new cell ---------------------------------------------

SCOPES = {"kda_scan_ms_per_step", "kda_intra_ms_per_step",
          "kda_state_ms_per_step", "kda_proj_ms_per_step",
          "gated_attn_ms_per_step"}
COUNTERS = {"kda_chunk_log_decay_mean", "kda_beta_mean"}
# what the step shares with the older cells (PR 44): the flash kernels of
# its one attention layer, the nine KDA convolutions, the expert layer
SHARED = {"flash_ms_per_step", "flash_roofline_pct", "ssm_conv_ms_per_step",
          "ssm_conv_bwd_roofline", "moe_route_ms_per_step",
          "moe_experts_ms_per_step", "moe_shared_ms_per_step",
          "moe_pairs_per_step", "moe_load_max_over_mean"}


def test_the_new_metrics_are_the_new_cells_alone():
    for w in SPEC["workloads"]:
        expected = set(run.expected_metrics(SPEC, w["name"], True))
        assert (SCOPES | COUNTERS <= expected) == (w["name"] == CELL)
        if w["name"] != CELL:
            assert not (SCOPES | COUNTERS) & expected
    # none of them reads a kernel's own events: a rehearsal reports all
    assert not may_lack_on_the_cpu(SCOPES | COUNTERS)
    # PR 44, a `benchmark` PR, put the cell on the lists of the nine
    # readings its step already ran, and on no other of the older ones
    for m in SPEC["per_layer"]:
        if m["name"].startswith(("flash_", "ssm_", "moe_")):
            assert (CELL in m["workloads"]) == (m["name"] in SHARED), m["name"]


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
    if trace:
        assert SCOPES | COUNTERS <= set(line["metrics"])
        # weights from the init rules: a step of 0.01, a rate of 1, a
        # chunk of 32 at the rehearsal's sizes; beta = 2 sigmoid(small)
        decay = line["metrics"]["kda_chunk_log_decay_mean"]["value"]
        assert decay == pytest.approx(-0.32, rel=0.05)
        assert line["metrics"]["kda_beta_mean"]["value"] == \
            pytest.approx(1.0, abs=0.05)
        # the shared readings: all but the two kernels' own, which only
        # the chip's trace has
        assert SHARED <= set(expected)
        assert SHARED - set(line["metrics"]) == may_lack_on_the_cpu(SHARED)
        # 128 tokens, 4 of 16 experts a token, 4 held, 3 layers, the
        # biases solved on the first batch: 32 a held expert, give or
        # take a batch's sampling noise
        pairs = line["metrics"]["moe_pairs_per_step"]["value"]
        assert 0.75 * 384 < pairs < 1.25 * 384
        assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] < 1.6
        (solved,) = [s for s in said if s.startswith("balance: ")]
        assert all(f"layers_{k} " in solved for k in range(3))
    else:
        assert not (SCOPES | COUNTERS | SHARED) & set(line["metrics"])
