"""The `nemotron_h` reference (benchmarks/reference/nemotron_h.py) and the
configuration `nemotron3_super_l11`: the program against the reference at
a tiny size on the CPU in float32, each kind of layer alone and a whole
period; the share test (what all the chips' shares give adds up to the
uncut layer); the counts the yardstick takes from the reference, by hand;
and a rehearsal of both new cells."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run
from benchmarks.data import make_tokens
from benchmarks.reference import common
from benchmarks.reference import nemotron_h as ref
from benchmarks.weights import Weights
from pytorch_distributed_template_tpu import models  # noqa: F401
from pytorch_distributed_template_tpu.config import MODELS
from pytorch_distributed_template_tpu.engine.losses import lm_cross_entropy

from test_bm_data import may_lack_on_the_cpu
from test_bm_reference import flat_of, nested
from test_bm_run import SPEC, rehearse

CONFIG = json.loads(
    (run.BENCH / "configs" / "nemotron3_super_l11.json").read_text())
CELL = "nemotron3_super_l11.seq8k"
# every kind of size differs from every other, so that a transposed or
# swapped width cannot pass
TINY = dict(
    pattern="EMEMEMEMEM*", d_model=48, vocab_size=256, n_head=4, n_kv_head=2,
    head_dim=8, ssm_n_head=4, ssm_head_dim=6, ssm_n_group=2, ssm_state=10,
    ssm_conv=4, ssm_chunk=16, moe_n_routed=12, moe_held=[3, 5], moe_top_k=4,
    moe_latent=20, moe_d_ff=28, moe_shared_d_ff=36, moe_scale=2.5,
    rms_eps=1e-5)


def model_of(a):
    return MODELS.get("NemotronH")(
        **a, max_len=128, bfloat16=False, attn_impl="xla", remat=False,
        fused_head=False)


@pytest.mark.parametrize("pattern", ["E", "M", "*", "EMEMEMEMEM*"])
def test_reference_matches_the_programs_model(pattern):
    """Loss and every leaf's gradient, 40 tokens a row: the chunk of 16
    does not divide them, and the scan runs over two and a half chunks."""
    a = {**TINY, "pattern": pattern}
    weights = Weights(ref.param_shapes(a), ref.init_rules(a), 2**31 + 7)
    params = weights.make()
    tokens = make_tokens(3, 4, 40, a["vocab_size"])
    model = model_of(a)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 40), jnp.int32)))
    assert {k: v.shape for k, v in flat_of(shapes["params"]).items()} == \
        weights.shapes

    def program_loss(flat):
        logits = model.apply({"params": nested(flat)}, jnp.asarray(tokens),
                             train=True)
        return jnp.mean(lm_cross_entropy(logits, jnp.asarray(tokens)))

    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(program_loss)(params)
    loss, grads = common.Follower(ref, a).loss_and_grads(params, tokens, 2)
    assert loss == pytest.approx(float(want_loss), rel=2e-6)
    for path in want:
        scale = float(jnp.max(jnp.abs(want[path]))) + 1e-12
        np.testing.assert_allclose(grads[path], want[path], rtol=0,
                                   atol=2e-4 * scale, err_msg=path)
    for path in want:       # no gradient reaches the selection bias
        if path.endswith("selection_bias"):
            assert not np.any(np.asarray(grads[path]))
            assert not np.any(np.asarray(want[path]))


def test_init_rules_give_the_scan_a_carry_that_matters():
    rules = Weights(ref.param_shapes(TINY), ref.init_rules(TINY), 5)
    made = rules.make()
    dt = jax.nn.softplus(made["layers_1/mixer/dt_bias"])
    np.testing.assert_allclose(dt, 0.01, rtol=1e-5)
    assert not np.any(np.asarray(made["layers_1/mixer/A_log"]))
    assert np.all(np.asarray(made["layers_1/mixer/D"]) == 1)
    assert np.all(np.asarray(made["layers_1/mixer/norm_weight"]) == 1)
    assert np.all(np.asarray(made["layers_0/norm/weight"]) == 1)
    assert not np.any(np.asarray(made["layers_0/mixer/selection_bias"]))
    assert float(jnp.std(made["layers_1/mixer/conv_bias"])) > 0.005
    # the published initializer_range everywhere else, as ISSUE 33 fixed
    assert CONFIG["initializer_range"] == 0.02
    for leaf in ("embed_tokens/embedding", "lm_head/kernel",
                 "layers_1/mixer/out_proj/kernel",
                 "layers_10/mixer/o_proj/kernel",
                 "layers_0/mixer/shared_down/kernel",
                 "layers_0/mixer/latent_up/kernel",
                 "layers_0/mixer/router", "layers_0/mixer/experts_down"):
        assert float(jnp.std(made[leaf])) == \
            pytest.approx(0.02, rel=0.1), leaf
    assert CONFIG["moe_latent_size"] == 1024 and CONFIG["expand"] == 2
    assert CONFIG["num_experts_per_tok"] == 22


# -- rehearsals of the new cells --------------------------------------------

NEW_SCOPES = {"ssm_scan_ms_per_step", "moe_route_ms_per_step",
              "moe_experts_ms_per_step", "moe_shared_ms_per_step"}
NEW_COUNTERS = {"moe_pairs_per_step", "moe_load_max_over_mean"}
# PR 40: the convolution's scope, in both hybrid cells' lines and in no
# dense cell's; its backward kernel's roofline share, on the chip alone
CONV = {"ssm_conv_ms_per_step", "ssm_conv_bwd_roofline"}


@pytest.mark.parametrize("workload,trace", [
    (CELL, 0), (CELL, 1), ("mistral7b_l2.seq4k", 0),
    ("mistral7b_l2.seq4k", 1)])
def test_a_rehearsal_of_a_new_cell_ends_in_a_valid_line(workload, trace):
    from benchmarks import lastline

    line, code, said = rehearse(workload, trace)
    assert code == run.EXIT_REHEARSED != 0
    expected = run.expected_metrics(SPEC, workload, bool(trace))
    absent = {n for n in expected if n not in line["metrics"]}
    assert absent <= may_lack_on_the_cpu(expected)
    lastline.validate(line, {n: u for n, u in expected.items()
                             if n not in absent}, bool(trace))
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    new = (NEW_SCOPES | NEW_COUNTERS) & set(line["metrics"])
    if trace and workload == CELL:
        assert new == NEW_SCOPES | NEW_COUNTERS
        # 128 tokens x 2 rows, 6 of 16 experts a token, 4 held, 2 layers,
        # the biases solved on the first batch (PR 44): 96 a held expert,
        # give or take a batch's sampling noise
        pairs = line["metrics"]["moe_pairs_per_step"]["value"]
        assert 0.85 * 768 < pairs < 1.15 * 768
        assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] < 1.4
        (solved,) = [s for s in said if s.startswith("balance: ")]
        assert "layers_0 " in solved and "layers_2 " in solved
    else:
        assert not new
        # a cell without experts says nothing of a solve
        assert (workload == CELL) == any(
            s.startswith("balance: ") for s in said)
    assert (CONV <= set(expected)) == bool(trace and workload == CELL)
    assert ("ssm_conv_ms_per_step" in line["metrics"]) == bool(
        trace and workload == CELL)
    assert "ssm_conv_bwd_roofline" not in line["metrics"]    # no TPU here
