"""The blocks' checkpoint policy (models/remat_policy.py): what fits is kept.

CPU only: the choosing arithmetic over hand-made byte tables, the two
families with a capacity supplied (the CPU reports none, which means
today's ``nothing_saveable``), the jaxpr that shows what the backward no
longer recomputes, a data-parallel mesh against one device, what the
training step says it holds (accumulation, shadow weights, the optimizer's
own state), and the ``remat/policy`` record. Nothing here is a time or a
rate.
"""
import contextlib
import logging

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import pytorch_distributed_template_tpu.models  # noqa: F401
from pytorch_distributed_template_tpu.config.registry import MODELS
from pytorch_distributed_template_tpu.engine.state import create_train_state
from pytorch_distributed_template_tpu.engine.steps import make_train_step
from pytorch_distributed_template_tpu.models import remat_policy as rp
from pytorch_distributed_template_tpu.observability import trace
from pytorch_distributed_template_tpu.observability.trace import get_recorder
from pytorch_distributed_template_tpu.ops.flash import named_residual_bytes
from pytorch_distributed_template_tpu.parallel.mesh import build_mesh
from pytorch_distributed_template_tpu.parallel.sharding import batch_sharding

GIB = 1 << 30
# one Mistral-7B layer at 1 x 8192 tokens in bfloat16, and one GPT-2-large
# layer at 8 x 1024: the tables the benchmark's cells reckon
MISTRAL = {"attn_out": 67108864, "attn_lse": 1048576, "qkv_proj": 100663296,
           "attn_proj": 67108864, "mlp_gate": 234881024,
           "mlp_up": 234881024, "attn_qkv": 201326592}
GPT2_LARGE = {"attn_out": 41943040, "attn_lse": 655360,
              "qkv_proj": 62914560, "attn_proj": 20971520,
              "mlp_up": 83886080, "attn_qkv": 125829120}
ATTN = ("attn_out", "attn_lse")
MATMULS = ("qkv_proj", "attn_proj", "mlp_gate", "mlp_up")


@pytest.mark.parametrize("table,blocks,budget,want", [
    (MISTRAL, 2, 0, ()),
    (MISTRAL, 2, -GIB, ()),
    (MISTRAL, 2, 2 * 68157440 - 1, ()),
    (MISTRAL, 2, 2 * 68157440, ATTN),
    (MISTRAL, 2, 2 * GIB, ATTN + MATMULS + ("attn_qkv",)),
    (MISTRAL, 2, GIB + GIB // 2, ATTN + MATMULS),
    (MISTRAL, 2, GIB, ATTN + MATMULS[:3]),
    (GPT2_LARGE, 36, 2 * GIB, ATTN),
    (GPT2_LARGE, 36, 5 * GIB, ATTN + ("qkv_proj", "attn_proj")),
    (GPT2_LARGE, 36, 12 * GIB, ATTN + ("qkv_proj", "attn_proj", "mlp_up",
                                       "attn_qkv")),
    # the XLA attention has no log-sum-exp and no kernel operands, a GELU
    # MLP no gate
    ({"attn_out": 10, "qkv_proj": 10, "mlp_up": 10}, 3, 90,
     ("attn_out", "qkv_proj", "mlp_up")),
], ids=["empty", "negative", "one-byte-short", "attention-only", "all",
        "all-but-operands", "all-but-up", "gpt2-attention", "gpt2-part",
        "gpt2-all", "absent-names"])
def test_choose_names_is_a_prefix_that_fits(table, blocks, budget, want):
    got = rp.choose_names([(table, blocks)], budget)
    assert got == want
    assert blocks * sum(table[n] for n in got) <= max(budget, 0)
    # the same answer on every call, whatever the table's order
    assert rp.choose_names([(dict(reversed(table.items())), blocks)],
                           budget) == got


def test_preference_stops_at_the_first_group_that_does_not_fit():
    # the up projection would fit where the larger gate does not: a
    # prefix, not a knapsack, so that a smaller budget never keeps what a
    # larger one leaves out
    table = {"attn_out": 1, "qkv_proj": 1, "attn_proj": 1, "mlp_gate": 100,
             "mlp_up": 1}
    assert rp.choose_names([(table, 1)], 50) == ("attn_out", "qkv_proj",
                                                 "attn_proj")


def test_attention_output_is_kept_with_its_log_sum_exp_or_not_at_all():
    table = {"attn_out": 10, "attn_lse": 2, "qkv_proj": 1}
    assert rp.choose_names([(table, 1)], 11) == ()
    assert rp.choose_names([(table, 1)], 12) == ATTN


def test_empty_choice_is_todays_policy():
    assert rp.policy_of(()) is jax.checkpoint_policies.nothing_saveable
    assert rp.policy_of(ATTN) is not jax.checkpoint_policies.nothing_saveable


def test_budget_is_capacity_less_what_is_held_and_margin():
    # GPT-2-large's own figures: 774 M float32 parameters and AdamW's two
    # moments held by the step, wte and wpe (51.5 M) outside the blocks,
    # 36 inputs of 8 x 1024 x 1280
    args = dict(held_bytes=3 * 3096120320, outside_param_bytes=262568960,
                block_input_bytes=20971520, head_bytes=2 * 20971520,
                blocks=[(GPT2_LARGE, 36)])
    budget = rp.budget_bytes(16_900_000_000, **args)
    assert budget == (16_900_000_000 - 3 * 3096120320 - 262568960
                      - 36 * 20971520 - 2 * 20971520
                      - 2 * sum(GPT2_LARGE.values()) - rp.HEADROOM_BYTES)
    assert rp.budget_bytes(17_900_000_000, **args) == budget + 10 ** 9
    assert rp.choose_names([(GPT2_LARGE, 36)], budget) == ATTN + (
        "qkv_proj", "attn_proj")
    # shadow weights beside them leave room for the attention's output
    # alone; accumulation's gradient sum and micro-batch gradient for none
    for copies, want in ((1, ATTN), (2, ())):
        held = args["held_bytes"] + copies * 3096120320
        assert rp.choose_names([(GPT2_LARGE, 36)], rp.budget_bytes(
            16_900_000_000, **{**args, "held_bytes": held})) == want


@pytest.mark.parametrize("shape,table", [
    ((1, 8192, 32, 128), MISTRAL), ((8, 1024, 20, 64), GPT2_LARGE),
], ids=["mistral", "gpt2-large"])
def test_flash_reckons_its_own_residuals(shape, table):
    """ops/flash.py says what its named residuals weigh: the head size
    padded to the lanes, the tokens to the blocks, float32 log-sum-exp."""
    got = named_residual_bytes(*shape, jnp.bfloat16)
    assert got == {n: table[n] for n in ("attn_out", "attn_lse", "attn_qkv")}
    b, t, h, d = shape
    longer = named_residual_bytes(b, t + 1, h, d, jnp.bfloat16)
    assert longer["attn_lse"] > got["attn_lse"]
    assert longer["attn_lse"] % 512 == 0        # whole blocks of tokens


@pytest.mark.parametrize("axes,batch,seq_sharded,want", [
    (None, 4, False, 1),
    ({"data": 4}, 4, False, 4),
    ({"data": 2, "fsdp": 2}, 8, False, 4),
    ({"data": 4}, 1, False, 1),            # a batch no axis divides
    ({"data": 2, "seq": 2}, 2, True, 4),
    ({"data": 2, "seq": 2}, 2, False, 2),  # seq axis, attention not SP
    ({"data": 2, "tensor": 2}, 2, False, 2),    # reckoned whole over tensor
], ids=["no-mesh", "dp4", "dp2-fsdp2", "indivisible", "dp2-sp2",
        "dp2-seq-unused", "dp2-tp2"])
def test_tokens_are_reckoned_per_device(axes, batch, seq_sharded, want):
    mesh = build_mesh(axes, devices=jax.devices()[:int(np.prod(
        list(axes.values())))]) if axes else None
    assert rp.token_shards(mesh, batch, 8192, seq_sharded) == want


def _tokens(b=2, t=32):
    return jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (b, t)), jnp.int32)


def _loss_fn(model, tokens, held=0):
    """A loss whose gradient is taken as a training step takes it: inside
    ``step_holds`` (``held=None``: outside it)."""
    def loss(params):
        with (rp.step_holds(held) if held is not None
              else contextlib.nullcontext()):
            out = model.apply({"params": params}, tokens, train=True,
                              rngs={"dropout": jax.random.key(2)})
        return jnp.mean(out.astype(jnp.float32) ** 2)
    return loss


@pytest.fixture
def capacity(monkeypatch):
    """Supply the capacity the CPU does not report (item 4 of ISSUE 27:
    patch the one function that reads it)."""
    def supply(n):
        monkeypatch.setattr(rp, "device_capacity_bytes", lambda mesh=None: n)
    trace._said.clear()
    get_recorder().clear()
    return supply


FAMILIES = [("TinyLlama", 6), ("TinyLM", 3)]    # matmuls a block spared


@pytest.mark.parametrize("family,matmuls", FAMILIES)
@pytest.mark.parametrize("attn_impl", ["xla", "flash"])
def test_kept_values_change_no_arithmetic(capacity, family, matmuls,
                                          attn_impl):
    """With everything kept, loss and gradients equal those of
    ``remat=False`` and of ``nothing_saveable``, and the backward's jaxpr
    recomputes ``matmuls`` fewer dots a block and one attention fewer."""
    tokens = _tokens()
    plain = MODELS.get(family)(remat=False, attn_impl=attn_impl)
    remat = MODELS.get(family)(remat=True, attn_impl=attn_impl)
    params = jax.jit(plain.init)(jax.random.key(1), tokens)["params"]

    def run(model):
        fn = jax.value_and_grad(_loss_fn(model, tokens))
        text = str(jax.make_jaxpr(fn)(params))
        return jax.jit(fn)(params), text.count("dot_general"), text.count(
            "pallas_call")

    capacity(None)                      # the CPU: nothing kept
    (l_none, g_none), dots_none, kernels_none = run(remat)
    capacity(64 * GIB)
    (l_kept, g_kept), dots_kept, kernels_kept = run(remat)
    (l_plain, g_plain), _, _ = run(plain)
    for other_l, other_g in ((l_none, g_none), (l_plain, g_plain)):
        np.testing.assert_allclose(l_kept, other_l, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g_kept), jax.tree.leaves(other_g)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    n_layer = 2
    if attn_impl == "flash":
        # the forward kernel once a layer, no longer twice
        assert kernels_none - kernels_kept == n_layer
        # (the kernel's own two dots are printed with each of its calls)
        assert dots_none - dots_kept == n_layer * (matmuls + 2)
    else:
        # the XLA attention keeps its output: its second einsum goes too
        assert kernels_none == kernels_kept == 0
        assert dots_none - dots_kept == n_layer * (matmuls + 1)


@pytest.mark.parametrize("family", ["TinyLlama", "TinyLM"])
def test_unknown_capacity_is_todays_policy(capacity, family, caplog):
    """``memory_stats()`` None (the CPU, unpatched): ``nothing_saveable``,
    no record, and the whole block recomputed."""
    assert rp.device_capacity_bytes() is None
    tokens = _tokens()
    model = MODELS.get(family)(remat=True)
    params = jax.jit(model.init)(jax.random.key(1), tokens)["params"]
    with caplog.at_level(logging.INFO, logger=rp.logger.name):
        text = str(jax.make_jaxpr(jax.grad(_loss_fn(model, tokens)))(params))
    assert "policy=None" in text or "nothing_saveable" in text
    assert not [e for e in get_recorder().snapshot()
                if e["name"] == "remat/policy"]
    assert not caplog.records


def _record():
    events = [e for e in get_recorder().snapshot()
              if e["name"] == "remat/policy"]
    return [e["args"] for e in events]


@pytest.mark.parametrize("family", ["TinyLlama", "TinyLM"])
def test_policy_record_and_determinism(capacity, family, caplog):
    """One INFO line and one ``remat/policy`` span a choice: names, bytes
    kept a block and in all, budget, capacity, blocks. A second build in
    the process chooses the same and adds nothing."""
    capacity(64 * GIB)
    tokens = _tokens()
    with caplog.at_level(logging.INFO, logger=rp.logger.name):
        for _ in range(2):
            model = MODELS.get(family)(remat=True, attn_impl="flash")
            params = jax.jit(model.init)(jax.random.key(1), tokens)["params"]
            jax.make_jaxpr(jax.grad(_loss_fn(model, tokens)))(params)
    (rec,) = _record()
    assert rec["names"].split(",")[:2] == ["attn_out", "attn_lse"]
    assert rec["blocks"] == 2
    assert rec["kept_bytes"] == 2 * rec["kept_bytes_per_block"] > 0
    assert rec["capacity_bytes"] == 64 * GIB
    assert rec["kept_bytes"] <= rec["budget_bytes"] < rec["capacity_bytes"]
    assert rec["held_bytes"] == 0           # as _loss_fn said
    (line,) = [r.getMessage() for r in caplog.records]
    assert "remat/policy" in line and rec["names"] in line
    # evaluation and init take no gradient and choose nothing
    trace._said.clear()
    get_recorder().clear()
    model.apply({"params": params}, tokens, train=False)
    assert not _record()


@pytest.mark.parametrize("family", ["TinyLlama", "TinyLM"])
def test_tight_capacity_keeps_a_prefix(capacity, family):
    """A capacity that leaves room for the attention output alone keeps it
    alone, and the gradients still agree."""
    tokens = _tokens()
    model = MODELS.get(family)(remat=True)
    plain = MODELS.get(family)(remat=False)
    params = jax.jit(plain.init)(jax.random.key(1), tokens)["params"]
    capacity(64 * GIB)
    jax.make_jaxpr(jax.grad(_loss_fn(model, tokens)))(params)
    (full,) = _record()
    attn_bytes = 2 * 2 * 32 * 64 * 4        # blocks x [2, 32, 64] float32
    capacity(64 * GIB - full["budget_bytes"] + attn_bytes)
    got = jax.jit(jax.grad(_loss_fn(model, tokens)))(params)
    assert [r["names"] for r in _record()][-1] == "attn_out"
    want = jax.jit(jax.grad(_loss_fn(plain, tokens)))(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("family", ["TinyLlama", "TinyLM"])
def test_data_parallel_mesh_picks_what_one_device_picks(capacity, family):
    """Global batch 4 over ``data: 4`` reckons one row a device: the names
    and the bytes a device keeps are those of one device at batch 1 (the
    four-chip cell against its one-chip twin)."""
    tokens1, tokens4 = _tokens(b=1), _tokens(b=4)
    one = MODELS.get(family)(remat=True, attn_impl="flash")
    params = jax.jit(one.init)(jax.random.key(1), tokens1)["params"]
    # room for the attention output, its log-sum-exp and the projections
    # of one row, not of four
    capacity(64 * GIB)
    jax.make_jaxpr(jax.grad(_loss_fn(one, tokens1)))(params)
    (full,) = _record()
    tight = 64 * GIB - full["budget_bytes"] + full["kept_bytes"] // 2
    trace._said.clear()
    get_recorder().clear()
    capacity(tight)
    jax.make_jaxpr(jax.grad(_loss_fn(one, tokens1)))(params)
    (rec1,) = _record()
    assert 0 < rec1["kept_bytes"] < full["kept_bytes"]

    mesh = build_mesh({"data": 4}, devices=jax.devices()[:4])
    four = MODELS.get(family)(remat=True, attn_impl="flash", mesh=mesh)
    trace._said.clear()
    get_recorder().clear()
    step = jax.jit(jax.grad(_loss_fn(
        four, jax.device_put(tokens4, batch_sharding(mesh)))))
    grads = step(params)
    (rec4,) = _record()
    assert rec4 == rec1
    # and the sharded step's gradients are those of the plain model
    plain = MODELS.get(family)(remat=False)
    want = jax.jit(jax.grad(_loss_fn(plain, tokens4)))(params)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("attn_impl", ["ring", "ring_flash", "ulysses"])
def test_sequence_parallel_model_keeps_what_its_mesh_leaves(capacity,
                                                            attn_impl):
    """Attention over ``seq``: a ring's steps (each a kernel call of its
    own under ``ring_flash``) and the all-to-all's share of heads are not
    reckoned, so no name of the attention's is kept, neither an output
    without its log-sum-exp; the projections around it are kept at the
    bytes of a device's share of the tokens, and the gradients agree."""
    mesh = build_mesh({"data": 2, "seq": 2}, devices=jax.devices()[:4])
    tokens = _tokens(b=2, t=64)
    sp = MODELS.get("TinyLlama")(remat=True, attn_impl=attn_impl, mesh=mesh)
    plain = MODELS.get("TinyLlama")(remat=False, attn_impl=attn_impl,
                                    mesh=mesh)
    params = jax.jit(plain.init)(jax.random.key(1), tokens)["params"]
    capacity(64 * GIB)
    got = jax.jit(jax.grad(_loss_fn(sp, tokens)))(params)
    (rec,) = _record()
    assert rec["names"] == "qkv_proj,attn_proj,mlp_gate,mlp_up"
    d, kv, ff = 64, 2 * 32, 176             # TinyLlama: 4 heads, 2 KV heads
    per_token = 4 * ((d + kv) + d + 2 * ff)          # float32
    assert rec["kept_bytes_per_block"] == per_token * 2 * 64 // 4
    want = jax.jit(jax.grad(_loss_fn(plain, tokens)))(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("family", ["TinyLlama", "TinyLM"])
def test_gradient_outside_a_training_step_keeps_nothing(capacity, family):
    """Only a step that says what it holds (``step_holds``) has its blocks
    keep anything: the policy cannot know what else a caller's gradient
    lives beside."""
    capacity(64 * GIB)
    tokens = _tokens()
    model = MODELS.get(family)(remat=True)
    params = jax.jit(model.init)(jax.random.key(1), tokens)["params"]
    text = str(jax.make_jaxpr(
        jax.grad(_loss_fn(model, tokens, held=None)))(params))
    assert "policy=None" in text or "nothing_saveable" in text
    assert not _record()


def _square_loss(output, target):
    return jnp.mean(output.astype(jnp.float32) ** 2, axis=(1, 2))


@pytest.mark.parametrize("family", ["TinyLlama", "TinyLM"])
@pytest.mark.parametrize("setting,tx,extra", [
    ("accum4", optax.adamw(1e-3), 2), ("ema", optax.adamw(1e-3), 1),
    ("sgd", optax.sgd(1e-3), -2),
], ids=lambda v: v if isinstance(v, str) else "")
def test_the_step_says_what_it_holds(capacity, family, setting, tx, extra):
    """``make_train_step`` reckons what it holds through the backward from
    the state it is given: shadow weights (``ema_decay``) take one more
    copy of the parameters out of the budget, accumulation
    (``grad_accum_steps`` 4) two, the gradients' float32 sum and a
    micro-batch's own gradient, which joins it when its backward is over;
    plain SGD's missing moments give two back. AdamW without either holds
    three."""
    capacity(64 * GIB)
    model = MODELS.get(family)(remat=True)

    def record(tx, batch, **kw):
        trace._said.clear()
        get_recorder().clear()
        tokens = np.zeros((batch, 32), np.int32)
        state = create_train_state(model, tx, tokens[:1], seed=0,
                                   with_ema="ema_decay" in kw)
        step = make_train_step(model, tx, _square_loss, [],
                               input_key="tokens", target_key="tokens", **kw)
        jax.make_jaxpr(step)(state, {"tokens": tokens,
                                     "mask": np.ones(batch, bool)})
        (rec,) = _record()
        return rec, sum(x.size * x.dtype.itemsize
                        for x in jax.tree.leaves(state.params))

    kw = {"accum4": {"grad_accum_steps": 4},
          "ema": {"ema_decay": 0.9}}.get(setting, {})
    got, param_bytes = record(tx, 8, **kw)
    # against AdamW alone at the batch the blocks see (a micro-batch)
    base, _ = record(optax.adamw(1e-3), 8 // kw.get("grad_accum_steps", 1))
    assert 0 <= base["held_bytes"] - 3 * param_bytes < 64   # and counters
    for field, sign in (("held_bytes", 1), ("budget_bytes", -1)):
        assert sign * (got[field] - base[field]) == pytest.approx(
            extra * param_bytes, abs=64), field
    assert got["kept_bytes"] == base["kept_bytes"] > 0


# -- blocks of unequal bytes (a stack whose layers are of several kinds) ----

# nemotron3_super_l11.seq8k's three kinds at 2 x 8192 tokens in bfloat16:
# 5 state-space layers, 5 expert layers (the router's logits float32; 8 held
# of 512 and 22 a token: two places a token in the room for its pairs, so
# the first product is 2 x 2688 features a token where every held expert
# over every token made 8 x 2688 = 21504, and the pairs' layout has a name),
# one attention layer of 4 heads of 128
TOK = 2 * 8192 * 2
SSM = {"ssm_in_proj": TOK * 2320}
EXPERTS = {"moe_router": TOK * 1024, "moe_pairs": TOK * 43,
           "moe_latent": TOK * 1024, "moe_experts_out": TOK * 1024,
           "moe_shared_up": TOK * 5376, "moe_experts_up": TOK * 5376}
ATTENDS = {"attn_out": 16777216, "attn_lse": 262144, "qkv_proj": TOK * 768,
           "attn_proj": TOK * 4096, "attn_qkv": 3 * 16777216}
HYBRID = [(SSM, 5), (EXPERTS, 5), (ATTENDS, 1)]
HYBRID_ORDER = ("attn_out", "attn_lse", "moe_router", "moe_pairs", "qkv_proj",
                "attn_proj", "ssm_in_proj", "moe_experts_out", "moe_latent",
                "moe_shared_up", "attn_qkv", "moe_experts_up")
# what the policy reckons on the chip with the E kind's names in its margin
# (4 588 793 516 when the scan's kind was the largest; 3 180 555 948 with a
# first product over every held expert, 21504 features a token, PR 47-50)
HYBRID_BUDGET = 4_234_702_508


def kept_bytes(blocks, names):
    return sum(count * table.get(n, 0) for table, count in blocks
               for n in names)


@pytest.mark.parametrize("budget,n_kept", [
    (0, 0), (17039360 - 1, 0), (17039360, 2),
    (17039360 + 5 * TOK * 1024, 3), (17039360 + 5 * TOK * (1024 + 43), 4),
    (GIB // 2, 6), (899121152, 8), (GIB, 9), (2_000_000_000, 11),
    (1_998_028_800 + 5 * TOK * 5376 - 1, 11), (HYBRID_BUDGET, 12),
    (6 * GIB, 12)],
    ids=["empty", "one-byte-short", "attention", "router", "the-pairs-layout",
         "projections", "the-layers-sum-and-no-latent", "latent",
         "all-but-the-first-product", "the-first-product-a-byte-short",
         "the-cells-4.23-GB", "room-to-spare"])
def test_unequal_blocks_are_summed_by_kind_and_count(budget, n_kept):
    got = rp.choose_names(HYBRID, budget)
    assert got == HYBRID_ORDER[:n_kept]
    assert kept_bytes(HYBRID, got) <= budget
    if n_kept < len(HYBRID_ORDER):      # the next group would not fit
        step = 2 if n_kept == 0 else 1
        assert kept_bytes(HYBRID, HYBRID_ORDER[:n_kept + step]) > budget
    # the kinds' order is nothing to the choice
    assert rp.choose_names(HYBRID[::-1], budget) == got


def test_a_name_costs_only_the_kinds_that_make_it():
    """`moe_router` is kept in 5 of 11 blocks and costs 5 blocks' bytes;
    a kind without the name is passed over, not counted at another's."""
    assert kept_bytes(HYBRID, ("moe_router",)) == 5 * TOK * 1024
    assert kept_bytes(HYBRID, ("qkv_proj",)) == TOK * 768
    assert kept_bytes(HYBRID, ("moe_experts_out",)) == 5 * TOK * 1024
    one_kind = rp.choose_names([(EXPERTS, 5)], GIB)
    assert one_kind == ("moe_router", "moe_pairs", "moe_experts_out",
                        "moe_latent")
    assert rp.choose_names([(EXPERTS, 5), ({}, 6)], GIB) == one_kind


def test_equal_blocks_as_kinds_choose_what_one_table_chooses():
    """The two dense configurations are told the same names: a stack of
    n equal blocks is one kind counted n times, however it is split."""
    for table, n in ((MISTRAL, 2), (GPT2_LARGE, 36)):
        for budget in (0, GIB // 4, GIB, 2 * GIB, 5 * GIB, 12 * GIB):
            whole = rp.choose_names([(table, n)], budget)
            assert rp.choose_names([(table, 1), (table, n - 1)],
                                   budget) == whole
    assert rp.choose_names([(MISTRAL, 2)], 2 * GIB) == ATTN + MATMULS + (
        "attn_qkv",)
    assert rp.choose_names([(GPT2_LARGE, 36)], 5 * GIB) == ATTN + (
        "qkv_proj", "attn_proj")


def test_the_margin_is_the_largest_kinds_backward():
    args = dict(held_bytes=8410386260, outside_param_bytes=536887296,
                block_input_bytes=TOK * 4096, head_bytes=2 * TOK * 4096)
    budget = rp.budget_bytes(16_909_336_064, blocks=HYBRID, **args)
    assert budget == (16_909_336_064 - 8410386260 - 536887296
                      - 11 * TOK * 4096 - 2 * TOK * 4096
                      - 2 * sum(EXPERTS.values()) - rp.HEADROOM_BYTES)
    # 5 + 5 + 1 blocks of input, whatever the kinds' order
    assert rp.budget_bytes(16_909_336_064, blocks=HYBRID[::-1],
                           **args) == budget


def test_a_kinds_scratch_is_part_of_its_backwards_room():
    """What a scan makes inside itself (its decay masks) no name keeps;
    the kind of block says it as `scratch`, and the room left for one
    block's backward is the largest kind's names AND scratch, twice. A
    kind that is not the largest with its scratch changes nothing."""
    from pytorch_distributed_template_tpu.models.mixers import (
        mamba_block_sizes,
    )

    args = dict(held_bytes=8410386260, outside_param_bytes=536887296,
                block_input_bytes=TOK * 4096, head_bytes=2 * TOK * 4096)
    # the expert layers without their routed experts' first product, its
    # pairs' layout and their sum, as they were reckoned before those had
    # names: 7424 features a token
    blocks = [(SSM, 5), ({n: b for n, b in EXPERTS.items() if not
                          n.startswith(("moe_experts_", "moe_pairs"))}, 5),
              (ATTENDS, 1)]
    plain = rp.budget_bytes(16_909_336_064, blocks=blocks, **args)
    assert rp.budget_bytes(16_909_336_064, blocks=blocks,
                           scratch=[0, 0, 0], **args) == plain
    # the hybrid cell's scan: 16 heads, chunk 128: 2320 + 6144 features a
    # token, over those 7424
    widths, scratch = mamba_block_sizes(16, 64, 1, 128, 128, 2)
    assert widths == {"ssm_in_proj": 2320} and scratch == 16 * 128 * 3
    with_masks = rp.budget_bytes(16_909_336_064, blocks=blocks,
                                 scratch=[TOK * scratch, 0, 0], **args)
    assert plain - with_masks == 2 * TOK * (2320 + 6144 - 7424)
    small = rp.budget_bytes(16_909_336_064, blocks=blocks,
                            scratch=[TOK * 1024, 0, 0], **args)
    assert small == plain
    # with the first product (5376 features in a room of two places a
    # token) the expert layers are the largest kind still, 13867 features
    # against 8464, and the scan's masks change nothing
    assert rp.budget_bytes(
        16_909_336_064, blocks=HYBRID, scratch=[TOK * scratch, 0, 0],
        **args) == rp.budget_bytes(16_909_336_064, blocks=HYBRID, **args)
    # granite-4.0-h-micro's: 64 heads, the published chunk of 256: the
    # float32 mask 537 MB and its bfloat16 product 268 MB at 8192 tokens
    widths, scratch = mamba_block_sizes(64, 64, 1, 128, 256, 2)
    assert widths == {"ssm_in_proj": 8512}
    assert 8192 * 2 * scratch == 8192 * 64 * 256 * (4 + 2)


def test_new_names_leave_the_old_names_order_as_it_was():
    later = ("moe_", "ssm_", "kda_", "conv_", "attn_gate")
    old = [g for g in rp.PREFERENCE
           if not any(n.startswith(later) for n in g)]
    assert old == [("attn_out", "attn_lse"), ("qkv_proj",), ("attn_proj",),
                   ("mlp_gate",), ("mlp_up",), ("attn_qkv",)]
    new = [n for g in rp.PREFERENCE for n in g if n.startswith(later)]
    # PR 50's one, the layout of an expert layer's pairs, beside the router
    assert new.index("moe_pairs") == new.index("moe_router") + 1
    new.remove("moe_pairs")
    # PR 33's four in their order, PR 42's three between them
    # PR 47's one among them: the layer's sum over the experts held, as
    # wide as `moe_latent` and a product over every held expert's features
    # spared where that spares one over `d_model`, so in front of it
    assert new.index("moe_experts_out") == new.index("moe_latent") - 1
    new.remove("moe_experts_out")
    assert [n for n in new if n.startswith(("moe_", "ssm_"))][:4] == [
        "moe_router", "ssm_in_proj", "moe_latent", "moe_shared_up"]
    # PR 49's two, a short convolution mixer's projections, behind
    # `attn_proj` and beside the KDA's
    assert new[4:6] == ["conv_in_proj", "conv_out_proj"]
    del new[4:6]
    assert new[:7] == ["moe_router", "attn_gate", "kda_in_proj",
                       "kda_out_proj", "ssm_in_proj", "moe_latent",
                       "moe_shared_up"]
    # PR 43's two behind every other name, each a group of its own, so
    # that no model loses a name to them and room for one keeps one
    assert new[7:] == ["moe_experts_gate", "moe_experts_up"]
    assert rp.PREFERENCE[-2:] == (("moe_experts_gate",),
                                  ("moe_experts_up",))
    assert list(rp.PREFERENCE[:-2]) == [
        ("attn_out", "attn_lse"), ("moe_router",), ("moe_pairs",),
        ("qkv_proj",),
        ("attn_gate",), ("attn_proj",), ("kda_in_proj",), ("kda_out_proj",),
        ("conv_in_proj",), ("conv_out_proj",),
        ("ssm_in_proj",), ("moe_experts_out",), ("moe_latent",),
        ("mlp_gate",), ("mlp_up",), ("moe_shared_up",), ("attn_qkv",)]


NEMOTRON_CELL = dict(
    vocab_size=16384, pattern="EMEMEMEMEM*", n_head=4, n_kv_head=1,
    ssm_n_head=16, ssm_n_group=1, moe_held=(0, 8))


def test_the_hybrid_model_states_its_three_kinds():
    model = MODELS.get("NemotronH")(**NEMOTRON_CELL)
    ssm, experts, attends = model._block_kinds()
    assert (ssm.count, experts.count, attends.count) == (5, 5, 1)
    # the routed experts' first product: two places a token in the room
    # for its pairs (`moe.token_places(22, 8, 512)`) of 2688 features,
    # their layout, and the sum over the experts held, where `latent_up`
    # reads it
    assert experts.widths == {"moe_router": 1024, "moe_pairs": 43,
                              "moe_latent": 1024, "moe_experts_out": 1024,
                              "moe_shared_up": 5376,
                              "moe_experts_up": 2 * 2688}
    assert {n: TOK * w for n, w in experts.widths.items()} == EXPERTS
    assert {n: TOK * w for n, w in ssm.widths.items()} == SSM
    assert (attends.attn_heads, attends.head_dim) == (4, 128)
    # none said held: all 512 are, of which a token takes 22: the first
    # product runs over the pairs, whose room is 22 rows a token
    whole = MODELS.get("NemotronH")(pattern="E")._block_kinds()[0]
    assert whole.widths["moe_experts_up"] == 22 * 2688
    # the E kind, 243 -> 454 MB, is the largest with the product in it
    # (948 MB when the product ran over every held expert): the margin
    # grows by twice 176 MB, twice 33.5 MB with the sum, twice 1.4 MB with
    # the layout
    args = dict(held_bytes=8410386260, outside_param_bytes=536887296,
                block_input_bytes=TOK * 4096, head_bytes=2 * TOK * 4096)
    budget = rp.budget_bytes(16_909_336_064, blocks=HYBRID,
                             scratch=[TOK * ssm.scratch, 0, 0], **args)
    assert budget == HYBRID_BUDGET == (
        16_909_336_064 - 8410386260 - 536887296 - 13 * TOK * 4096
        - 2 * TOK * (1024 + 43 + 1024 + 1024 + 5376 + 5376)
        - rp.HEADROOM_BYTES)
    # (before, the scan's kind with its masks was: 2320 + 6144 features;
    # with a first product over every held expert the E kind was 29952)
    assert budget == 4_588_793_516 - 2 * TOK * (13867 - 2320 - 6144)
    assert budget == 3_180_555_948 + 2 * TOK * (29952 - 13867)


def test_the_hybrid_cell_keeps_every_name_and_the_first_product_too():
    """`nemotron3_super_l11.seq8k` at its chip's budget: in a room of two
    places a token the first product is 176 MB a layer, 0.881 GB over
    five, and fits beside the ten names kept before (1.991 GB) and the
    pairs' layout (1.4 MB a layer): every name the kinds make. Over
    every held expert it was 705 MB a layer, 3.52 GB over five, more than
    the whole budget it left itself (PR 43-50)."""
    got = rp.choose_names(HYBRID, HYBRID_BUDGET)
    assert got == HYBRID_ORDER
    assert kept_bytes(HYBRID, got) == 1_990_983_680 + 5 * TOK * (43 + 5376) \
        == 2_878_832_640 <= HYBRID_BUDGET
    over_every_token = [(SSM, 5), (dict(
        EXPERTS, moe_experts_up=TOK * 21504), 5), (ATTENDS, 1)]
    assert kept_bytes(over_every_token, ("moe_experts_up",)) \
        == 3_523_215_360 > 3_180_555_948
    assert rp.choose_names(over_every_token, 3_180_555_948) \
        == HYBRID_ORDER[:11]


# the routed experts' two forms under the stacks (models/moe.ExpertLayer
# reads which from its shapes, `token_places`): the tiny stacks hold all 8
# experts and a token takes 2, two places a token, so their products run
# over the pairs; a share of 2 held of the 8 has a place for every expert
# held and takes every held expert over every token. (The cells' own
# layers, 8 held of 512 or of 320, have two places and one: the tables
# above.) The first products are 2 x 48 features a token either way; the
# layout of the pairs (`moe_pairs`, 86 bytes a token: 22 of float32's four)
# is the pairs' alone
FORMS = pytest.mark.parametrize("held,pairs", [((0, 0), 22), ((0, 2), 0)],
                                ids=["over-the-pairs", "every-held-expert"])


def _names(text, pairs):
    return text if pairs else text.replace("moe_pairs,", "")


@FORMS
def test_hybrid_model_reckons_three_kinds_and_says_so(monkeypatch, caplog,
                                                      held, pairs):
    """The pattern-built stack under a training step on a device of known
    capacity: one `remat/policy` record for its 5 blocks of three kinds,
    names from every kind, and the same loss and gradient as with nothing
    kept, in both forms of the routed experts' products."""
    from pytorch_distributed_template_tpu.engine.losses import (
        lm_cross_entropy,
    )

    trace._said.clear()
    get_recorder().clear()
    model = MODELS.get("TinyNemotronH")(pattern="EMEM*", remat=True,
                                        moe_held=held)
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 256)
    params = jax.jit(model.init)(jax.random.key(1), tokens)["params"]

    def loss(p):
        logits = model.apply({"params": p}, tokens, train=True)
        return jnp.mean(lm_cross_entropy(logits, tokens))

    # each side one jitted program, traced where it stands (a new
    # function a side, so the second is no cache hit of the first)
    want = jax.jit(jax.value_and_grad(loss))(params)    # no step: nothing
    monkeypatch.setattr(rp, "device_capacity_bytes",
                        lambda mesh=None: 2 * GIB)
    with caplog.at_level(logging.INFO), rp.step_holds(1 << 20):
        got = jax.jit(jax.value_and_grad(loss))(params)
    (said,) = [e["args"] for e in get_recorder().snapshot()
               if e["name"] == "remat/policy"]
    assert said["blocks"] == 5
    assert said["names"] == _names(
        "attn_out,moe_router,moe_pairs,qkv_proj,attn_proj,ssm_in_proj,"
        "moe_experts_out,moe_latent,moe_shared_up,moe_experts_up", pairs)
    tok = 2 * 32 * 4
    d_in, heads = 4 * 16, 4
    assert said["kept_bytes"] == tok * (
        heads * 16                                  # attn_out, one block
        + 2 * (8 + pairs + 32 + 32 + 96 + 2 * 48)   # two expert layers: the
        # pairs' layout, 2 experts a token of the 8 held or both of 2 held
        + (heads + 2 * 2) * 16 + 64                 # qkv_proj, attn_proj
        + 2 * (2 * d_in + heads + 2 * 2 * 16))      # two in_proj outputs
    assert "remat/policy: keeping [attn_out,moe_router" in caplog.text
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)


# -- solar_open2_l4.seq8k's two kinds: a KDA block and a gated-attention
# block, each with the gated experts, at 1 x 8192 tokens in bfloat16 --------

SOLAR_TOK = 1 * 8192 * 2
# (8 held of 320 and 8 a token: one place a token in the room for its pairs,
# so each first product is 1280 features a token where every held expert
# over every token made 10240, and the pairs' layout has a name)
SOLAR_EXPERTS = {"moe_router": SOLAR_TOK * 640, "moe_pairs": SOLAR_TOK * 39,
                 "mlp_gate": SOLAR_TOK * 1280, "mlp_up": SOLAR_TOK * 1280,
                 "moe_experts_gate": SOLAR_TOK * 1280,
                 "moe_experts_up": SOLAR_TOK * 1280}
SOLAR_KDA = {"kda_in_proj": SOLAR_TOK * 3072, "kda_out_proj": SOLAR_TOK * 4096,
             **SOLAR_EXPERTS}
SOLAR_ATTN = {"attn_out": 16777216, "attn_lse": 262144,
              "qkv_proj": SOLAR_TOK * 1280, "attn_gate": SOLAR_TOK * 1024,
              "attn_proj": SOLAR_TOK * 4096, "attn_qkv": 3 * 16777216,
              **SOLAR_EXPERTS}
SOLAR = [(SOLAR_KDA, 3), (SOLAR_ATTN, 1)]
SOLAR_ORDER = ("attn_out", "attn_lse", "moe_router", "moe_pairs", "qkv_proj",
               "attn_gate", "attn_proj", "kda_in_proj", "kda_out_proj",
               "mlp_gate", "mlp_up", "attn_qkv", "moe_experts_gate",
               "moe_experts_up")
# what the policy reckons on the chip with the experts' two products and
# their pairs' layout in the KDA kind's margin (3 123 629 792 without them;
# 2 452 541 152 with two products over every held expert, PR 43-50)
SOLAR_BUDGET = 3_038_465_760


@pytest.mark.parametrize("budget,n_kept", [
    (0, 0), (17039360, 2), (17039360 + 4 * SOLAR_TOK * 640, 3),
    (17039360 + 4 * SOLAR_TOK * (640 + 39), 4),
    (GIB // 4, 7), (GIB // 2, 9), (736_821_248, 12), (800_000_000, 12),
    (736_821_248 + 4 * SOLAR_TOK * 1280, 13), (900_000_000, 13),
    (SOLAR_BUDGET, 14)],
    ids=["empty", "attention", "router", "the-pairs-layout",
         "attention-block", "kda",
         "neither-product", "neither-product-with-room-to-spare",
         "the-gate-product-alone", "the-gate-product-and-79-MB",
         "the-cells-3.04-GB"])
def test_the_two_new_kinds_keep_a_prefix_that_fits(budget, n_kept):
    got = rp.choose_names(SOLAR, budget)
    assert got == SOLAR_ORDER[:n_kept]
    assert kept_bytes(SOLAR, got) <= budget
    if n_kept < len(SOLAR_ORDER):
        step = 2 if n_kept == 0 else 1
        assert kept_bytes(SOLAR, SOLAR_ORDER[:n_kept + step]) > budget
    # every older name the two kinds make and the pairs' layout are 0.737
    # GB, each of the routed experts' products 0.084 GB over the four
    # layers in a room of one place a token (0.671 over every held
    # expert: 2.076 GB in all then): 0.905 GB, inside the 3.04 GB the
    # policy reckons on the chip
    assert kept_bytes(SOLAR, SOLAR_ORDER[:12]) == 736_821_248
    assert kept_bytes(SOLAR, SOLAR_ORDER) == 904_593_408 <= SOLAR_BUDGET


def test_the_solar_model_states_its_two_kinds():
    from pytorch_distributed_template_tpu.ops.linear_attention import (
        SUB_CHUNK,
    )

    model = MODELS.get("SolarOpen2")(
        pattern="*KKK", vocab_size=24576, n_head=8, n_kv_head=1,
        kda_n_head=8, moe_held=(0, 8))
    kda, attn = model._block_kinds()
    assert (kda.count, attn.count) == (3, 1)
    assert {n: SOLAR_TOK * w for n, w in kda.widths.items()} == SOLAR_KDA
    assert attn.widths == {"qkv_proj": 1280, "attn_gate": 1024,
                           "attn_proj": 4096, "moe_router": 640,
                           "moe_pairs": 39, "mlp_gate": 1280, "mlp_up": 1280,
                           "moe_experts_gate": 1280,
                           "moe_experts_up": 1280}
    # no latent, so no projection reads the routed experts' sum and
    # neither kind states `moe_experts_out`: to the byte what it was
    assert "moe_experts_out" not in {**kda.widths, **attn.widths}
    # 8 held of 320 experts of 1280 features, 8 a token: one place a
    # token (`moe.token_places(8, 8, 320)`); none said held: all 320 are,
    # of which a token takes 8: the pairs' room is 8 rows a token
    assert kda.widths["moe_experts_gate"] == 1 * 1280
    whole = MODELS.get("SolarOpen2")(pattern="K")._block_kinds()[0]
    assert whole.widths["moe_experts_up"] == 8 * 1280
    assert (attn.attn_heads, attn.head_dim, attn.scratch) == (8, 128, 0)
    # the scan's pairwise decays: 8 heads x 16 positions x 128 channels of
    # float32 a token, 537 MB a layer at 8192 tokens
    assert kda.scratch == 8 * SUB_CHUNK * 128 * 2
    assert 8192 * 2 * kda.scratch == 536870912
    args = dict(held_bytes=10_090_508_064, outside_param_bytes=805_322_752,
                block_input_bytes=SOLAR_TOK * 4096,
                head_bytes=2 * SOLAR_TOK * 4096)
    budget = rp.budget_bytes(16_909_336_064, blocks=SOLAR,
                             scratch=[SOLAR_TOK * kda.scratch, 0], **args)
    assert budget == (16_909_336_064 - 10_090_508_064 - 805_322_752
                      - 6 * SOLAR_TOK * 4096
                      - 2 * (sum(SOLAR_KDA.values()) + 536870912)
                      - rp.HEADROOM_BYTES)
    # the KDA kind's backward holds both products (42 MB) and their
    # cotangents, and the pairs' layout: 85 MB less than before they had
    # names (671 MB less when they ran over every held expert)
    assert budget == SOLAR_BUDGET == 3_123_629_792 - 2 * SOLAR_TOK * (
        2 * 1280 + 39)
    assert rp.choose_names(SOLAR, budget) == SOLAR_ORDER


@FORMS
def test_solar_model_reckons_two_kinds_and_says_so(monkeypatch, caplog, held,
                                                   pairs):
    """The stack under a training step on a device of known capacity: one
    `remat/policy` record for its 3 blocks of two kinds, names from both,
    and the same loss and gradient as with nothing kept, in both forms of
    the routed experts' products."""
    from pytorch_distributed_template_tpu.engine.losses import (
        lm_cross_entropy,
    )

    trace._said.clear()
    get_recorder().clear()
    model = MODELS.get("TinySolarOpen2")(pattern="*KK", remat=True,
                                         moe_held=held)
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 256)
    params = jax.jit(model.init)(jax.random.key(1), tokens)["params"]

    def loss(p):
        logits = model.apply({"params": p}, tokens, train=True)
        return jnp.mean(lm_cross_entropy(logits, tokens))

    # each side one jitted program, traced where it stands (a new
    # function a side, so the second is no cache hit of the first)
    want = jax.jit(jax.value_and_grad(loss))(params)    # no step: nothing
    monkeypatch.setattr(rp, "device_capacity_bytes",
                        lambda mesh=None: 2 * GIB)
    with caplog.at_level(logging.INFO), rp.step_holds(1 << 20):
        got = jax.jit(jax.value_and_grad(loss))(params)
    (said,) = [e["args"] for e in get_recorder().snapshot()
               if e["name"] == "remat/policy"]
    assert said["blocks"] == 3
    assert said["names"] == _names(
        "attn_out,moe_router,moe_pairs,qkv_proj,attn_gate,attn_proj,"
        "kda_in_proj,kda_out_proj,mlp_gate,mlp_up,moe_experts_gate,"
        "moe_experts_up", pairs)
    tok = 2 * 32 * 4
    assert said["kept_bytes"] == tok * (
        4 * 16                                      # attn_out, one block
        + 3 * (8 + pairs + 48 + 48)                 # router, the pairs' layout,
        # shared gate, up
        + 3 * 2 * 2 * 48                            # two products: 2 experts a
        # token of the 8 held, or both of 2 held
        + (4 + 2 * 2) * 16 + 4 * 16 + 64            # qkv, gate, attn_proj
        + 2 * (3 * 4 * 16 + 64))                    # two KDA blocks
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)


# -- a convolution/attention stack whose second sublayer changes with depth
# (models/hybrid.py's LFM2_MOE): a kind is a mixer AND a second sublayer ----

LFM2_CELL = dict(
    vocab_size=16384, n_dense_layers=1, moe_held=(0, 16),
    layer_types=("conv", "full_attention", "conv", "conv", "conv"))


def test_the_lfm2_model_states_a_kind_a_mixer_and_second_sublayer():
    from pytorch_distributed_template_tpu.models.mixers import (
        short_conv_block_sizes,
    )

    model = MODELS.get("Lfm2Moe")(**LFM2_CELL)
    dense, conv, attn = model._block_kinds()
    assert (dense.count, conv.count, attn.count) == (1, 3, 1)
    mixer = {"conv_in_proj": 3 * 2048, "conv_out_proj": 2048}
    # 16 held of which a token takes 4: the first products run over the
    # pairs, whose room is 4 rows a token
    experts = {"moe_router": 128, "moe_pairs": 84,
               "moe_experts_gate": 4 * 1536, "moe_experts_up": 4 * 1536}
    assert dense.widths == {**mixer, "mlp_gate": 11776, "mlp_up": 11776}
    assert conv.widths == {**mixer, **experts}
    assert attn.widths == {"qkv_proj": (32 + 2 * 8) * 64, "attn_proj": 2048,
                           **experts}
    assert (attn.attn_heads, attn.head_dim, attn.scratch) == (32, 64, 0)
    # the gated input and the convolution's result in float32
    assert short_conv_block_sizes(2048, 2) == (mixer, 2 * 2048 * 2)
    assert dense.scratch == conv.scratch == 8192
    # no leading dense layer: two kinds; the published stack: its two
    # leading layers are one kind, its 28 other convolution layers another
    two = MODELS.get("Lfm2Moe")(**{**LFM2_CELL, "n_dense_layers": 0})
    assert [k.count for k in two._block_kinds()] == [4, 1]
    whole = MODELS.get("Lfm2Moe")()._block_kinds()
    assert [k.count for k in whole] == [2, 28, 10]
    assert whole[1].widths["moe_experts_up"] == 4 * 1536
    # the families that came before state a kind a symbol, as they did
    granite = MODELS.get("GraniteHybrid")()._block_kinds()
    assert [k.count for k in granite] == [9, 1]      # one period


def test_lfm2_model_reckons_three_kinds_and_says_so(monkeypatch, caplog):
    """The stack under a training step on a device of known capacity: one
    `remat/policy` record for its 3 blocks of three kinds, both new names
    among those kept, and the loss and gradient of nothing kept."""
    from pytorch_distributed_template_tpu.engine.losses import (
        lm_cross_entropy,
    )

    trace._said.clear()
    get_recorder().clear()
    model = MODELS.get("TinyLfm2Moe")(remat=True)
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 256)
    params = jax.jit(model.init)(jax.random.key(1), tokens)["params"]

    def loss(p):
        logits = model.apply({"params": p}, tokens, train=True)
        return jnp.mean(lm_cross_entropy(logits, tokens))

    want = jax.jit(jax.value_and_grad(loss))(params)    # no step: nothing
    monkeypatch.setattr(rp, "device_capacity_bytes",
                        lambda mesh=None: 2 * GIB)
    with caplog.at_level(logging.INFO), rp.step_holds(1 << 20):
        got = jax.jit(jax.value_and_grad(loss))(params)
    (said,) = [e["args"] for e in get_recorder().snapshot()
               if e["name"] == "remat/policy"]
    assert said["blocks"] == 3
    assert said["names"] == ("attn_out,moe_router,moe_pairs,qkv_proj,attn_proj,"
                             "conv_in_proj,conv_out_proj,mlp_gate,mlp_up,"
                             "moe_experts_gate,moe_experts_up")
    tok = 2 * 32 * 4
    assert said["kept_bytes"] == tok * (
        4 * 16 + (4 + 2 * 2) * 16 + 64              # attn_out, qkv, attn_proj
        + 2 * (3 * 64 + 64)                         # two convolution mixers
        + 2 * 96                                    # the leading layer's MLP
        + 2 * (8 + 22 + 2 * 2 * 48))                # two expert layers: two
    # products over the pairs of 2 experts a token of the 8 held
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)
