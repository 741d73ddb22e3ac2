"""v5e compiles of the main path's kernels at real widths, without a
chip (tests/chip_compile_common.py says how): the flash kernels, the
state-space convolution's backward, the paged decode kernel, and the
checkpoint policy's arithmetic against the compiler's own.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from chip_compile_common import (  # noqa: F401  (fixtures by name)
    _abstract_step_inputs, four_chips, one_chip, topo,
)
from pytorch_distributed_template_tpu.ops.flash import (
    flash_attention, paged_attention,
)


def _qkv(shape, sharding):
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)] * 3


# [batch, tokens, heads, head size] and band of: GPT-2-small attention
# (the chip_smoke.py width), a Llama-style head_dim-128 layer, the calls
# of the benchmark's cells (gpt2_large.seq1k; mistral7b_l2.seq8k and
# seq8k_dp4 on a chip) and the 4096-token shape where the band is
# inactive. Each takes the blocks `pick_block_sizes` gives it, so a pair
# Mosaic refuses fails here before it meets the chip.
SHAPES = {
    "8x1024x12x64": ((8, 1024, 12, 64), 0),
    "8x1024x16x128": ((8, 1024, 16, 128), 0),
    "gpt2_large.seq1k": ((8, 1024, 20, 64), 0),
    "mistral7b_l2.seq8k": ((1, 8192, 32, 128), 4096),
    "mistral7b_l2.seq4k": ((2, 4096, 32, 128), 4096),
}


@pytest.mark.parametrize("shape,window", SHAPES.values(), ids=SHAPES.keys())
def test_flash_forward_compiles_for_v5e(one_chip, shape, window):
    fwd = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=False))
    text = fwd.lower(*_qkv(shape, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,window", SHAPES.values(), ids=SHAPES.keys())
def test_flash_forward_backward_compiles_for_v5e(one_chip, shape, window):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    text = step.lower(*_qkv(shape, one_chip)).compile().as_text()
    # forward + the dkv and dq backward kernels
    assert text.count("tpu_custom_call") >= 3


def test_flash_kernels_carry_their_names_for_v5e(one_chip):
    """`name=` on the pallas_calls reaches the HLO: each kernel's
    custom call is under its own name in `op_name` (what a trace's
    reduction joins on) and the instruction is named after it."""
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    text = step.lower(
        *_qkv(SHAPES["8x1024x16x128"][0], one_chip)).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for kernel in ("flash_fwd", "flash_dkv", "flash_dq"):
        (line,) = [ln for ln in calls
                   if re.search(rf'op_name="[^"]*{kernel}[^"]*pallas_call',
                                ln)]
        assert kernel in line.split(" = ")[0]


@pytest.mark.parametrize("names,forward_calls", [
    ((), 2), (("attn_out", "attn_lse"), 1),
], ids=["nothing-kept", "attention-kept"])
def test_checkpoint_policy_spares_the_second_flash_forward_for_v5e(
        one_chip, names, forward_calls):
    """The names on the custom-vjp forward rules' residuals reach the
    compiled program: a checkpoint policy that keeps the attention output
    and its log-sum-exp (models/remat_policy.py) leaves one `flash_fwd`
    call where `nothing_saveable` leaves two."""
    from pytorch_distributed_template_tpu.models.remat_policy import (
        policy_of,
    )

    def loss(q, k, v):
        attend = jax.checkpoint(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            interpret=False),
            policy=policy_of(names))
        return jnp.sum(attend(q, k, v).astype(jnp.float32))

    # the loss too, or the forward pass itself has nothing to give
    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    text = step.lower(
        *_qkv(SHAPES["8x1024x16x128"][0], one_chip)).compile().as_text()
    calls = [ln.split(" = ")[0] for ln in text.splitlines()
             if "tpu_custom_call" in ln]
    assert sum("flash_fwd" in c for c in calls) == forward_calls
    assert sum("flash_dkv" in c or "flash_dq" in c for c in calls) == 2


@pytest.mark.parametrize("accum,capacity,names", [
    (1, 5_400_000_000, "attn_out,attn_lse,qkv_proj,attn_proj"),
    (4, 6_900_000_000, "attn_out,attn_lse,qkv_proj,attn_proj"),
    (4, 5_400_000_000, ""),
], ids=["plain", "accum4", "accum4-tight"])
def test_what_the_policy_keeps_fits_the_capacity_for_v5e(
        one_chip, monkeypatch, accum, capacity, names):
    """The arithmetic of models/remat_policy.py against the compiler's own
    `memory_analysis()`: a whole training step of six GPT-2-large blocks
    (the benchmark's widths, batch and sequence) with a capacity supplied
    that leaves room for part of the names. What the policy then keeps, the
    compiled step holds inside that capacity, with `grad_accum_steps` 4
    too, where the step holds a gradient sum and a micro-batch's gradient
    more. A change to names, shapes or the budget that crosses the limit
    fails here and not on the chip."""
    import optax

    from pytorch_distributed_template_tpu.config.registry import MODELS
    from pytorch_distributed_template_tpu.engine.losses import resolve_loss
    from pytorch_distributed_template_tpu.engine.steps import make_train_step
    from pytorch_distributed_template_tpu.models import remat_policy
    from pytorch_distributed_template_tpu.observability import trace
    from pytorch_distributed_template_tpu.observability.trace import (
        get_recorder,
    )
    from pytorch_distributed_template_tpu.ops import flash

    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat_policy, "device_capacity_bytes",
                        lambda mesh=None: capacity)
    trace._said.clear()
    get_recorder().clear()
    model = MODELS.get("GPT2")(
        size="gpt2-large", n_layer=6, bfloat16=True, attn_impl="flash",
        remat=True, fused_head=True, dropout=0.0)
    tx = optax.adamw(1e-4)
    state, _, feed = _abstract_step_inputs(
        model, tx, 8 * accum, 1024, one_chip, one_chip)
    step = make_train_step(
        model, tx, resolve_loss({"type": "fused_lm_cross_entropy",
                                 "args": {"chunk": 256}}), [],
        input_key="tokens", target_key="tokens", grad_clip_norm=1.0,
        grad_accum_steps=accum)
    m = jax.jit(step, donate_argnums=0).lower(
        state, feed).compile().memory_analysis()
    (record,) = [e["args"] for e in get_recorder().snapshot()
                 if e["name"] == "remat/policy"]
    assert record["names"] == names
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total <= capacity


# [batch, positions, the projection's width], where the convolution's
# channels start and how many they are, the type: the two hybrid cells'
# layers read where they lie; float32 (fewer positions a block); a debug
# config's widths, which no block divides, cut out and padded
CONVOLUTIONS = {
    "granite4_h_micro_l10.seq8k": ((1, 8192, 8512), 4096, 4352, jnp.bfloat16),
    "nemotron3_super_l11.seq8k": ((2, 8192, 2320), 1024, 1280, jnp.bfloat16),
    "float32": ((1, 8192, 8512), 4096, 4352, jnp.float32),
    "debug-widths": ((2, 200, 232), 64, 96, jnp.bfloat16),
}


def _convolution_step(start, mesh):
    from pytorch_distributed_template_tpu.ops.ssm import sharded_conv_silu

    def loss(zxd, taps, bias):
        out = sharded_conv_silu(zxd, taps, bias, start, mesh)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


@pytest.mark.parametrize("shape,start,channels,dtype", CONVOLUTIONS.values(),
                         ids=CONVOLUTIONS.keys())
def test_convolutions_backward_kernel_compiles_for_v5e(
        one_chip, monkeypatch, shape, start, channels, dtype):
    """ops/ssm.causal_conv_silu's backward with the blocks `conv_blocks`
    gives the shape: Mosaic takes the lane rotations, the block's fast
    memory and the accumulated tile of sums."""
    from pytorch_distributed_template_tpu.ops import flash

    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    text = _convolution_step(start, None).lower(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((4, channels), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((channels,), jnp.float32, sharding=one_chip),
    ).compile().as_text()
    # the forward is the compiler's own fusion; one kernel, the backward
    assert len(re.findall(r"%ssm_conv_bwd(\.\d+)? = ", text)) == 1
    assert text.count("tpu_custom_call") == 1


# tokens, the experts' width and features, held, a token's, routed: shapes
# other than the three cells' that take the pairs' form (theirs are in the
# whole steps' compiles, test_chip_compile.py and
# test_chip_compile_delta_rule.py): a room that is no whole number of any
# row tile, a second width, a whole uncut SolarOpen2's wide experts on one
# chip, and a room under its bound, one place a token of a bound of four,
# with the sum's tail behind the places
EXPERT_LAYERS = {
    "an-odd-room": (60, 256, 128, 8, 2, 32),
    "a-second-width": (2048, 1024, 512, 8, 2, 32),
    "a-wide-expert": (2048, 4096, 1280, 16, 8, 64),
    "a-room-under-its-bound": (1000, 512, 256, 4, 4, 64),
}


@pytest.mark.parametrize("sizes", EXPERT_LAYERS.values(),
                         ids=EXPERT_LAYERS.keys())
def test_an_expert_layer_over_the_pairs_compiles_for_v5e(one_chip, sizes):
    """models/moe.ExpertLayer where a token has fewer places in the room
    than experts are held, forward and every gradient on one v5e: the
    compiler takes the grouped products (`jax.lax.ragged_dot`,
    ops/grouped.py) at whatever room and width, as kernels of its own."""
    from pytorch_distributed_template_tpu.models.moe import (
        ExpertLayer, token_places,
    )
    from pytorch_distributed_template_tpu.observability import trace
    from pytorch_distributed_template_tpu.observability.trace import (
        get_recorder,
    )

    trace._said.clear()
    get_recorder().clear()
    tokens, width, d_ff, held, top_k, routed = sizes
    layer = ExpertLayer(d_model=width, d_ff=d_ff, n_routed=routed,
                        top_k=top_k, held=(0, held), gated=True,
                        selection_bias=True, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((1, tokens, width), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.key(0), x)["params"])

    def loss(p, x):
        return jnp.sum(layer.apply({"params": p}, x).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    (said,) = [e["args"] for e in get_recorder().snapshot()
               if e["name"] == "moe/dispatch"]
    assert said["rows"] == tokens * token_places(top_k, held, routed) \
        < said["dense_rows"]
    assert said["rows"] <= tokens * min(top_k, held)
    # three products forward, a rows' gradient and a matrix's each
    assert len(re.findall(r"%ragged-dot[-\w.]* = ", text)) >= 9


def test_a_convolution_without_a_bias_compiles_for_v5e(one_chip, monkeypatch):
    """The KDA mixer's three: 1024 channels read from their own
    projection, no bias leaf; the kernel is the same one."""
    from pytorch_distributed_template_tpu.ops import flash
    from pytorch_distributed_template_tpu.ops.ssm import causal_conv_silu

    monkeypatch.setattr(flash, "_on_tpu", lambda: True)

    def loss(zxd, taps):
        out = causal_conv_silu(zxd, taps, None)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        jax.ShapeDtypeStruct((1, 8192, 1024), jnp.bfloat16,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((4, 1024), jnp.float32, sharding=one_chip),
    ).compile().as_text()
    assert len(re.findall(r"%ssm_conv_bwd(\.\d+)? = ", text)) == 1
    assert text.count("tpu_custom_call") == 1


def test_convolutions_backward_kernel_is_partitioned_over_the_batch_for_v5e(
        four_chips, monkeypatch):
    """Four chips, data parallel: inside `shard_map` each chip's kernel
    takes its row of the batch, and the parameters' gradients cross."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_template_tpu.ops import flash

    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    rows, whole = (NamedSharding(four_chips, P("data")),
                   NamedSharding(four_chips, P()))
    text = _convolution_step(1024, four_chips).lower(
        jax.ShapeDtypeStruct((4, 8192, 2320), jnp.bfloat16, sharding=rows),
        jax.ShapeDtypeStruct((4, 1280), jnp.float32, sharding=whole),
        jax.ShapeDtypeStruct((1280,), jnp.float32, sharding=whole),
    ).compile().as_text()
    (kernel,) = re.findall(r"%ssm_conv_bwd(?:\.\d+)? = \((\S+), ", text)
    assert kernel.startswith("bf16[1,1280,8192]")
    assert re.search(r"all-reduce", text)


@pytest.mark.xfail(strict=True, raises=ValueError,
                   reason="paged decode kernel refused: 'the last two "
                          "dimensions of your block shape [must be] divisible "
                          "by 8 and 128 ... or equal to the ... overall "
                          "array' — its (1, t_pad, 1, d) q block and "
                          "(1, bt, 1, d) pool blocks put a block of 1 on the "
                          "second-minor (head) axis; the serving PR that "
                          "re-lays the pool must flip this")
def test_paged_attention_compiles_for_v5e(one_chip):
    b, h, kvh, d, bt, nb = 8, 32, 8, 128, 16, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decode = jax.jit(lambda q, pk, pv, tables, starts, pads: paged_attention(
        q, pk, pv, tables, starts, pads, impl="pallas", interpret=False))
    decode.lower(
        sds((b, 1, h, d), jnp.bfloat16),
        sds((1024, bt, kvh, d), jnp.bfloat16),
        sds((1024, bt, kvh, d), jnp.bfloat16),
        sds((b, nb), jnp.int32), sds((b,), jnp.int32), sds((b,), jnp.int32),
    ).compile()
