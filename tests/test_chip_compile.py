"""v5e compiles of the main path's kernels at real widths, without a chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached — so what it refuses (block shapes off the
tiling, too much fast memory, a kernel it cannot partition) fails a test
instead of a chip run. Nothing executes: a compile that passes is not a
chip run and says nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and under xdist every
worker imports every test file. Keep these tests in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pytorch_distributed_template_tpu.ops.flash import (
    flash_attention, paged_attention,
)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # such a compile is written to the persistent cache but cannot be
    # read back without a chip: keep the cache off around these tests
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


def _qkv(shape, sharding):
    return [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding)] * 3


# GPT-2-small attention (the chip_smoke.py width) and a Llama-style
# head_dim-128 layer
SHAPES = [(8, 1024, 12, 64), (8, 1024, 16, 128)]


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_flash_forward_compiles_for_v5e(one_chip, shape):
    fwd = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))
    text = fwd.lower(*_qkv(shape, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_flash_forward_backward_compiles_for_v5e(one_chip, shape):
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    text = step.lower(*_qkv(shape, one_chip)).compile().as_text()
    # forward + the dkv and dq backward kernels
    assert text.count("tpu_custom_call") >= 3


def test_flash_kernels_carry_their_names_for_v5e(one_chip):
    """`name=` on the pallas_calls reaches the HLO: each kernel's
    custom call is under its own name in `op_name` (what a trace's
    reduction joins on) and the instruction is named after it."""
    import re

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    text = step.lower(*_qkv(SHAPES[1], one_chip)).compile().as_text()
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
    text = step.lower(*_qkv(SHAPES[1], one_chip)).compile().as_text()
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
    import numpy as np
    import optax

    from pytorch_distributed_template_tpu.config.registry import MODELS
    from pytorch_distributed_template_tpu.engine.losses import resolve_loss
    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )
    from pytorch_distributed_template_tpu.engine.steps import make_train_step
    from pytorch_distributed_template_tpu.models import remat_policy
    from pytorch_distributed_template_tpu.observability.trace import (
        get_recorder,
    )
    from pytorch_distributed_template_tpu.ops import flash

    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    monkeypatch.setattr(remat_policy, "device_capacity_bytes",
                        lambda mesh=None: capacity)
    remat_policy._logged.clear()
    get_recorder().clear()
    model = MODELS.get("GPT2")(
        size="gpt2-large", n_layer=6, bfloat16=True, attn_impl="flash",
        remat=True, fused_head=True, dropout=0.0)
    tx = optax.adamw(1e-4)
    batch, seq = 8 * accum, 1024
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: create_train_state(
            model, tx, np.zeros((1, seq), np.int32), seed=0)))
    feed = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                           sharding=one_chip),
            "mask": jax.ShapeDtypeStruct((batch,), jnp.bool_,
                                         sharding=one_chip)}
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
