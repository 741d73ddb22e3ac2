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
