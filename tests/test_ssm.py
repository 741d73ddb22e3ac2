"""ops/ssm.py: the chunked Mamba-2 scan against the recurrence it stands
for, one position at a time.

The forms under test run jitted, as the models run them: a case is then
one compile at its shapes, where operation by operation it was a compile
a primitive and three to thirteen seconds. The references (the
recurrence, the four-slice convolution) are called as they are written;
their gradients are jitted too, which changes no line of them. The cases
that compare bit for bit stay operation by operation on both sides."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_template_tpu.ops.ssm import ssd_recurrence, ssd_scan


def operands(t, b=2, h=4, p=8, g=2, n=16, dtype=jnp.float32, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (b, t, h, p), dtype),
            jax.nn.softplus(jax.random.normal(k[1], (b, t, h)) - 2.0),
            -jnp.exp(0.5 * jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (b, t, g, n), dtype),
            jax.random.normal(k[4], (b, t, g, n), dtype),
            jax.random.normal(k[5], (h,)))


scan = jax.jit(ssd_scan, static_argnums=6)


def grads_through(form):
    """The jitted gradient, by all six operands, of `sum(sin(form))`."""
    return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(form(*a))),
                            argnums=range(6)))


# 37 and 21: lengths the chunk of 16 does not divide (two chunks and a
# part, one and a part); 16 and 48: whole chunks; 5: less than one
@pytest.mark.parametrize("t,chunk", [(37, 16), (21, 16), (16, 16), (48, 16),
                                     (5, 16), (40, 8)])
def test_chunked_scan_is_the_recurrence(t, chunk):
    args = operands(t)
    with jax.default_matmul_precision("highest"):
        got = scan(*args, chunk)
        want = ssd_recurrence(*args)
    assert got.shape == want.shape == (2, t, 4, 8)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.max(jnp.abs(want))))


# granite-4.0-h-micro's layout: 64 heads that all read ONE group's B and
# C (r = 64), the published chunk of 256; two chunks and a part, and two
# whole ones
@pytest.mark.parametrize("t", [600, 512])
def test_sixty_four_heads_in_one_group_at_chunk_256(t):
    args = operands(t, b=1, h=64, p=16, g=1, n=32, seed=11)
    with jax.default_matmul_precision("highest"):
        got = scan(*args, 256)
        want = ssd_recurrence(*args)
    assert got.shape == want.shape == (1, t, 64, 16)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.max(jnp.abs(want))))


def test_sixty_four_heads_in_one_group_have_the_recurrences_gradient():
    args = operands(300, b=1, h=64, p=8, g=1, n=16, seed=13)
    with jax.default_matmul_precision("highest"):
        got = grads_through(lambda *a: ssd_scan(*a, 256))(*args)
        want = grads_through(ssd_recurrence)(*args)
    for name, g, w in zip("x dt a b c d".split(), got, want):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=2e-5 * float(jnp.max(jnp.abs(w))),
            err_msg=name)


@pytest.mark.parametrize("t,chunk", [(37, 16), (16, 16)])
def test_chunked_scan_has_the_recurrences_gradient(t, chunk):
    args = operands(t, seed=3)
    with jax.default_matmul_precision("highest"):
        got = grads_through(lambda *a: ssd_scan(*a, chunk))(*args)
        want = grads_through(ssd_recurrence)(*args)
    for name, g, w in zip("x dt a b c d".split(), got, want):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=2e-5 * float(jnp.max(jnp.abs(w))),
            err_msg=name)


def test_the_state_is_carried_from_chunk_to_chunk():
    """With a slow decay an input in the first chunk is still read in the
    third: cut there, the output differs."""
    x, dt, a, b, c, d = operands(48, seed=5)
    a = jnp.full_like(a, -0.01)
    with jax.default_matmul_precision("highest"):
        whole = scan(x, dt, a, b, c, d, 16)
        cut = scan(x.at[:, :16].set(0.0), dt, a, b, c, d, 16)
    assert float(jnp.max(jnp.abs(whole[:, 32:] - cut[:, 32:]))) > 1e-2


def test_bfloat16_operands_keep_their_type_and_stay_close():
    args = operands(64, dtype=jnp.bfloat16, seed=7)
    got = scan(*args, 16)
    assert got.dtype == jnp.bfloat16
    want = ssd_recurrence(*args)
    err = jnp.abs(got.astype(jnp.float32) - want)
    assert float(jnp.max(err)) < 0.05 * float(jnp.max(jnp.abs(want)))


# -- the depthwise causal convolution with its bias and silu ----------------


def four_slices(xbc, taps, bias):
    """The convolution as models/mixers.py's `Mamba2Mixer` wrote it out
    before ISSUE 39: the input widened and padded, one shifted slice a
    tap, jax's own backward."""
    k, t = taps.shape[0], xbc.shape[1]
    padded = jnp.pad(xbc.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(bias + sum(
        taps[i] * padded[:, i:i + t] for i in range(k))).astype(xbc.dtype)


def conv_operands(b, t, c, k, dtype, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(keys[0], (b, t, c), dtype),
            0.5 * jax.random.normal(keys[1], (k, c)),
            0.1 * jax.random.normal(keys[2], (c,)),
            jax.random.normal(keys[3], (b, t, c), dtype))


def conv_loss(conv, weight):
    return lambda *a: jnp.sum(conv(*a).astype(jnp.float32)
                              * weight.astype(jnp.float32))


def assert_conv_gradients_close(got, want, positions, dtype):
    """(input's, taps', bias's) gradients: the input's to one unit in the
    last place of the type it is rounded to, the sums to float32's over
    `positions` products of size one."""
    ulp = float(jnp.finfo(dtype).eps)
    for name, g, w, tol in zip(("input", "taps", "bias"), got, want,
                               (max(ulp, 2e-6), 2e-6, 2e-6)):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert g.shape == w.shape, name
        np.testing.assert_allclose(
            g, w, rtol=0, err_msg=name,
            atol=tol * max(1.0, float(jnp.max(jnp.abs(w))))
            * (1 if name == "input" else positions ** 0.5))


# the two cells' widths at a short length, an odd handful of channels;
# lengths no block of rows divides, one shorter than the taps, one row
CONV_CASES = {
    "granite-width": (1, 24, 4352, 4), "hybrid-width": (2, 40, 1280, 4),
    "odd-handful": (2, 37, 5, 4), "no-block-divides": (1, 300, 24, 4),
    "shorter-than-the-taps": (2, 3, 7, 4), "two-taps": (2, 37, 24, 2),
    "one-position-two-taps": (1, 1, 3, 2), "whole-blocks": (2, 512, 128, 4),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("b,t,c,k", CONV_CASES.values(),
                         ids=CONV_CASES.keys())
def test_convolution_is_the_four_slice_form(b, t, c, k, dtype):
    from pytorch_distributed_template_tpu.ops.ssm import causal_conv_silu

    xbc, taps, bias, weight = conv_operands(b, t, c, k, dtype)
    got = jax.jit(causal_conv_silu)(xbc, taps, bias)
    want = four_slices(xbc, taps, bias)
    assert got.dtype == want.dtype == dtype and got.shape == (b, t, c)
    # one unit in the last place of the type it is rounded to
    ulp = float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), rtol=ulp,
        atol=1e-6)
    got = jax.jit(jax.grad(conv_loss(causal_conv_silu, weight), (0, 1, 2)))(
        xbc, taps, bias)
    want = jax.jit(jax.grad(conv_loss(four_slices, weight), (0, 1, 2)))(
        xbc, taps, bias)
    assert got[0].dtype == dtype and got[1].dtype == got[2].dtype \
        == jnp.float32
    assert_conv_gradients_close(got, want, b * t, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_nothing_crosses_from_one_row_of_the_batch_to_the_next(dtype):
    from pytorch_distributed_template_tpu.ops.ssm import causal_conv_silu

    xbc, taps, bias, weight = conv_operands(2, 21, 12, 4, dtype, seed=3)
    other = xbc.at[0].set(conv_operands(1, 21, 12, 4, dtype, seed=4)[0][0])

    def both(x):
        return (causal_conv_silu(x, taps, bias),
                jax.grad(conv_loss(causal_conv_silu, weight))(x, taps, bias))

    (y, dx), (y_other, dx_other) = both(xbc), both(other)
    # row 0 changed: row 1's output and input gradient are bit for bit
    # what they were, and row 0's are not
    for a, a_other in ((y, y_other), (dx, dx_other)):
        np.testing.assert_array_equal(np.asarray(a[1], np.float32),
                                      np.asarray(a_other[1], np.float32))
        assert np.any(np.asarray(a[0], np.float32)
                      != np.asarray(a_other[0], np.float32))
    # and each row starts from zeros: position 0 sees the last tap alone
    np.testing.assert_allclose(
        np.asarray(y[:, 0], np.float32),
        np.asarray(jax.nn.silu(bias + taps[-1] * xbc[:, 0].astype(
            jnp.float32)).astype(dtype), np.float32), rtol=0, atol=0)


def test_convolutions_gradient_is_the_same_under_the_mixers_checkpoint():
    from jax.ad_checkpoint import checkpoint_name

    from pytorch_distributed_template_tpu.ops.ssm import causal_conv_silu

    xbc, taps, bias, weight = conv_operands(2, 37, 24, 4, jnp.bfloat16,
                                            seed=5)

    def block(x, taps, bias):
        # as models/mixers.py's `Mamba2Mixer`: it reads a slice of the kept
        # projection, and its own output is recomputed
        zxd = checkpoint_name(jnp.concatenate([x, x * 0.5, x], -1),
                              "ssm_in_proj")
        y = causal_conv_silu(zxd[..., 24:48] * 2.0, taps, bias)
        return jnp.sum(jnp.tanh(y.astype(jnp.float32))
                       * weight.astype(jnp.float32))

    kept = jax.checkpoint(
        block, policy=jax.checkpoint_policies.save_only_these_names(
            "ssm_in_proj"))
    want = jax.grad(block, (0, 1, 2))(xbc, taps, bias)
    got = jax.grad(kept, (0, 1, 2))(xbc, taps, bias)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    # what the rule keeps from forward to backward is what it was given:
    # the input as it came, the taps, the bias; no pre-activation
    from jax._src.ad_checkpoint import saved_residuals

    kept = saved_residuals(lambda *a: jnp.sum(causal_conv_silu(*a)),
                           xbc, taps, bias)
    assert [(r.dtype, r.shape) for r, _ in kept] == [
        (jnp.bfloat16, (2, 37, 24)), (jnp.float32, (4, 24)),
        (jnp.float32, (24,))]
    assert all("from the argument" in why for _, why in kept)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_convolution_reads_its_channels_where_they_lie(dtype):
    """`start`: the convolution of the channels `[start, start + C)` of a
    wider array is the convolution of that slice, and the wider array's
    gradient is the slice's between zeros."""
    from pytorch_distributed_template_tpu.ops.ssm import causal_conv_silu

    zxd, taps, bias, weight = conv_operands(2, 37, 40, 4, dtype, seed=7)
    taps, bias, weight = taps[:, :24], bias[:24], weight[..., :24]
    got = causal_conv_silu(zxd, taps, bias, 8)
    want = causal_conv_silu(zxd[..., 8:32], taps, bias)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    got = jax.grad(conv_loss(lambda *a: causal_conv_silu(*a, 8), weight),
                   (0, 1, 2))(zxd, taps, bias)
    want = jax.grad(conv_loss(causal_conv_silu, weight), (0, 1, 2))(
        zxd[..., 8:32], taps, bias)
    assert got[0].shape == zxd.shape and got[0].dtype == dtype
    np.testing.assert_array_equal(np.asarray(got[0][..., 8:32], np.float32),
                                  np.asarray(want[0], np.float32))
    assert not np.any(np.asarray(got[0][..., :8], np.float32))
    assert not np.any(np.asarray(got[0][..., 32:], np.float32))
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(g, w)


# the backward kernel, interpreted: blocks smaller than the shapes, so
# that taps reach across the blocks' edges on both sides; the channels
# read where they lie (`start` a whole block) and cut out (not one);
# lengths and widths no block divides
KERNEL_CASES = {
    "three-blocks-of-positions": ((128, 128), 2, 384, 128, 4, 0, 128),
    "where-they-lie": ((128, 128), 1, 256, 256, 4, 128, 520),
    "cut-out-and-padded": ((128, 256), 2, 300, 72, 4, 24, 100),
    "two-taps": ((128, 128), 1, 260, 128, 2, 0, 128),
    "by-the-shapes-own-blocks": (None, 2, 37, 24, 4, 0, 24),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("blocks,b,t,c,k,start,width", KERNEL_CASES.values(),
                         ids=KERNEL_CASES.keys())
def test_backward_kernel_is_the_four_slice_forms_gradient(
        blocks, b, t, c, k, start, width, dtype, monkeypatch):
    from pytorch_distributed_template_tpu.ops import ssm

    if blocks:
        monkeypatch.setattr(ssm, "conv_blocks", lambda t, c, size: blocks)
    zxd, taps, bias, dy = conv_operands(b, t, width, k, dtype, seed=9)
    taps, bias, dy = taps[:, :c], bias[:c], dy[..., :c]
    # a new jitted function a case: the blocks are read while it is traced
    got = jax.jit(functools.partial(ssm._conv_bwd_pallas, interpret=True),
                  static_argnums=1)(zxd, start, taps, bias, dy)
    want = jax.jit(jax.grad(conv_loss(four_slices, dy), (0, 1, 2)))(
        zxd[..., start:start + c], taps, bias)
    assert_conv_gradients_close(got, want, b * t, dtype)


@pytest.mark.parametrize("t,c,size,want", [
    (8192, 4352, 2, (128, 2048)),      # granite's layer
    (8192, 1280, 2, (128, 2048)),      # the hybrid's
    (8192, 4352, 4, (128, 1536)),      # float32: fewer positions
    (40, 96, 4, (96, 128)),            # a debug config: one block
    (300, 384, 2, (128, 384)),         # three blocks of 128 channels
    (3, 5, 2, (16, 128)),              # an odd handful: one sublane tile
])
def test_convolutions_blocks_follow_the_shapes(t, c, size, want):
    from pytorch_distributed_template_tpu.ops.ssm import conv_blocks

    assert conv_blocks(t, c, size) == want


@pytest.mark.parametrize("axes,batch", [({"data": 4, "tensor": 2}, 4),
                                        ({"data": 2, "fsdp": 4}, 8),
                                        ({"data": 8}, 2)],
                         ids=["data-and-tensor", "data-and-fsdp",
                              "batch-the-axes-do-not-divide"])
def test_convolution_under_a_mesh_is_the_one_devices(axes, batch):
    """`sharded_conv_silu`: the batch over the data axes inside
    `shard_map` (whole where they do not divide it), the parameters whole
    on every device, their gradients summed over the devices."""
    from pytorch_distributed_template_tpu.ops.ssm import (
        causal_conv_silu, sharded_conv_silu,
    )
    from pytorch_distributed_template_tpu.parallel import build_mesh

    mesh = build_mesh(axes)
    zxd, taps, bias, weight = conv_operands(batch, 21, 40, 4, jnp.float32,
                                            seed=11)
    taps, bias, weight = taps[:, :24], bias[:24], weight[..., :24]

    def on_mesh(z, w, b):
        return sharded_conv_silu(z, w, b, 8, mesh)

    got = jax.jit(jax.value_and_grad(conv_loss(on_mesh, weight), (0, 1, 2)))(
        zxd, taps, bias)
    want = jax.value_and_grad(
        conv_loss(lambda *a: causal_conv_silu(*a, 8), weight), (0, 1, 2))(
        zxd, taps, bias)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=0, atol=2e-6 * max(
            1.0, float(jnp.max(jnp.abs(w)))))
    assert sharded_conv_silu(zxd, taps, bias, 8, None).shape == (
        batch, 21, 24)


# -- the convolution without bias or activation (a gated short convolution's)


def _conv_by_positions(x, taps):
    """`sum_i taps[i] * x[t - (k - 1) + i]`, one position at a time."""
    k, t = taps.shape[0], x.shape[1]
    rows = []
    for at in range(t):
        total = jnp.zeros_like(x[:, 0])
        for i in range(k):
            if at - (k - 1) + i >= 0:
                total = total + taps[i] * x[:, at - (k - 1) + i]
        rows.append(total)
    return jnp.stack(rows, axis=1)


@pytest.mark.parametrize("t", [1, 2, 3, 7, 33])
@pytest.mark.parametrize("k", [3, 4])
def test_the_plain_convolution_is_the_loop_over_positions(t, k):
    """Outputs and both gradients, at lengths under, at and over the
    taps' reach and at an odd one; float32 inside, the input's type
    outside; no bias, no activation."""
    from pytorch_distributed_template_tpu.ops.ssm import causal_conv

    key = jax.random.key(t * 10 + k)
    x = jax.random.normal(jax.random.fold_in(key, 0), (2, t, 5), jnp.float32)
    taps = jax.random.normal(jax.random.fold_in(key, 1), (k, 5), jnp.float32)
    ct = jax.random.normal(jax.random.fold_in(key, 2), (2, t, 5), jnp.float32)
    got, pull = jax.vjp(jax.jit(causal_conv), x, taps)
    want, pull_want = jax.vjp(jax.jit(_conv_by_positions), x, taps)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for g, w in zip(pull(ct), pull_want(ct)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
    # linear: twice the input is twice the output, and zeros give zeros
    np.testing.assert_allclose(causal_conv(2 * x, taps), 2 * got, rtol=1e-5,
                               atol=1e-6)
    assert not np.any(np.asarray(causal_conv(jnp.zeros_like(x), taps)))
    low = causal_conv(x.astype(jnp.bfloat16), taps)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(low.astype(jnp.float32), want, rtol=0.02,
                               atol=0.05)


def test_the_gated_mixer_is_its_three_lines():
    """`ShortConvMixer`: `[B, C, z] = split3(in_proj(h))`,
    `out_proj(C * conv(B * z))`, against the loop."""
    from pytorch_distributed_template_tpu.models.mixers import ShortConvMixer

    mixer = ShortConvMixer(6, 3, jnp.float32)
    h = jax.random.normal(jax.random.key(4), (2, 9, 6), jnp.float32)
    held = jax.jit(mixer.init)(jax.random.key(5), h)["params"]
    assert {k: jax.tree.leaves(v)[0].shape for k, v in held.items()} == {
        "in_proj": (6, 18), "conv_kernel": (3, 6), "out_proj": (6, 6)}
    gate_in, gate_out, z = jnp.split(h @ held["in_proj"]["kernel"], 3, -1)
    want = (gate_out * _conv_by_positions(gate_in * z, held["conv_kernel"])
            ) @ held["out_proj"]["kernel"]
    got = jax.jit(lambda p: mixer.apply({"params": p}, h))(held)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
