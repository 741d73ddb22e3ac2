"""ops/ssm.py: the chunked Mamba-2 scan against the recurrence it stands
for, one position at a time."""
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


# 37 and 21: lengths the chunk of 16 does not divide (two chunks and a
# part, one and a part); 16 and 48: whole chunks; 5: less than one
@pytest.mark.parametrize("t,chunk", [(37, 16), (21, 16), (16, 16), (48, 16),
                                     (5, 16), (40, 8)])
def test_chunked_scan_is_the_recurrence(t, chunk):
    args = operands(t)
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*args, chunk)
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
        got = ssd_scan(*args, 256)
        want = ssd_recurrence(*args)
    assert got.shape == want.shape == (1, t, 64, 16)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=2e-5 * float(jnp.max(jnp.abs(want))))


def test_sixty_four_heads_in_one_group_have_the_recurrences_gradient():
    args = operands(300, b=1, h=64, p=8, g=1, n=16, seed=13)
    loss = lambda scan: lambda *a: jnp.sum(jnp.sin(scan(*a)))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda *a: ssd_scan(*a, 256)),
                       argnums=range(6))(*args)
        want = jax.grad(loss(ssd_recurrence), argnums=range(6))(*args)
    for name, g, w in zip("x dt a b c d".split(), got, want):
        np.testing.assert_allclose(
            g, w, rtol=0, atol=2e-5 * float(jnp.max(jnp.abs(w))),
            err_msg=name)


@pytest.mark.parametrize("t,chunk", [(37, 16), (16, 16)])
def test_chunked_scan_has_the_recurrences_gradient(t, chunk):
    args = operands(t, seed=3)

    def through(scan):
        return lambda *a: jnp.sum(jnp.sin(scan(*a)))

    with jax.default_matmul_precision("highest"):
        got = jax.grad(through(lambda *a: ssd_scan(*a, chunk)),
                       argnums=range(6))(*args)
        want = jax.grad(through(ssd_recurrence), argnums=range(6))(*args)
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
        whole = ssd_scan(x, dt, a, b, c, d, 16)
        cut = ssd_scan(x.at[:, :16].set(0.0), dt, a, b, c, d, 16)
    assert float(jnp.max(jnp.abs(whole[:, 32:] - cut[:, 32:]))) > 1e-2


def test_bfloat16_operands_keep_their_type_and_stay_close():
    args = operands(64, dtype=jnp.bfloat16, seed=7)
    got = ssd_scan(*args, 16)
    assert got.dtype == jnp.bfloat16
    want = ssd_recurrence(*args)
    err = jnp.abs(got.astype(jnp.float32) - want)
    assert float(jnp.max(err)) < 0.05 * float(jnp.max(jnp.abs(want)))
