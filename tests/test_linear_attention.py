"""ops/linear_attention.py: the chunked gated delta rule against the
recurrence it stands for, one position at a time; and ops/ssm's
convolution without a bias, which the KDA mixer calls three times.

The chunked form runs jitted, as the mixer runs it: a case is one compile
at its shapes, where operation by operation it was a compile a primitive
and up to forty seconds. `kda_recurrence` is called as it is written; the
gradients of both go through one jitted helper."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_template_tpu.ops import linear_attention, ssm
from pytorch_distributed_template_tpu.ops.linear_attention import (
    _unit_lower_inverse, kda_chunked, kda_recurrence,
)


def operands(t, b=2, h=3, dk=16, dv=12, rate=1.0, step=0.01, beta_shift=0.0,
             dtype=jnp.float32, seed=0):
    """As the mixer makes them: q and k of unit length (q times
    dk ** -0.5), the log decay ``-rate * softplus(.)`` round ``-rate *
    step`` a channel, beta in (0, 2)."""
    k = jax.random.split(jax.random.key(seed), 5)

    def unit(z):
        return z / jnp.linalg.norm(z, axis=-1, keepdims=True)

    q = unit(jax.random.normal(k[0], (b, t, h, dk))) * dk ** -0.5
    key = unit(jax.random.normal(k[1], (b, t, h, dk)))
    v = jax.random.normal(k[2], (b, t, h, dv))
    g = -rate * jax.nn.softplus(
        jax.random.normal(k[3], (b, t, h, dk)) + np.log(np.expm1(step)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(k[4], (b, t, h)) + beta_shift)
    return q.astype(dtype), key.astype(dtype), v.astype(dtype), g, beta


def close(got, want, tol, name=""):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=0,
        atol=tol * float(jnp.max(jnp.abs(want))), err_msg=name)


kda = jax.jit(kda_chunked, static_argnums=(5, 6))


def grads(scan, args):
    return jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(scan(*a))),
                            argnums=range(5)))(*args)


# 37 and 21: lengths the chunk of 16 does not divide; 16 and 48: whole
# chunks; 5: less than one; (150, 64): sub-chunks of 16 inside a chunk;
# (70, 32, 8): other powers of two
@pytest.mark.parametrize("t,chunk,sub", [
    (37, 16, 16), (21, 16, 16), (16, 16, 16), (48, 16, 16), (5, 16, 16),
    (150, 64, 16), (70, 32, 8)])
def test_chunked_rule_is_the_recurrence(t, chunk, sub):
    args = operands(t)
    with jax.default_matmul_precision("highest"):
        got = kda(*args, chunk, sub)
        want = kda_recurrence(*args)
    assert got.shape == want.shape == (2, t, 3, 12)
    close(got, want, 2e-5)


@pytest.mark.parametrize("t,chunk", [(150, 64), (37, 16)])
def test_chunked_rule_has_the_recurrences_gradients(t, chunk):
    args = operands(t, seed=3)
    with jax.default_matmul_precision("highest"):
        got = grads(lambda *a: kda_chunked(*a, chunk), args)
        want = grads(kda_recurrence, args)
    for name, g, w in zip("q k v g beta".split(), got, want):
        close(g, w, 2e-5, name)


def naive(q, k, v, g, beta, chunk=64):
    """The factorised form, one chunk: ``(K exp(G)) (K exp(-G))^T``."""
    run = jnp.cumsum(g[:, :chunk], axis=1)
    return jnp.einsum("bthd,bshd->bhts", k[:, :chunk] * jnp.exp(run),
                      k[:, :chunk] * jnp.exp(-run))


def test_where_the_factorised_form_overflows_the_sub_chunks_are_exact():
    """A rate of 16 at a step of 0.1, the strong end of what the family
    draws: a chunk's summed log decay passes -88 and exp(-G) is not
    finite in float32; this form agrees with the recurrence to float32's
    rounding, outputs and gradients."""
    args = operands(192, rate=16.0, step=0.1, seed=1)
    summed = jnp.sum(args[3][:, :64], axis=1)
    assert float(jnp.min(summed)) < -100 and float(jnp.max(summed)) < -88
    assert not bool(jnp.all(jnp.isfinite(naive(*args))))
    with jax.default_matmul_precision("highest"):
        got, want = kda(*args), kda_recurrence(*args)
        assert bool(jnp.all(jnp.isfinite(got)))
        close(got, want, 2e-5)
        for name, g, w in zip("q k v g beta".split(),
                              grads(kda_chunked, args),
                              grads(kda_recurrence, args)):
            assert bool(jnp.all(jnp.isfinite(g))), name
            close(g, w, 2e-5, name)


def test_beta_near_two_and_keys_that_repeat():
    """beta over 1.9 on most positions and every fourth key the same:
    the entries of the triangular system are as large as they get, and
    the eigenvalue 1 - beta of a step is near -1."""
    q, k, v, g, beta = operands(130, beta_shift=4.0, seed=5)
    assert float(jnp.mean(beta)) > 1.9
    k = k.at[:, 4::4].set(k[:, :1])
    args = (q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        close(kda(*args), kda_recurrence(*args), 5e-5)
        for name, got, want in zip("q k v g beta".split(),
                                   grads(kda_chunked, args),
                                   grads(kda_recurrence, args)):
            close(got, want, 1e-4, name)


def test_bfloat16_operands_stay_near_the_float32_recurrence():
    """The products in bfloat16 (8 bits of mantissa: 4e-3 a rounding),
    summed in float32, over sums of up to 64 terms and a state carried
    through five chunks; g, beta and the state float32: within 3% of the
    largest output. The float32 path stays at 2e-5 (above)."""
    args = operands(300, dtype=jnp.bfloat16, seed=7)
    got = kda(*args)
    assert got.dtype == jnp.bfloat16
    close(got, kda_recurrence(*args), 3e-2)


def test_padding_passes_the_state_unchanged():
    """Positions of g = 0 and beta = 0 neither decay nor write."""
    args = operands(40, seed=9)
    with jax.default_matmul_precision("highest"):
        whole = kda(*args, 16)
        # the same 40 positions with 8 idle ones in front of the last 8
        idle = [jnp.concatenate([z[:, :32], jnp.zeros_like(z[:, :8]),
                                 z[:, 32:]], axis=1) for z in args]
        spread = kda(*idle, 16)
    close(spread[:, :32], whole[:, :32], 2e-5)
    close(spread[:, 40:], whole[:, 32:], 2e-5)


def test_unit_lower_inverse_is_the_inverse():
    low = jnp.tril(2.0 * jax.random.normal(jax.random.key(0), (3, 2, 32, 32)),
                   -1) / 4
    with jax.default_matmul_precision("highest"):
        inv = _unit_lower_inverse(low)
        eye = jnp.eye(32)
        np.testing.assert_allclose(
            jnp.einsum("...ab,...bc->...ac", inv, eye + low),
            jnp.broadcast_to(eye, low.shape), atol=2e-4)
    assert not np.any(np.triu(np.asarray(inv), 1))


def test_chunk_and_sub_chunk_are_powers_of_two():
    args = operands(16)
    with pytest.raises(ValueError, match="powers of two"):
        kda_chunked(*args, 48, 16)
    with pytest.raises(ValueError, match="powers of two"):
        kda_chunked(*args, 64, 12)


def test_the_line_says_chunks_and_the_largest_intermediate(caplog):
    import logging

    from pytorch_distributed_template_tpu.observability import trace
    trace._said.clear()
    with caplog.at_level(logging.INFO, logger=linear_attention.__name__):
        # traced alone: the line is said where the shapes are read
        jax.eval_shape(lambda: kda_chunked(
            *operands(150, b=1, h=2, dk=8, dv=8), 64, 16))
    said = [r for r in caplog.records if r.msg.startswith("kda/chunks")]
    assert len(said) == 1
    record = said[0].args
    assert record["chunks"] == 3 and record["sub_chunk"] == 16
    assert record["pair_bytes"] == 1 * 3 * 2 * 64 * 16 * 8 * 4


# -- the convolution without a bias ------------------------------------------


def test_convolution_without_a_bias_is_the_one_with_a_zero_bias():
    k = jax.random.split(jax.random.key(2), 3)
    z = jax.random.normal(k[0], (2, 50, 24))
    taps = 0.5 * jax.random.normal(k[1], (4, 24))
    dy = jax.random.normal(k[2], (2, 50, 24))
    zero = jnp.zeros((24,))
    got, back = jax.vjp(lambda z, w: ssm.causal_conv_silu(z, w, None), z,
                        taps)
    want, back0 = jax.vjp(ssm.causal_conv_silu, z, taps, zero)
    np.testing.assert_array_equal(got, want)
    for g, w in zip(back(dy), back0(dy)[:2]):
        np.testing.assert_allclose(g, w, atol=1e-6)


def test_the_kernels_backward_takes_no_bias_either(monkeypatch):
    """On the TPU the rule hands the kernel zeros it makes itself; here
    the kernel is interpreted."""
    import functools

    monkeypatch.setattr(ssm.flash, "_on_tpu", lambda: True)
    monkeypatch.setattr(ssm, "_conv_bwd_pallas", functools.partial(
        ssm._conv_bwd_pallas, interpret=True))
    k = jax.random.split(jax.random.key(4), 3)
    z = jax.random.normal(k[0], (1, 256, 16))
    taps = 0.5 * jax.random.normal(k[1], (4, 16))
    dy = jax.random.normal(k[2], (1, 256, 16))
    got = jax.vjp(lambda z, w: ssm.causal_conv_silu(z, w, None), z,
                  taps)[1](dy)
    want = ssm._conv_bwd_xla(z, taps, None, dy)
    assert want[2] is None and len(got) == 2
    for g, w in zip(got, want[:2]):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()))
