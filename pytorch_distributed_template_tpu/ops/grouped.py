"""Grouped products: rows laid group by group against a matrix a group.

``rows [M, K]`` hold ``sizes[0]`` rows of group 0, then ``sizes[1]`` of
group 1, and so on; what lies behind the last group is room nobody
filled. ``grouped_matmul(rows, weights, sizes)`` is ``rows[g's rows] @
weights[g]`` a group: the routed experts' products over the pairs of
token and held expert (models/moe.ExpertLayer), one group a held expert.

The three products (the forward, the rows' gradient against the
transposed matrices, a matrix's gradient ``rows^T x cotangent`` a group)
are ``jax.lax.ragged_dot`` and ``ragged_dot_general``, on every backend
and on a mesh of several devices, where the partitioner takes them as it
takes any product. For the v5e the compiler makes each a kernel of its
own with tiles of 512 rows that visits only the tiles a group has a row
in, so the time follows the rows filled and not the room: at 8 114 rows
filled of 32 768, against 16 matrices of 2048 x 1536, the forward 0.74 ms
and 1.89 with every row filled, where the product over every token takes
5.73 (my chip run, PR 50). jax's megablox kernels ``gmm`` and ``tgmm``
with 128-row tiles took 0.50, and the whole step 196.4 ms where this
takes 204.8; they were taken out for what 36 kernels more cost a start
(PERF.md section 6, PR 50, review round).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_ROWS_BY_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_matmul(rows, weights, sizes, out_dtype=None):
    """``rows[g's rows] @ weights[g]`` a group: ``rows [M, K]``,
    ``weights [G, K, N]``, ``sizes [G]`` (int32, their sum at most
    ``M``) -> ``[M, N]`` in ``out_dtype`` (``rows``' own unless given),
    summed in float32. Its backward is ``grouped_matmul_gradients``: the
    cotangent is rounded to the rows' type first, as a dense product's
    is."""
    return jax.lax.ragged_dot(
        rows, weights, sizes,
        preferred_element_type=jnp.dtype(out_dtype or rows.dtype))


def grouped_matmul_gradients(rows, weights, sizes, g):
    """``grouped_matmul``'s backward, for a caller that has a rule of its
    own: the cotangent ``g [M, N]`` of the product -> (the rows'
    gradient ``g @ weights[g].T`` a group, in ``rows``' type; the
    matrices' gradient ``rows[g's rows].T @ g[g's rows]`` a group, in
    ``weights``'), both summed in float32."""
    g = g.astype(rows.dtype)
    d_rows = jax.lax.ragged_dot(g, weights.swapaxes(1, 2), sizes,
                                preferred_element_type=rows.dtype)
    d_weights = jax.lax.ragged_dot_general(
        rows, g, sizes, _ROWS_BY_ROWS, preferred_element_type=weights.dtype)
    return d_rows, d_weights


grouped_matmul.defvjp(
    lambda rows, weights, sizes, out_dtype: (
        grouped_matmul(rows, weights, sizes, out_dtype),
        (rows, weights, sizes)),
    lambda out_dtype, kept, g: (*grouped_matmul_gradients(*kept, g), None))
