"""pick_block_sizes: the block rule as the sweep on the chip left it
(ops/flash.py; PERF.md section 6 has the table).

Host-side contract checks. The times behind the rule are measured on
hardware; what is pinned here is the rule's table, and the two
properties the callers lean on: a pair divides the length it is given
or is clipped to it, and the padded length does not depend on anything
but the length (``named_residual_bytes`` reckons it from ``t`` alone).
"""
import pytest

from pytorch_distributed_template_tpu.ops.flash import (
    DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, _padded_len, pick_block_sizes,
    tile_counts,
)

# (t, d) -> (block_q, block_k)
RULE = {
    "gpt2_large.seq1k": ((1024, 64), (1024, 1024)),
    "mistral7b_l2.seq8k": ((8192, 128), (1024, 1024)),
    "mistral7b_l2.seq4k": ((4096, 128), (1024, 1024)),
    "head-128-at-2048": ((2048, 128), (1024, 1024)),
    "head-64-at-4096": ((4096, 64), (1024, 1024)),
    # lengths 1024 does not divide keep the square 512
    "a-ring-block": ((512, 64), (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)),
    "1536": ((1536, 64), (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)),
    "2560": ((2560, 128), (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)),
    "3584": ((3584, 64), (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)),
    "shorter-than-a-block": ((100, 64), (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)),
    "vit-197": ((197, 64), (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)),
}


@pytest.mark.parametrize("shape,blocks", RULE.values(), ids=RULE.keys())
def test_rule_table(shape, blocks):
    t, _ = shape
    assert pick_block_sizes(*shape) == blocks
    # the blocks, clipped to t as the kernels clip them, divide the
    # padded length, which never exceeds the next multiple of 512
    t_pad = _padded_len(t, *blocks)
    assert t_pad % min(blocks[0], t_pad) == 0
    assert t_pad % min(blocks[1], t_pad) == 0
    assert t <= t_pad <= max(t, -(-t // 512) * 512)


@pytest.mark.parametrize("shape,window,most", [
    ((1024, 64), 0, 1.25), ((8192, 128), 4096, 1.07),
    ((4096, 128), 4096, 1.07),
], ids=["gpt2_large.seq1k", "mistral7b_l2.seq8k", "mistral7b_l2.seq4k"])
def test_rule_keeps_the_backward_near_the_seen_scores(shape, window, most):
    """At the rule's blocks the backward kernels, which hold 7 of the 9
    matmuls, compute little more than the scores a query sees."""
    t, d = shape
    counts = tile_counts(t, t, *pick_block_sizes(t, d), True, window)
    for kernel in ("dkv", "dq"):
        assert counts[kernel]["computed_over_useful"] <= most
