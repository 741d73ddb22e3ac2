"""Operations and bytes from shapes. The yardstick for `mfu_pct` and for
a kernel's roofline share: nothing here asks XLA's cost analysis or the
program's own profiler.

Model FLOPs per token are what the forward and backward passes require,
and an architecture's plain reference (`benchmarks/reference/<arch>.py`)
states both halves of the count itself: its `matmul_weights` (6 a weight
a token: 2 forward, 4 backward) and its `mixer_flops_per_token` (the
products no weight enters). Recomputation (remat, the flash backward's
second look at the scores) is work the program chose, and is not
credited. This file assumes nothing about what a layer is.

`sizes` is the `sizes` block of a configuration file.
"""
from __future__ import annotations


def mean_visible_keys(seq_len: int, window: int = 0) -> float:
    """Mean over query positions t < seq_len of the keys t sees: t + 1
    under causality, at most `window` under a band."""
    if window <= 0 or window >= seq_len:
        return (seq_len + 1) / 2
    ramp = window * (window + 1) / 2            # positions 0 .. window-1
    return (ramp + (seq_len - window) * window) / seq_len


def model_flops_per_token(arch, s: dict, seq_len: int) -> float:
    """Forward plus backward, for one token of a sequence of `seq_len`.
    `arch` is the configuration's reference module, and owes two counts:

    `matmul_weights(sizes)`: the parameters a token is multiplied by (the
    blocks' matrices and the head, tied or not, without embedding
    lookups, norms and biases). Weights that only some tokens meet count
    by the tokens that meet them: a routed expert by the experts a token
    takes over the published number of experts.

    `mixer_flops_per_token(sizes, seq_len)`: forward plus backward of the
    products that no weight enters (attention's scores and context at
    the keys a query really sees, 12 x heads x head size x
    `mean_visible_keys` a layer; a scan's chunk products), summed over
    the layers that have them.

    There is no fallback: an architecture without either raises, so none
    inherits a count that is not its own."""
    return 6 * arch.matmul_weights(s) + arch.mixer_flops_per_token(s, seq_len)


# -- the flash attention kernels (ops/flash.py: forward, dkv, dq) ----------
# A call of the family has two head sizes: the query-key one, which the
# scores are made over, and the value one, which the context is (the same
# in every kernel the program has today; a latent-attention call has 192
# and 128). Per visible (query, key) pair and head a product costs twice
# the head size it runs over. (Products over the query-key size, over the
# value size). Forward: scores; context. dkv: scores again, dK; dP, dV.
# dq: scores again, dQ; dP.
_PRODUCTS = {"fwd": (1, 1), "dkv": (2, 2), "dq": (2, 1)}
# Whole [B, T, H, D] tensors each call has to move once, in the compute
# type, (at the query-key size, at the value size): fwd reads q, k; v and
# writes o. dkv reads q, k and writes dk; reads v, do and writes dv. dq
# reads q, k and writes dq; reads v, do (lse and delta are 1/D of a
# tensor and left out).
_TENSORS = {"fwd": (2, 2), "dkv": (3, 3), "dq": (3, 2)}


def _widths(table: dict, kind: str, head_dim: int, v_head_dim) -> int:
    at_qk, at_v = table[kind]
    return at_qk * head_dim + at_v * (
        head_dim if v_head_dim is None else v_head_dim)


def flash_call_flops(kind: str, batch: int, seq_len: int, n_head: int,
                     head_dim: int, window: int = 0,
                     v_head_dim: int | None = None) -> float:
    """`head_dim` is the query-key head size; `v_head_dim` the value
    one, where it differs."""
    pairs = batch * n_head * seq_len * mean_visible_keys(seq_len, window)
    return 2 * _widths(_PRODUCTS, kind, head_dim, v_head_dim) * pairs


def flash_call_bytes(kind: str, batch: int, seq_len: int, n_head: int,
                     head_dim: int, itemsize: int = 2,
                     v_head_dim: int | None = None) -> float:
    return (batch * seq_len * n_head
            * _widths(_TENSORS, kind, head_dim, v_head_dim) * itemsize)


# -- the state-space convolution's backward (ops/ssm.py: ssm_conv_bwd) -----
# One call over `[batch, channels, positions]` with `taps` taps a channel
# remakes the pre-activation, and gives the input's gradient, the taps'
# and the bias's. An entry of the input: `taps` multiply-adds for the
# pre-activation (2 taps), silu' as sigmoid (negate, exp, add, reciprocal:
# 4) and sig * (1 + pre * (1 - sig)) (4), its product with the cotangent
# (1), `taps` multiply-adds for the input's gradient (2 taps - 1), `taps`
# for the taps' sums (2 taps), one add for the bias's: 6 taps + 9. None of
# it is a matmul, so against `bf16_flops_per_s` the bytes bound it always.


def ssm_conv_bwd_flops(batch: int, channels: int, positions: int,
                       taps: int) -> float:
    return (6 * taps + 9) * batch * channels * positions


def ssm_conv_bwd_bytes(batch: int, channels: int, positions: int, taps: int,
                       itemsize: int = 2) -> float:
    """The input and the cotangent read once and the input's gradient
    written once, in the compute type; taps and bias read, their
    gradients written, in float32. The kernel moves more: 128 positions
    beside each block of 2048, three times, and a tile of 128 float32 a
    channel for its five sums."""
    return (3 * batch * channels * positions * itemsize
            + (2 * taps + 2) * channels * 4)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")
