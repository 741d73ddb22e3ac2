"""Operations and bytes from shapes. The yardstick for `mfu_pct` and for
a kernel's roofline share: nothing here asks XLA's cost analysis or the
program's own profiler.

Model FLOPs per token are what the forward and backward passes require:
6 per matmul weight per token (2 forward, 4 backward), the head counted,
the embedding lookup not, and attention at 4 x heads x head size x the
keys a query really sees forward, three times that with the backward.
Recomputation (remat, the flash backward's second look at the scores) is
work the program chose, and is not credited.

`sizes` is the `sizes` block of a configuration file; the count of an
architecture's weights sits with its plain reference
(`benchmarks/reference/<arch>.py`: `matmul_weights`, `parameters`).
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
    """`arch` is the configuration's reference module: it gives
    `matmul_weights(sizes)`, the parameters that multiply every token
    (the blocks' matrices and the head, tied or not, without embeddings,
    norms and biases). A new architecture brings its count with its
    reference, and this file stays as it is."""
    attention = 12 * s["n_layer"] * s["d_model"] * mean_visible_keys(
        seq_len, s.get("window", 0))
    return 6 * arch.matmul_weights(s) + attention


# -- the flash attention kernels (ops/flash.py: forward, dkv, dq) ----------
# Per visible (query, key) pair and head: a product with the head size
# costs 2 x head size. Forward: scores and context (2 products). dkv:
# scores again, dP, dV, dK (4). dq: scores again, dP, dQ (3).
_PRODUCTS = {"fwd": 2, "dkv": 4, "dq": 3}
# Whole [B, T, H, D] tensors each call has to move once, in the compute
# type: fwd reads q, k, v and writes o; dkv reads q, k, v, do and writes
# dk, dv; dq reads q, k, v, do and writes dq (lse and delta are 1/D of
# a tensor and left out).
_TENSORS = {"fwd": 4, "dkv": 6, "dq": 5}


def flash_call_flops(kind: str, batch: int, seq_len: int, n_head: int,
                     head_dim: int, window: int = 0) -> float:
    pairs = batch * n_head * seq_len * mean_visible_keys(seq_len, window)
    return _PRODUCTS[kind] * 2 * head_dim * pairs


def flash_call_bytes(kind: str, batch: int, seq_len: int, n_head: int,
                     head_dim: int, itemsize: int = 2) -> float:
    return _TENSORS[kind] * batch * seq_len * n_head * head_dim * itemsize


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """The least time the chip could take, and which bound sets it."""
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops >= by_bytes
                                     else "bytes")
