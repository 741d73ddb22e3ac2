"""benchmarks/flops.py against counts worked out by hand."""
import json
from pathlib import Path

import pytest

from benchmarks import flops
from benchmarks.reference import gpt2, llama

ROOT = Path(__file__).resolve().parents[2]


def sizes(name):
    return json.loads(
        (ROOT / "benchmarks" / "configs" / f"{name}.json").read_text())["sizes"]


def test_gpt2_large_by_hand():
    s = sizes("gpt2_large")
    d, n, v = 1280, 36, 50257
    # a block: qkv 3d^2 + out d^2 + up 4d^2 + down 4d^2, biases 3d+d+4d+d,
    # two LayerNorms 4d; embeddings; final LayerNorm
    by_hand = n * (12 * d * d + 9 * d + 4 * d) + v * d + 1024 * d + 2 * d
    assert gpt2.parameters(s) == by_hand == 774_030_080
    per_token = 6 * (n * 12 * d * d + d * v) + 12 * n * d * (1024 + 1) / 2
    assert flops.model_flops_per_token(gpt2, s, 1024) == per_token
    assert per_token == pytest.approx(4.916e9, rel=1e-3)


def test_mistral7b_l2_by_hand():
    s = sizes("mistral7b_l2")
    d, ff, v = 4096, 14336, 32000
    block = d * d + 2 * d * 1024 + d * d + 3 * d * ff   # q, k+v, o, SwiGLU
    assert llama.parameters(s) == 2 * block + 2 * v * d + 5 * d
    assert llama.parameters(s) == 698_372_096
    # 8192 positions under a band of 4096: the first 4096 see t+1 keys,
    # the rest 4096
    keys = (4096 * 4097 / 2 + 4096 * 4096) / 8192
    assert flops.mean_visible_keys(8192, 4096) == keys == 3072.25
    per_token = 6 * (2 * block + d * v) + 12 * 2 * d * keys
    assert flops.model_flops_per_token(llama, s, 8192) == per_token
    assert per_token == pytest.approx(3.706e9, rel=1e-3)


def test_agrees_with_chip_smokes_formula_for_gpt2_small():
    m = {"n_layer": 12, "d_model": 768, "vocab_size": 50257, "n_head": 12,
         "max_len": 1024}
    seq = 1024
    smoke = (6 * (12 * m["n_layer"] * m["d_model"] ** 2
                  + m["d_model"] * m["vocab_size"])
             + 6 * m["n_layer"] * seq * m["d_model"])    # chip_smoke.py
    # chip_smoke takes seq/2 keys a query, the exact mean is (seq+1)/2
    assert flops.model_flops_per_token(gpt2, m, seq) == pytest.approx(
        smoke, rel=2e-4)


@pytest.mark.parametrize("seq,window,mean", [
    (4, 0, 2.5), (4, 2, (1 + 2 + 2 + 2) / 4), (4, 4, 2.5), (4, 9, 2.5)])
def test_mean_visible_keys(seq, window, mean):
    assert flops.mean_visible_keys(seq, window) == mean


def test_flash_kernel_counts():
    kw = dict(batch=1, seq_len=8192, n_head=32, head_dim=128)
    pairs = 32 * 8192 * 3072.25
    assert flops.flash_call_flops("fwd", window=4096, **kw) == 4 * 128 * pairs
    assert flops.flash_call_flops("dkv", window=4096, **kw) == 8 * 128 * pairs
    assert flops.flash_call_flops("dq", window=4096, **kw) == 6 * 128 * pairs
    assert flops.flash_call_bytes("fwd", **kw) == 4 * 8192 * 32 * 128 * 2
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flops.roofline_seconds(197e12, 1.0, peak)
    assert (t, bound) == (1.0, "flops")
    assert flops.roofline_seconds(1.0, 819e9, peak) == (1.0, "bytes")


def test_the_count_follows_the_reference_module():
    class Arch:
        @staticmethod
        def matmul_weights(s):
            return 100

    s = {"n_layer": 1, "d_model": 8}
    assert flops.model_flops_per_token(Arch, s, 3) == 600 + 12 * 8 * 2
