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


@pytest.mark.parametrize("kind,products,tensors", [
    # products over the query-key size and over the value size; tensors
    # moved at either: worked out in flops.py's comments, again here
    ("fwd", 192 + 128, 2 * 192 + 2 * 128),          # QK', PV; q k, v o
    ("dkv", 2 * 192 + 2 * 128, 3 * 192 + 3 * 128),  # QK' dK, dP dV
    ("dq", 2 * 192 + 128, 3 * 192 + 2 * 128)])      # QK' dQ, dP
def test_flash_counts_with_two_head_sizes_by_hand(kind, products, tensors):
    """A latent-attention call at [1, 8192, 32, 192 / 128], full causal:
    whatever kernel runs it, this is the work."""
    kw = dict(batch=1, seq_len=8192, n_head=32, head_dim=192, v_head_dim=128)
    pairs = 32 * 8192 * 4096.5
    assert flops.flash_call_flops(kind, **kw) == 2 * products * pairs
    assert flops.flash_call_bytes(kind, **kw) == tensors * 8192 * 32 * 2
    # read with the query-key size for both, the same call counts
    # 12.5-20% more operations than it has
    one = dict(kw, v_head_dim=None)
    over = flops.flash_call_flops(kind, **one) / flops.flash_call_flops(
        kind, **kw)
    assert over == {"fwd": 1.2, "dkv": 1.2, "dq": 1.125}[kind]


@pytest.mark.parametrize("kind", ["fwd", "dkv", "dq"])
@pytest.mark.parametrize("head_dim,window", [(128, 4096), (64, 0)])
def test_equal_head_sizes_count_as_before_to_the_last_bit(kind, head_dim,
                                                          window):
    """The parent's expressions, written out: `flash_roofline_pct` in the
    six cells holds only while these do not move."""
    kw = dict(batch=1, seq_len=8192, n_head=32, head_dim=head_dim)
    products = {"fwd": 2, "dkv": 4, "dq": 3}[kind]
    tensors = {"fwd": 4, "dkv": 6, "dq": 5}[kind]
    pairs = 1 * 32 * 8192 * flops.mean_visible_keys(8192, window)
    for v in (None, head_dim):
        assert flops.flash_call_flops(kind, window=window, v_head_dim=v,
                                      **kw) == products * 2 * head_dim * pairs
        assert flops.flash_call_bytes(kind, v_head_dim=v, **kw) == \
            tensors * 1 * 8192 * 32 * head_dim * 2


def test_ssm_conv_bwd_counts_by_hand():
    """Granite's call, [1, 4352, 8192] in bfloat16 with four taps: the
    input and the cotangent in, the input's gradient out (213.9 MB,
    PERF.md's figure), taps and bias in and their gradients out in
    float32 (0.17 MB); 33 operations an entry. The bytes bound it."""
    b, c, t, k = 1, 4352, 8192, 4
    assert flops.ssm_conv_bwd_bytes(b, c, t, k) == \
        3 * 4352 * 8192 * 2 + (4 + 1 + 4 + 1) * 4352 * 4 == 214_083_584
    assert 3 * 4352 * 8192 * 2 == 213_909_504
    # pre-activation 2k, silu' 8, times dy 1, dx 2k - 1, dtaps 2k, dbias 1
    assert flops.ssm_conv_bwd_flops(b, c, t, k) == \
        (8 + 8 + 1 + 7 + 8 + 1) * 4352 * 8192 == 33 * 4352 * 8192
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = flops.roofline_seconds(
        flops.ssm_conv_bwd_flops(b, c, t, k),
        flops.ssm_conv_bwd_bytes(b, c, t, k), peak)
    assert bound == "bytes" and seconds == pytest.approx(0.2614e-3, rel=1e-3)
    # the hybrid's call, two rows of 1280 channels
    assert flops.ssm_conv_bwd_bytes(2, 1280, 8192, 4) == \
        3 * 2 * 1280 * 8192 * 2 + 10 * 1280 * 4
    assert flops.ssm_conv_bwd_bytes(1, 8, 16, 4, itemsize=4) == \
        3 * 8 * 16 * 4 + 10 * 8 * 4


class Weights100:
    @staticmethod
    def matmul_weights(s):
        return 100


class WithMixer(Weights100):
    @staticmethod
    def mixer_flops_per_token(s, seq_len):
        return 7


def test_the_count_follows_the_reference_module():
    """Both halves are the reference's: 6 a weight plus what its mixers
    say, whatever the sizes hold (here nothing)."""
    assert flops.model_flops_per_token(WithMixer, {}, 3) == 6 * 100 + 7


def test_an_architecture_without_its_mixer_count_inherits_none():
    with pytest.raises(AttributeError, match="mixer_flops_per_token"):
        flops.model_flops_per_token(
            Weights100, {"n_layer": 1, "d_model": 8}, 3)


@pytest.mark.parametrize("config,arch,seq_len,count,mfu_per_token_per_s", [
    ("mistral7b_l2", llama, 8192, 3705692160.0, 1.88106e-3),
    ("gpt2_large", gpt2, 1024, 4916098560.0, 2.49548e-3)])
def test_the_cells_counts_stay_to_the_last_digit(config, arch, seq_len, count,
                                                 mfu_per_token_per_s):
    """`mfu_pct` is `tokens_per_s` times this over the chips' peak: the
    ledger's levels hold only while it does not move."""
    assert flops.model_flops_per_token(arch, sizes(config), seq_len) == count
    assert 100.0 * count / 197e12 == pytest.approx(mfu_per_token_per_s,
                                                   rel=2e-6)


@pytest.mark.parametrize("arch,s,seq_len,by_hand", [
    (gpt2, {"n_layer": 3, "d_model": 16}, 8, 12 * 3 * 16 * 4.5),
    (llama, {"n_layer": 2, "d_model": 16, "window": 0}, 8, 12 * 2 * 16 * 4.5),
    (llama, {"n_layer": 2, "d_model": 16, "window": 2}, 4,
     12 * 2 * 16 * (1 + 2 + 2 + 2) / 4)])
def test_each_reference_states_its_mixers_products(arch, s, seq_len, by_hand):
    assert arch.mixer_flops_per_token(s, seq_len) == by_hand


def test_flops_assumes_nothing_about_a_layer():
    """No size of any architecture is named where the count is made."""
    text = Path(flops.__file__).read_text()
    assert "n_layer" not in text and "d_model" not in text
