"""The v5e compile of `solar_open2_l4.seq8k`'s whole training step at its
real shape, without a chip (tests/chip_compile_common.py says how): a
file of its own, beside test_chip_compile.py's three, so that the
longest compiles of the suite do not queue on one xdist worker. The
tests here read one compile (`solar_step`).
"""
import re

import pytest

from chip_compile_common import (  # noqa: F401  (fixtures by name)
    V5E_BYTES_LIMIT, _copies_of, _grouped_products_under, _optimizer_reads,
    _said, _step_compiled_once, topo,
)


@pytest.fixture(scope="module")
def solar_step(topo):
    """The cell's step, compiled once for the tests that read it."""
    import json
    from pathlib import Path

    from pytorch_distributed_template_tpu.config.registry import MODELS
    import pytorch_distributed_template_tpu.models  # noqa: F401

    arch = json.loads((
        Path(__file__).resolve().parent.parent / "benchmarks" / "configs"
        / "solar_open2_l4.json").read_text())["experiment"]["arch"]
    return _step_compiled_once(MODELS.get(arch["type"])(**arch["args"]),
                               topo, 1, 8192)


def test_delta_rule_step_lowers_and_fits_for_v5e(solar_step):
    """`solar_open2_l4.seq8k`'s step (1 x 8192 on one chip): three KDA
    blocks and a gated attention block, each with 8 held of 320 gated
    experts, compile for the v5e with plain XLA for the scan (its
    triangular system, its loop over 128 chunks), the three flash kernels
    at 8 heads on one key-value head and nine convolution kernels without
    a bias; the step stays 1 GiB under the chip's `bytes_limit` with
    840.9 M parameters held.

    A token takes 8 of 320 experts, so uniform routing gives it 0.2 of the
    8 held here: one place a token in a room of 8192 rows
    (`moe.token_places`), an eighth of every held expert over every
    token, and the routed experts' products run over the pairs (ISSUE
    51): nine grouped products a layer under `moe_experts`, no array a
    held expert by every token wide made anywhere, and the checkpoint
    policy keeps every name both kinds of block make: the two first
    products are 21 MB a layer each where they were 168 (0.905 GB kept
    where it was 2.076, of a budget 0.586 GB larger)."""
    text, said = solar_step.text, solar_step.said
    assert "triangular-solve" not in text
    for kernel in ("flash_fwd", "flash_dkv", "flash_dq"):
        assert len(re.findall(rf"%{kernel}(\.\d+)? = ", text)) == 1
    assert len(re.findall(r"%ssm_conv_bwd(\.\d+)? = ", text)) == 9
    # a layer: three products forward and a rows' gradient each backward
    # over the room's rows; a matrix's gradient each, as the leaf lies
    products = _grouped_products_under(text, "moe_experts")
    assert sorted(products) == sorted(4 * (
        3 * ["bf16[8192,1280]"] + 2 * ["bf16[8192,4096]"]
        + ["f32[8192,4096]"] + 2 * ["bf16[8,4096,1280]"]
        + ["bf16[8,1280,4096]"]))
    # the kernels that are not the grouped products' (each of those brings
    # one that lays out its groups)
    assert text.count("tpu_custom_call") - len(re.findall(
        r"%ragged-dot[-\w.]* = [^\n]*tpu_custom_call", text)) == 12
    assert not re.search(r"\[8,8192,1280\]|\[8192,8,1280\]|\[8,1280,8192\]"
                         r"|\[65536,(1280|4096)\]", text)
    (policy,) = _said("remat/policy", said)
    assert policy["blocks"] == 4
    assert abs(policy["held_bytes"] - 840_875_672 * 12) < 64
    assert policy["names"] == (
        "attn_out,attn_lse,moe_router,moe_pairs,qkv_proj,attn_gate,"
        "attn_proj,kda_in_proj,kda_out_proj,mlp_gate,mlp_up,attn_qkv,"
        "moe_experts_gate,moe_experts_up")
    assert policy["kept_bytes"] == 904_593_408 <= policy["budget_bytes"]
    assert abs(policy["budget_bytes"] - 3_038_465_760) < 64
    (chunks,) = _said("kda/chunks", said)
    assert chunks == dict(chunk=64, sub_chunk=16, chunks=128, heads=8,
                          pair_bytes=8192 * 8 * 16 * 128 * 4)
    (pattern,) = _said("model/pattern", said)
    assert pattern["pattern"] == "*KKK" and pattern["held"] == 8
    dispatch = [d for d in _said("moe/dispatch", said)
                if d["tokens"] == 8192]
    assert dispatch == [dict(tokens=8192, held=8, routed=320, top_k=8,
                             expected=1638.4, rows=8192, experts="gated",
                             dense_rows=65536)]
    (conv,) = _said("ssm/conv", said)
    assert (conv["channels"], conv["positions"], conv["backward"]) == (
        1024, 8192, "kernel")
    (head,) = _said("head_loss/slice", said)
    assert head["gradients"] == "forward"
    print(f"delta-rule step: {solar_step.total_bytes} bytes compiled, "
          f"policy {policy}")
    # under what the step over every token compiled to (PR 46-50), and
    # with it 1 GiB under the chip's limit
    assert solar_step.total_bytes < 15_016_682_496 < V5E_BYTES_LIMIT - (
        1 << 30)


def test_the_optimizer_reads_the_expert_matrices_where_they_lie(solar_step):
    """The optimizer's fusion runs in its gradient's order, and the jit's
    arguments and donated results are row-major: a gradient that left the
    backward as `[E][F][D]` had the parameter, `mu` and `nu` of
    `experts_gate` and `experts_up` each copied into that order and the
    three results back, 48 copies of 168 MB a step (24.6 ms of 394 on the
    chip, PR 46). Over the pairs the matrices' gradients leave the
    grouped products as the leaves are stored, so no float32 array of an
    expert matrix's shape is copied, and the pass that updates each of
    the twelve matrices takes the jit's own three arguments."""
    copies = _copies_of(solar_step.text, (8, 4096, 1280), (8, 1280, 4096))
    # the backward's rows' gradients take each matrix transposed: its cast
    # to bfloat16 writes it in that order, one pass a matrix as a cast is
    assert len(copies) == 4 * 3 and all(c.startswith("bf16[") for c in copies)
    reads = _optimizer_reads(solar_step.text, r"experts_(gate|up|down)__")
    assert len(reads) == 4 * 3 * 3              # layers, matrices, holders
    for parameter, results in reads.items():
        wide = (8, 1280, 4096) if "experts_down" in parameter else (
            8, 4096, 1280)
        assert results.count(list(wide)) == 3, (parameter, results)
