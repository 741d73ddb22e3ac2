"""The v5e compile of `solar_open2_l4.seq8k`'s whole training step at its
real shape, without a chip (tests/chip_compile_common.py says how): a
file of its own, beside test_chip_compile.py's two, so that the three
longest compiles of the suite do not queue on one xdist worker. The
tests here read one compile (`solar_step`).
"""
import re

import pytest

from chip_compile_common import (  # noqa: F401  (fixtures by name)
    V5E_BYTES_LIMIT, _copies_of, _optimizer_reads, _said,
    _step_compiled_once, topo,
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
    experts over every token, compile for the v5e with plain XLA for the
    scan (its triangular system, its loop over 128 chunks), the three
    flash kernels at 8 heads on one key-value head and nine convolution
    kernels without a bias; the checkpoint policy reckons both kinds of
    block and keeps every name they make, the routed experts' first two
    products last (1.342 GB of the 2.076 kept); the step stays 1 GiB under
    the chip's `bytes_limit` with 840.9 M parameters held."""
    text, said = solar_step.text, solar_step.said
    assert "ragged-dot" not in text and "triangular-solve" not in text
    for kernel in ("flash_fwd", "flash_dkv", "flash_dq"):
        assert len(re.findall(rf"%{kernel}(\.\d+)? = ", text)) == 1
    assert len(re.findall(r"%ssm_conv_bwd(\.\d+)? = ", text)) == 9
    assert text.count("tpu_custom_call") == 12
    (policy,) = _said("remat/policy", said)
    assert policy["blocks"] == 4
    assert abs(policy["held_bytes"] - 840_875_672 * 12) < 64
    assert policy["names"] == (
        "attn_out,attn_lse,moe_router,qkv_proj,attn_gate,attn_proj,"
        "kda_in_proj,kda_out_proj,mlp_gate,mlp_up,attn_qkv,"
        "moe_experts_gate,moe_experts_up")
    assert policy["kept_bytes"] == 2_076_442_624 <= policy["budget_bytes"]
    assert abs(policy["budget_bytes"] - 2_452_541_152) < 64
    (chunks,) = _said("kda/chunks", said)
    assert chunks == dict(chunk=64, sub_chunk=16, chunks=128, heads=8,
                          pair_bytes=8192 * 8 * 16 * 128 * 4)
    (pattern,) = _said("model/pattern", said)
    assert pattern["pattern"] == "*KKK" and pattern["held"] == 8
    dispatch = [d for d in _said("moe/dispatch", said)
                if d["tokens"] == 8192]
    assert dispatch == [dict(tokens=8192, held=8, routed=320, top_k=8,
                             expected=1638.4, rows=65536, experts="gated")]
    (conv,) = _said("ssm/conv", said)
    assert (conv["channels"], conv["positions"], conv["backward"]) == (
        1024, 8192, "kernel")
    (head,) = _said("head_loss/slice", said)
    assert head["gradients"] == "forward"
    print(f"delta-rule step: {solar_step.total_bytes} bytes compiled, "
          f"policy {policy}")
    assert solar_step.total_bytes < V5E_BYTES_LIMIT - (1 << 30)


def test_the_optimizer_reads_the_expert_matrices_where_they_lie(solar_step):
    """The optimizer's fusion runs in its gradient's order, and the jit's
    arguments and donated results are row-major: the gradient of a matrix
    that a first product reads left the backward as `[E][F][D]`, and the
    parameter, `mu` and `nu` of `experts_gate` and `experts_up` were each
    copied into that order and the three results back, 48 copies of 168
    MB a step (24.6 ms of 394 on the chip). With the gradient pinned to
    the stored order (`models/moe.gradient_as_stored`) no array of an
    expert matrix's shape is copied, and the pass that updates each of the
    twelve matrices takes the jit's own three arguments."""
    assert not _copies_of(solar_step.text, (8, 4096, 1280), (8, 1280, 4096))
    reads = _optimizer_reads(solar_step.text, r"experts_(gate|up|down)__")
    assert len(reads) == 4 * 3 * 3              # layers, matrices, holders
    for parameter, results in reads.items():
        wide = (8, 1280, 4096) if "experts_down" in parameter else (
            8, 4096, 1280)
        assert results.count(list(wide)) == 3, (parameter, results)
