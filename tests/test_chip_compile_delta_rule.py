"""The v5e compile of `solar_open2_l4.seq8k`'s whole training step at its
real shape, without a chip (tests/chip_compile_common.py says how): a
file of its own, beside test_chip_compile.py's two, so that the three
longest compiles of the suite do not queue on one xdist worker.
"""
import re

from chip_compile_common import (  # noqa: F401  (fixtures by name)
    V5E_BYTES_LIMIT, _compiled_train_step, _said, fresh_records, topo,
)


def test_delta_rule_step_lowers_and_fits_for_v5e(topo, monkeypatch,
                                                 fresh_records):
    """`solar_open2_l4.seq8k`'s step (1 x 8192 on one chip): three KDA
    blocks and a gated attention block, each with 8 held of 320 gated
    experts over every token, compile for the v5e with plain XLA for the
    scan (its triangular system, its loop over 128 chunks), the three
    flash kernels at 8 heads on one key-value head and nine convolution
    kernels without a bias; the checkpoint policy reckons both kinds of
    block and keeps every name they make, the routed experts' first two
    products last (1.342 GB of the 2.076 kept); the step stays 1 GiB under
    the chip's `bytes_limit` with 840.9 M parameters held."""
    import json
    from pathlib import Path

    from pytorch_distributed_template_tpu.config.registry import MODELS
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.parallel import build_mesh

    arch = json.loads((
        Path(__file__).resolve().parent.parent / "benchmarks" / "configs"
        / "solar_open2_l4.json").read_text())["experiment"]["arch"]
    mesh = build_mesh({"data": 1}, devices=topo.devices[:1])
    _, compiled = _compiled_train_step(
        MODELS.get(arch["type"])(**arch["args"]), mesh, 1, 8192, monkeypatch)
    text = compiled.as_text()
    assert "ragged-dot" not in text and "triangular-solve" not in text
    for kernel in ("flash_fwd", "flash_dkv", "flash_dq"):
        assert len(re.findall(rf"%{kernel}(\.\d+)? = ", text)) == 1
    assert len(re.findall(r"%ssm_conv_bwd(\.\d+)? = ", text)) == 9
    assert text.count("tpu_custom_call") == 12
    (policy,) = _said("remat/policy")
    assert policy["blocks"] == 4
    assert abs(policy["held_bytes"] - 840_875_672 * 12) < 64
    assert policy["names"] == (
        "attn_out,attn_lse,moe_router,qkv_proj,attn_gate,attn_proj,"
        "kda_in_proj,kda_out_proj,mlp_gate,mlp_up,attn_qkv,"
        "moe_experts_gate,moe_experts_up")
    assert policy["kept_bytes"] == 2_076_442_624 <= policy["budget_bytes"]
    assert abs(policy["budget_bytes"] - 2_452_541_152) < 64
    (chunks,) = _said("kda/chunks")
    assert chunks == dict(chunk=64, sub_chunk=16, chunks=128, heads=8,
                          pair_bytes=8192 * 8 * 16 * 128 * 4)
    (pattern,) = _said("model/pattern")
    assert pattern["pattern"] == "*KKK" and pattern["held"] == 8
    dispatch = [d for d in _said("moe/dispatch") if d["tokens"] == 8192]
    assert dispatch == [dict(tokens=8192, held=8, routed=320, top_k=8,
                             expected=1638.4, rows=65536, experts="gated")]
    (conv,) = _said("ssm/conv")
    assert (conv["channels"], conv["positions"], conv["backward"]) == (
        1024, 8192, "kernel")
    (said,) = _said("head_loss/slice")
    assert said["gradients"] == "forward"
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"delta-rule step: {total} bytes compiled, policy {policy}")
    assert total < V5E_BYTES_LIMIT - (1 << 30)
