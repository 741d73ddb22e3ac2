"""v5e compiles of two hybrid cells' whole training steps at their real
shapes, without a chip (tests/chip_compile_common.py says how). The third
hybrid cell's is in test_chip_compile_delta_rule.py (a file of few long
compiles is the tail of an xdist run), the dense steps in
test_chip_compile_dense.py and test_chip_compile_head_loss.py; kernels,
convolutions and the checkpoint policy in test_chip_compile_kernels.py.
"""
import re

import pytest

from chip_compile_common import (  # noqa: F401  (fixtures by name)
    V5E_BYTES_LIMIT, _compiled_bytes, _compiled_train_step, _copies_of,
    _entry_lines, _grouped_products_under, _optimizer_reads, _said,
    _scope_instructions, _step_compiled_once, fresh_records, topo,
)


NEMOTRON = dict(
    vocab_size=16384, pattern="EMEMEMEMEM*", d_model=4096, n_head=4,
    n_kv_head=1, head_dim=128, ssm_n_head=16, ssm_head_dim=64, ssm_n_group=1,
    ssm_state=128, ssm_conv=4, ssm_chunk=128, moe_n_routed=512,
    moe_held=(0, 8), moe_top_k=22, moe_latent=1024, moe_d_ff=2688,
    moe_shared_d_ff=5376, moe_scale=5.0, rms_eps=1e-5, bfloat16=True,
    attn_impl="flash", remat=True, fused_head=True)


@pytest.fixture(scope="module")
def hybrid_step(topo):
    """The hybrid cell's step, compiled once for the tests that read it."""
    from pytorch_distributed_template_tpu.config.registry import MODELS
    import pytorch_distributed_template_tpu.models  # noqa: F401

    return _step_compiled_once(MODELS.get("NemotronH")(**NEMOTRON), topo, 2,
                               8192)


def test_hybrid_step_lowers_and_fits_for_v5e(hybrid_step):
    """`nemotron3_super_l11.seq8k`'s step (2 x 8192 on one chip): the
    pattern-built stack with its scan, its dropless expert layers and
    the flash kernels compiles for the v5e, the checkpoint policy reckons
    three kinds of block, and the step stays under the chip's
    `bytes_limit`.

    8 of 512 experts are held and a token takes 22: uniform routing gives
    a token 0.34 pairs here, so it has two places in a room of 32768 rows
    (`moe.token_places`), a quarter of every held expert over every
    token, and the routed experts' products run over the pairs (ISSUE
    51): six grouped products a layer under `moe_experts`, no array a
    held expert by every token wide, and the first product, a quarter as
    wide as it was, kept by name with every other name the kinds make."""
    text, said = hybrid_step.text, hybrid_step.said
    for kernel in ("flash_fwd", "flash_dkv", "flash_dq"):
        assert re.search(rf"%{kernel}(\.\d+)? = ", text)
    # a layer: two products forward, the second's rows float32; backward a
    # rows' gradient each and a matrix's gradient each, as the leaf lies
    products = _grouped_products_under(text, "moe_experts")
    assert sorted(products) == sorted(
        5 * (2 * ["bf16[32768,2688]"] + ["f32[32768,1024]"]
             + ["bf16[32768,1024]", "bf16[8,1024,2688]",
                "bf16[8,2688,1024]"]))
    assert not re.search(r"\[8,16384,(2688|1024)\]|\[16384,8,(2688|1024)\]"
                         r"|\[8,(2688|1024),16384\]", text)
    (policy,) = _said("remat/policy", said)
    print(f"hybrid step: {hybrid_step.total_bytes} bytes compiled, "
          f"policy {policy}")
    assert policy["blocks"] == 11
    # every name its kinds make: the first product is 176 MB a layer in
    # the room (705 over every held expert, 3.52 GB over five layers,
    # which no budget held), the pairs' layout 1.4 MB
    assert policy["names"] == ("attn_out,attn_lse,moe_router,moe_pairs,"
                               "qkv_proj,attn_proj,ssm_in_proj,"
                               "moe_experts_out,moe_latent,moe_shared_up,"
                               "attn_qkv,moe_experts_up")
    assert policy["budget_bytes"] >= policy["kept_bytes"] == 2_878_832_640
    # the state's init traces one sequence, the step two
    dispatch = [d for d in _said("moe/dispatch", said) if d["tokens"] == 16384]
    assert dispatch == [dict(tokens=16384, held=8, routed=512, top_k=22,
                             expected=5632, rows=32768, dense_rows=131072)]
    # five mixers' convolutions: a backward kernel each, over two rows
    conv = [c for c in _said("ssm/conv", said) if c["positions"] == 16384]
    assert conv == [dict(
        taps=4, channels=1280, positions=16384, block_channels=128,
        block_positions=2048, backward="kernel",
        forward_bytes=2 * 16384 * 1280 * 2,
        backward_bytes=3 * 16384 * 1280 * 2)]
    assert len(re.findall(r"%ssm_conv_bwd(\.\d+)? = ", text)) == 5
    assert hybrid_step.total_bytes < V5E_BYTES_LIMIT - (1 << 30)


def test_the_optimizer_reads_the_hybrids_expert_matrices_where_they_lie(
        hybrid_step):
    """As `test_chip_compile_delta_rule.py` has it for three matrices an
    expert: the matrices' gradients leave the grouped products `[8][1024]
    [2688]` and `[8][2688][1024]` as the leaves are stored, so no leaf,
    moment or result is copied from one order to another (30 copies of
    88 MB a step before `models/moe.gradient_as_stored`), and the pass
    over each of the ten matrices takes the jit's own three arguments
    (or the compiler's prefetch of one, in the same order)."""
    copies = _copies_of(hybrid_step.text, (8, 1024, 2688), (8, 2688, 1024))
    # the backward's rows' gradients take each matrix transposed: its cast
    # to bfloat16 writes it in that order, one pass a matrix as a cast is;
    # no float32 array, a leaf, a moment or a gradient, is copied
    assert len(copies) == 5 * 2 and all(c.startswith("bf16[") for c in copies)
    reads = _optimizer_reads(hybrid_step.text, r"experts_(up|down)__")
    assert len(reads) == 5 * 2 * 3              # layers, matrices, holders
    for parameter, results in reads.items():
        wide = (8, 2688, 1024) if "experts_down" in parameter else (
            8, 1024, 2688)
        assert results.count(list(wide)) == 3, (parameter, results)


def test_no_product_of_the_hybrids_experts_runs_a_second_time(hybrid_step):
    """After the forward nothing runs a product of the routed experts
    again. The token's weight lies on the activation, so no `[8, 16384,
    1024]` result a held expert wide (268 MB a layer) is made anywhere in
    the step; the layer's `[16384, 1024]` sum, which `latent_up`'s weight
    gradient reads, and the first product over the room's rows are both
    kept by name, so of the thirty grouped products none stands in the
    recomputation (over every held expert the first did, 21.6 ms a
    step), and the dense products left under `moe_experts` are the
    branch's that a step whose pairs pass the room takes, an expert at a
    time."""
    text = hybrid_step.text
    assert "[8,16384,1024]" not in text
    assert not re.search(r"\[16384,8,1024\]|\[8,1024,16384\]", text)
    grouped = [ln for ln in text.splitlines()
               if re.match(r"\s*(?:ROOT )?%ragged-dot[-\w.]* = \w+\[", ln)
               and "/moe_experts/" in ln]
    assert len(grouped) == 5 * 6
    assert not [ln for ln in grouped if "rematted_computation" in ln]
    lines = {m.group(1): m.string for m in (
        re.match(r"\s*(?:ROOT )?%(\S+) = ", ln) for ln in _entry_lines(text))
        if m}
    again = [name for name, *_ in _scope_instructions(text, "moe_experts")
             if "rematted_computation" in lines[name]
             and (" convolution(" in lines[name] or "ragged" in lines[name])]
    assert not again, again
    assert hybrid_step.total_bytes < V5E_BYTES_LIMIT - (1 << 30)


def test_granite_step_lowers_and_fits_for_v5e(topo, monkeypatch,
                                              fresh_records):
    """`granite4_h_micro_l10.seq8k`'s step (1 x 8192 on one chip): the
    stack of a mixer and a gated MLP a layer, nine scans of 64 heads in
    one group at the published chunk of 256 (537 MB of float32 decay mask
    a layer), one attention at head 64 over 8192 positions through the
    three flash kernels, and the tied scaled head through the fused loss
    compiles for the v5e; the checkpoint policy reckons both kinds of
    block; the step stays 1 GiB under the chip's `bytes_limit`."""
    import json
    from pathlib import Path

    from pytorch_distributed_template_tpu.config.registry import MODELS
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.parallel import build_mesh

    arch = json.loads((
        Path(__file__).resolve().parent.parent / "benchmarks" / "configs"
        / "granite4_h_micro_l10.json").read_text())["experiment"]["arch"]
    mesh = build_mesh({"data": 1}, devices=topo.devices[:1])
    _, compiled = _compiled_train_step(
        MODELS.get(arch["type"])(**arch["args"]), mesh, 1, 8192, monkeypatch)
    text = compiled.as_text()
    for kernel in ("flash_fwd", "flash_dkv", "flash_dq"):
        assert re.search(rf"%{kernel}(\.\d+)? = ", text)
    (policy,) = _said("remat/policy")
    assert policy["blocks"] == 10
    # parameters and both moments; the gradient is the backward's own
    assert abs(policy["held_bytes"] - 772_160_448 * 12) < 64
    kept = policy["names"].split(",")
    assert kept == ["attn_out", "attn_lse", "qkv_proj", "attn_proj",
                    "ssm_in_proj", "mlp_gate"]
    assert set(kept) <= {"attn_out", "attn_lse", "qkv_proj", "attn_proj",
                         "ssm_in_proj", "mlp_gate", "mlp_up", "attn_qkv"}
    chunk = arch["args"]["ssm_chunk"]
    (chunks,) = _said("ssm/chunks")
    assert chunks == dict(chunk=chunk, chunks=8192 // chunk, heads=64,
                          groups=1, mask_bytes=8192 * 64 * chunk * 4)
    (pattern,) = _said("model/pattern")
    assert pattern["pattern"] == "mmmmmammmm"
    assert (pattern["rows"], pattern["of_rows"]) == (12544, 100352)
    (said,) = _said("head_loss/slice")
    assert said["gradients"] == "forward" and said["rows_per_device"] == 2048
    # the convolution (ISSUE 39): said once for nine layers of one shape;
    # one backward kernel a layer; and under its scope no float32 array
    # of the size of its input, padded or not, in any phase: the
    # forward's and the recomputation's fusions take the bfloat16 slice
    # and give bfloat16, the kernel keeps float32 in its block
    (conv,) = _said("ssm/conv")
    assert conv == dict(
        taps=4, channels=4352, positions=8192, block_channels=128,
        block_positions=2048, backward="kernel",
        forward_bytes=2 * 8192 * 4352 * 2, backward_bytes=3 * 8192 * 4352 * 2)
    assert len(re.findall(r"%ssm_conv_bwd(\.\d+)? = ", text)) == 9
    made = [(name, array) for name, _, arrays, _, _ in _scope_instructions(
        text, "ssm_conv") for array in arrays]
    assert len(made) > 9 * 4
    wide = [(name, dims) for name, (dtype, dims, _, _) in made
            if dtype == "f32" and sorted(dims)[-2:] in ([4352, 8192],
                                                        [4352, 8195])]
    assert not wide, wide
    total = _compiled_bytes(compiled)
    print(f"granite step: {total} bytes compiled, policy {policy}")
    assert total < V5E_BYTES_LIMIT - (1 << 30)


@pytest.fixture(scope="module")
def lfm2_step(topo):
    """`lfm2_24b_a2b_l5.seq8k`'s step, compiled once for the tests that
    read it."""
    import json
    from pathlib import Path

    from pytorch_distributed_template_tpu.config.registry import MODELS
    import pytorch_distributed_template_tpu.models  # noqa: F401

    arch = json.loads((
        Path(__file__).resolve().parent.parent / "benchmarks" / "configs"
        / "lfm2_24b_a2b_l5.json").read_text())["experiment"]["arch"]
    return _step_compiled_once(MODELS.get(arch["type"])(**arch["args"]),
                               topo, 1, 8192)


def test_lfm2_step_lowers_and_fits_for_v5e(lfm2_step):
    """`lfm2_24b_a2b_l5.seq8k`'s step (1 x 8192 on one chip): four gated
    short convolutions (plain `jax.numpy`, no kernel) and one rotated,
    q/k-normed attention at head 64 through the three flash kernels, the
    leading layer's 11776-wide gated MLP, 16 held of 64 gated experts in
    the four layers behind it, and the tied head over a quarter of the
    vocabulary through the fused loss compile for the v5e; the
    checkpoint policy reckons three kinds of block and keeps the names it
    was told; the step stays under the chip's `bytes_limit`.

    A token takes 4 of the 16 experts held, so the routed experts'
    products run over the pairs (ISSUE 50): grouped products with one
    group a held expert in a room of 32768 rows, which the compiler makes
    kernels of its own under `moe_experts`; no array a held expert by every token wide is made
    outside the branch a step with ties takes; and both first products
    are kept by name, a quarter as wide as they were."""
    text, said = lfm2_step.text, lfm2_step.said
    for kernel in ("flash_fwd", "flash_dkv", "flash_dq"):
        assert len(re.findall(rf"%{kernel}(\.\d+)? = ", text)) == 1
    assert "ssm_conv_bwd" not in text
    # a layer: three products forward and a rows' gradient each backward
    # over the room's rows; a matrix's gradient each, as the leaf lies
    products = _grouped_products_under(text, "moe_experts")
    assert len(products) == 4 * 9
    rows = [p for p in products if "[32768," in p]
    assert sorted(set(rows)) == ["bf16[32768,1536]", "bf16[32768,2048]",
                                 "f32[32768,2048]"] and len(rows) == 4 * 6
    assert sorted(p for p in products if p not in rows) == sorted(
        4 * ["bf16[16,1536,2048]"] + 8 * ["bf16[16,2048,1536]"])
    assert not re.search(r"\[16,8192,1536\]|\[8192,16,1536\]"
                         r"|\[16,1536,8192\]", text)
    (policy,) = _said("remat/policy", said)
    print(f"lfm2 step: {lfm2_step.total_bytes} bytes compiled, "
          f"policy {policy}")
    assert policy["blocks"] == 5
    # parameters and both moments; the gradient is the backward's own
    assert abs(policy["held_bytes"] - 788_052_352 * 12) < 64
    (pattern,) = _said("model/pattern", said)
    assert pattern["pattern"] == "cfccc" and pattern["n_dense_layers"] == 1
    assert (pattern["rows"], pattern["of_rows"]) == (16384, 65536)
    assert (pattern["held"], pattern["moe_n_routed"], pattern["moe_top_k"],
            pattern["conv_taps"]) == (16, 64, 4, 3)
    (conv,) = [c for c in _said("conv/short", said)
               if c["positions"] == 8192]
    assert conv == dict(taps=3, channels=2048, positions=8192,
                        read_bytes=3 * 8192 * 2048 * 2,
                        written_bytes=8192 * 2048 * 2)
    (dispatch,) = [d for d in _said("moe/dispatch", said)
                   if d["tokens"] == 8192]
    assert dispatch["rows"] == 32768 and dispatch["expected"] == 8192
    assert dispatch["experts"] == "gated"
    assert dispatch["dense_rows"] == 16 * 8192
    (said_slice,) = _said("head_loss/slice", said)
    assert said_slice["gradients"] == "forward"
    # every name its three kinds make: the first products over the pairs'
    # room are 101 MB a layer each (403 over every token, where `up`'s did
    # not fit: 2.895 GB kept of a budget of 3.688 then), and beside the
    # router the pairs' layout, 1.4 MB a layer
    assert policy["names"].split(",") == [
        "attn_out", "attn_lse", "moe_router", "moe_pairs", "qkv_proj",
        "attn_proj", "conv_in_proj", "conv_out_proj", "mlp_gate", "mlp_up",
        "attn_qkv", "moe_experts_gate", "moe_experts_up"]
    assert policy["budget_bytes"] >= policy["kept_bytes"] == 2_095_316_992
    # under what the step over every token compiled to (PR 49), and with
    # it 1 GiB under the chip's limit
    assert lfm2_step.total_bytes < 13_889_780_224 < V5E_BYTES_LIMIT - (1 << 30)


def test_the_optimizer_reads_lfm2s_expert_matrices_where_they_lie(lfm2_step):
    """As for solar's three matrices an expert (PR 46), over the pairs:
    the matrices' gradients leave the grouped products `[16][2048][1536]`
    and `[16][1536][2048]` as the leaves are stored, so no leaf, moment
    or result is copied from one order to another, and the pass over
    each of the twelve matrices takes the jit's own three arguments."""
    copies = _copies_of(lfm2_step.text, (16, 2048, 1536), (16, 1536, 2048))
    # the backward's rows' gradients take each matrix transposed: its cast
    # to bfloat16 writes it in that order, one pass a matrix as a cast is;
    # no float32 array, a leaf, a moment or a gradient, is copied
    assert len(copies) == 4 * 3 and all(c.startswith("bf16[") for c in copies)
    reads = _optimizer_reads(lfm2_step.text, r"experts_(gate|up|down)__")
    assert len(reads) == 4 * 3 * 3              # layers, matrices, holders
    for parameter, results in reads.items():
        wide = (16, 1536, 2048) if "experts_down" in parameter else (
            16, 2048, 1536)
        assert results.count(list(wide)) == 3, (parameter, results)
