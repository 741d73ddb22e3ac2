"""v5e compiles of the dense cells' whole training steps at their own
shapes, read for the head and the fused loss, without a chip
(tests/chip_compile_common.py says how): `mistral7b_l2.seq8k` on one chip
and `seq8k_dp4` on four, and `gpt2_large.seq1k`.
"""
import re

import jax

from chip_compile_common import (  # noqa: F401  (fixtures by name)
    MISTRAL, V5E_BYTES_LIMIT, _abstract_step_inputs, _compile_train_step,
    _compiled_train_step, _computation, _crossings, _said, four_chips,
    fresh_records, one_chip, topo,
)


def _while_loops(text):
    """(op_name, operand shapes, body) of the entry computation's loops."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text, re.S | re.M).group(1)
    for line in entry.splitlines():
        m = re.match(r"\s*%\S+ = (\(.*\)) while\(", line)
        if m:
            op = re.search(r'op_name="([^"]*)"', line)     # not every loop
            yield (op.group(1) if op else "", m.group(1),
                   re.search(r"body=%([\w\.\-]+)", line).group(1))


def _body_and_called(text, body):
    """The text of a loop's body and of every computation it calls."""
    own = _computation(text, body)
    return [own] + [_computation(text, c)
                    for c in set(re.findall(r"calls=%([\w\.\-]+)", own))]


def _matmuls_of(text, body):
    """Result shapes of the matmuls (`convolution`, on the TPU) a loop's
    body runs a turn: its own and those inside the fusions it calls."""
    return [shape for part in _body_and_called(text, body)
            for shape in re.findall(
                r"= (\w+\[[\d,]+\])\S* convolution\(", part)]


def _head_loss_loops(text):
    """The entry computation's loops that are the fused loss's: under
    `head_loss` by their own name or, where the partitioner rebuilt the
    loop and left it none, by the name of what their body runs."""
    return [(op, shapes, body) for op, shapes, body in _while_loops(text)
            if "head_loss" in op
            or "head_loss)/while/body" in _computation(text, body)]


def _collectives_in(text, body):
    """The lines of a loop's body, and of what it calls, that cross chips."""
    return [ln for part in _body_and_called(text, body)
            for ln in part.splitlines()
            if re.search(r"all-reduce|async_collective|all-gather|"
                         r"reduce-scatter|collective-permute", ln)]


def test_mistral_head_and_loss_take_four_turns_inside_the_chip_for_v5e(
        topo, monkeypatch, fresh_records):
    """`mistral7b_l2.seq8k`'s step (1 x 8192 on one chip, the floor 256
    positions): the train step sums through the fused loss, so the head
    and loss are ONE loop of 4 slices of 2048 rows, in the forward, whose
    turn runs three matmuls of the slice's shape (the logits and the two
    gradients made of them at once) and nothing of the loss is
    recomputed; the blocks' checkpoint policy chooses what it chose (all
    seven names: its arithmetic leaves the slices to its headroom), and
    the compiled step stays under the chip's `bytes_limit`."""
    from pytorch_distributed_template_tpu.config.registry import MODELS
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.parallel import build_mesh

    mesh = build_mesh({"data": 1}, devices=topo.devices[:1])
    _, compiled = _compiled_train_step(
        MODELS.get("Mistral")(**MISTRAL), mesh, 1, 8192, monkeypatch)
    (said,) = _said("head_loss/slice")
    assert said == dict(rows_per_device=2048, positions=2048, turns=4,
                        slice_bytes=2048 * 32000 * 4, floor_positions=256,
                        gradients="forward")
    text = compiled.as_text()
    ((op, shapes, body),) = _head_loss_loops(text)
    assert "jvp(head_loss)" in op and "transpose" not in op
    assert "bf16[4,2048,4096]" in shapes    # the batch of one folded away
    assert "bf16[32," not in shapes
    assert "bf16[4096,32000]" in shapes     # the accumulator, carried
    assert sorted(_matmuls_of(text, body)) in (
        # logits, the hidden state's gradient, the weight's share
        ["bf16[2048,32000]", "bf16[2048,4096]", "f32[4096,32000]"],
        ["bf16[2048,4096]", "f32[2048,32000]", "f32[4096,32000]"])
    scopes = re.findall(r'op_name="([^"]*)"', text)
    assert not [sc for sc in scopes
                if "head_loss" in sc and "rematted_computation" in sc]
    # the label goes into the softmax's gradient as a one-hot select
    # inside the matmuls' operands, never as a scatter over a slice
    assert not re.search(r"= f32\[[\d,]+\]\S* scatter\(", text)
    assert not [sc for sc in scopes
                if "head_loss" in sc and "scatter" in sc]
    (policy,) = _said("remat/policy")
    assert policy["names"] == ("attn_out,attn_lse,qkv_proj,attn_proj,"
                               "mlp_gate,mlp_up,attn_qkv")
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < V5E_BYTES_LIMIT - (1 << 30)


def test_head_crossing_rides_the_four_turn_loss_loop_for_v5e(
        four_chips, monkeypatch, fresh_records):
    """`mistral7b_l2.seq8k_dp4`'s step (4 x 8192 over four chips): the
    step traces the global batch and the slice is still reckoned a chip
    (2048 rows, 4 turns). The head's weight gradient is summed in the
    forward's loop, and a sum over a batch that is spread over chips is
    a partial sum on each: the partitioner keeps the partial sum through
    the loop (nothing in the loop's body crosses) and the whole
    `[4096, 32000]` crosses ONCE a step, behind the loop. Not four times,
    which a crossing inside the body would be."""
    from pytorch_distributed_template_tpu.config.registry import MODELS
    import pytorch_distributed_template_tpu.models  # noqa: F401

    _, text = _compile_train_step(
        MODELS.get("Mistral")(**MISTRAL), four_chips, 4, 8192, monkeypatch)
    (said,) = _said("head_loss/slice")
    assert (said["rows_per_device"], said["positions"], said["turns"],
            said["gradients"]) == (2048, 2048, 4, "forward")
    ((_, shapes, body),) = _head_loss_loops(text)
    assert "bf16[4,1,2048,4096]" in shapes
    assert len(_matmuls_of(text, body)) == 3
    assert not _collectives_in(text, body)
    bare, carried = _crossings(text)
    assert len([s for s in bare if "[4096,32000]" in s]) <= 1, bare
    assert len(bare) <= 3 and len(carried) >= 14


def test_gpt2_large_step_is_left_as_it_was_for_v5e(
        one_chip, monkeypatch, fresh_records):
    """`gpt2_large.seq1k`'s shape (8 x 1024, the floor 256 positions) has
    2048 rows a slice already: with the rule and with every slice held to
    the floor, which is what the loss did before it reckoned rows, the
    compiled step is the same text."""
    import optax

    from pytorch_distributed_template_tpu.config.registry import MODELS
    from pytorch_distributed_template_tpu.engine import losses
    from pytorch_distributed_template_tpu.engine.steps import make_train_step
    from pytorch_distributed_template_tpu.ops import flash

    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    model = MODELS.get("GPT2")(
        size="gpt2-large", n_layer=2, bfloat16=True, attn_impl="flash",
        remat=True, fused_head=True, dropout=0.0)
    tx = optax.adamw(1e-4)
    state, _, feed = _abstract_step_inputs(
        model, tx, 8, 1024, one_chip, one_chip)

    def text():
        step = make_train_step(
            model, tx, losses.fused_lm_cross_entropy(chunk=256), [],
            input_key="tokens", target_key="tokens", grad_clip_norm=1.0)
        return jax.jit(step, donate_argnums=0).lower(
            state, feed).compile().as_text()

    texts = []
    for held_to_the_floor in (False, True):
        if held_to_the_floor:
            monkeypatch.setattr(
                losses, "slice_positions",
                lambda sequences, chunk, seq_len, vocab: chunk)
        texts.append(text())    # one line: the text holds its caller's
    (said,) = _said("head_loss/slice")
    assert said == dict(rows_per_device=2048, positions=256, turns=4,
                        slice_bytes=2048 * 50257 * 4, floor_positions=256,
                        gradients="forward")
    assert texts[0] == texts[1]
