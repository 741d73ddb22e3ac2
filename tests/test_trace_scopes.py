"""The program's spans in the profiler's trace, the flight record's
host-time fields, and the names the compiled step carries (ISSUE 25).

All on the CPU: what is checked is that the names are where a reduction
of a chip trace looks for them, never a time.
"""
import json
import re
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_template_tpu.observability.trace import (
    SpanRecorder,
)

REPO = Path(__file__).parent.parent


def _lines(profile_dir):
    """The host plane's lines, one per thread (threads share names, so
    a list and not a dict), of the newest capture under `profile_dir`:
    [[(event name, start, end, stats)]]."""
    from jax.profiler import ProfileData

    path = sorted(Path(profile_dir).glob("plugins/profile/*/*.xplane.pb"),
                  key=lambda p: p.stat().st_mtime)[-1]
    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for e in line.events]
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:CPU") for line in plane.lines]


def _line_of(lines, name):
    found = [i for i, events in enumerate(lines)
             if any(e[0] == name for e in events)]
    assert len(found) == 1, (name, found)
    return found[0]


def test_spans_land_on_the_calling_threads_line(tmp_path):
    rec = SpanRecorder()

    def other_thread():
        with rec.span("data/host_gather"):
            np.ones(8).sum()

    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("train/log", step=3):
            with rec.span("train/log_fetch", step=3):
                jnp.ones(4).block_until_ready()
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    finally:
        jax.profiler.stop_trace()

    lines = _lines(tmp_path)
    mine = _line_of(lines, "train/log")
    assert _line_of(lines, "train/log_fetch") == mine
    assert _line_of(lines, "data/host_gather") != mine
    by_name = {e[0]: e for e in lines[mine]}
    outer, inner = by_name["train/log"], by_name["train/log_fetch"]
    assert outer[1] <= inner[1] and inner[2] <= outer[2]    # nested
    assert int(inner[3]["step"]) == 3
    # the ring is as it was: both spans, innermost finished first
    assert [e["name"] for e in rec.snapshot()] == [
        "train/log_fetch", "train/log", "data/host_gather"]
    assert rec.active_spans() == []


def test_span_outside_a_capture_and_through_an_exception():
    rec = SpanRecorder()
    with pytest.raises(KeyError):
        with rec.span("checkpoint/save", epoch=1):
            raise KeyError("boom")
    (event,) = rec.snapshot()
    assert event["name"] == "checkpoint/save"
    assert event["args"] == {"epoch": 1, "error": True}
    assert rec.active_spans() == []


@pytest.fixture(scope="module")
def traced_epoch(tmp_path_factory):
    """One tiny Trainer epoch with a profiler window in its middle."""
    import pytorch_distributed_template_tpu.data  # noqa: F401
    import pytorch_distributed_template_tpu.engine  # noqa: F401
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.config import (
        ConfigParser, LOADERS, LOSSES, METRICS, MODELS,
    )
    from pytorch_distributed_template_tpu.engine import Trainer
    from pytorch_distributed_template_tpu.parallel import mesh_from_config

    cfg = json.loads((REPO / "configs" / "mnist_debug.json").read_text())
    cfg["trainer"]["save_dir"] = str(tmp_path_factory.mktemp("runs"))
    cfg["trainer"]["epochs"] = 1
    cfg["trainer"]["tensorboard"] = False
    # log_step is sqrt(batch size): a flush every fourth of 32 batches
    cfg["train_loader"]["args"]["batch_size"] = 16
    cfg["trainer"]["profiler"] = {
        "enabled": True, "trace_start_step": 3, "trace_steps": 6,
    }
    config = ConfigParser(cfg, run_id="spans")
    trainer = Trainer(
        config.init_obj("arch", MODELS), LOSSES.get(config["loss"]),
        [METRICS.get(m) for m in config["metrics"]], config=config,
        train_loader=config.init_obj("train_loader", LOADERS),
        mesh=mesh_from_config(config),
    )
    trainer.train()
    records = [r for r in trainer.recorder.last() if "wall_ms" in r]
    return trainer, records, _lines(Path(config.log_dir) / "profile")


def test_flight_records_say_where_host_time_went(traced_epoch):
    trainer, records, _ = traced_epoch
    assert len(records) >= 6
    flushed = [r for r in records if "log_flush_ms" in r]
    assert flushed and len(flushed) < len(records)
    for r in records:
        assert r["dispatch_ms"] >= 0 and r["health_fetch_ms"] >= 0
        parts = (r["data_wait_ms"] + r["dispatch_ms"] + r["health_fetch_ms"]
                 + r.get("log_flush_ms", 0.0))
        # each is two clock readings inside the iteration; the record's
        # values are rounded to 1 us
        assert parts <= r["wall_ms"] + 0.005, r
    # a flush is on the record of the iteration after the log step that
    # ran it, the one whose wall_ms holds it
    steps = {r["step"] for r in flushed}
    logged = {r["step"] for r in records if "loss" in r}
    assert steps and all(s - 1 in logged for s in steps)


def test_loop_spans_are_on_the_dispatching_threads_line(traced_epoch):
    _, _, lines = traced_epoch
    dispatching = _line_of(lines, "train_step/dispatch")
    names = {e[0] for e in lines[dispatching]}
    assert {"data/next_batch", "train/health_fetch", "train/log",
            "train/log_fetch", "train/log_lr"} <= names
    assert "train/step" not in names        # dropped: one span a call
    fetch = [e for e in lines[dispatching] if e[0] == "train/log_fetch"]
    log = [e for e in lines[dispatching] if e[0] == "train/log"]
    # the fetch says which step's value it reads, and lies inside train/log
    assert all("step" in e[3] for e in fetch)
    assert all(any(o[1] <= e[1] and e[2] <= o[2] for o in log)
               for e in fetch)


def _tiny_step_text(grad_accum_steps: int) -> str:
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.config import MODELS
    from pytorch_distributed_template_tpu.engine.losses import resolve_loss
    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )
    from pytorch_distributed_template_tpu.engine.steps import make_train_step

    model = MODELS.get("Mistral")(
        vocab_size=256, n_layer=1, n_head=2, n_kv_head=1, d_model=32,
        d_ff=64, max_len=128, window=32, bfloat16=True, attn_impl="flash",
        remat=True, fused_head=True)
    tx = optax.adamw(1e-3)
    tokens = np.zeros((4, 64), np.int32)
    # shapes alone: lowering reads no value, and the init's forward pass,
    # run eagerly, is most of what this test took
    state = jax.eval_shape(lambda: create_train_state(model, tx, tokens))
    step = make_train_step(
        model, tx, resolve_loss({"type": "fused_lm_cross_entropy",
                                 "args": {"chunk": 32}}),
        (), input_key="tokens", target_key="tokens", grad_clip_norm=1.0,
        grad_accum_steps=grad_accum_steps, skip_nonfinite=True, health=True)
    batch = {"tokens": jnp.asarray(tokens),
             "mask": jnp.ones((4,), jnp.float32)}
    return jax.jit(step).lower(state, batch).compile().as_text()


@pytest.mark.parametrize("grad_accum_steps", [1, 4])
def test_compiled_step_carries_the_scopes(grad_accum_steps):
    names = set(re.findall(r'op_name="([^"]*)"',
                           _tiny_step_text(grad_accum_steps)))
    assert names

    def some(pattern):
        return any(re.search(pattern, n) for n in names)

    assert some(r"/optimizer/")
    # the step sums through the fused loss, whose gradient rule makes the
    # head's gradients in the forward's loop: head and loss are under the
    # forward's scope alone, and none of it is recomputed
    assert some(r"jvp\(head_loss\)/while/body")
    assert not some(r"transpose\(jvp\(head_loss\)\)/while")
    assert not some(r"rematted_computation.*head_loss")
    assert not some(r"head_loss.*rematted_computation")
    assert some(r"rematted_computation")             # the remat marker
    assert some(r"/health_summary/") and some(r"/metrics/")
    assert some(r"/grad_accum/") == (grad_accum_steps > 1)
    # interpret mode lowers a kernel to a loop under the kernel's name
    for kernel in ("flash_fwd", "flash_dkv", "flash_dq"):
        assert some(rf"/self_attn/{kernel}/"), kernel
    # forward, recomputation and backward can be told apart: the
    # recomputed forward kernel is under the marker, the first is not
    assert some(r"rematted_computation/.*flash_fwd")
    assert any("self_attn/flash_fwd" in n and "transpose(" not in n
               and "rematted_computation" not in n for n in names)
    # nothing of the optimizer is inside forward or backward
    assert not some(r"jvp\(.*optimizer")


# -- the hybrid stack's scopes, lines and counters (PR 33) -----------------


def _hybrid_trainer(tmp_path, steps=6, config="nemotron_h_debug.json"):
    import pytorch_distributed_template_tpu.data  # noqa: F401
    import pytorch_distributed_template_tpu.engine  # noqa: F401
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.config import (
        ConfigParser, LOADERS, LOSSES, METRICS, MODELS,
    )
    from pytorch_distributed_template_tpu.engine import Trainer
    from pytorch_distributed_template_tpu.parallel import mesh_from_config

    cfg = json.loads((REPO / "configs" / config).read_text())
    cfg["trainer"].update(save_dir=str(tmp_path), epochs=1, save_period=100,
                          tensorboard=False, monitor="off")
    cfg["arch"]["args"]["moe_held"] = [2, 4]
    cfg["train_loader"]["args"].update(n=16 * steps, batch_size=16)
    cfg.pop("valid_loader")
    config = ConfigParser(cfg, run_id="hybrid")
    return Trainer(
        config.init_obj("arch", MODELS), LOSSES.get(config["loss"]),
        [METRICS.get(m) for m in config["metrics"]], config=config,
        train_loader=config.init_obj("train_loader", LOADERS),
        mesh=mesh_from_config(config))


def test_model_counters_reach_the_flight_record(tmp_path):
    """What the expert layers count rides the step's metrics to the log
    flush that fetches the loss anyway, and lands beside it."""
    trainer = _hybrid_trainer(tmp_path)
    trainer._train_epoch(1)     # the loop alone: no signal handler, no save
    logged = [r for r in trainer.recorder.last() if "loss" in r]
    assert logged
    for r in logged:
        # 16 x 32 tokens, 2 of 8 experts a token, 4 of them held: 512
        # pairs a layer at uniform routing, 2 layers
        assert 0.5 * 1024 < r["moe_pairs_here"] < 1.5 * 1024
        assert r["moe_load_max_over_mean"] >= 1.0
        assert 0 <= r["moe_tokens_unserved"] <= 512
        # every expert family carries the counter that says which way a
        # step went: over the pairs here, two places a token
        assert r["moe_rows_run"] == r["moe_pairs_here"]
    assert all("moe_pairs_here" not in r
               for r in trainer.recorder.last() if "loss" not in r)
    # the configuration states a selection_bias_rate: six steps have moved
    # each router's biases by whole rates, against the experts' loads
    for name in ("layers_0", "layers_2"):
        bias = np.asarray(
            trainer.state.params[name]["mixer"]["selection_bias"]) / 1e-3
        assert np.any(bias) and np.abs(bias).max() <= 6.001
        np.testing.assert_allclose(bias, np.round(bias), atol=1e-3)


def test_hybrid_step_carries_its_scopes():
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.config.registry import MODELS
    from pytorch_distributed_template_tpu.engine.losses import (
        lm_cross_entropy,
    )
    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )
    from pytorch_distributed_template_tpu.engine.steps import make_train_step

    model = MODELS.get("TinyNemotronH")(pattern="EM*", remat=True)
    tx = optax.adamw(1e-3)
    state = jax.eval_shape(lambda: create_train_state(
        model, tx, np.zeros((1, 32), np.int32), seed=0))     # shapes alone
    step = make_train_step(model, tx, lm_cross_entropy, [],
                           input_key="tokens", target_key="tokens")
    batch = {"tokens": jnp.zeros((2, 32), jnp.int32),
             "mask": jnp.ones((2,), jnp.float32)}
    names = set(re.findall(
        r'op_name="([^"]*)"',
        jax.jit(step).lower(state, batch).compile().as_text()))

    def some(pattern):
        return any(re.search(pattern, n) for n in names)

    for scope in ("ssm_scan", "ssm_scan/ssm_conv", "moe_route",
                  "moe_experts", "moe_shared"):
        assert some(rf"jvp\(.*/{scope}/"), scope
        assert some(rf"transpose\(jvp\(.*/{scope}/"), scope
    assert some(r"layers_0/mixer/moe_route") and some(r"layers_1/mixer/ssm_")
    # the convolution is inside the scan's scope, the projections outside
    assert not some(r"ssm_scan/.*in_proj") and not some(r"ssm_scan/.*out_proj")


def test_every_operation_of_the_convolutions_rule_lies_under_its_scope():
    """ops/ssm.causal_conv_silu's forward and backward are its own: both
    carry `ssm_scan/ssm_conv` themselves, so in every phase of a
    checkpointed block everything they compute is the scope's."""
    from pytorch_distributed_template_tpu.ops.ssm import causal_conv_silu

    def block(x, taps, bias):
        with jax.named_scope("layers_0"):
            return jnp.sum(jnp.sin(causal_conv_silu(x * 2.0, taps, bias)))

    x = jnp.ones((2, 37, 24), jnp.bfloat16)
    taps, bias = jnp.ones((4, 24)), jnp.ones((24,))
    names = set(re.findall(r'op_name="([^"]*)"', jax.jit(jax.grad(
        jax.checkpoint(block), (0, 1, 2))).lower(x, taps, bias).compile(
        ).as_text()))
    # outside it: the arguments and the block's own doubling, sine and
    # sum; nothing the rule's bodies are made of (shifts, widening, silu)
    inside = {n for n in names if "ssm_" in n}
    assert {n.rsplit("/", 1)[-1] for n in names - inside} <= {
        "x", "taps", "bias", "mul", "broadcast_in_dim", "reduce_sum",
        "remat2", "sin", "cos"}, names - inside
    assert {"pad", "slice", "convert_element_type", "exp"} <= {
        n.rsplit("/", 1)[-1] for n in inside}
    assert inside and all("/layers_0/ssm_scan/ssm_conv" in n for n in inside)
    for phase in (r"checkpoint/layers_0/ssm_scan/ssm_conv",
                  r"rematted_computation/layers_0/ssm_scan/ssm_conv"):
        assert any(re.search(phase, n) for n in inside), phase
    # not under differentiation: the same scope, once
    plain = set(re.findall(r'op_name="([^"]*)"', jax.jit(block).lower(
        x, taps, bias).compile().as_text()))
    assert any("/layers_0/ssm_scan/ssm_conv" in n for n in plain)
    assert not any("ssm_conv/ssm_scan" in n for n in names | plain)


def test_a_choice_is_said_once_a_process_and_distinct_record(caplog):
    import logging

    from pytorch_distributed_template_tpu.observability import trace

    trace._said.clear()
    trace.get_recorder().clear()
    log = logging.getLogger("said")
    with caplog.at_level(logging.INFO, logger="said"):
        for rows in (64, 64, 128):
            trace.say_once(log, "moe/dispatch", dict(rows=rows, held=8),
                           "buffer of %(rows)d rows")
        # a line whose text takes its own arguments, as remat/policy's
        trace.say_once(log, "moe/dispatch", dict(rows=256, held=8),
                       "buffer of %.1f k rows", 0.256)
    spans = [e for e in trace.get_recorder().snapshot()
             if e["name"] == "moe/dispatch"]
    assert [e["args"] for e in spans] == [{"rows": 64, "held": 8},
                                          {"rows": 128, "held": 8},
                                          {"rows": 256, "held": 8}]
    assert [r.getMessage() for r in caplog.records] == [
        "buffer of 64 rows", "buffer of 128 rows", "buffer of 0.3 k rows"]


# -- the delta-rule stack (models/hybrid.py's SOLAR_OPEN2, models/mixers.py,
# ops/linear_attention.py) ---


def test_kda_counters_reach_the_flight_record(tmp_path):
    """The mixer's two counters ride beside the expert layers' three."""
    trainer = _hybrid_trainer(tmp_path, config="solar_open2_debug.json")
    trainer._train_epoch(1)
    logged = [r for r in trainer.recorder.last() if "loss" in r]
    assert logged
    for r in logged:
        # the program's own init draws the rate in 1..16 and the step in
        # 0.001..0.1 a channel: a chunk of 16 sums to between the two ends
        assert -16 * 16 * 0.1 < r["kda_chunk_log_decay_mean"] < -16 * 0.001
        assert 0.8 < r["kda_beta_mean"] < 1.2
        # 16 x 32 tokens, 2 of 8 experts a token, 4 held, 4 layers
        assert 0.5 * 2048 < r["moe_pairs_here"] < 1.5 * 2048
        assert r["moe_load_max_over_mean"] >= 1.0
    assert all("kda_beta_mean" not in r
               for r in trainer.recorder.last() if "loss" not in r)
    # the configuration states a selection_bias_rate: every layer's router
    # has had its biases moved by whole rates
    for name in ("layers_0", "layers_3"):
        bias = np.asarray(
            trainer.state.params[name]["experts"]["selection_bias"]) / 1e-3
        assert np.any(bias) and np.abs(bias).max() <= 6.001
        np.testing.assert_allclose(bias, np.round(bias), atol=1e-3)


def test_delta_rule_step_carries_its_scopes(caplog):
    import logging

    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.config.registry import MODELS
    from pytorch_distributed_template_tpu.engine.losses import (
        lm_cross_entropy,
    )
    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )
    from pytorch_distributed_template_tpu.engine.steps import make_train_step
    from pytorch_distributed_template_tpu.observability import trace

    trace._said.clear()
    trace.get_recorder().clear()
    model = MODELS.get("TinySolarOpen2")(pattern="*K", remat=True)
    tx = optax.adamw(1e-3)
    with caplog.at_level(logging.INFO):
        # shapes alone; the init's probe is traced all the same, and says
        # its one row
        state = jax.eval_shape(lambda: create_train_state(
            model, tx, np.zeros((1, 40), np.int32), seed=0))
        step = make_train_step(model, tx, lm_cross_entropy, [],
                               input_key="tokens", target_key="tokens")
        batch = {"tokens": jnp.zeros((2, 40), jnp.int32),
                 "mask": jnp.ones((2,), jnp.float32)}
        names = set(re.findall(
            r'op_name="([^"]*)"',
            jax.jit(step).lower(state, batch).compile().as_text()))

    def some(pattern):
        return any(re.search(pattern, n) for n in names)

    for scope in ("kda_scan", "kda_scan/kda_intra", "kda_scan/kda_state",
                  "kda_proj", "gated_attn", "ssm_scan/ssm_conv",
                  "moe_route", "moe_experts", "moe_shared"):
        assert some(rf"jvp\(.*/{scope}/"), scope
        assert some(rf"transpose\(jvp\(.*/{scope}/"), scope
    assert some(r"layers_0/gated_attn/mixer/g_proj")
    assert some(r"layers_1/mixer/kda_proj/q_proj")
    assert some(r"layers_1/experts/moe_experts")
    # the scan is outside the projections' scope and the other way round
    assert not some(r"kda_proj/.*kda_scan") and not some(r"kda_scan/.*_proj")
    assert not some(r"kda_(proj|scan)/.*ssm_conv")
    # what the stack and the scan chose from shapes, once each
    for name in ("model/pattern", "kda/chunks", "moe/dispatch", "ssm/conv"):
        assert [e for e in trace.get_recorder().snapshot()
                if e["name"] == name], name
    # the init probe's one row and the step's two: distinct records
    said = [e["args"] for e in trace.get_recorder().snapshot()
            if e["name"] == "kda/chunks"]
    assert [(c["chunks"], c["chunk"], c["sub_chunk"], c["heads"])
            for c in said] == [(3, 16, 16, 4)] * 2
    assert [c["pair_bytes"] for c in said] == [
        b * 3 * 4 * 16 * 16 * 16 * 4 for b in (1, 2)]
    assert "model/pattern: *K (2 layers" in caplog.text
    assert "three matrices an expert" in caplog.text


# -- the convolution/attention stack (models/hybrid.py's LFM2_MOE,
# models/mixers.ShortConvMixer) ---


def test_lfm2_counters_reach_the_flight_record(tmp_path):
    """The expert layers' four counters through the Trainer, from the
    three layers behind the leading dense one. A token takes 2 of the 4
    experts held, so the products run over the pairs: `moe_rows_run`,
    which this family alone carries, reads the rows the products' groups
    hold, the pairs themselves, and not tokens x held."""
    trainer = _hybrid_trainer(tmp_path, config="lfm2_moe_debug.json")
    assert trainer.model.step_counters == (
        "moe_pairs_here", "moe_load_max_over_mean", "moe_tokens_unserved",
        "moe_rows_run")
    trainer._train_epoch(1)
    logged = [r for r in trainer.recorder.last() if "loss" in r]
    assert logged
    for r in logged:
        # 16 x 32 tokens, 2 of 8 experts a token, 4 held, 3 expert layers
        assert 0.5 * 1536 < r["moe_pairs_here"] < 1.5 * 1536
        assert r["moe_rows_run"] == r["moe_pairs_here"] < 3 * 512 * 4
        assert r["moe_load_max_over_mean"] >= 1.0
        assert 0 <= r["moe_tokens_unserved"] <= 3 * 512
    params = trainer.state.params
    assert "experts" not in params["layers_0"] and "mlp" in params["layers_0"]
    bias = np.asarray(params["layers_3"]["experts"]["selection_bias"]) / 1e-3
    assert np.any(bias) and np.abs(bias).max() <= 6.001


def test_short_conv_step_carries_its_scopes(caplog):
    import logging

    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.config.registry import MODELS
    from pytorch_distributed_template_tpu.engine.losses import (
        lm_cross_entropy,
    )
    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )
    from pytorch_distributed_template_tpu.engine.steps import make_train_step
    from pytorch_distributed_template_tpu.observability import trace

    trace._said.clear()
    trace.get_recorder().clear()
    model = MODELS.get("TinyLfm2Moe")(remat=True)
    tx = optax.adamw(1e-3)
    with caplog.at_level(logging.INFO):
        state = jax.eval_shape(lambda: create_train_state(
            model, tx, np.zeros((1, 40), np.int32), seed=0))
        step = make_train_step(model, tx, lm_cross_entropy, [],
                               input_key="tokens", target_key="tokens")
        batch = {"tokens": jnp.zeros((2, 40), jnp.int32),
                 "mask": jnp.ones((2,), jnp.float32)}
        names = set(re.findall(
            r'op_name="([^"]*)"',
            jax.jit(step).lower(state, batch).compile().as_text()))

    def some(pattern):
        return any(re.search(pattern, n) for n in names)

    for scope in ("short_conv", "short_conv_proj", "qknorm_attn", "dense_mlp",
                  "moe_route", "moe_experts"):
        assert some(rf"jvp\(.*/{scope}/"), scope
        assert some(rf"transpose\(jvp\(.*/{scope}/"), scope
    assert some(r"layers_0/mixer/short_conv_proj/in_proj")
    assert some(r"layers_2/mixer/short_conv_proj/out_proj")
    assert some(r"layers_1/qknorm_attn/mixer/q_layernorm")
    assert some(r"layers_0/dense_mlp/mlp") and some(r"layers_1/experts/moe_")
    # the leading layer has no experts, the others no dense MLP
    assert not some(r"layers_0/experts") and not some(r"layers_[12]/dense_mlp")
    # the gates and the convolution are outside the projections' scope
    assert not some(r"short_conv/.*_proj") and not some(r"short_conv_proj/.*"
                                                        r"short_conv/")
    assert not some(r"ssm_conv|gated_attn")
    said = [e["args"] for e in trace.get_recorder().snapshot()
            if e["name"] == "conv/short"]
    # the init probe's one row and the step's two: distinct records
    assert said == [dict(taps=3, channels=64, positions=b * 40,
                         read_bytes=3 * b * 40 * 64 * 4,
                         written_bytes=b * 40 * 64 * 4) for b in (1, 2)]
    assert "model/pattern: cfc (3 layers, each a mixer and gated experts, " \
        "the first 1 a gated MLP of 96); c: a gated convolution of 3 taps" \
        in caplog.text
    assert "rotation of base 1e+06, a norm a head of q and of k" in caplog.text
