"""Mesh construction and sharding-rule tests on the 8-device CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_distributed_template_tpu.parallel import (
    batch_sharding,
    build_mesh,
    apply_rules,
    train_step_compile_options,
)
from pytorch_distributed_template_tpu.parallel.mesh import (
    axis_size,
    resolve_axis_sizes,
)


def test_eight_devices():
    assert jax.device_count() == 8, "conftest must force 8 CPU devices"


def test_resolve_axis_sizes():
    assert resolve_axis_sizes(None, 8) == {"data": 8}
    assert resolve_axis_sizes({"data": -1, "tensor": 2}, 8) == {
        "data": 4,
        "tensor": 2,
    }
    with pytest.raises(ValueError):
        resolve_axis_sizes({"data": 3}, 8)
    with pytest.raises(ValueError):
        resolve_axis_sizes({"data": -1, "tensor": -1}, 8)
    with pytest.raises(ValueError):
        resolve_axis_sizes({"bogus": 8}, 8)


def test_build_mesh_default_dp():
    mesh = build_mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.shape["data"] == 8


def test_build_mesh_2d():
    mesh = build_mesh({"data": 2, "tensor": 4})
    assert axis_size(mesh, "data") == 2
    assert axis_size(mesh, "tensor") == 4
    assert axis_size(mesh, "seq") == 1


def test_batch_sharding_splits_batch():
    mesh = build_mesh({"data": 8})
    x = jnp.zeros((16, 4))
    xs = jax.device_put(x, batch_sharding(mesh))
    # each device holds 2 rows
    assert xs.addressable_shards[0].data.shape == (2, 4)


def test_batch_sharding_data_fsdp_combined():
    mesh = build_mesh({"data": 2, "fsdp": 4})
    x = jnp.zeros((16, 4))
    xs = jax.device_put(x, batch_sharding(mesh))
    assert xs.addressable_shards[0].data.shape == (2, 4)  # 16/(2*4)


def test_apply_rules_tp_and_replicate():
    mesh = build_mesh({"data": 2, "tensor": 4})
    params = {
        "dense": {"kernel": jnp.zeros((8, 16)), "bias": jnp.zeros((16,))},
        "attn": {"qkv": {"kernel": jnp.zeros((8, 12))}},
    }
    rules = [
        (r"attn/qkv/kernel", P(None, "tensor")),
    ]
    shardings = apply_rules(params, mesh, rules)
    assert shardings["attn"]["qkv"]["kernel"].spec == P(None, "tensor")
    assert shardings["dense"]["kernel"].spec == P()


def test_apply_rules_prunes_absent_axes():
    mesh = build_mesh({"data": 8})  # no tensor axis
    params = {"qkv": {"kernel": jnp.zeros((8, 12))}}
    rules = [(r"qkv/kernel", P(None, "tensor"))]
    shardings = apply_rules(params, mesh, rules)
    # pruned to fully-replicated (the exact spec spelling — P() vs
    # P(None, None) — is not part of the contract)
    assert all(e is None for e in shardings["qkv"]["kernel"].spec)


def test_fsdp_fallback_covers_pruned_rule_matches():
    """A TP rule on an fsdp-only mesh prunes to nothing — the leaf
    must then take the ZeRO-3 fallback, NOT silently replicate
    (round-5 compiled-HLO audit finding: per-device param bytes were
    99% of full because every rule-matched kernel replicated)."""
    mesh = build_mesh({"data": 2, "fsdp": 4})
    params = {"qkv": {"kernel": jnp.zeros((8, 12))},
              "norm": {"scale": jnp.zeros((64,))}}
    rules = [(r"qkv/kernel", P(None, "tensor"))]
    shardings = apply_rules(params, mesh, rules)
    assert "fsdp" in jax.tree_util.tree_leaves(
        tuple(shardings["qkv"]["kernel"].spec))
    # unmatched leaves keep taking the fallback too
    assert shardings["norm"]["scale"].spec == P("fsdp")


def test_fsdp_default_shards_largest_axis():
    mesh = build_mesh({"fsdp": 8})
    params = {"w": jnp.zeros((24, 7)), "scalar": jnp.zeros(())}
    shardings = apply_rules(params, mesh, [])
    assert shardings["w"].spec == P("fsdp", None)
    assert shardings["scalar"].spec == P()


@pytest.mark.parametrize("axes,want", [
    (None, 8 * 16 * 4 + 16 * 2 + 8),
    ({"data": 8}, 8 * 16 * 4 + 16 * 2 + 8),             # replicated
    ({"data": 2, "tensor": 4}, 8 * 4 * 4 + 16 * 2 + 8),  # the rule's share
    ({"data": 2, "fsdp": 4}, 8 * 4 * 4 + 4 * 2 + 8),     # ZeRO-3's share
], ids=["no-mesh", "dp8", "dp2-tp4", "dp2-fsdp4"])
def test_per_device_bytes_follow_the_rules(axes, want):
    """What one device holds of a tree under ``apply_rules``, at each
    leaf's dtype; shapes and dtypes are enough (a traced state)."""
    from pytorch_distributed_template_tpu.parallel.sharding import (
        per_device_bytes,
    )

    tree = {"qkv": {"kernel": jax.ShapeDtypeStruct((8, 16), jnp.float32),
                    "bias": jax.ShapeDtypeStruct((16,), jnp.bfloat16)},
            "rng": jax.eval_shape(lambda: jax.random.key(0))}
    mesh = build_mesh(axes) if axes else None
    rules = [(r"qkv/kernel", P(None, "tensor"))]
    assert per_device_bytes(tree, mesh, rules) == want


def _axes_id(axes):
    return "x".join(f"{k}{v}" for k, v in axes.items())


@pytest.mark.parametrize("axes", [
    {"data": 8}, {"fsdp": 8}, {"data": 4, "tensor": 2},
    {"data": 2, "fsdp": 2, "seq": 2}, {"tensor": 8}, {"data": 1, "tensor": 8},
], ids=_axes_id)
def test_no_compile_option_off_the_tpu(axes):
    """The CPU backend gets the program it always got, whatever the mesh."""
    assert train_step_compile_options(build_mesh(axes)) == {}


@pytest.mark.parametrize("axes", [
    {"tensor": 8}, {"data": 1, "tensor": 8}, {"seq": 4, "tensor": 2},
    {"data": 1, "fsdp": 1, "pipe": 8},
], ids=_axes_id)
def test_no_compile_option_with_one_device_along_the_batch_axes(axes):
    """No gradient crosses between chips, so nothing is asked of the TPU
    compiler either: the one-chip program and its cache key stay."""
    assert train_step_compile_options(build_mesh(axes), backend="tpu") == {}


@pytest.mark.parametrize("axes", [
    {"fsdp": 8}, {"data": 4, "tensor": 2}, {"data": 2, "fsdp": 4},
    {"data": 2, "seq": 4},
], ids=_axes_id)
def test_same_compile_options_for_every_mesh_that_splits_the_batch(axes):
    """One rule, not one per strategy: whatever splits the batch over
    more than one TPU device gets what plain data parallel gets, and every
    value is one the compiler's option parser takes (bool or int)."""
    want = train_step_compile_options(build_mesh({"data": 8}), backend="tpu")
    assert want and all(type(v) in (bool, int) for v in want.values())
    assert train_step_compile_options(build_mesh(axes),
                                      backend="tpu") == want


@pytest.mark.parametrize("axes", [
    {"data": 8}, {"fsdp": 8}, {"data": 4, "tensor": 2},
], ids=_axes_id)
def test_step_compiled_as_the_trainer_compiles_it_matches_one_device(axes):
    """The training step jitted the way engine/trainer.py jits it (the
    mesh's compile options on the jitted function; none on this backend)
    gives the single-device loss, gradient norm and gradients."""
    import optax

    from pytorch_distributed_template_tpu.config.registry import (
        LOSSES, MODELS,
    )
    import pytorch_distributed_template_tpu.engine  # noqa: F401
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.data.datasets import synthetic_lm
    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )
    from pytorch_distributed_template_tpu.engine.steps import make_train_step

    model = MODELS.get("TinyLM")(vocab_size=64, d_model=64, max_len=32)
    tx = optax.sgd(1.0)     # the step a parameter takes IS its gradient
    tokens = synthetic_lm(n=16, seq_len=32, vocab_size=64, seed=0)["tokens"]
    step = make_train_step(model, tx, LOSSES.get("lm_cross_entropy"), [],
                           input_key="tokens", target_key="tokens",
                           grad_clip_norm=1.0, log_grad_norm=True)

    def run(mesh):
        state = create_train_state(model, tx, model.batch_template(1),
                                   seed=0)
        batch = {"tokens": jnp.asarray(tokens), "mask": jnp.ones(16, bool)}
        options = None
        if mesh is not None:
            state = jax.device_put(
                state, apply_rules(state, mesh, model.partition_rules()))
            batch = jax.device_put(batch, batch_sharding(mesh))
            options = train_step_compile_options(mesh) or None
        new, m = jax.jit(step, compiler_options=options)(state, batch)
        return (float(m["loss_sum"]), float(m["grad_norm_sum"]),
                jax.tree.map(np.asarray, new.params))

    loss, gnorm, params = run(build_mesh(axes))
    ref_loss, ref_gnorm, ref_params = run(None)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    np.testing.assert_allclose(gnorm, ref_gnorm, rtol=1e-4)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=1e-4, atol=2e-6), params, ref_params)


def test_psum_grad_equivalence_on_mesh():
    """A jitted sharded loss-grad equals the unsharded one (the DDP allreduce
    contract, reference trainer/trainer.py:57, expressed by XLA)."""
    mesh = build_mesh({"data": 8})
    w = jnp.arange(4.0)
    x = np.random.RandomState(0).randn(16, 4).astype(np.float32)

    def loss(w, x):
        return jnp.mean(jnp.sum(x * w, axis=-1) ** 2)

    g_ref = jax.grad(loss)(w, jnp.asarray(x))
    xs = jax.device_put(jnp.asarray(x), batch_sharding(mesh))
    g_sharded = jax.jit(jax.grad(loss))(w, xs)
    np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_sharded), rtol=1e-6)


@pytest.mark.slow
def test_three_axis_composition_dp_tp_sp():
    """One mesh, three strategies at once: {data:2, tensor:2, seq:2} —
    batch sharded, params TP-sharded by the model's rules, attention
    sequence-parallel via ring — logits match the single-device model and
    training decreases the loss."""
    import optax

    from pytorch_distributed_template_tpu.config.registry import (
        LOSSES, METRICS, MODELS,
    )
    import pytorch_distributed_template_tpu.engine  # noqa: F401
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.data.datasets import synthetic_lm
    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )
    from pytorch_distributed_template_tpu.engine.steps import make_train_step

    mesh = build_mesh({"data": 2, "tensor": 2, "seq": 2})
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (4, 32)), jnp.int32
    )
    m_ref = MODELS.get("TinyLM")(vocab_size=64, d_model=64, max_len=64)
    m_sp = MODELS.get("TinyLM")(vocab_size=64, d_model=64, max_len=64,
                                attn_impl="ring", mesh=mesh,
                                seq_layout="zigzag")
    tx = optax.adam(3e-3)
    state = create_train_state(m_ref, tx, m_ref.batch_template(1), seed=0)

    # logits parity: sharded params + ring attention == plain single-device
    ref = m_ref.apply({"params": state.params}, tokens, train=False)
    sharded = jax.device_put(
        state, apply_rules(state, mesh, m_sp.partition_rules())
    )
    out = jax.jit(
        lambda p, t: m_sp.apply({"params": p}, t, train=False)
    )(sharded.params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)

    # and the full train step converges under all three axes at once
    step = jax.jit(
        make_train_step(m_sp, tx, LOSSES.get("lm_cross_entropy"),
                        [METRICS.get("lm_token_accuracy")],
                        input_key="tokens", target_key="tokens"),
        donate_argnums=0,
    )
    data = synthetic_lm(n=32, seq_len=32, vocab_size=64, seed=0)
    bs = batch_sharding(mesh)
    batch = {"tokens": jax.device_put(data["tokens"], bs),
             "mask": jax.device_put(np.ones(32, bool), bs)}
    losses = []
    s = sharded
    for _ in range(20):
        s, m = step(s, batch)
        losses.append(float(m["loss_sum"]) / float(m["count"]))
    assert losses[-1] < losses[0] - 0.3, losses[::6]


def test_three_axis_composition_dp_tp_ulysses():
    """Ulysses also composes with TP on one mesh: {data:2, tensor:2,
    seq:2} — per-device heads after TP (4/2=2) still split over seq."""
    import optax

    from pytorch_distributed_template_tpu.config.registry import MODELS
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )

    mesh = build_mesh({"data": 2, "tensor": 2, "seq": 2})
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, (4, 32)), jnp.int32
    )
    m_ref = MODELS.get("TinyLM")(vocab_size=64, d_model=64, max_len=64)
    m_u = MODELS.get("TinyLM")(vocab_size=64, d_model=64, max_len=64,
                               attn_impl="ulysses", mesh=mesh)
    state = create_train_state(m_ref, optax.adam(1e-3),
                               m_ref.batch_template(1), seed=0)
    ref = m_ref.apply({"params": state.params}, tokens, train=False)
    sharded = jax.device_put(
        state, apply_rules(state, mesh, m_u.partition_rules())
    )
    out = jax.jit(
        lambda p, t: m_u.apply({"params": p}, t, train=False)
    )(sharded.params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_save_outputs_step_tp_sharded_rows_complete():
    """--save-outputs under TP: the dump step's batch-only sharding
    constraint must yield host-local rows with the FULL vocab axis (the
    head kernel is vocab-sharded, so without the constraint each shard
    would hold a V/tp column slice and the dedup would drop columns)."""
    import optax

    from pytorch_distributed_template_tpu.config.registry import MODELS
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.engine.evaluator import (
        _host_local_rows, _make_output_step,
    )
    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )

    mesh = build_mesh({"data": 2, "tensor": 4})
    model = MODELS.get("TinyLM")(vocab_size=64, d_model=32, n_layer=1,
                                 n_head=2, max_len=16)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (8, 12)), jnp.int32
    )
    state = create_train_state(model, optax.sgd(0.1),
                               model.batch_template(1), seed=0)
    ref = np.asarray(
        model.apply({"params": state.params}, tokens, train=False)
    )
    sharded = jax.device_put(
        state, apply_rules(state, mesh, model.partition_rules())
    )
    batch = {
        "tokens": jax.device_put(tokens, batch_sharding(mesh)),
        "mask": jax.device_put(jnp.ones(8, bool), batch_sharding(mesh)),
    }
    step = jax.jit(
        _make_output_step(model, "tokens", use_ema=False, mesh=mesh)
    )
    rows = _host_local_rows(step(sharded, batch))
    assert rows.shape == ref.shape  # full vocab axis, all rows
    np.testing.assert_allclose(rows, ref, atol=1e-4, rtol=1e-4)


@pytest.mark.slow
def test_dp_fsdp_training_matches_dp_only():
    """ZeRO-3 is an optimizer-memory layout, not a different algorithm:
    the dp2 x fsdp4 mesh (params/opt-state sharded over fsdp, batch over
    both axes) must reproduce the dp8 loss trajectory step for step.
    Closes the VERDICT r2 evidence gap: fsdp previously had sharding-spec
    tests but no training-equivalence proof."""
    import optax

    from pytorch_distributed_template_tpu.config.registry import (
        LOSSES, METRICS, MODELS,
    )
    import pytorch_distributed_template_tpu.engine  # noqa: F401
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.data.datasets import synthetic_lm
    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )
    from pytorch_distributed_template_tpu.engine.steps import make_train_step

    model = MODELS.get("TinyLM")(vocab_size=64, d_model=64, max_len=32)
    tx = optax.adamw(3e-3)
    data = synthetic_lm(n=32, seq_len=32, vocab_size=64, seed=0)

    def run(axes, n_steps=6):
        mesh = build_mesh(axes)
        state = create_train_state(model, tx, model.batch_template(1),
                                   seed=0)
        state = jax.device_put(
            state, apply_rules(state, mesh, model.partition_rules())
        )
        if "fsdp" in axes:
            # the proof is only meaningful if fsdp actually sharded params:
            # at least one leaf must carry the fsdp axis in its spec
            specs = jax.tree.leaves(jax.tree.map(
                lambda x: "fsdp" in jax.tree_util.tree_leaves(
                    tuple(x.sharding.spec)),
                state.params,
            ))
            assert any(specs), "fsdp mesh left every param replicated"
        step = jax.jit(
            make_train_step(model, tx, LOSSES.get("lm_cross_entropy"),
                            [METRICS.get("lm_token_accuracy")],
                            input_key="tokens", target_key="tokens"),
            donate_argnums=0,
        )
        bs = batch_sharding(mesh)
        batch = {"tokens": jax.device_put(data["tokens"], bs),
                 "mask": jax.device_put(np.ones(32, bool), bs)}
        losses = []
        for _ in range(n_steps):
            state, m = step(state, batch)
            losses.append(float(m["loss_sum"]) / float(m["count"]))
        return losses

    dp = run({"data": 8})
    fsdp = run({"data": 2, "fsdp": 4})
    np.testing.assert_allclose(fsdp, dp, rtol=2e-4, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("axes", [{"data": 2, "tensor": 4},
                                  {"data": 2, "fsdp": 4}])
def test_lora_trains_under_tp_and_fsdp_meshes(axes):
    """LoRA composes with the parallelism axes: base kernels shard per
    the partition rules (tp) or the fsdp fallback while the small
    adapter factors ride along (unmatched by rules -> replicated or
    fsdp-sharded), the trainable-freeze optimizer keeps every frozen
    leaf bit-identical across steps, and the adapters actually move."""
    import optax

    from pytorch_distributed_template_tpu.config.registry import (
        LOSSES, METRICS, MODELS,
    )
    import pytorch_distributed_template_tpu.engine  # noqa: F401
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.data.datasets import synthetic_lm
    from pytorch_distributed_template_tpu.engine.optim import (
        _trainable_only,
    )
    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )
    from pytorch_distributed_template_tpu.engine.steps import make_train_step

    model = MODELS.get("TinyLlama")(
        vocab_size=64, n_layer=2, n_head=4, n_kv_head=2, d_model=32,
        max_len=32, lora_rank=4,
    )
    tx = _trainable_only(optax.adamw(3e-3), ["lora_"])
    mesh = build_mesh(axes)
    state = create_train_state(model, tx, model.batch_template(1), seed=0)
    state = jax.device_put(
        state, apply_rules(state, mesh, model.partition_rules())
    )
    if "tensor" in axes:
        spec = state.params["layers_0"]["self_attn"]["q_proj"]["kernel"] \
            .sharding.spec
        assert "tensor" in jax.tree_util.tree_leaves(tuple(spec))
    before = jax.device_get(state.params)
    step = jax.jit(
        make_train_step(model, tx, LOSSES.get("lm_cross_entropy"),
                        [METRICS.get("lm_token_accuracy")],
                        input_key="tokens", target_key="tokens",
                        grad_clip_norm=1.0,
                        trainable_patterns=["lora_"]),
        donate_argnums=0,
    )
    data = synthetic_lm(n=16, seq_len=32, vocab_size=64, seed=0)
    bs = batch_sharding(mesh)
    batch = {"tokens": jax.device_put(data["tokens"][:16], bs),
             "mask": jax.device_put(np.ones(16, bool), bs)}
    for _ in range(3):
        state, m = step(state, batch)
    after = jax.device_get(state.params)
    flat_b = jax.tree_util.tree_flatten_with_path(before)[0]
    flat_a = jax.tree_util.tree_flatten_with_path(after)[0]
    frozen = max(
        float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for (p, b), (_, a) in zip(flat_b, flat_a) if "lora" not in str(p)
    )
    lora = max(
        float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for (p, b), (_, a) in zip(flat_b, flat_a) if "lora" in str(p)
    )
    assert frozen == 0.0, "frozen base moved under the sharded step"
    assert lora > 0.0, "adapters did not train"


def test_hlo_collectives_reads_tpu_tuple_shapes():
    """The TPU compiler prints tuple-shaped collectives with
    ``/*index=5*/`` markers; their ``=`` must not hide the instruction
    (the v5e text of the 4-chip GPT-2 step lost 3 of its 4 all-reduces
    that way). CPU-style and async lines still count."""
    from pytorch_distributed_template_tpu.parallel.tp import hlo_collectives

    text = "\n".join([
        "  %all-reduce.153 = (f32[]{:T(128)}, f32[768]{0:T(1024)S(1)}, "
        "f32[768]{0:T(1024)S(1)}, f32[768]{0:T(1024)S(1)}, "
        "f32[768]{0:T(1024)S(1)}, /*index=5*/f32[768]{0:T(1024)S(1)}) "
        "all-reduce(%a, %b, %c, %d, %e, %f), channel_id=1, "
        "replica_groups=[1,4]<=[4], to_apply=%add",
        "  %all-reduce.150 = bf16[50257,768]{1,0:T(8,128)(2,1)} "
        "all-reduce(%fusion.2), channel_id=152, replica_groups=[1,4]<=[4], "
        "use_global_device_ids=true, to_apply=%region",
        "  %ars = (f32[128]{0}, f32[128]{0}) all-reduce-start(%x), "
        "channel_id=3",
        "  %ag = f32[8,64]{1,0} all-gather(%y), dimensions={0}",
        "  %fusion.2 = bf16[50257,768]{1,0} fusion(%p), kind=kLoop",
    ])
    counts, nbytes = hlo_collectives(text)
    assert counts == {"all-reduce": 3, "all-gather": 1}
    assert nbytes["all-reduce"] == 4 + 50257 * 768 * 2 + 128 * 4
    assert nbytes["all-gather"] == 8 * 64 * 4
