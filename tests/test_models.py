"""Model-zoo tests: shapes, batch_stats plumbing, learnability, registries.

The reference has no tests at all (SURVEY.md §4); these cover the expanded
model zoo the seed's baseline file ladder requires (ResNet / ViT / GPT-2) on the
8-device CPU mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from pytorch_distributed_template_tpu.config.registry import (
    LOSSES, METRICS, MODELS,
)
import pytorch_distributed_template_tpu.engine  # noqa: F401  (registers)
import pytorch_distributed_template_tpu.models  # noqa: F401
from pytorch_distributed_template_tpu.engine.state import create_train_state
from pytorch_distributed_template_tpu.engine.steps import make_train_step
from pytorch_distributed_template_tpu.parallel.mesh import build_mesh
from pytorch_distributed_template_tpu.parallel.sharding import (
    apply_rules, batch_sharding,
)


def _image_batch(rng, n, shape, num_classes):
    return {
        "image": rng.normal(size=(n, *shape)).astype(np.float32),
        "label": rng.integers(0, num_classes, size=n).astype(np.int32),
        "mask": np.ones(n, bool),
    }


class TestResNet:
    def test_forward_shapes_cifar(self):
        model = MODELS.get("ResNet18")(num_classes=10, cifar_stem=True)
        state = create_train_state(
            model, optax.sgd(0.1), model.batch_template(2), seed=0
        )
        out = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            jnp.zeros((2, 32, 32, 3)), train=False,
        )
        assert out.shape == (2, 10)
        assert state.batch_stats  # BatchNorm state exists
        # log-probabilities: each row sums to ~1 in prob space
        assert np.allclose(np.exp(np.asarray(out)).sum(-1), 1.0, atol=1e-4)

    def test_resnet50_param_count(self):
        """ResNet-50/ImageNet has the canonical ~25.5M params."""
        from pytorch_distributed_template_tpu.models.base import param_count

        model = MODELS.get("ResNet50")(num_classes=1000)
        state = create_train_state(
            model, optax.sgd(0.1), model.batch_template(1), seed=0
        )
        n = param_count(state.params)
        assert 25.0e6 < n < 26.0e6, n

    def test_bfloat16_compute_fp32_params(self):
        model = MODELS.get("ResNet18")(
            num_classes=10, cifar_stem=True, bfloat16=True
        )
        state = create_train_state(
            model, optax.sgd(0.1), model.batch_template(2), seed=0
        )
        leaves = jax.tree_util.tree_leaves(state.params)
        assert all(l.dtype == jnp.float32 for l in leaves)
        out = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            jnp.zeros((2, 32, 32, 3)), train=False,
        )
        assert out.dtype == jnp.float32  # head upcasts

    def test_space_to_depth_stem(self):
        """The MLPerf s2d stem variant: same output shape, correct 2x2
        channel packing, and a 4x4x12xF init conv kernel."""
        model = MODELS.get("ResNet50")(num_classes=10, space_to_depth=True,
                                       input_shape=(64, 64, 3))
        state = create_train_state(
            model, optax.sgd(0.1), model.batch_template(2), seed=0
        )
        assert state.params["conv_init"]["kernel"].shape == (4, 4, 12, 64)
        out = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            jnp.zeros((2, 64, 64, 3)), train=False,
        )
        assert out.shape == (2, 10)
        # packing correctness of the reshape: the [0,0] corner of every
        # 2x2 tile must land in the first C channels
        x = np.zeros((1, 64, 64, 3), np.float32)
        x[:, ::2, ::2, :] = 1.0
        b, h, w, c = x.shape
        packed = x.reshape(b, h // 2, 2, w // 2, 2, c)
        packed = packed.transpose(0, 1, 3, 2, 4, 5).reshape(
            b, h // 2, w // 2, 4 * c
        )
        # channel block 0 (the [0,0] corner of each tile) carries the 1s
        assert packed[..., :3].min() == 1.0
        assert packed[..., 3:].max() == 0.0

    def test_space_to_depth_guards(self):
        import pytest

        with pytest.raises(ValueError, match="incompatible with cifar"):
            MODELS.get("ResNet18")(cifar_stem=True, space_to_depth=True)
        model = MODELS.get("ResNet50")(space_to_depth=True,
                                       input_shape=(65, 65, 3))
        with pytest.raises(ValueError, match="even spatial dims"):
            create_train_state(
                model, optax.sgd(0.1), model.batch_template(1), seed=0
            )

    def test_trains_and_updates_batch_stats(self):
        mesh = build_mesh({"data": -1})
        model = MODELS.get("ResNet18")(num_classes=10, cifar_stem=True)
        tx = optax.sgd(0.1, momentum=0.9)
        state = create_train_state(model, tx, model.batch_template(1), seed=0)
        state = jax.device_put(state, apply_rules(state, mesh, []))
        step = jax.jit(
            make_train_step(model, tx, LOSSES.get("nll_loss"),
                            [METRICS.get("accuracy")]),
            donate_argnums=0,
        )
        rng = np.random.default_rng(0)
        bs = batch_sharding(mesh)
        stats_before = jax.tree_util.tree_leaves(state.batch_stats)[0].copy()
        losses = []
        for i in range(8):
            batch = {
                k: jax.device_put(v, bs)
                for k, v in _image_batch(rng, 32, (32, 32, 3), 10).items()
            }
            state, m = step(state, batch)
            losses.append(float(m["loss_sum"]) / float(m["count"]))
        stats_after = jax.tree_util.tree_leaves(state.batch_stats)[0]
        assert not np.allclose(stats_before, stats_after)
        assert int(state.step) == 8
        assert all(np.isfinite(l) for l in losses)


class TestViT:
    def _tiny(self, **kw):
        return MODELS.get("ViT")(
            size="vit-ti", num_classes=10, image_size=32, patch_size=8,
            n_layer=2, **kw,
        )

    def test_forward_shape_and_logprobs(self):
        model = self._tiny()
        state = create_train_state(
            model, optax.sgd(0.1), model.batch_template(2), seed=0
        )
        out = model.apply({"params": state.params},
                          jnp.zeros((2, 32, 32, 3)), train=False)
        assert out.shape == (2, 10)
        assert np.allclose(np.exp(np.asarray(out)).sum(-1), 1.0, atol=1e-4)

    def test_vit_b_param_count(self):
        """ViT-B/16 at 224px has the canonical ~86M params."""
        from pytorch_distributed_template_tpu.models.base import param_count

        model = MODELS.get("ViT")(size="vit-b", num_classes=1000)
        state = create_train_state(
            model, optax.sgd(0.1), model.batch_template(1), seed=0
        )
        n = param_count(state.params)
        assert 85.0e6 < n < 88.0e6, n

    def test_mean_pool_variant(self):
        model = self._tiny(pool="mean")
        state = create_train_state(
            model, optax.sgd(0.1), model.batch_template(2), seed=0
        )
        out = model.apply({"params": state.params},
                          jnp.zeros((2, 32, 32, 3)), train=False)
        assert out.shape == (2, 10)

    def test_tp_sharded_train_step(self):
        """ViT trains under DP x TP with its megatron partition rules."""
        mesh = build_mesh({"data": 4, "tensor": 2})
        model = self._tiny(n_head=4, d_model=64)
        tx = optax.adam(1e-3)
        state = create_train_state(model, tx, model.batch_template(1), seed=0)
        rules = model.partition_rules()
        state = jax.device_put(state, apply_rules(state, mesh, rules))
        qkv = state.params["h_0"]["qkv"]["kernel"]
        assert qkv.sharding.spec == jax.sharding.PartitionSpec(None, "tensor")
        step = jax.jit(
            make_train_step(model, tx, LOSSES.get("nll_loss"),
                            [METRICS.get("accuracy")]),
            donate_argnums=0,
        )
        rng = np.random.default_rng(0)
        bs = batch_sharding(mesh)
        batch = {
            k: jax.device_put(v, bs)
            for k, v in _image_batch(rng, 16, (32, 32, 3), 10).items()
        }
        losses = []
        for _ in range(6):
            state, m = step(state, batch)
            losses.append(float(m["loss_sum"]) / float(m["count"]))
        assert losses[-1] < losses[0]  # memorizes a fixed batch
        assert all(np.isfinite(l) for l in losses)
