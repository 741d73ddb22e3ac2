"""Warm-path layer (ISSUE 2 tentpole): background AOT warmup installs
compiled executables before step 1 (first invocation records a dispatch
span, not a compile span), the persistent compilation cache round-trips
in a temp dir (an identical second compile is a cache hit, not a new
compile), and a failed warmup degrades gracefully to lazy compile."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_template_tpu.engine.steps import instrument_step
from pytorch_distributed_template_tpu.engine.warmup import (
    StepWarmup, abstract_batch,
)
from pytorch_distributed_template_tpu.observability.trace import (
    get_recorder,
)


def _make_step():
    """A fresh jitted toy step per call: a NEW jit wrapper each time, so
    nothing is pre-seeded by jax's in-memory jit cache."""
    def f(state, batch):
        s = jnp.sum(batch["x"]) * 1.5
        return state + s, {"loss_sum": s}

    return jax.jit(f)


def _span_names(since: int) -> list:
    return [e["name"] for e in get_recorder().snapshot()[since:]]


# ---------------------------------------------------------------------------
# AOT warmup -> first call dispatches
# ---------------------------------------------------------------------------


def test_warm_first_invocation_records_dispatch_not_compile():
    jitted = _make_step()
    w = StepWarmup()
    w.add("train_step", jitted, jnp.float32(0),
          {"x": jax.ShapeDtypeStruct((4,), jnp.float32)})
    w.start()
    assert w.result("train_step") is not None   # compile finished

    step = instrument_step(jitted, "train_step", warmup=w)
    mark = len(get_recorder().snapshot())
    state, m = step(jnp.float32(0), {"x": jnp.ones((4,), jnp.float32)})
    assert float(state) == pytest.approx(6.0)
    names = _span_names(mark)
    assert "train_step/dispatch" in names
    assert "train_step/compile+execute" not in names
    # the warm first dispatch is flagged so traces distinguish it
    (first,) = [e for e in get_recorder().snapshot()[mark:]
                if e["name"] == "train_step/dispatch"]
    assert first["args"]["warm"] is True

    # steady state still dispatches (and stays numerically identical)
    state2, _ = step(jnp.float32(1), {"x": jnp.ones((4,), jnp.float32)})
    assert float(state2) == pytest.approx(7.0)


def test_warmup_matches_lazy_results():
    """The AOT-compiled executable computes exactly what the lazy jit
    path computes (same program, different install path)."""
    x = {"x": jnp.arange(4, dtype=jnp.float32)}
    lazy_out, _ = instrument_step(_make_step(), "s_lazy")(
        jnp.float32(2), x)
    jitted = _make_step()
    w = StepWarmup()
    w.add("s_warm", jitted, jnp.float32(0),
          {"x": jax.ShapeDtypeStruct((4,), jnp.float32)})
    warm_out, _ = instrument_step(jitted, "s_warm", warmup=w.start())(
        jnp.float32(2), x)
    assert float(warm_out) == float(lazy_out)


def test_warmup_failure_degrades_to_lazy_compile():
    """A warmup job that blows up (wrong arity here) must leave the
    wrapped step fully functional on the lazy path — first call records
    the compile span, results are correct, no exception escapes."""
    jitted = _make_step()
    w = StepWarmup()
    w.add("train_step", jitted, jnp.float32(0))   # missing the batch arg
    w.start()
    assert w.result("train_step") is None

    step = instrument_step(jitted, "train_step", warmup=w)
    mark = len(get_recorder().snapshot())
    state, _ = step(jnp.float32(0), {"x": jnp.ones((4,), jnp.float32)})
    assert float(state) == pytest.approx(6.0)
    names = _span_names(mark)
    assert "train_step/compile+execute" in names
    assert "train_step/dispatch" not in names


def test_warm_executable_input_mismatch_falls_back_to_lazy():
    """A warmed executable whose abstract spec diverged from the real
    inputs (dtype drift) must NOT crash the first step: the compiled
    call raises before executing and the wrapper falls back to lazy
    jit with the real avals."""
    jitted = _make_step()
    w = StepWarmup()
    w.add("train_step", jitted, jnp.float32(0),
          {"x": jax.ShapeDtypeStruct((4,), jnp.int32)})   # wrong dtype
    w.start()
    assert w.result("train_step") is not None

    step = instrument_step(jitted, "train_step", warmup=w)
    mark = len(get_recorder().snapshot())
    state, _ = step(jnp.float32(0), {"x": jnp.ones((4,), jnp.float32)})
    assert float(state) == pytest.approx(6.0)
    names = _span_names(mark)
    assert "train_step/compile+execute" in names  # lazy path took over
    # later calls stay on the lazy jit (no stale warm executable)
    state2, _ = step(jnp.float32(1), {"x": jnp.ones((4,), jnp.float32)})
    assert float(state2) == pytest.approx(7.0)


def test_warmup_unknown_name_and_no_warmup():
    w = StepWarmup()
    assert w.result("never_registered") is None
    # warmup=None is the default wiring and must keep the old contract
    step = instrument_step(_make_step(), "plain")
    out, _ = step(jnp.float32(0), {"x": jnp.ones((4,), jnp.float32)})
    assert float(out) == pytest.approx(6.0)


def test_options_on_the_jitted_step_reach_both_compile_paths():
    """`compiler_options` given to `jax.jit` ride on the function: the
    warm-up's `lower().compile()` and the lazy first call both build with
    them, so neither can end up with another executable. An option the
    compiler does not know is refused on both."""
    def f(state, batch):
        return state + jnp.sum(batch["x"]), {}

    jitted = jax.jit(f, compiler_options={"xla_no_such_option": True})
    w = StepWarmup()
    w.add("train_step", jitted, jnp.float32(0),
          {"x": jax.ShapeDtypeStruct((4,), jnp.float32)})
    assert w.start().result("train_step") is None     # refused ahead of time
    with pytest.raises(Exception, match="xla_no_such_option"):
        jitted(jnp.float32(0), {"x": jnp.ones((4,), jnp.float32)})


def test_trainer_compiles_its_step_with_the_mesh_compile_options(
        tmp_path, monkeypatch):
    """engine/trainer.py asks parallel.train_step_compile_options for the
    mesh's options once and puts them on the jitted train step, so every
    path that compiles it gets them: with an option no compiler knows, the
    ahead-of-time warm-up degrades and the lazy first step is refused."""
    import json
    from pathlib import Path

    from pytorch_distributed_template_tpu.config import (
        ConfigParser, LOADERS, LOSSES, METRICS, MODELS,
    )
    import pytorch_distributed_template_tpu.data  # noqa: F401
    import pytorch_distributed_template_tpu.models  # noqa: F401
    from pytorch_distributed_template_tpu.engine import Trainer
    from pytorch_distributed_template_tpu.engine import trainer as trainer_mod
    from pytorch_distributed_template_tpu.parallel import mesh_from_config

    asked = []

    def options(mesh):
        asked.append(mesh)
        return {"xla_no_such_option": True}

    monkeypatch.setattr(trainer_mod, "train_step_compile_options", options)
    cfg = json.loads(
        (Path(__file__).parent.parent / "configs" / "mnist_debug.json")
        .read_text())
    cfg["trainer"]["save_dir"] = str(tmp_path)
    cfg["trainer"]["epochs"] = 1
    config = ConfigParser(cfg, run_id="opts")
    mesh = mesh_from_config(config)
    trainer = Trainer(
        config.init_obj("arch", MODELS), LOSSES.get(config["loss"]),
        [METRICS.get(m) for m in config["metrics"]], config=config,
        train_loader=config.init_obj("train_loader", LOADERS), mesh=mesh,
    )
    assert asked == [mesh]
    assert trainer._warmup.result("train_step") is None
    with pytest.raises(Exception, match="xla_no_such_option"):
        trainer.train()


# ---------------------------------------------------------------------------
# abstract batches from loader specs
# ---------------------------------------------------------------------------


def test_abstract_batch_matches_loader_and_transform():
    from pytorch_distributed_template_tpu.data.loader import (
        ArrayDataLoader,
    )
    from pytorch_distributed_template_tpu.parallel import (
        batch_sharding, build_mesh,
    )

    mesh = build_mesh({"data": -1}, jax.devices())
    sharding = batch_sharding(mesh)
    loader = ArrayDataLoader(
        {"image": np.zeros((40, 6, 6, 3), np.uint8),
         "label": np.zeros((40,), np.int64)},
        batch_size=8,
        normalize={"key": "image", "mean": [0.5], "std": [0.5],
                   "on_device": True},
    )
    sds = abstract_batch(loader, sharding,
                         transform=loader.device_transform)
    assert set(sds) == {"image", "label", "mask"}
    assert sds["image"].shape == (8, 6, 6, 3)
    # the on-device normalize runs AFTER the transfer: the abstract
    # batch must carry its post-transform dtype
    assert sds["image"].dtype == jnp.float32
    assert sds["mask"].shape == (8,) and sds["mask"].dtype == bool
    assert all(s.sharding == sharding for s in jax.tree.leaves(sds))

    # HOST-side normalization (no on_device): arrays stay uint8 but
    # batches leave the gather as float32 — the spec must match the
    # batch, or the warmed executable rejects the first real step
    host_loader = ArrayDataLoader(
        {"image": np.zeros((40, 6, 6, 3), np.uint8),
         "label": np.zeros((40,), np.int64)},
        batch_size=8,
        normalize={"key": "image", "mean": [0.5], "std": [0.5]},
    )
    assert host_loader.device_transform is None
    host_sds = abstract_batch(host_loader, sharding)
    assert host_sds["image"].dtype == jnp.float32
    real = next(iter(host_loader))
    assert real["image"].dtype == host_sds["image"].dtype


# ---------------------------------------------------------------------------
# persistent compilation cache round-trip
# ---------------------------------------------------------------------------


@pytest.fixture
def cache_config(monkeypatch):
    """Start from the rule's case (c): no env var. (tests/conftest.py puts
    jax's cache settings back after every test, these too.)"""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)


def test_persistent_cache_roundtrip(tmp_path, cache_config):
    """With ``compile_cache`` pointed at a temp dir, compiling an
    identical function a second time (fresh jit wrapper, so the
    in-memory jit cache cannot serve it) emits a cache HIT and no new
    compile (no cache miss) — the executable comes from disk."""
    from pytorch_distributed_template_tpu.observability.telemetry import (
        compile_cache_stats, drain_compile_events,
    )
    from pytorch_distributed_template_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    active = configure_compile_cache(
        {"compile_cache": {"dir": str(tmp_path / "xla-cache")}})
    assert active == str(tmp_path / "xla-cache")
    assert compile_cache_stats()["enabled"]

    def make():
        def g(x):
            return jnp.tanh(x) @ x.T + 0.317
        return jax.jit(g)

    x = jnp.ones((16, 16))
    before = compile_cache_stats()
    make()(x).block_until_ready()
    mid = compile_cache_stats()
    assert mid["misses"] > before["misses"]   # cold: real compiles
    drain_compile_events()

    make()(x).block_until_ready()             # identical fn, new jit
    after = compile_cache_stats()
    assert after["misses"] == mid["misses"]   # NO new compile
    assert after["hits"] > mid["hits"]        # served from disk
    events = [e["event"] for e in drain_compile_events()]
    assert any(e.endswith("cache_hits") for e in events)
    assert not any(e.endswith("cache_misses") for e in events)


def test_configure_compile_cache_noop_without_section(cache_config):
    """Rule (c): no env var, no ``compile_cache`` section, no argument
    -> the cache is ON at the one fixed path inside the checkout."""
    from pathlib import Path

    from pytorch_distributed_template_tpu.utils.compile_cache import (
        DEFAULT_CACHE_DIR, configure_compile_cache,
    )

    repo = Path(__file__).resolve().parent.parent
    assert DEFAULT_CACHE_DIR == str(repo / ".cache" / "xla")
    assert configure_compile_cache({}) == DEFAULT_CACHE_DIR
    assert configure_compile_cache(None) == DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == DEFAULT_CACHE_DIR


def test_compile_cache_env_var_wins(tmp_path, cache_config, monkeypatch):
    """Rule (a): with ``JAX_COMPILATION_CACHE_DIR`` set, neither a
    config section nor an explicit argument sets another directory."""
    from pytorch_distributed_template_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    env_dir = str(tmp_path / "from-env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    section = {"compile_cache": {"dir": str(tmp_path / "from-config")}}
    assert configure_compile_cache(section) == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert configure_compile_cache(
        section, cache_dir=str(tmp_path / "from-arg")) == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert not (tmp_path / "from-config").exists()
    assert not (tmp_path / "from-arg").exists()


def test_compile_cache_default_independent_of_cwd(tmp_path, cache_config,
                                                  monkeypatch):
    """Rule (c) names the same directory from any working directory (a
    cache that moves with the cwd never hits)."""
    from pytorch_distributed_template_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    seen = []
    for name in ("a", "b"):
        (tmp_path / name).mkdir()
        monkeypatch.chdir(tmp_path / name)
        seen.append(configure_compile_cache({}))
    assert seen[0] == seen[1]
    assert not seen[0].startswith(str(tmp_path))


def test_no_shipped_config_sets_a_cache_dir():
    """The rule lives in utils/compile_cache.py alone: no file under
    configs/ names a cache directory of its own."""
    import json
    from pathlib import Path

    configs = sorted(
        (Path(__file__).resolve().parent.parent / "configs").glob("*.json"))
    assert configs
    offenders = [p.name for p in configs
                 if "dir" in (json.loads(p.read_text())
                              .get("compile_cache") or {})]
    assert offenders == []
