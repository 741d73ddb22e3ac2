#!/usr/bin/env python3
"""Compile a cell's real train step for a described v5e, without a chip.

    JAX_PLATFORMS=cpu python3 benchmarks/compile_rehearsal.py <cell> [<cell> ...]

Run by hand before a chip call (never from a test: it describes the TPU
topology at top level, and only one process may hold the TPU library).
Builds the step as `engine/trainer.py` does, from shapes only, on the
first `chips` devices of a `v5e:2x2`, and prints the compiler's
`memory_analysis()` and the collectives and kernels in `as_text()`.
Nothing runs: a compile that passes is not a chip run.
"""
from __future__ import annotations

import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def compile_cell(name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.run import experiment_of, load_cell
    from pytorch_distributed_template_tpu import models  # noqa: F401
    from pytorch_distributed_template_tpu.config import MODELS
    from pytorch_distributed_template_tpu.engine.losses import resolve_loss
    from pytorch_distributed_template_tpu.engine.optim import build_optimizer
    from pytorch_distributed_template_tpu.engine.state import create_train_state
    from pytorch_distributed_template_tpu.engine.steps import make_train_step
    from pytorch_distributed_template_tpu.models.base import inject_mesh
    from pytorch_distributed_template_tpu.ops import flash
    from pytorch_distributed_template_tpu.parallel import (
        batch_sharding, mesh_from_config,
    )
    from pytorch_distributed_template_tpu.parallel.sharding import apply_rules

    _, cell, config = load_cell(name)
    exp = experiment_of(cell, config)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = mesh_from_config(exp, devices=topo.devices[:cell["chips"]])
    flash._on_tpu = lambda: True    # the compile is for the chip

    model = inject_mesh(
        MODELS.get(exp["arch"]["type"])(**exp["arch"]["args"]), mesh)
    tx, _, _ = build_optimizer(exp, 1)
    batch = exp["train_loader"]["args"]["batch_size"]
    seq = cell["data"]["seq_len"]
    sample = jnp.zeros((1, seq), jnp.int32)
    abstract = jax.eval_shape(
        lambda: create_train_state(model, tx, sample, seed=0))
    shardings = apply_rules(abstract, mesh, model.partition_rules())
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings)
    bs = batch_sharding(mesh)
    feed = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                           sharding=bs),
            "mask": jax.ShapeDtypeStruct((batch,), jnp.bool_, sharding=bs)}
    step = make_train_step(
        model, tx, resolve_loss(exp["loss"]), [], input_key="tokens",
        target_key="tokens",
        grad_clip_norm=exp["trainer"]["grad_clip_norm"],
        skip_nonfinite=exp["trainer"]["skip_nonfinite"],
        health=exp["trainer"]["health"]["enabled"])
    t0 = time.perf_counter()
    compiled = jax.jit(
        step, donate_argnums=0,
        out_shardings=(shardings, NamedSharding(mesh, P()))
    ).lower(state, feed).compile()
    text = compiled.as_text()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: compiled for {cell['chips']} x v5e in "
          f"{time.perf_counter() - t0:.0f} s; per device: arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f} GB, aliased "
          f"{m.alias_size_in_bytes / 1e9:.2f} GB, in all "
          f"{total / 1e9:.2f} GB; all-reduce "
          f"{text.count(' all-reduce(') + text.count(' all-reduce-start(')}, "
          f"tpu_custom_call {text.count('tpu_custom_call')}", flush=True)


if __name__ == "__main__":
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    for cell_name in sys.argv[1:]:
        compile_cell(cell_name)
