"""Distributed evaluation: the reference's ``test.py`` as a library.

Parity with /root/reference/test.py:14-101: build components from config,
restore a checkpoint, run a no-grad loop over the test loader, and compute
metrics over the *global* dataset. The reference all_gathers every rank's
outputs/targets as pickles and computes metrics on rank 0 (test.py:87-95);
here metric sufficient statistics reduce in-graph, so every host holds the
identical global result and nothing crosses the interconnect as pickle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..config.registry import LOADERS, METRICS, MODELS
from ..data.loader import prefetch_to_device
from ..models.base import inject_mesh
from ..observability.trace import span
from ..parallel import batch_sharding, dist, mesh_from_config
from ..utils.util import write_json
from .losses import resolve_loss
from .optim import build_optimizer
from .state import create_sharded_train_state
from .steps import _accepts_example_mask, finalize_metrics, make_eval_step


def _build_test_loader(config):
    """Resolve the eval loader like the reference does: an explicit
    ``test_loader`` block wins; otherwise reuse the experiment's loader
    config with ``training=False`` (reference test.py:43-52 rebuilds the
    training config's loader in eval mode), preferring ``valid_loader``."""
    if config.get("test_loader", None):
        return config.init_obj("test_loader", LOADERS)
    for block in ("valid_loader", "train_loader"):
        spec = config.get(block, None)
        if spec:
            args = dict(spec.get("args", {}))
            args["training"] = False
            args["shuffle"] = False
            return LOADERS.get(spec["type"])(**args)
    raise KeyError(
        "config defines none of test_loader/valid_loader/train_loader"
    )


def restore_template_state(config, model, mesh, template=None):
    """Restore ``config.resume`` into a freshly-built template state.

    The template's tree matches what training saved: optimizer slot shapes
    depend only on optimizer type + param shapes, and ``ema_params`` is
    present iff the training config enabled EMA. Shared by the evaluation
    and sampling CLIs (test.py, generate.py). Returns
    ``(state, ema_decay)``.
    """
    from ..checkpoint import CheckpointManager

    tx, _, _ = build_optimizer(config, steps_per_epoch=1)
    ema_decay = float(config["trainer"].get("ema_decay", 0.0))
    if template is None:
        template = model.batch_template(1)
    state, _ = create_sharded_train_state(
        model, tx, template, mesh, with_ema=ema_decay > 0,
    )
    manager = CheckpointManager(config.resume.parent)
    state, _, _ = manager.restore(
        config.resume, state, config.config, type(model).__name__
    )
    return state, ema_decay


def _make_output_step(model, input_key: str, use_ema: bool, mesh,
                      eval_rng: bool = False):
    """Jitted raw-output forward for ``--save-outputs``: returns the
    model's per-example outputs (logits), materializing them even for
    ``fused_head`` models. This is a second forward pass on top of
    ``eval_step`` — accepted: the dump is opt-in, and keeping the metric
    path's in-graph global reductions untouched beats threading a
    [B, T, V] residual through it.

    The result is sharding-constrained to batch-only (non-batch dims
    replicated): under TP the head kernel is vocab-sharded, and without
    the constraint each host's shards would cover only a V/tp column
    slice of its rows."""
    pass_example_mask = _accepts_example_mask(model)
    out_sharding = batch_sharding(mesh)

    def output_step(state, batch, rng=None):
        params = (
            state.ema_params
            if use_ema and state.ema_params is not None
            else state.params
        )
        variables = {"params": params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        extra = (
            {"example_mask": batch["mask"]} if pass_example_mask else {}
        )
        if eval_rng:
            # SAME per-batch key as eval_step: the dumped logits/mask
            # must describe the batch the metrics actually scored
            extra["rngs"] = {"eval": rng}
        out = model.apply(variables, batch[input_key], train=False, **extra)
        if getattr(model, "mlm_output", False):
            # (logits, per-position eval mask) — the BERT MLM pair
            # (models/bert.py, dispatched by the class attribute, NOT
            # by shape sniffing): keep both; the dump writes the mask
            # next to the logits so saved outputs never depend on the
            # model's private mask rule
            logits, sel = out
            return (
                jax.lax.with_sharding_constraint(
                    logits.astype(jnp.float32), out_sharding
                ),
                jax.lax.with_sharding_constraint(
                    sel.astype(jnp.float32), out_sharding
                ),
            )
        if isinstance(out, tuple):
            # fused_head: (hidden [B,T,D], w [D,V]) — materialize logits
            hidden, w = out
            out = hidden @ w
        return jax.lax.with_sharding_constraint(
            out.astype(jnp.float32), out_sharding
        )

    return output_step


def _host_local_rows(arr) -> np.ndarray:
    """Rows of a batch-sharded global array that live on THIS host, in
    batch order, deduplicating replicated shards (e.g. over a tensor
    axis). The per-host analogue of the reference's gather-to-rank-0
    (test.py:87-95) — over DCN each host dumps its own rows instead of
    pickling activations across the network."""
    by_start = {}
    for s in arr.addressable_shards:
        # batch-only sharding contract: every non-batch dim must be a full
        # slice, else dedup-by-row-start would silently drop columns.
        # A real error (not an assert) so the contract survives `python -O`.
        if not all(
            sl.start in (None, 0) and sl.stop in (None, n)
            for sl, n in zip(s.index[1:], arr.shape[1:])
        ):
            raise ValueError(
                f"shard {s.index} is split along a non-batch axis; "
                "save_outputs requires batch-only sharding"
            )
        start = s.index[0].start or 0
        if start not in by_start:
            by_start[start] = np.asarray(s.data)
    return np.concatenate(
        [by_start[k] for k in sorted(by_start)], axis=0
    )


def evaluate(config, mesh=None, save_outputs=None, seed=None) -> dict:
    """Evaluate ``config.resume`` on the config's ``test_loader``.

    ``save_outputs``: optional directory; when set, every host writes its
    per-example model outputs/targets (pad-filtered, eval order) as
    ``outputs_p{K}.npy`` / ``targets_p{K}.npy`` for post-hoc analysis —
    the capability the reference exposes by gathering raw predictions
    (reference test.py:87-95, base_trainer.py:176-181).

    ``seed``: optional int; seeds eval-time model randomness (the
    ``"eval"`` rng stream, folded per batch — e.g. BertMLM's seeded
    random eval mask). ``None`` keeps the fully deterministic eval path.
    The reference's ``--seed`` crashes outright (reference test.py:125,
    numpy unimported); here it is wired end to end.
    """
    logger = config.get_logger("test")
    assert config.resume is not None, "evaluation requires a checkpoint (-r)"

    model = config.init_obj("arch", MODELS)
    criterion = resolve_loss(config["loss"])
    metric_fns = [METRICS.get(m) for m in config["metrics"]]
    test_loader = _build_test_loader(config)
    mesh = mesh if mesh is not None else mesh_from_config(config)
    model = inject_mesh(model, mesh)

    dk = config.get("data_keys", {}) or {}
    input_key = dk.get("input", "image")
    target_key = dk.get("target", "label")

    template = test_loader.arrays[input_key][:1]
    device_transform = getattr(test_loader, "device_transform", None)
    if device_transform is not None:
        template = np.asarray(
            device_transform({input_key: template})[input_key]
        )
    state, ema_decay = restore_template_state(
        config, model, mesh, template=template
    )

    use_ema = ema_decay > 0 and bool(
        config["trainer"].get("eval_with_ema", True)
    )
    eval_step = jax.jit(
        make_eval_step(
            model, criterion, metric_fns,
            input_key=input_key, target_key=target_key,
            use_ema=use_ema, eval_rng=seed is not None,
        )
    )
    base_key = (
        jax.random.key(int(seed)) if seed is not None else None
    )

    output_step = None
    if save_outputs is not None:
        output_step = jax.jit(
            _make_output_step(
                model, input_key, use_ema=use_ema, mesh=mesh,
                eval_rng=seed is not None,
            )
        )
        dumped_out, dumped_tgt, dumped_msk = [], [], []

    from ..utils.util import maybe_tqdm

    batches = prefetch_to_device(
        test_loader, batch_sharding(mesh),
        size=max(int(config["trainer"].get("prefetch_depth", 2)), 1),
        transform=device_transform,
    )
    if dist.is_main_process():
        # reference test.py:71 wraps the eval loop in tqdm (TTY-gated)
        batches = maybe_tqdm(batches, total=len(test_loader), desc="eval",
                             enable=config["trainer"].get("progress"))
    accum = None
    for i, batch in enumerate(batches):
        # per-batch key: every host folds the same global batch index,
        # so the mask agrees across hosts of a sharded batch
        rng_args = (
            (jax.random.fold_in(base_key, i),)
            if base_key is not None else ()
        )
        with span("eval/step", batch=i):
            m = eval_step(state, batch, *rng_args)
        accum = m if accum is None else jax.tree.map(jnp.add, accum, m)
        if output_step is not None:
            with span("eval/save_outputs", batch=i):
                res = output_step(state, batch, *rng_args)
            keep = _host_local_rows(batch["mask"]).astype(bool)
            if isinstance(res, tuple):          # MLM: (logits, eval mask)
                res, msk = res
                # bool on host: the dump exists for large eval sets, and
                # a f32 position mask would 4x the file + transfer
                dumped_msk.append(
                    _host_local_rows(msk)[keep].astype(bool)
                )
            out = _host_local_rows(res)
            tgt = _host_local_rows(batch[target_key])
            dumped_out.append(out[keep])
            dumped_tgt.append(tgt[keep])

    if output_step is not None:
        from pathlib import Path

        out_dir = Path(save_outputs)
        out_dir.mkdir(parents=True, exist_ok=True)
        p = dist.process_index()
        if dumped_out:
            np.save(out_dir / f"outputs_p{p}.npy", np.concatenate(dumped_out))
            np.save(out_dir / f"targets_p{p}.npy", np.concatenate(dumped_tgt))
            if dumped_msk:
                # the MLM eval mask rides along so post-hoc scoring never
                # re-derives the model's private masking rule
                np.save(out_dir / f"masks_p{p}.npy",
                        np.concatenate(dumped_msk))
            logger.info("saved per-example outputs to %s", out_dir)
        else:
            # No local batches at all: writing a shape/dtype-less
            # placeholder would poison post-hoc cross-host concatenation
            # of outputs_p*.npy, so skip the files and say so.
            logger.info(
                "no local eval rows on process %d; skipping output dump", p
            )

    n_samples = int(accum["count"]) if accum else 0
    result = finalize_metrics(jax.tree.map(float, accum)) if accum else {}
    if dist.is_main_process():
        logger.info({"n_samples": n_samples, **result})
        # machine-readable twin of the trainer's summary.json
        write_json(
            {"n_samples": n_samples, **result,
             "checkpoint": str(config.resume),
             "device": dist.device_summary()},
            config.save_dir / "summary.json",
        )
    return result
