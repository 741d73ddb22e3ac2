"""The jitted train/eval step builders — the framework's hot loop.

Replaces the reference's per-batch Python sequence (H2D copy, zero_grad,
forward, loss, ``dist.reduce``, backward, DDP allreduce, optimizer step —
/root/reference/trainer/trainer.py:45-58) with ONE compiled SPMD program:

- the batch arrives already sharded over the mesh's data axes;
- ``jnp`` reductions over the sharded batch dimension compile to ``psum``
  over ICI (the DDP gradient allreduce *and* the reference's per-step
  ``reduce_loss`` collective, fused into the step instead of blocking it —
  the reference syncs before backward, SURVEY.md §2.1 bug list);
- masked per-example losses/metrics make duplicate-padded batches exact;
- the optimizer update runs in-graph (optax), so there is no host round-trip
  between micro-batches.

Metrics are returned as sufficient statistics ``{name_sum, count}`` — the
TPU-idiomatic version of the reference's gather-everything-to-rank-0 eval
(SURVEY.md §3.5).
"""
from __future__ import annotations

import functools
import inspect
import logging
from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import optax
from flax.traverse_util import flatten_dict, unflatten_dict

from ..models.base import param_count
from ..models.remat_policy import step_holds
from ..observability.trace import say_once
from ..parallel.sharding import per_device_bytes
from .losses import step_mesh

logger = logging.getLogger(__name__)


def _masked_sum(per_example, mask):
    return jnp.sum(per_example * mask)


def _accepts_example_mask(model) -> bool:
    """Whether the model's ``__call__`` takes ``example_mask`` — models with
    cross-example coupling (MoE capacity routing) need the batch mask inside
    the forward pass; per-token models are exact from loss masking alone."""
    try:
        return "example_mask" in inspect.signature(
            type(model).__call__
        ).parameters
    except (TypeError, ValueError):  # exotic callables
        return False


def _on_mesh_of(model, step):
    """``step``, traced with the model's mesh in the fused loss's sight
    (engine/losses.step_mesh): its criterion and metrics see the global
    batch and reckon their slices in rows on one device."""
    mesh = getattr(model, "mesh", None)

    @functools.wraps(step)
    def told(*args, **kwargs):
        with step_mesh(mesh):
            return step(*args, **kwargs)

    return told


COUNTER_PREFIX = "counter/"


def _step_counters(mutated, names, weight):
    """What the model's layers sowed under ``counters`` this forward
    (models/moe.ExpertLayer), summed by name over the layers, as entries
    of the step's metrics: ``counter/<name>_sum`` times the valid count,
    so that the metrics' divide-by-count reads a step's mean. The names
    are the model's ``step_counters``; one no layer sowed reads 0."""
    sums = dict.fromkeys(names, jnp.zeros((), jnp.float32))
    flat, _ = jax.tree_util.tree_flatten_with_path(
        mutated.get("counters", {}))
    for path, value in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        if name in sums:
            sums[name] = sums[name] + jnp.sum(value)
    return {f"{COUNTER_PREFIX}{name}_sum": v * weight
            for name, v in sums.items()}


BIAS_LEAF = "selection_bias"


def _bias_loads(params, sown=None):
    """``{path: load}`` for every ``selection_bias`` leaf of ``params``:
    what the layers sowed under ``router_load`` this forward (the tokens
    each published expert got, models/moe.ExpertLayer), zeros without."""
    sown = flatten_dict(sown or {})
    return {path: sown.get(path, jnp.zeros_like(p))
            for path, p in flatten_dict(params).items()
            if path[-1] == BIAS_LEAF}


def selection_bias_step(params, loads, rate: float):
    """The rule a router's selection bias trains by (no gradient reaches
    it): after a step each expert's bias goes up by ``rate`` if the
    expert got fewer tokens than the experts' mean over the step's whole
    batch, down by ``rate`` if more (auxiliary-loss-free balancing, Wang
    et al. arXiv:2408.15664, as DeepSeek-V3 and Megatron-LM's
    ``moe_router_enable_expert_bias`` apply it; their rate is 1e-3)."""
    flat = flatten_dict(params)
    for path, load in loads.items():
        flat[path] = flat[path] + rate * jnp.sign(
            jnp.mean(load) - load).astype(flat[path].dtype)
    return unflatten_dict(flat)


def _accumulator_dtype(dtype):
    return jnp.promote_types(dtype, jnp.float32)


def _held_through_backward(state, model, grad_accum_steps: int) -> int:
    """Bytes on one device that the step holds from its first backward to
    its last, for the blocks' checkpoint policy (models/remat_policy.py):
    the state as it is traced (parameters, whatever the optimizer keeps,
    shadow weights where ``ema_decay`` set them up, statistics) and, with
    accumulation, the running sum of the micro-batches' gradients and one
    micro-batch's own gradient, which is added to it only when its backward
    is over. Under the sharding the trainer gives the state
    (engine/state.py)."""
    mesh = getattr(model, "mesh", None)
    rules = getattr(model, "partition_rules", lambda: [])()
    held = per_device_bytes(state, mesh, rules)
    if grad_accum_steps > 1:
        held += per_device_bytes(state.params, mesh, rules)
        held += per_device_bytes(jax.tree.map(
            lambda p: jax.ShapeDtypeStruct(p.shape,
                                           _accumulator_dtype(p.dtype)),
            state.params), mesh, rules)
    return held


def _state_names(opt_state) -> str:
    """An optimizer by what it keeps: the names of its state's records
    (optax's named tuples, ``State`` and the empty ones dropped), outermost
    first."""
    names = []

    def walk(node):
        if hasattr(node, "_fields"):
            name = type(node).__name__.removesuffix("State")
            if name != "Empty" and name not in names:
                names.append(name)
        if isinstance(node, (tuple, list)):
            for child in node:
                walk(child)
        elif isinstance(node, dict):
            for child in node.values():
                walk(child)

    walk(opt_state)
    return "/".join(names) or "stateless"


def _say_pass(state, grads, skip_nonfinite, have_norm, clip):
    """The optimizer pass, once a process and distinct choice: a log line
    and a zero-length span, as ``remat/policy`` and ``head_loss/slice``
    have. ``ok`` says where the skip rule's verdict comes from: the
    gradients' ``norm``, a ``scan`` of every leaf where no norm exists, or
    ``none`` without the rule. The bytes a parameter are what the pass has
    to move if each leaf's state crosses memory once: the gradient read,
    the parameter and the optimizer's state read and written."""
    n = param_count(state.params)
    moved = (per_device_bytes(grads, None)
             + 2 * per_device_bytes(state.params, None)
             + 2 * per_device_bytes(state.opt_state, None))
    record = dict(
        leaves=len(jax.tree.leaves(grads)), parameters=n,
        optimizer=_state_names(state.opt_state),
        ok=("none" if not skip_nonfinite
            else "norm" if have_norm else "scan"),
        scalar="clip/count" if clip else "1/count",
        bytes_per_parameter=round(moved / max(n, 1), 2))
    say_once(
        logger, "optimizer/pass", record,
        "optimizer/pass: %d leaves, %d parameters, %s; ok from %s; each "
        "gradient leaf is read once and multiplied by %s; %.2f bytes a "
        "parameter if each leaf's state crosses memory once",
        record["leaves"], n, record["optimizer"], record["ok"],
        record["scalar"], record["bytes_per_parameter"])


def make_train_step(model, tx, criterion: Callable,
                    metric_fns: Sequence[Callable] = (),
                    input_key: str = "image", target_key: str = "label",
                    grad_clip_norm: float = 0.0,
                    grad_accum_steps: int = 1,
                    ema_decay: float = 0.0,
                    skip_nonfinite: bool = False,
                    augment=None,
                    mixup_alpha: float = 0.0,
                    log_grad_norm: bool = False,
                    trainable_patterns=None,
                    health: bool = False,
                    inject_nan_grad_step=None):
    """Build ``train_step(state, batch) -> (state, metrics)``.

    ``metrics`` holds scalar sums + count; callers divide after accumulating
    across batches (exact masked averages).

    ``grad_accum_steps > 1`` splits the batch into that many microbatches and
    runs them through a ``lax.scan`` (one compiled body, k iterations),
    summing *unnormalized* (masked-sum) gradients and dividing once by the
    global valid count — the same mean-gradient math as the unaccumulated
    step on the full batch (equal up to float reassociation; dropout draws
    per-microbatch keys and BatchNorm normalizes per microbatch, so those
    layers see genuinely different — not wrong — randomness/statistics), at
    1/k the activation memory. The reference has no accumulation (SURVEY.md
    §2.4); this is the TPU-idiomatic way to trade HBM for FLOPs alongside
    remat.

    ``ema_decay > 0`` maintains ``state.ema_params`` (shadow weights) with
    ``ema = d*ema + (1-d)*params`` after each update.

    The optimizer pass reads each gradient leaf ONCE, as the backward (or
    the accumulation, or the all-reduce) left it. The division by the
    global valid count and the clip ride one float32 scalar,
    ``min(1, clip / (gnorm + 1e-6)) / count``, multiplied into the leaf
    (in float32, handed on at the leaf's own dtype) in the map that feeds
    ``tx.update``; ``gnorm`` is the summed gradients' norm over the count
    (the norm is homogeneous). Nothing else lies between the summed
    gradients and the new state but scalars, so the compiler makes one
    fusion a leaf and the leaf's state crosses memory once
    (``tests/test_chip_compile_dense.py`` holds the v5e compile to it; the
    ``optimizer/pass`` line says what was built). The division sits on
    the scalar: exact where the count is a power of two, else a leaf
    differs from ``g / count`` in the last place.

    ``skip_nonfinite`` guards the update in-graph: when the loss or any
    gradient leaf is non-finite the whole update is suppressed via
    ``jnp.where`` — params/opt_state/EMA keep their old values and
    ``skipped_sum`` counts the event — instead of poisoning the weights.
    Where the step has the gradients' norm (a clip, ``log_grad_norm`` or
    ``health``), the verdict is ``isfinite(loss) & isfinite(gnorm)``: a
    non-finite element makes the norm non-finite, so no leaf is read a
    second time to learn it. A tree of finite gradients whose squares
    overflow float32 (an element above 1.8e19) has an infinite norm and
    is skipped too; a per-leaf scan would let it through to a clip scale
    of 0 and an update of decay alone. Without a norm every leaf is
    scanned.
    A branchless select keeps the step a single static XLA program (no
    host round-trip, unlike torch-style ``if not torch.isfinite(loss)``
    Python checks). The step counter still advances so dropout keys and
    schedules stay aligned with wall progress.

    ``augment`` (ops/augment.build_augment) is applied to the input batch
    in-graph before the forward pass, keyed per step — train-time only.

    ``health`` adds the numerics-forensics summary
    (observability/health) as ONE packed f32 vector under
    ``metrics["health"]``: per-example loss, global grad/update norms,
    and non-finite element counts for the post-update params and the
    summed gradients per top-level param group (field order:
    ``health_layout(params)``; the count of a float32 leaf is taken of
    its bfloat16 rounding, so that the compiler hands the counting branch
    the backward's own buffer). A handful of scalar reductions and a
    single tiny output, so the summary rides the dispatch pipeline
    instead of stalling it. Appended AFTER the ``skip_nonfinite``
    zeroing so a suppressed step still reports the non-finite counts
    that got it suppressed (that report is the whole point). Callers
    strip the ``health`` key out of the epoch accumulator.

    ``inject_nan_grad_step`` (resilience/faults ``nan_grad@step:N``):
    when set, every gradient leaf is NaN-poisoned at exactly that
    global step via a branchless in-graph select on ``state.step`` —
    the deterministic trigger for the numerics-forensics /
    ``skip_nonfinite`` recovery paths. Injected BEFORE normalization,
    clipping, and the health capture, so the poisoned step looks
    exactly like a real gradient blow-up to every detector downstream.

    ``mixup_alpha > 0`` enables mixup (Zhang et al. 2018) in-graph: one
    Beta(alpha, alpha) draw per step mixes the batch with a random
    permutation of itself, and the loss becomes the matching convex
    combination ``lam * L(out, y) + (1-lam) * L(out, y_perm)``. Metrics
    are still computed against the original labels. Composes with
    ``augment`` (mixup runs after) and grad accumulation (the mixed
    targets ride the batch pytree through the microbatch split).
    """
    pass_example_mask = _accepts_example_mask(model)
    counter_names = tuple(getattr(model, "step_counters", ()))
    bias_rate = float(getattr(model, "selection_bias_rate", 0.0))
    # a criterion may offer ``summed(output, target, weights) -> (sum_b
    # weights[b] * per_example[b], per_example)``
    # (engine/losses.fused_lm_cross_entropy)
    summed = getattr(criterion, "summed", None)

    def sumloss_and_output(params, batch_stats, batch, dropout_rng):
        """Masked SUM of per-example losses (normalized by the caller after
        accumulation, so microbatched grads sum exactly).

        The ``losses`` collection collects auxiliary objectives modules sow
        (e.g. the MoE load-balancing loss, models/moe.py); they are scalars
        scaled by the microbatch's valid count so the final
        divide-by-global-count yields their count-weighted mean.
        """
        variables = {"params": params}
        mutable = ["losses"]
        if batch_stats:
            variables["batch_stats"] = batch_stats
            mutable = ["batch_stats", "losses"]
        if counter_names:
            mutable.append("counters")
        if bias_rate:
            mutable.append("router_load")
        extra = (
            {"example_mask": batch["mask"]} if pass_example_mask else {}
        )
        output, mutated = model.apply(
            variables, batch[input_key], train=True,
            mutable=mutable, rngs={"dropout": dropout_rng}, **extra,
        )
        new_stats = mutated.get("batch_stats", batch_stats)
        if summed is not None and mixup_alpha <= 0:
            # the criterion sums for itself, told the weights: it then
            # knows every example's cotangent before its own forward
            mask = batch["mask"].astype(jnp.float32)
            loss_sum, _ = summed(output, batch[target_key], mask)
        else:
            per_ex = criterion(output, batch[target_key])
            if mixup_alpha > 0:
                lam = batch["_mix_lam"].astype(per_ex.dtype)
                per_ex = (
                    lam * per_ex
                    + (1.0 - lam) * criterion(output, batch["_mix_target"])
                )
            mask = batch["mask"].astype(per_ex.dtype)
            loss_sum = _masked_sum(per_ex, mask)
        aux = jax.tree.leaves(mutated.get("losses", {}))
        if aux:
            loss_sum = loss_sum + sum(jnp.sum(a) for a in aux) * mask.sum()
        counters = _step_counters(mutated, counter_names, mask.sum())
        if bias_rate:
            counters["router_load"] = _bias_loads(
                params, mutated.get("router_load"))
        return loss_sum, (output, new_stats, mask, counters)

    grad_fn = jax.value_and_grad(sumloss_and_output, has_aux=True)

    def micro_metrics(output, target, mask):
        out = {}
        for fn in metric_fns:
            out[f"{fn.__name__}_sum"] = _masked_sum(fn(output, target), mask)
        return out

    def train_step(state, batch):
        dropout_rng = jax.random.fold_in(state.rng, state.step)
        if augment is not None:
            # 7919/7920 are outside the 0..k-1 microbatch fold-in range
            batch = dict(batch)
            batch[input_key] = augment(
                jax.random.fold_in(dropout_rng, 7919), batch[input_key]
            )
        if mixup_alpha > 0:
            mk = jax.random.fold_in(dropout_rng, 7920)
            lam = jax.random.beta(mk, mixup_alpha, mixup_alpha)
            x = batch[input_key]
            # partner = batch rolled by a random shift: pairs examples
            # uniformly across steps like a permutation, but on a
            # data-sharded batch it compiles to a cheap cyclic shard
            # exchange instead of the full cross-device gather a random
            # x[perm] would cost every step
            shift = jax.random.randint(
                jax.random.fold_in(mk, 1), (), 1, x.shape[0]
            )
            batch = dict(batch)
            batch["_mix_target"] = jnp.roll(  # before x overwrite
                batch[target_key], shift, axis=0
            )
            batch[input_key] = (
                lam.astype(x.dtype) * x
                + (1.0 - lam).astype(x.dtype) * jnp.roll(x, shift, axis=0)
            )
            # broadcast to [B] so the grad-accum microbatch split applies
            batch["_mix_lam"] = jnp.full((x.shape[0],), lam, jnp.float32)
        k = grad_accum_steps
        holds = step_holds(_held_through_backward(state, model, k))

        if k <= 1:
            with holds:
                (loss_sum, (output, new_stats, mask, counters)), grads = \
                    grad_fn(state.params, state.batch_stats, batch,
                            dropout_rng)
            count = mask.sum()
            metrics = {"loss_sum": loss_sum, "count": count, **counters}
            with jax.named_scope("metrics"):
                metrics.update(
                    micro_metrics(output, batch[target_key], mask))
        else:
            # [B, ...] -> [k, B/k, ...]; B is static so this is shape-checked
            # at trace time.
            def split(x):
                b = x.shape[0]
                if b % k != 0:
                    raise ValueError(
                        f"batch size {b} not divisible by "
                        f"grad_accum_steps {k}"
                    )
                return x.reshape((k, b // k) + x.shape[1:])

            micro = jax.tree.map(split, batch)

            @jax.named_scope("grad_accum")
            def body(carry, mb):
                stats, gsum, msum = carry
                rng = jax.random.fold_in(dropout_rng, mb["_idx"])
                mb = {kk: v for kk, v in mb.items() if kk != "_idx"}
                (loss_sum, (output, new_stats, mask, counters)), grads = \
                    grad_fn(state.params, stats, mb, rng)
                m = {"loss_sum": loss_sum, "count": mask.sum(), **counters}
                with jax.named_scope("metrics"):
                    m.update(micro_metrics(output, mb[target_key], mask))
                gsum = jax.tree.map(jnp.add, gsum, grads)
                msum = jax.tree.map(jnp.add, msum, m)
                return (new_stats, gsum, msum), None

            micro["_idx"] = jnp.arange(k)
            zeros_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, _accumulator_dtype(p.dtype)),
                state.params,
            )
            zeros_m = {"loss_sum": jnp.zeros((), jnp.float32),
                       "count": jnp.zeros((), jnp.float32)}
            for fn in metric_fns:
                zeros_m[f"{fn.__name__}_sum"] = jnp.zeros((), jnp.float32)
            for name in counter_names:
                zeros_m[f"{COUNTER_PREFIX}{name}_sum"] = jnp.zeros(
                    (), jnp.float32)
            if bias_rate:
                zeros_m["router_load"] = _bias_loads(state.params)
            with holds:
                (new_stats, grads, metrics), _ = jax.lax.scan(
                    body, (state.batch_stats, zeros_g, zeros_m), micro
                )
            loss_sum, count = metrics["loss_sum"], metrics["count"]
        # the whole batch's loads, the micro-batches' summed; not a metric
        loads = metrics.pop("router_load", None)

        if inject_nan_grad_step is not None:
            poison = jnp.where(
                state.step == jnp.int32(inject_nan_grad_step),
                jnp.float32(jnp.nan), jnp.float32(0.0),
            )
            grads = jax.tree.map(
                lambda g: g + poison.astype(g.dtype), grads
            )

        # everything between the summed gradients and the new state, under
        # one name in the compiled step's op_name metadata (the device
        # trace's optimizer share reads it); names only, same program
        with jax.named_scope("optimizer"):
            # the global valid count: the summed gradients over it are the
            # gradient of the mean on the full batch. Nothing divides a
            # leaf by it; it rides the one scalar below
            denom = jnp.maximum(count.astype(jnp.float32), 1.0)

            if trainable_patterns:
                # Mirror the optimizer's ``trainable`` freeze (optim.py
                # _trainable_only) on the gradients themselves: frozen leaves
                # still produce real grads (only LoRADense's base kernels are
                # stop_gradient-pruned in-graph — embeddings, norms, biases
                # are not), and counting those soon-to-be-discarded grads in
                # the global norm below would over-clip the surviving updates
                # and misreport grad_norm. The mask is static (Python bools at
                # trace time), so the zeroed branches fold away.
                import re as _re

                from ..parallel.sharding import path_str

                pats = [_re.compile(p) for p in trainable_patterns]

                def _freeze(path, g):
                    if any(p.search(path_str(path)) for p in pats):
                        return g
                    return jnp.zeros_like(g)

                grads = jax.tree_util.tree_map_with_path(_freeze, grads)

            # the health summary counts the SUMMED gradients, pre-clip and
            # post-freeze: clipping can smear one NaN over every group (NaN
            # global norm -> NaN scale), destroying the per-module
            # attribution the dump exists for. A positive finite scalar
            # changes no element's finiteness, so the counts are those of
            # the mean gradient, and the tree is the very one gnorm below
            # is computed on — the lax.cond fast path in
            # pack_health_summary is only sound when they match (a NaN in a
            # frozen — training-inert — leaf is deliberately out of scope
            # for both).
            # Its counting branch runs on a bad step alone, but a
            # conditional's operands are buffers: handed a float32 leaf
            # that the backward made as a bfloat16 sum and widened, the
            # compiler writes the widened copy out every step for it (2.8
            # GB a Mistral step). Narrowed, the widening folds away and the
            # branch is handed the backward's own buffer. bfloat16 has
            # float32's exponent, so finite stays finite and the counts
            # stand, up to an element within 0.4% of float32's largest,
            # which rounds to inf (its square overflowed long before).
            health_grads = jax.tree.map(
                lambda g: g.astype(jnp.bfloat16)
                if g.dtype == jnp.float32 else g, grads) if health else None

            have_norm = log_grad_norm or grad_clip_norm > 0 or health
            if have_norm:
                # pre-clip global norm of the mean gradient (the norm is
                # homogeneous: the count divides the scalar)
                gnorm = optax.global_norm(grads) / denom
            if log_grad_norm:
                # count-weighted so finalize_metrics' divide-by-count yields
                # the epoch's mean per-step grad norm
                metrics["grad_norm_sum"] = gnorm * jnp.maximum(count, 1.0)
            # the ONE scalar between the summed gradients and the update:
            # the clip's scale, where there is a clip, over the count
            scale = 1.0
            if grad_clip_norm > 0:
                scale = jnp.minimum(1.0, grad_clip_norm / (gnorm + 1e-6))
            factor = scale / denom

            ok = jnp.array(True)
            if skip_nonfinite:
                ok = jnp.isfinite(loss_sum)
                if have_norm:
                    # a non-finite element makes the norm non-finite (NaN
                    # propagates through the squared sum; inf squares to
                    # inf): no leaf is read a second time to learn it
                    ok = ok & jnp.isfinite(gnorm)
                else:
                    for g in jax.tree.leaves(grads):
                        ok = ok & jnp.all(jnp.isfinite(g))
            _say_pass(state, grads, skip_nonfinite, have_norm,
                      grad_clip_norm > 0)

            def _scaled(g):
                # each leaf is read once, as the backward (or the
                # accumulation, or the all-reduce) left it; the product is
                # made in float32 and handed on at the leaf's own dtype (a
                # no-op for float32 leaves; a bfloat16 parameter's moments
                # stay bfloat16, as optax set them up); zero on a bad step,
                # so that the (discarded) optimizer update below is
                # NaN-free even under jax_debug_nans
                g = (g.astype(_accumulator_dtype(g.dtype))
                     * factor).astype(g.dtype)
                if skip_nonfinite:
                    g = jnp.where(ok, g, jnp.zeros_like(g))
                return g

            grads = jax.tree.map(_scaled, grads)

            updates, new_opt_state = tx.update(
                grads, state.opt_state, state.params)
            if state.lr_scale is not None:
                # host-driven LR multiplier (ReduceLROnPlateau): every
                # registered optimizer ends in scale_by_learning_rate, so
                # scaling the final update equals scaling the learning rate
                s = state.lr_scale.astype(jnp.float32)
                updates = jax.tree.map(
                    lambda u: (u * s).astype(u.dtype), updates)
            if health:
                # post-LR-scale update magnitude: an optimizer blow-up shows
                # here even when the gradients themselves were finite
                health_update_norm = optax.global_norm(updates)
            new_params = optax.apply_updates(state.params, updates)
            if bias_rate:
                new_params = selection_bias_step(new_params, loads, bias_rate)
            if skip_nonfinite:
                # branchless select: a suppressed step leaves params/opt_state/
                # batch_stats bit-identical (no host round-trip, stays one XLA
                # program), and its contaminated sufficient statistics are
                # zeroed so epoch aggregates exclude the bad batch entirely
                sel = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
                new_params = jax.tree.map(sel, new_params, state.params)
                new_opt_state = jax.tree.map(
                    sel, new_opt_state, state.opt_state)
                new_stats = jax.tree.map(sel, new_stats, state.batch_stats)
                with jax.named_scope("metrics"):
                    metrics = {
                        kk: jnp.where(ok, v, jnp.zeros_like(v))
                        for kk, v in metrics.items()
                    }
                    metrics["skipped_sum"] = (
                        (1.0 - ok.astype(jnp.float32))
                        * jnp.maximum(count, 1.0)
                    )
            new_ema = state.ema_params
            if ema_decay > 0 and new_ema is not None:
                d = jnp.float32(ema_decay)
                new_ema = jax.tree.map(
                    lambda e, p: (e * d + p.astype(e.dtype) * (1 - d)),
                    new_ema, new_params,
                )
                if skip_nonfinite:
                    new_ema = jax.tree.map(
                        lambda n, o: jnp.where(ok, n, o),
                        new_ema, state.ema_params,
                    )
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
            ema_params=new_ema,
        )
        if health:
            # nonfinite_params counts the post-select weights: what the
            # next step will actually train from (0 when the guard
            # suppressed the poisoned update, as designed). Packed as
            # ONE f32 vector, merged after the metrics zeroing above —
            # a suppressed step's health fields must survive to reach
            # the detector
            from ..observability.health import pack_health_summary

            with jax.named_scope("health_summary"):
                summary = pack_health_summary(
                    loss=loss_sum.astype(jnp.float32) / denom,
                    grad_norm=gnorm,
                    update_norm=health_update_norm,
                    grads=health_grads,
                    new_params=new_params,
                )
            metrics = {**metrics, "health": summary}
        return new_state, metrics

    return _on_mesh_of(model, train_step)


def make_eval_step(model, criterion: Callable,
                   metric_fns: Sequence[Callable] = (),
                   input_key: str = "image", target_key: str = "label",
                   use_ema: bool = False, eval_rng: bool = False):
    """Build ``eval_step(state, batch) -> metrics`` (sufficient statistics).

    Equivalent to the reference's no-grad validation forward
    (trainer/trainer.py:94-113) + the rank-0 global metric computation
    (trainer/trainer.py:75-88), but reduced in-graph: no pickle gathers, no
    full prediction set on one host. ``use_ema`` evaluates the shadow EMA
    weights instead of the live params.

    ``eval_rng=True`` changes the signature to ``eval_step(state, batch,
    rng)`` and exposes the key as the ``"eval"`` rng stream — the
    ``test.py --seed`` path; models that consume eval-time randomness
    (BertMLM's seeded eval mask) pick it up via ``self.has_rng("eval")``
    and everything else ignores it.
    """

    pass_example_mask = _accepts_example_mask(model)

    def eval_step(state, batch, rng=None):
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
            extra["rngs"] = {"eval": rng}
        output = model.apply(variables, batch[input_key], train=False,
                             **extra)
        per_ex = criterion(output, batch[target_key])
        mask = batch["mask"].astype(per_ex.dtype)
        metrics = {
            "loss_sum": _masked_sum(per_ex, mask),
            "count": mask.sum(),
        }
        for fn in metric_fns:
            metrics[f"{fn.__name__}_sum"] = _masked_sum(
                fn(output, batch[target_key]), mask
            )
        return metrics

    return _on_mesh_of(model, eval_step)


def finalize_metrics(sums: Dict[str, float]) -> Dict[str, float]:
    """Convert accumulated sufficient statistics to averages.

    ``count == 0`` (every batch skipped by the non-finite guard) yields
    NaN averages, not 0.0 — a 0.0 loss would be recorded as an unbeatable
    false best by a ``min``-mode monitor. ``skipped_sum`` is a raw example
    count, not an average (its examples are excluded from ``count``).
    """
    raw_count = float(sums.get("count", 1.0))
    count = raw_count or 1.0
    out = {}
    for k, v in sums.items():
        if k == "count":
            continue
        if k == "skipped_sum":
            out["skipped"] = float(v)
        elif k.endswith("_sum"):
            out[k[: -len("_sum")]] = (
                float(v) / count if raw_count > 0 else float("nan")
            )
        else:
            out[k] = float(v)
    return out


def instrument_step(jitted_fn, name: str, warmup=None):
    """Wrap a jitted step callable in telemetry spans that split the
    one-time compile from steady-state dispatch.

    The first invocation of a jitted function traces + XLA-compiles
    before executing — on big models that is minutes, and on the host
    timeline it is indistinguishable from a hang unless labeled. The
    wrapper records the first call as ``<name>/compile+execute`` and
    every later one as ``<name>/dispatch`` (dispatch spans measure jit
    dispatch + donation backpressure, not device runtime — device time
    belongs to ``jax.profiler``). With a ``warmup``, the first call's
    wait for the warmed executable is ``<name>/await_warmup``: the part
    of ``warmup/<name>/trace|lower|compile`` (engine/warmup.py, on the
    warm-up's thread) that the calling thread did not get to hide. The
    trainer's loop opens no span of its own round the call and times
    it, wait and all, into the flight record's ``dispatch_ms``. Like
    every ``span()`` they are ``TraceAnnotation``s too, so a profiler
    capture shows them on the dispatching thread's line beside the
    device's. A shape change
    mid-run recompiles inside a ``dispatch`` span; the recompilation
    still surfaces, as a ``compile_events`` entry on the next
    flight-recorder record (observability/telemetry).

    ``warmup``: an optional ``engine.warmup.StepWarmup``. At the first
    call the wrapper collects the background-compiled executable for
    ``name`` and dispatches THROUGH it from then on — so a warmed
    step's first invocation records ``<name>/dispatch`` (with
    ``warm=True``), never ``<name>/compile+execute``. A warmup that
    failed (or was never registered under ``name``) yields None and
    the wrapper falls back to the lazy jit path unchanged.

    AOT attributes (``lower``/``eval_shape``) pass through so cost
    analysis (``profiler.compiled_flops``) keeps working on the wrapped
    callable.
    """
    from ..observability.trace import span

    state = {"first": True, "fn": jitted_fn}

    @functools.wraps(jitted_fn)
    def wrapped(*args, **kwargs):
        if state["first"]:
            state["first"] = False
            compiled = None
            if warmup is not None:
                with span(f"{name}/await_warmup"):
                    compiled = warmup.result(name)
            if compiled is not None:
                try:
                    with span(f"{name}/dispatch", warm=True):
                        out = compiled(*args, **kwargs)
                    state["fn"] = compiled
                    return out
                except TypeError:
                    # aval/sharding mismatch between the warmup's
                    # abstract spec and the real inputs (raised BEFORE
                    # execution, so nothing was donated): the degrade-
                    # to-lazy contract must hold here too, not only for
                    # compile-time failures
                    import logging

                    logging.getLogger(__name__).warning(
                        "AOT-warmed %s rejected the real inputs; "
                        "falling back to lazy compile", name,
                        exc_info=True,
                    )
            with span(f"{name}/compile+execute"):
                return state["fn"](*args, **kwargs)
        with span(f"{name}/dispatch"):
            return state["fn"](*args, **kwargs)

    for attr in ("lower", "eval_shape", "trace"):
        if hasattr(jitted_fn, attr):
            setattr(wrapped, attr, getattr(jitted_fn, attr))
    return wrapped
