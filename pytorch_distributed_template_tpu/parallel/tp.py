"""Tensor-parallel SERVING: one logical model, ``tp`` chips, one SPMD
decode step.

Training already speaks meshes (parallel/mesh.py, parallel/sharding.py)
and the MULTICHIP dryruns prove the DP/TP/SP collective plans compile on
8 devices — but until now ``serve.py`` and both decode engines were
strictly single-chip, so a model bigger than one chip's HBM could not
serve at all. This module is the serving-side counterpart of those two
files: the mesh, the geometry contract, and the sharding placements
that turn the existing prefill/admit/decode/speculative executables
into SPMD programs.

Design (megatron TP, the model's own ``partition_rules()``):

- **weights** shard column/row-parallel over the ``tensor`` axis
  (q/k/v/gate/up columns, o/down rows, vocab-sharded embedding +
  lm_head) — ``shard_serving_params`` applies the rules and commits
  the tree to the serving mesh;
- **KV cache / paged pool leaves** shard on the KV-HEAD axis
  (``[B, T, KVH, D]`` caches and ``[pool_blocks, block_tokens, KVH,
  D]`` pool pages, axis 2): attention is embarrassingly parallel over
  heads, so decode needs NO attention-time collectives — each shard
  reads and appends only its own head slice of the pool;
- **block tables, the radix index, row starts, slot state** stay
  REPLICATED host-side metadata: a page id means the same thing on
  every shard, so the paged admit stays a pointer update (zero copy)
  under TP exactly as at tp=1;
- the per-step collectives are the megatron pair — one all-reduce
  after ``o_proj`` and one after ``down_proj`` per layer, plus one for
  the vocab-sharded embedding lookup — inserted by XLA from the
  sharding annotations alone (the SNIPPETS.md [2]/[3] pjit pattern).

Everything here is geometry + placement; the engines themselves are
unchanged SPMD programs. Develop/test on CPU with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (tests/conftest
already forces it): greedy decode is token-identical at tp=1 vs tp>1
— the collectives change the schedule, not the math.
"""
from __future__ import annotations

import re
from typing import Optional

import numpy as np

#: the serving TP mesh axis — same name the training rules use, so one
#: ``partition_rules()`` set serves both worlds
TP_AXIS = "tensor"

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}


def serving_mesh(tp: int):
    """A ``{"tensor": tp}`` mesh over the first ``tp`` local devices,
    or ``None`` for ``tp <= 1`` (the single-chip path stays exactly as
    it was — no mesh, no constraints, no collectives)."""
    import jax
    from jax.sharding import Mesh

    tp = int(tp)
    if tp <= 1:
        return None
    devices = jax.devices()
    if len(devices) < tp:
        raise ValueError(
            f"serving.tensor_parallel={tp} needs {tp} devices, found "
            f"{len(devices)} (on CPU dev boxes: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={tp})")
    return Mesh(np.asarray(devices[:tp]).reshape(tp), (TP_AXIS,))


def validate_dp_geometry(dp: int, tp: int) -> None:
    """Refuse a DP×TP replica geometry the host cannot place — LOUDLY,
    before any executable builds (the ISSUE 12 follow-on to PR 10's
    ``validate_tp_geometry``): ``dp`` independent tensor groups of
    ``tp`` chips each need ``dp * tp`` local devices."""
    import jax

    dp, tp = int(dp), int(tp)
    if dp < 1 or tp < 1:
        raise ValueError(f"need dp >= 1 and tp >= 1 (got dp={dp}, "
                         f"tp={tp})")
    need = dp * tp
    have = len(jax.devices())
    if have < need:
        raise ValueError(
            f"dp={dp} x tp={tp} needs {need} devices, found {have} "
            "(on CPU dev boxes: XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need})")


def dp_group_devices(group: int, tp: int):
    """The device slice owned by DP group ``group`` (groups tile the
    local device list in order: group g owns ``[g*tp, (g+1)*tp)``)."""
    import jax

    tp = max(int(tp), 1)
    devices = jax.devices()
    lo = int(group) * tp
    if lo + tp > len(devices):
        raise ValueError(
            f"dp group {group} needs devices [{lo}, {lo + tp}) but "
            f"only {len(devices)} exist")
    return devices[lo:lo + tp]


def dp_group_mesh(group: int, tp: int):
    """A group-local ``{"tensor": tp}`` mesh for DP group ``group``
    (DP×TP serving, ISSUE 12: N independent tp groups tiling one host
    mesh — a decode-role replica runs several small groups while a
    prefill-role replica runs one wide one). ``tp <= 1`` returns None
    — the group is a single chip, pinned by committing its params to
    ``dp_group_devices(group, 1)[0]`` (uncommitted engine state
    follows the committed params at first dispatch, then lives on the
    group device as donated jit outputs)."""
    from jax.sharding import Mesh

    tp = int(tp)
    devices = dp_group_devices(group, tp)
    if tp <= 1:
        return None
    return Mesh(np.asarray(devices).reshape(tp), (TP_AXIS,))


def tp_degree(mesh) -> int:
    """Size of the ``tensor`` axis (1 when no mesh / axis absent)."""
    if mesh is None or TP_AXIS not in mesh.axis_names:
        return 1
    return int(mesh.shape[TP_AXIS])


def model_geometry(model) -> dict:
    """The divisibility-relevant shape of a serving model — what a TP
    layout must divide. Also recorded into serving-artifact manifests
    (scripts/make_serving_artifact.py) so a restore can refuse a
    geometry mismatch loudly instead of failing deep inside a jit."""
    n_head = int(getattr(model, "n_head", 0) or 0)
    n_kv = int(getattr(model, "n_kv_head", 0) or 0) or n_head
    d_model = int(getattr(model, "d_model", 0) or 0)
    d_ff = int(getattr(model, "d_ff", 0) or 0)
    if not d_ff and d_model:
        # each family's own d_ff=0 default, mirrored: the Llama family
        # (the one with a GQA n_kv_head field) rounds ~8/3 x d_model up
        # to a 16-multiple (models/llama.LlamaLM); the GPT-2 family
        # uses the classic 4 x d_model (models/transformer.TransformerLM)
        if hasattr(model, "n_kv_head"):
            d_ff = -(-int(d_model * 8 / 3) // 16) * 16
        else:
            d_ff = 4 * d_model
    return {
        "n_head": n_head,
        "n_kv_head": n_kv,
        "d_model": d_model,
        "d_ff": d_ff,
        "vocab_size": int(getattr(model, "vocab_size", 0) or 0),
    }


def validate_tp_geometry(model, tp: int,
                         geometry: Optional[dict] = None) -> None:
    """Refuse a TP degree the model cannot shard — LOUDLY, with every
    violated divisibility in one message, BEFORE any executable builds.
    ``geometry`` overrides the model-derived shape (the artifact-
    manifest validation path passes the recorded one)."""
    tp = int(tp)
    if tp <= 1:
        return
    if not hasattr(model, "partition_rules"):
        raise ValueError(
            f"{type(model).__name__} declares no partition_rules(): "
            "tensor-parallel serving needs the TP sharding contract "
            "(the Llama/GPT-2 families)")
    g = dict(geometry or model_geometry(model))
    bad = []
    for key in ("n_head", "n_kv_head", "d_ff", "vocab_size"):
        val = int(g.get(key, 0) or 0)
        if val and val % tp:
            bad.append(f"{key}={val}")
    if bad:
        raise ValueError(
            f"tensor_parallel={tp} does not divide model geometry: "
            f"{', '.join(bad)} (KV heads shard over the tensor axis; "
            "pick tp dividing every listed dimension)")


def kv_pool_pspec(ndim: int = 4):
    """PartitionSpec for pool pages ``[pool_blocks, block_tokens, KVH,
    D]`` and cache leaves ``[B, T, KVH, D]``: KV heads over ``tensor``,
    everything else replicated. ``ndim=3`` covers the int8-KV pool's
    scale leaves ``[pool_blocks, block_tokens, KVH]`` (ISSUE 15) whose
    head axis is last."""
    from jax.sharding import PartitionSpec as P

    if ndim == 3:
        return P(None, None, TP_AXIS)
    return P(None, None, TP_AXIS, None)


def _is_kv_leaf(path, leaf) -> bool:
    last = path[-1]
    name = str(getattr(last, "key", getattr(last, "name", last)))
    if (getattr(leaf, "ndim", 0) == 3
            and name in ("cached_key_scale", "cached_value_scale")):
        # int8-KV pool scale leaves (ISSUE 15): shard with their pages
        return True
    return (getattr(leaf, "ndim", 0) == 4
            and name in ("cached_key", "cached_value"))


def shard_kv_tree(tree, mesh):
    """Commit a cache/pool pytree to the serving mesh: K/V leaves shard
    on the head axis, everything else (pos_index, int8 scales — which
    never reach TP anyway) replicates. Host-side ``device_put``; no-op
    without a TP mesh. Used at pool construction and cache warmup so
    warmed executable signatures equal the dispatch-path ones (a
    committed/uncommitted mismatch mints fresh XLA compiles mid-traffic
    — the exact stall class engine/continuous's warmup exists to
    kill)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if tp_degree(mesh) <= 1:
        return tree
    rep = NamedSharding(mesh, P())

    def put(path, leaf):
        if _is_kv_leaf(path, leaf):
            return jax.device_put(leaf, NamedSharding(
                mesh, kv_pool_pspec(getattr(leaf, "ndim", 4))))
        return jax.device_put(leaf, rep)

    return jax.tree_util.tree_map_with_path(put, tree)


def constrain_kv_tree(tree, mesh):
    """The in-graph twin of :func:`shard_kv_tree`:
    ``with_sharding_constraint`` on the K/V leaves of a cache built
    INSIDE a jit (the engines build zero caches in-graph — without the
    constraint GSPMD is free to replicate a freshly-zeroed cache and
    pay a per-step head all-gather forever after). No-op without a TP
    mesh, so the single-chip executables are byte-identical to
    before."""
    import jax
    from jax.sharding import NamedSharding

    if tp_degree(mesh) <= 1:
        return tree

    def put(path, leaf):
        if _is_kv_leaf(path, leaf):
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(
                    mesh, kv_pool_pspec(getattr(leaf, "ndim", 4))))
        return leaf

    return jax.tree_util.tree_map_with_path(put, tree)


def shard_serving_params(model, params, mesh):
    """Commit a param tree to the serving mesh per the model's own
    ``partition_rules()`` (megatron column/row TP — the same rules
    training uses). No-op without a TP mesh."""
    import jax

    from .sharding import apply_rules

    if tp_degree(mesh) <= 1:
        return params
    rules = (model.partition_rules()
             if hasattr(model, "partition_rules") else [])
    return jax.device_put(params, apply_rules(params, mesh, rules))


# ---------------------------------------------------------------------------
# collective accounting (the MULTICHIP dryrun technique, serving-side)
# ---------------------------------------------------------------------------


def hlo_collectives(hlo: str):
    """Count collective instructions in compiled HLO text and sum the
    bytes of their result shapes — the same evidence the MULTICHIP
    dryruns use (``ok=true`` alone cannot distinguish a real TP program
    from silent replication). Returns ``(counts, bytes)`` dicts keyed
    by op name. Of a tuple-shaped collective only the first element's
    bytes are counted (a floor, as the callers use it)."""
    # the TPU compiler prints long tuple shapes with ``/*index=5*/``
    # markers, whose ``=`` would end the ``[^=]*?`` scan below
    hlo = re.sub(r"/\*.*?\*/", "", hlo)
    pat = re.compile(
        r"=\s*\(?\s*(\w+)\[([0-9,]*)\][^=]*?\s"
        r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(")
    counts: dict = {}
    nbytes: dict = {}
    for dtype, dims, op in pat.findall(hlo):
        size = _DTYPE_BYTES.get(dtype, 4)
        for d in dims.split(","):
            if d.strip():
                size *= int(d)
        counts[op] = counts.get(op, 0) + 1
        nbytes[op] = nbytes.get(op, 0) + size
    return counts, nbytes


def analytic_decode_floor_bytes(model, batch: int = 1, t: int = 1) -> int:
    """Analytic LOWER bound on per-decode-step all-reduce payload under
    megatron TP: the row-parallel ``o_proj``/``down_proj`` pair moves
    one full ``[B, t, d_model]`` activation per layer each — anything
    less and the program cannot be doing the reduction the algorithm
    requires. The vocab-sharded embedding lookup adds one more in
    practice (counted by the bench, NOT in the floor: XLA may lower the
    gather as an all-gather of the table instead). Matches the
    MULTICHIP phase1 floor construction (__graft_entry__.py)."""
    g = model_geometry(model)
    itemsize = np.dtype(
        getattr(model, "dtype", np.float32)).itemsize
    return int(2 * int(model.n_layer) * batch * t * g["d_model"]
               * itemsize)


def _decode_step_hlo(model, params, batch: int):
    """AOT-compile one 1-token decode step (fully ABSTRACT inputs —
    params keep their real shardings, the cache is an eval_shape tree
    with the head sharding attached; no device allocation happens)
    and return its HLO text."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = getattr(model, "mesh", None)
    total = min(int(model.max_len), 64)

    def step(p, c, tok):
        logits, vs = model.apply(
            {"params": p, "cache": c}, tok,
            train=False, decode=True, mutable=["cache"])
        return logits[:, -1], vs["cache"]

    def shapes_of(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=getattr(x, "sharding", None)), tree)

    cache_shapes = jax.eval_shape(
        lambda p: model.apply(
            {"params": p}, jnp.zeros((batch, total), jnp.int32),
            train=False, decode=True, mutable=["cache"],
        ),
        params,
    )[1]["cache"]
    rep = NamedSharding(mesh, P())

    def abstract(path, s):
        if _is_kv_leaf(path, s):
            return jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=NamedSharding(mesh, kv_pool_pspec(len(s.shape))))
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep)

    cache = jax.tree_util.tree_map_with_path(abstract, cache_shapes)
    lowered = jax.jit(step).lower(
        shapes_of(params), cache,
        jax.ShapeDtypeStruct((batch, 1), jnp.int32))
    return lowered.compile().as_text()


def decode_step_collectives(model, params, batch: int = 1) -> dict:
    """Compile one single-token decode step AOT and account its
    collectives from the compiled HLO (the dryrun technique) — the
    per-step communication a TP serving deployment actually pays,
    exported as telemetry (serve.py /metrics ``tp_*`` gauges) and
    gated by the ``serve_tp`` bench rung against
    :func:`analytic_decode_floor_bytes`. Returns::

        {"tp_degree", "collective_count_per_step",
         "collective_bytes_per_step", "analytic_floor_bytes",
         "counts": {op: n}, "bytes": {op: B}}

    Single-chip models (no mesh / tp=1) short-circuit to zeros — no
    extra compile on the path everyone runs today."""
    mesh = getattr(model, "mesh", None)
    tp = tp_degree(mesh)
    out = {"tp_degree": tp, "collective_count_per_step": 0,
           "collective_bytes_per_step": 0,
           "analytic_floor_bytes": 0, "counts": {}, "bytes": {}}
    if tp <= 1:
        return out
    counts, nbytes = hlo_collectives(
        _decode_step_hlo(model, params, int(batch)))
    out.update(
        collective_count_per_step=int(sum(counts.values())),
        collective_bytes_per_step=int(sum(nbytes.values())),
        analytic_floor_bytes=analytic_decode_floor_bytes(model, batch),
        counts=dict(counts), bytes=dict(nbytes))
    return out
