"""What the five files of v5e compiles share: the described topology, a
whole training step compiled for it, and readers of the compiled text.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached — so what it refuses (block shapes off the
tiling, too much fast memory, a kernel it cannot partition) fails a test
instead of a chip run. Nothing executes: a compile that passes is not a
chip run and says nothing about results or times.

The topology is described inside a module-scoped fixture, never at
import: under xdist every worker imports every test file. The files
(`test_chip_compile.py` and `test_chip_compile_delta_rule.py`: the hybrid
cells' steps; `test_chip_compile_dense.py`: the Mistral step on one and
four chips; `test_chip_compile_head_loss.py`: the head and the loss;
`test_chip_compile_kernels.py`: kernels, convolutions and the checkpoint
policy) go to several workers, and only one process at a time may load the
TPU library unless `ALLOW_MULTIPLE_LIBTPU_LOAD=1` is in the environment:
the tier-1 command sets it (`commands` of `/root/TESTS_LAST_RUN.json`,
`scripts/run_tier1.sh`), and nothing here does. Without it, under xdist,
the files that come second are skipped, and the skip says so; one process
(`-p no:xdist`) runs them all. No process here takes a chip. Tests that
read the same compiled text (`_TEXTS`) live in the same file, so that no
program is compiled twice. xdist starts the files with the fewest tests
last, so a file of few long compiles is the run's tail: keep such a file
short.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # such a compile is written to the persistent cache but cannot be
    # read back without a chip: keep the cache off around these tests
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(
                f"no v5e:2x2 topology can be described here: {e} (if another "
                "worker holds the TPU library: the tier-1 command sets "
                "ALLOW_MULTIPLE_LIBTPU_LOAD=1 for its CPU-only test run)")
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """Plain data parallel over the four chips of the described host."""
    from pytorch_distributed_template_tpu.parallel import build_mesh

    return build_mesh({"data": 4}, devices=topo.devices[:4])


def _abstract_step_inputs(model, tx, batch, seq, state_sharding, batch_sharding):
    """(state, feed) of a language-model training step as shapes alone:
    `state_sharding` is one sharding for every leaf or a function from the
    abstract state to a tree of them."""
    import numpy as np

    from pytorch_distributed_template_tpu.engine.state import (
        create_train_state,
    )

    abstract = jax.eval_shape(lambda: create_train_state(
        model, tx, np.zeros((1, seq), np.int32), seed=0))
    shardings = (state_sharding(abstract) if callable(state_sharding)
                 else jax.tree.map(lambda _: state_sharding, abstract))
    state = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        abstract, shardings)
    feed = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                           sharding=batch_sharding),
            "mask": jax.ShapeDtypeStruct((batch,), jnp.bool_,
                                         sharding=batch_sharding)}
    return state, shardings, feed


_TEXTS = {}


def _compile_train_step(model, mesh, batch, seq, monkeypatch, without=None):
    """The scheduled text of `_compiled_train_step`'s program, compiled
    once a module for the tests that read the same step."""
    key = (repr(model), mesh.devices.size, batch, seq, without)
    if key not in _TEXTS:
        options, compiled = _compiled_train_step(model, mesh, batch, seq,
                                                 monkeypatch, without)
        _TEXTS[key] = options, compiled.as_text()
    return _TEXTS[key]


def _compiled_train_step(model, mesh, batch, seq, monkeypatch, without=None):
    """A whole training step on `mesh`, compiled the way engine/trainer.py
    jits it: the state under the model's partition rules, the batch over
    the batch axes, and the compile options that
    `train_step_compile_options` gives for the mesh on the function
    (less the one named `without`)."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_distributed_template_tpu.engine.losses import resolve_loss
    from pytorch_distributed_template_tpu.engine.steps import make_train_step
    from pytorch_distributed_template_tpu.models.base import inject_mesh
    from pytorch_distributed_template_tpu.ops import flash
    from pytorch_distributed_template_tpu.parallel import (
        apply_rules, batch_sharding, train_step_compile_options,
    )

    monkeypatch.setattr(flash, "_on_tpu", lambda: True)
    model = inject_mesh(model, mesh)
    tx = optax.adamw(1e-4)
    state, shardings, feed = _abstract_step_inputs(
        model, tx, batch, seq,
        lambda abstract: apply_rules(abstract, mesh, model.partition_rules()),
        batch_sharding(mesh))
    step = make_train_step(
        model, tx, resolve_loss({"type": "fused_lm_cross_entropy",
                                 "args": {"chunk": 256}}), [],
        input_key="tokens", target_key="tokens", grad_clip_norm=1.0,
        skip_nonfinite=True, health=True)
    options = {k: v for k, v in train_step_compile_options(mesh).items()
               if k != without}
    compiled = jax.jit(
        step, donate_argnums=0,
        out_shardings=(shardings, NamedSharding(mesh, P())),
        compiler_options=options or None,
    ).lower(state, feed).compile()
    return options, compiled


def _step_compiled_once(model, topo, batch, seq):
    """What a module-scoped fixture hands the tests that read one cell's
    step on one chip, so that the step is compiled once for all of them:
    the scheduled `text`, the compile's `total_bytes`, and `said`, what
    the program said while the step was traced with the v5e's capacity
    supplied to the checkpoint policy (`_said(name, step.said)`)."""
    from types import SimpleNamespace

    from pytorch_distributed_template_tpu.observability.trace import (
        get_recorder,
    )
    from pytorch_distributed_template_tpu.parallel import build_mesh

    mesh = build_mesh({"data": 1}, devices=topo.devices[:1])
    with pytest.MonkeyPatch.context() as patch:
        _v5e_capacity_and_nothing_said(patch)
        _, compiled = _compiled_train_step(model, mesh, batch, seq, patch)
        return SimpleNamespace(
            text=compiled.as_text(), said=get_recorder().snapshot(),
            total_bytes=_compiled_bytes(compiled))


def _compiled_bytes(compiled):
    """What a compiled program takes of the device: arguments, results
    and temporaries, a donated argument and its result counted once."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _entry_lines(text):
    """The entry computation's instructions, in scheduled order."""
    return re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", text,
                     re.S | re.M).group(1).splitlines()


def _entry_instructions(text):
    """(opcode, result shape, called computation) of the entry
    computation's instructions, in scheduled order."""
    for line in _entry_lines(text):
        m = re.match(r"\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ([\w\-]+)\(", line)
        if m:
            calls = re.search(r"calls=%([\w\.\-]+)", line)
            yield m.group(2), m.group(1), calls.group(1) if calls else ""


def _computation(text, name):
    return re.search(rf"^%{re.escape(name)} \(.*?^\}}", text,
                     re.S | re.M).group(0)


V5E_BYTES_LIMIT = 16_909_336_064    # `bytes_limit` as the chip reports it


def _said(name, events=None):
    """What the program said under `name`: in `events`, or so far."""
    from pytorch_distributed_template_tpu.observability.trace import (
        get_recorder,
    )
    return [e["args"] for e in (get_recorder().snapshot() if events is None
                                else events) if e["name"] == name]


@pytest.fixture
def fresh_records(monkeypatch):
    """The v5e's capacity supplied to the checkpoint policy, and nothing
    said yet by it or by the fused loss."""
    _v5e_capacity_and_nothing_said(monkeypatch)


def _v5e_capacity_and_nothing_said(monkeypatch):
    from pytorch_distributed_template_tpu.models import remat_policy
    from pytorch_distributed_template_tpu.observability import trace
    from pytorch_distributed_template_tpu.observability.trace import (
        get_recorder,
    )

    monkeypatch.setattr(remat_policy, "device_capacity_bytes",
                        lambda mesh=None: V5E_BYTES_LIMIT)
    trace._said.clear()
    get_recorder().clear()


_ITEM_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}


def _arrays(shape):
    """(dtype, dimensions, bytes, in fast memory) of every array of an
    instruction's result shape, a tuple's members each."""
    out = []
    for dtype, dims, layout in re.findall(
            r"(\w+)\[([\d,]*)\](\{[^}]*\})?", shape):
        if dtype in _ITEM_BYTES:
            dims = [int(d) for d in dims.split(",") if d]
            size = _ITEM_BYTES[dtype]
            for d in dims:
                size *= d
            out.append((dtype, dims, size, "S(1)" in layout))
    return out


def _scope_instructions(text, scope):
    """The entry computation's instructions whose `op_name` lies under the
    `jax.named_scope` `scope`, each with what it reads and writes: (name,
    opcode, arrays of the result, [(operand, its opcode, its arrays)], the
    text of the computation a fusion calls)."""
    lines = [re.match(r"\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) ([\w\-]+)"
                      r"\((.*?)\)(?:, |$)", ln) for ln in _entry_lines(text)]
    made = {m.group(1): (m.group(3), _arrays(m.group(2))) for m in lines if m}
    for m in lines:
        if not m or m.group(3) in ("get-tuple-element", "tuple", "bitcast",
                                   "constant", "parameter"):
            continue
        op_name = re.search(r'op_name="([^"]*)"', m.string)
        if not op_name or f"/{scope}/" not in op_name.group(1) + "/":
            continue
        calls = re.search(r"calls=%([\w\.\-]+)", m.string)
        yield (m.group(1), m.group(3), _arrays(m.group(2)),
               [(o, *made.get(o, ("", [])))
                for o in re.findall(r"%([\w\.\-]+)", m.group(4))],
               _computation(text, calls.group(1)) if calls else "")


def _copies_of(text, *dims):
    """The result shapes of the entry computation's `copy` instructions
    that write an array of one of the shapes `dims`, whatever its dtype
    and layout: a leaf, a moment or a result moved from one order to
    another."""
    return [shape for op, shape, _ in _entry_instructions(text)
            if op == "copy" and any(a[1] == list(d) for a in _arrays(shape)
                                    for d in dims)]


_PREFETCH = ("copy-done", "copy-start", "slice-done", "slice-start",
             "ConcatBitcast")


def _grouped_products_under(text, scope):
    """The kernels the compiler made of `jax.lax.ragged_dot` and
    `ragged_dot_general` anywhere in the compiled text under the scope,
    by what each returns."""
    return [m.group(1) for m in re.finditer(
        r'%ragged-dot[-\w.]* = (\w+\[[\d,]*\])[^\n]*'
        r'custom_call_target="tpu_custom_call"[^\n]*'
        rf'op_name="[^"]*/{scope}/', text)]


def _optimizer_reads(text, leaf):
    """How the optimizer's pass reads the leaves whose name `leaf` finds:
    {the jit's parameter: the result shapes of the instruction under the
    scope `optimizer` that takes it as an operand}, itself or the
    compiler's prefetch of it into fast memory (whole, `copy-start` and
    `copy-done`, or in slices joined by a `ConcatBitcast`) in the order it
    is held in, and no `copy` into another order. The state's parameters,
    `mu` and `nu` are the jit's arguments, so a leaf the pass updates
    where it lies has three entries here."""
    made = {}
    for ln in _entry_lines(text):
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\(.*?\)|\S+) "
                     r"([\w\-]+)\(%?([\w\.\-]*)", ln)
        if m:
            joins = 'custom_call_target="ConcatBitcast"' in ln
            made[m.group(1)] = ("ConcatBitcast" if joins else m.group(3),
                                m.group(4),
                                re.findall(r"\{([\d,]*)", m.group(2))[:1])

    def held(name):
        op, source, order = made.get(name, ("", "", None))
        while op in _PREFETCH and made.get(source, ("", "", None))[2] == order:
            name, (op, source, order) = source, made[source]
        return name

    return {source: [a[1] for a in arrays]
            for _, _, arrays, operands, _ in _scope_instructions(
                text, "optimizer")
            for source in (held(o) for o, _, _ in operands)
            if re.search(leaf, source)}


def _crossings(text):
    """How the weight gradients cross the chips in the scheduled step:
    (bare synchronous all-reduces over a bfloat16 matrix, the compute
    fusions that carry one between its start and its done)."""
    bare, carried = [], []
    for op, shape, calls in _entry_instructions(text):
        if op == "all-reduce" and re.search(r"bf16\[\d+,\d+\]", shape):
            bare.append(shape)
        elif op == "fusion" and calls.startswith("async_collective_fusion"):
            carried.append(calls)
    return bare, carried


# two blocks at mistral7b_l2's published widths
MISTRAL = dict(vocab_size=32000, n_layer=2, n_head=32, n_kv_head=8,
               d_model=4096, d_ff=14336, max_len=32768, window=4096,
               rope_base=10000.0, rms_eps=1e-5, bfloat16=True,
               attn_impl="flash", remat=True, fused_head=True)
