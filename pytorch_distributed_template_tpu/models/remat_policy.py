"""What a rematerialized block keeps for its backward: as much as fits.

``remat=True`` wraps every block of the GPT-2 and Llama families in
``jax.checkpoint``. With ``nothing_saveable`` the backward runs the whole
block again, the flash forward kernel included. This module chooses, once
per traced training step, a ``save_only_these_names`` policy over a prefix
of one fixed preference order, by arithmetic on shapes, the mesh, the
device's capacity and what the training step says it holds
(``step_holds``, set by ``engine/steps.py`` around its backward: the
state under its optimizer, shadow weights and gradient accumulator,
whatever they are). Nothing here reads what is allocated, compiles a
candidate or runs a trial, so every build of one job on one kind of chip
chooses the same names, and says so in one log line and one
``remat/policy`` span.

The names (``jax.ad_checkpoint.checkpoint_name``), in order of preference,
which is the order of recomputation spared for a byte kept:

1. ``attn_out``, ``attn_lse``: the attention output and, from the flash
   kernel, its log-sum-exp rows. Only the kernel can make them
   (ops/flash.py names them where the custom-vjp forward rules build their
   residuals), so keeping both spares the backward a second forward call:
   7.8 ms for 68 MB in a Mistral-7B layer at 8192 tokens.
2. ``qkv_proj``, ``attn_proj``, ``mlp_gate``, ``mlp_up``: the block's
   matmul outputs. Each contracts over ``d_model``, so each spares the
   same recomputation a byte (1.6 ms for 67 MB there), and the tie is
   broken by what keeping costs the forward: the first three together
   1.1 ms of a forward of 80; ``mlp_up``, which the forward never wrote
   (it fused the activation into that matmul and wrote their product),
   4.7 ms, because the activation moves into the down projection's
   operand fusion (my chip runs, PR 27). The down projection's output has
   no name: it feeds only the residual sum, the backward needs it for
   nothing, and jax drops it from the recomputation whatever the policy.
3. ``attn_qkv``: the flash kernel's operands as it takes them (rotated,
   key and value heads repeated, folded to ``[heads, tokens, head size]``),
   its other three residuals. Kept, the backward no longer rotates,
   repeats and folds again (2.2 ms for 201 MB there) and needs
   ``qkv_proj`` no more, which jax then drops from what is kept.

A stack whose layers are of several kinds (models/hybrid.py: a mixer a
layer, by a pattern) names besides, each where its mixer makes it, its
width stated beside that mixer (models/mixers.py, models/moe.py):

- ``moe_router``: an expert layer's router logits, float32 and as wide as
  the experts published; they come from a float32 product of six passes,
  so a byte of them spares the most, and they go second;
- ``moe_pairs``: where an expert layer's products run over the pairs of
  token and held expert (models/moe.py), the pairs' layout, integers and
  each row's weight, a hundred or two bytes a token: kept, the backward
  does not sort and count again, and they stand with the router;
- ``ssm_in_proj``, ``moe_latent``, ``moe_shared_up``: a state-space
  layer's input projection, an expert layer's latent projection and its
  shared expert's first product. Each contracts over ``d_model`` like
  ``qkv_proj``, and they stand with the projections, the widest last;
- ``moe_experts_out``: the routed experts' sum over the experts held,
  where a projection reads it (an expert layer with a ``latent``, whose
  ``latent_up`` takes it: that matrix's gradient is the product of this
  sum and the cotangent, so without the name the backward runs the
  experts' second product again to have it). As wide as ``moe_latent``,
  and a byte of it spares a product over every held expert's features
  (``held * d_ff``, 21504 where ``d_model`` is 4096: 3.7 ms for 33.5 MB
  a layer at 16384 tokens), so it stands in front of ``moe_latent``. A
  layer without a latent adds the sum to its shared expert's output, no
  gradient reads it, and it makes no such name.

A stack of delta-rule and gated-attention layers, each with gated experts
(models/hybrid.py's ``SOLAR_OPEN2``), names three more, each beside its like:
``attn_gate`` (the output gate's projection, behind ``qkv_proj``),
``kda_in_proj`` (a KDA mixer's three projections in front of their
convolutions) and ``kda_out_proj`` (its ``o_proj``), behind ``attn_proj``;
its shared expert is a ``SwiGLU`` and makes ``mlp_gate`` and ``mlp_up``.
A gated short convolution (models/mixers.ShortConvMixer) names its two
projections ``conv_in_proj`` and ``conv_out_proj``, beside the KDA's.

The scan's output has no name: its backward needs what lies inside it,
so keeping the result would spare next to nothing. So it is with the
routed experts' where nothing reads their sum but a residual sum; where
a projection does, the sum is ``moe_experts_out``, above. What lies
inside the routed experts has two names (models/moe.py):
``moe_experts_gate`` and ``moe_experts_up``, the experts' first products
(one, ``moe_experts_up``, where an expert has two matrices): every held
expert's over every token as the einsums make them, ``held * d_ff``
features a token, where a token has a place for every expert held;
where it has fewer, the grouped products over the room for its pairs,
``places * d_ff`` (``models/moe.expert_block_sizes`` states the one or
the other by the layer's own rule, ``token_places``: 24576 and 6144
features a token at 16 held of 1536, 4 a token, so both names fit
there, 101 MB a layer each, where one of 403 did; 10240 and 1280 at 8
held of 320 experts of 1280, 8 a token, one place). By recomputation
spared for a byte they would stand with ``mlp_gate`` where they contract
over ``d_model`` and at a quarter of that over a 1024-wide latent, but
they are up to ``held`` times as wide as any other name, one order serves
every model and the choice is a prefix, so a name that does not fit drops
all behind it: they go last, each a group of its own, and no model loses
a name to them; a budget with room for one keeps one. Kept or not they
are alive in the block's backward, so they count in its margin
(``budget_bytes``). The bytes are reckoned by kind of block and summed over
the kinds' counts; a name a kind lacks costs it nothing. What a scan makes
inside itself (its float32 decay masks and their product with ``C . B``)
is the block's ``scratch``: kept by no name, alive in that block's
backward, and so part of the room one block's backward is left.

One policy serves every block of a model. An empty prefix is
``nothing_saveable``; so is a device whose capacity is unknown (the CPU),
and a gradient taken outside a step that says what it holds.
"""
from __future__ import annotations

import contextlib
import contextvars
import logging
from typing import Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from ..observability.trace import say_once
from ..ops.flash import named_residual_bytes
from ..parallel.mesh import axis_size
from ..parallel.sharding import DATA_AXES, per_device_bytes

logger = logging.getLogger(__name__)

# groups are kept whole: the attention output without its log-sum-exp
# would still cost the kernel call
PREFERENCE: Tuple[Tuple[str, ...], ...] = (
    ("attn_out", "attn_lse"),
    ("moe_router",),
    ("moe_pairs",),
    ("qkv_proj",),
    ("attn_gate",),
    ("attn_proj",),
    ("kda_in_proj",),
    ("kda_out_proj",),
    ("conv_in_proj",),
    ("conv_out_proj",),
    ("ssm_in_proj",),
    ("moe_experts_out",),
    ("moe_latent",),
    ("mlp_gate",),
    ("mlp_up",),
    ("moe_shared_up",),
    ("attn_qkv",),
    ("moe_experts_gate",),
    ("moe_experts_up",),
)


class BlockKind(NamedTuple):
    """One kind of block of a stack: ``widths`` maps a name to the bytes
    a token of it takes divided by the model's item size (the features of
    a matmul output in the compute type; twice them for float32), and
    ``count`` says how many such blocks there are. ``attn_heads`` > 0 says
    the block calls the model's attention once, with that many query
    heads of ``head_dim``. ``scratch``, in the same unit as a width, is
    what the block's backward makes beside its names and no name can
    keep: a scan's decay masks, 537 MB of float32 a layer at 64 heads and
    a chunk of 256 where the block's names come to 408 MB."""
    widths: Mapping[str, int]
    count: int
    attn_heads: int = 0
    head_dim: int = 0
    scratch: int = 0
# left free under the device's limit: the allocator's fragmentation, the
# compiler's own copies, and room for the step's peak to be read at least
# 1 GiB under ``bytes_limit``
HEADROOM_BYTES = 1 << 30

_held: contextvars.ContextVar = contextvars.ContextVar(
    "remat_policy_step_holds", default=None)


@contextlib.contextmanager
def step_holds(nbytes: int):
    """The training step's word to the policy, around the trace of its
    backward: ``nbytes`` on one device are alive through all of it, whatever
    the blocks keep (``engine/steps.py`` reckons them: the state and, with
    accumulation, the gradients' running sum). A model traced outside it
    keeps nothing."""
    token = _held.set(int(nbytes))
    try:
        yield
    finally:
        _held.reset(token)


def device_capacity_bytes(mesh=None) -> Optional[int]:
    """A device's memory as its runtime limits it: a constant of the chip
    for the life of the process. ``None`` where the backend reports none
    (the CPU) or the device is not this process's to ask. The only read of
    the device in this module; tests patch it."""
    device = (mesh.local_devices if mesh is not None
              else jax.local_devices())[0]
    if device not in jax.local_devices():
        return None     # described, not attached: a compile rehearsal
    stats = device.memory_stats()
    return int(stats["bytes_limit"]) if stats else None


def choose_names(blocks: Sequence[Tuple[Mapping[str, int], int]],
                 budget: int) -> Tuple[str, ...]:
    """The names to keep: the longest prefix of ``PREFERENCE`` whose bytes,
    kept in every block, stay inside ``budget``. ``blocks`` holds, for
    each kind of block, a map from a name to its bytes on one device in
    one such block, and the number of such blocks. A name no kind has (no
    gate in a GELU MLP, no log-sum-exp from the XLA attention) is passed
    over. A prefix, not a knapsack: a smaller budget never keeps what a
    larger one leaves out."""
    kept, total = [], 0
    for group in PREFERENCE:
        names = [n for n in group if any(n in table for table, _ in blocks)]
        total += sum(count * table.get(n, 0)
                     for table, count in blocks for n in names)
        if total > budget:
            break
        kept += names
    return tuple(kept)


def policy_of(names: Sequence[str]):
    if not names:
        return jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint_policies.save_only_these_names(*names)


def token_shards(mesh, batch: int, seq_len: int,
                 seq_sharded: bool = False) -> int:
    """The devices a block's ``[batch, seq_len, ...]`` values are spread
    over: the batch over the data axes, the sequence over ``seq`` where
    the attention is sequence-parallel. An axis that does not divide its
    dimension shards nothing, as in ``ops.attention._sp_partition``. The
    ``tensor`` axis is left out: a column-parallel output is reckoned
    whole, which errs to keeping less."""
    if mesh is None:
        return 1
    rows = int(np.prod([axis_size(mesh, a) for a in DATA_AXES]))
    shards = rows if batch % rows == 0 else 1
    if seq_sharded and seq_len % axis_size(mesh, "seq") == 0:
        shards *= axis_size(mesh, "seq")
    return shards


def budget_bytes(capacity: int, held_bytes: int, outside_param_bytes: int,
                 block_input_bytes: int, head_bytes: int,
                 blocks: Sequence[Tuple[Mapping[str, int], int]],
                 scratch: Sequence[int] = ()) -> int:
    """Bytes one device can give to kept intermediates.

    What is kept is all alive when the backward starts, and that is the
    moment reckoned here. The step's other high point, the optimizer pass
    with every gradient alive, holds nothing kept and is the same under
    any policy; between the two a block's kept bytes go as its gradient
    comes, so neither is passed. Alive when the backward starts, whatever
    the policy:

    - ``held_bytes``, the step's own account (``step_holds``);
    - the gradient as the step holds it then: that of the parameters
      outside the blocks (head, embeddings, final norm), which the head's
      backward makes first;
    - every block's input, which ``jax.checkpoint`` keeps under any policy;
    - ``head_bytes``, what stands in front of the head and behind it: the
      final hidden state and its cotangent where the loss is fused and
      works through them in chunks, the logits in full, their cotangent
      and the softmax between them where it is not;
    - one block's backward: the block's recomputed intermediates and as
      much again for their cotangents, reckoned as twice all its named
      bytes (the flash kernels' scratch is on-chip; their folded operands
      are of the size of ``qkv_proj``) and twice its ``scratch`` bytes
      (one entry a kind, in ``blocks``' order; none: no kind has any),
      of the kind of block with most;
    - ``HEADROOM_BYTES``.
    """
    n_blocks = sum(count for _, count in blocks)
    scratch = tuple(scratch) or (0,) * len(blocks)
    margin = (n_blocks * block_input_bytes + head_bytes
              + 2 * max(sum(table.values()) + extra
                        for (table, _), extra in zip(blocks, scratch))
              + HEADROOM_BYTES)
    return capacity - held_bytes - outside_param_bytes - margin


def block_policy(model, training: bool, kinds: Sequence[BlockKind],
                 batch: int, seq_len: int, block_key: str):
    """The checkpoint policy for the blocks of ``model`` (a bound
    ``TransformerLM``, ``LlamaLM`` or ``HybridLM`` inside its call,
    whose blocks' parameters are under keys that start with ``block_key``).

    Everything comes from the shapes traced there (``batch``, ``seq_len``,
    and ``kinds``: for each kind of block the features a token of each
    matmul output it names, how many such blocks there are and the heads
    it attends with; one kind for a stack of equal blocks), from the
    model's fields, mesh, partition rules and parameters, from
    ``step_holds`` and from the device's capacity. A call that is not ``training`` (evaluation,
    decode, init) takes no gradient: it chooses nothing and logs nothing.
    A training call logs its choice, once per distinct choice in a
    process."""
    mesh, held = model.mesh, _held.get()
    capacity = None
    if training and held is not None and not model.is_initializing():
        capacity = device_capacity_bytes(mesh)
    if capacity is None:
        return policy_of(())
    n_blocks = sum(kind.count for kind in kinds)
    tok = batch * seq_len * np.dtype(model.dtype).itemsize
    hidden = tok * model.d_model
    shards = token_shards(
        mesh, batch, seq_len,
        seq_sharded=model.attn_impl.split("_")[0] in ("ring", "ulysses"))
    blocks = []
    for kind in kinds:
        named = {name: tok * width for name, width in kind.widths.items()}
        # the attention's own names, where one call a block makes them in
        # the policy's sight; a ring's steps and the all-to-all's share of
        # heads are not reckoned, and keep nothing
        if kind.attn_heads and model.attn_impl == "xla":
            named["attn_out"] = tok * kind.attn_heads * kind.head_dim
        elif kind.attn_heads and model.attn_impl == "flash":
            named.update(named_residual_bytes(
                batch, seq_len, kind.attn_heads, kind.head_dim, model.dtype))
        blocks.append(({name: b // shards for name, b in named.items()},
                       kind.count))
    head = (2 * hidden if model.fused_head
            else 3 * batch * seq_len * model.vocab_size * 4)
    params = model.variables["params"]
    outside = per_device_bytes(
        {k: v for k, v in params.items() if not k.startswith(block_key)},
        mesh, model.partition_rules())
    budget = budget_bytes(capacity, held, outside, hidden // shards,
                          head // shards, blocks,
                          [tok * kind.scratch // shards for kind in kinds])
    names = choose_names(blocks, budget)
    kept = sum(count * table.get(n, 0) for table, count in blocks
               for n in names)
    kept_block = kept // n_blocks
    record = dict(
        names=",".join(names), kept_bytes_per_block=kept_block,
        kept_bytes=kept, budget_bytes=budget,
        capacity_bytes=capacity, blocks=n_blocks, held_bytes=held,
    )
    say_once(
        logger, "remat/policy", record,
        "remat/policy: keeping [%s] in each of %d blocks: %.1f MB a "
        "block, %.3f GB in all on a device, of a budget of %.3f GB "
        "(capacity %.3f GB, the step holds %.3f GB)", record["names"],
        n_blocks, kept_block / 1e6, kept / 1e9,
        budget / 1e9, capacity / 1e9, held / 1e9)
    return policy_of(names)
