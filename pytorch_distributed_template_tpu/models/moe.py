"""Mixture-of-Experts transformer blocks with expert parallelism.

The reference has no MoE (its model zoo is one CNN, SURVEY.md §2.3) — this
is a first-class extension of the transformer family for the framework's
expert-parallel (``expert`` mesh axis) story.

TPU-native design, following the GShard/Switch einsum formulation (the form
the XLA SPMD partitioner understands natively):

- routing builds **dispatch/combine one-hot tensors** ``[S, E, C]`` (token,
  expert, capacity slot) and the whole layer is four einsums — all MXU work,
  static shapes, no gather/scatter;
- expert weights are stacked ``[E, d, f]`` and sharded over the ``expert``
  mesh axis via ``partition_rules``; when tokens (batch-sharded) meet
  expert-sharded weights, XLA inserts the **all-to-all** pair — the same
  collective an MPI MoE implementation would hand-write;
- tokens over capacity are dropped (their combine weight is zero, the
  residual path carries them), keeping shapes static for XLA;
- the Switch load-balancing auxiliary loss is emitted through flax's
  ``losses`` collection (``sow``), picked up by the train step.

``ExpertLayer`` is the other expert layer, for the models whose experts
outnumber the chips: it is told which experts it holds, routes over all
the published ones, drops no token, and computes every expert it holds
over every token, so that a step's time does not follow its routing, or,
where a token can take fewer experts than are held, over the pairs of
token and held expert alone (``routed_over_pairs``, at the end of this
file).
``MoeMlp`` stays for the GShard form: capacity slots, the einsum dispatch
the SPMD partitioner turns into an all-to-all over an ``expert`` mesh
axis, the load-balancing loss and padded-example masks, none of which the
dropless layer has.
"""
from __future__ import annotations

import functools
import logging
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config.registry import MODELS
from ..observability.trace import say_once
from ..ops.grouped import grouped_matmul, grouped_matmul_gradients
from .llama import SwiGLU

logger = logging.getLogger(__name__)


def _init(stddev):
    return nn.initializers.normal(stddev=stddev)


class MoeMlp(nn.Module):
    """Top-k routed expert FFN (drop-in for the dense MlpBlock).

    :param num_experts: E, total experts (shard over ``expert`` mesh axis).
    :param top_k: experts per token (1 = Switch, 2 = GShard default).
    :param capacity_factor: per-expert slot headroom; capacity
        ``C = ceil(top_k * S / E * capacity_factor)``.
    :param aux_loss_weight: weight of the load-balancing loss sown into the
        ``losses`` collection.
    """

    d_model: int
    d_ff: int
    num_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    dropout: float = 0.0
    n_layer: int = 1
    dtype: Any = jnp.float32
    mesh: Optional[Any] = None
    # "gelu": wi/gelu/wo (GShard/Switch). "swiglu": adds a stacked gate
    # weight wg and computes silu(x@wg) * (x@wi) @ wo — the Mixtral-style
    # expert for the Llama family (biasless, like its dense SwiGLU).
    expert_act: str = "gelu"
    # Token routing implementation — SAME math, different cost model:
    # "einsum": GShard one-hot dispatch/combine einsums ([S,E,C] masks).
    #   The form the XLA SPMD partitioner turns into all-to-all when the
    #   expert axis is sharded — but its flops are O(S*E*C*d), which at
    #   single-chip scale (E*C ~ 2.5*S) COSTS 3x THE EXPERT MATH ITSELF
    #   (measured r4: 136% routing overhead on the moe bench rung), and
    #   the [S,E,C] masks are ~670 MB of HBM traffic per layer.
    # "gather": slot indices instead of one-hot masks — expert inputs
    #   gathered by row, outputs combined by row, O((S+E*C)*d) memory
    #   ops and no [S,E,C] tensor at all. Bit-for-bit the same routing
    #   decisions (tests assert parity with "einsum").
    # "auto": "gather" on an unsharded expert axis, "einsum" when the
    #   mesh actually shards experts (keeps the a2a path).
    dispatch_impl: str = "auto"

    @nn.compact
    def __call__(self, x, train: bool, example_mask=None):
        b, t, d = x.shape
        s = b * t
        e = self.num_experts
        k = min(self.top_k, e)
        cap = max(int(-(-k * s * self.capacity_factor // e)), 1)
        cap = min(cap, s)
        xf = x.reshape(s, d)
        # Per-token validity from the per-example mask: padded examples must
        # not claim expert capacity nor move the balance statistics, or
        # padding would change real tokens' outputs/gradients (the masked-
        # exactness contract of engine/steps.py). One caveat remains: the
        # capacity C is a *static* function of the padded token count (XLA
        # static shapes), so when real tokens are being capacity-dropped the
        # drop boundary can differ between padded and unpadded batches —
        # exactness is guaranteed only while no real token is dropped.
        if example_mask is not None:
            tok = jnp.broadcast_to(
                example_mask.astype(jnp.float32)[:, None], (b, t)
            ).reshape(s)
        else:
            tok = jnp.ones((s,), jnp.float32)

        # --- routing (fp32 for a stable softmax) --------------------------
        logits = nn.Dense(e, dtype=jnp.float32, kernel_init=_init(0.02),
                          name="router")(xf.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)            # [S, E]
        gate_vals, gate_idx = jax.lax.top_k(probs, k)       # [S, k]
        if k > 1:
            # GShard: renormalize the k selected gates.
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9
            )
        # else Switch: the RAW top-1 probability is the gate — renormalizing
        # would pin it to 1.0 and cut the router off from the task gradient.
        gate_vals = gate_vals * tok[:, None]

        if self.dispatch_impl not in ("auto", "gather", "einsum"):
            raise ValueError(
                f"dispatch_impl={self.dispatch_impl!r}; expected "
                "'auto'/'gather'/'einsum'"
            )
        use_gather = self.dispatch_impl == "gather" or (
            self.dispatch_impl == "auto"
            and not (self.mesh is not None
                     and "expert" in self.mesh.axis_names
                     and self.mesh.shape["expert"] > 1)
        )

        # --- capacity assignment: slot 0 fills first, then slot 1 ---------
        # Shared by both dispatch impls: per (token, slot), which
        # capacity slot of the chosen expert it lands in and whether it
        # fit — identical fill order, so the two impls route identically.
        combine = None if use_gather else jnp.zeros((s, e, cap),
                                                    jnp.float32)
        pos_s, keep_s = [], []                     # per slot: [S], [S]
        fill = jnp.zeros((e,), jnp.int32)
        for slot in range(k):
            oh = jax.nn.one_hot(gate_idx[:, slot], e, dtype=jnp.int32)
            oh = oh * tok[:, None].astype(jnp.int32)  # padding claims no slot
            pos = jnp.cumsum(oh, axis=0) - 1 + fill[None, :]   # [S, E]
            keep = (pos < cap) & (oh > 0)
            take = lambda a: jnp.take_along_axis(              # noqa: E731
                a, gate_idx[:, slot][:, None], axis=1)[:, 0]
            pos_s.append(take(pos))
            keep_s.append(take(keep))
            if combine is not None:
                combine = combine + (
                    gate_vals[:, slot, None, None]
                    * keep[..., None].astype(jnp.float32)
                    * jax.nn.one_hot(jnp.where(keep, pos, 0), cap,
                                     dtype=jnp.float32)
                )
            fill = fill + jnp.sum(keep, axis=0, dtype=jnp.int32)

        # --- load-balancing aux loss (Switch eq. 4): E * sum(me * ce),
        # statistics over VALID tokens only ---------------------------------
        if train and self.aux_loss_weight > 0:
            denom = jnp.maximum(tok.sum(), 1.0)
            me = (probs * tok[:, None]).sum(axis=0) / denom          # [E]
            ce = (jax.nn.one_hot(gate_idx[:, 0], e)
                  * tok[:, None]).sum(axis=0) / denom                # [E]
            aux = e * jnp.sum(me * ce)
            self.sow("losses", "moe_aux",
                     self.aux_loss_weight * aux,
                     reduce_fn=lambda a, b: a + b,
                     init_fn=lambda: jnp.zeros((), jnp.float32))

        # --- expert computation: everything is einsum (MXU + all_to_all) --
        wi = self.param("wi", _init(0.02), (e, d, self.d_ff), jnp.float32)
        wo = self.param(
            "wo", _init(0.02 / (2 * self.n_layer) ** 0.5),
            (e, self.d_ff, d), jnp.float32,
        )

        if use_gather:
            # flat slot id per (token, slot); dropped tokens target the
            # trailing scratch row, sliced off before the expert matmuls
            dst = jnp.stack([
                jnp.where(keep_s[i], gate_idx[:, i] * cap + pos_s[i],
                          e * cap)
                for i in range(k)
            ], axis=1)                                       # [S, k]
            # scatter INT indices (tiny), then gather ROWS (fast): the
            # direct row-scatter form measured ~2x slower on TPU. Empty
            # slots keep the sentinel s -> the appended zero row.
            inv = jnp.full((e * cap + 1,), s, jnp.int32)
            inv = inv.at[dst.reshape(-1)].set(
                jnp.repeat(jnp.arange(s, dtype=jnp.int32), k)
            )
            xf_ext = jnp.concatenate(
                [xf.astype(self.dtype),
                 jnp.zeros((1, d), self.dtype)], axis=0)
            expert_in = xf_ext[inv[: e * cap]].reshape(e, cap, d)
        else:
            dispatch = (combine > 0).astype(self.dtype)      # [S, E, C]
            expert_in = jnp.einsum("sec,sd->ecd", dispatch,
                                   xf.astype(self.dtype))    # [E, C, d]
        expert_in = self._constrain(expert_in, P("expert", None, None))
        if self.expert_act == "swiglu":
            wg = self.param("wg", _init(0.02), (e, d, self.d_ff),
                            jnp.float32)
            gate = jnp.einsum("ecd,edf->ecf", expert_in,
                              wg.astype(self.dtype))
            up = jnp.einsum("ecd,edf->ecf", expert_in,
                            wi.astype(self.dtype))
            h = nn.silu(gate) * up
            out = jnp.einsum("ecf,efd->ecd", h, wo.astype(self.dtype))
        elif self.expert_act == "gelu":
            bi = self.param("bi", nn.initializers.zeros, (e, self.d_ff),
                            jnp.float32)
            bo = self.param("bo", nn.initializers.zeros, (e, d),
                            jnp.float32)
            h = jnp.einsum("ecd,edf->ecf", expert_in,
                           wi.astype(self.dtype)) + bi.astype(
                               self.dtype)[:, None]
            h = nn.gelu(h)
            out = jnp.einsum("ecf,efd->ecd", h, wo.astype(
                self.dtype)) + bo.astype(self.dtype)[:, None]
        else:
            raise ValueError(
                f"expert_act={self.expert_act!r}; expected 'gelu'/'swiglu'"
            )
        out = self._constrain(out, P("expert", None, None))
        if use_gather:
            # row-gather each (token, slot)'s expert output and weight
            # by its gate; dropped slots read the zero scratch row
            out_ext = jnp.concatenate(
                [out.reshape(e * cap, d),
                 jnp.zeros((1, d), out.dtype)], axis=0)
            y = sum(
                (gate_vals[:, i] * keep_s[i].astype(jnp.float32)
                 )[:, None].astype(self.dtype) * out_ext[dst[:, i]]
                for i in range(k)
            )
        else:
            y = jnp.einsum("sec,ecd->sd", combine.astype(self.dtype),
                           out)
        y = nn.Dropout(self.dropout, deterministic=not train)(y)
        return y.reshape(b, t, d)

    def _constrain(self, arr, spec: P):
        """Pin the expert-stacked intermediate to the ``expert`` axis so the
        SPMD partitioner chooses the all-to-all dispatch layout (hint only;
        no-op without a mesh or when the axis doesn't divide)."""
        mesh = self.mesh
        if (
            mesh is None
            or "expert" not in mesh.axis_names
            or mesh.shape["expert"] == 1
            or arr.shape[0] % mesh.shape["expert"] != 0
        ):
            return arr
        return jax.lax.with_sharding_constraint(
            arr, NamedSharding(mesh, spec)
        )

    @staticmethod
    def partition_rules():
        """Expert-parallel placement: stacked expert weights shard over the
        ``expert`` axis (composable with TP on the inner dims); the router
        stays replicated."""
        return [
            (r"moe/wi", P("expert", None, "tensor")),
            (r"moe/wg", P("expert", None, "tensor")),
            (r"moe/wo", P("expert", "tensor", None)),
            (r"moe/bi", P("expert", "tensor")),
            (r"moe/bo", P("expert", None)),
            (r"moe/router/kernel", P()),
            (r"moe/router/bias", P()),
        ]


# ---------------------------------------------------------------------------
# The dropless layer: experts held here, routed over all published
# ---------------------------------------------------------------------------

@jax.custom_vjp
def gradient_as_stored(leaf):
    """``leaf`` itself; its gradient leaves in row-major order, the order
    the leaf is stored in. The optimizer's fusion runs in its gradient's
    order, and the jit's arguments and donated results cannot follow it:
    a gradient in another order costs six copies of the leaf a step, the
    parameter and both moments in and the three results out."""
    return leaf


def _gradient_as_stored_bwd(_, g):
    row_major = Layout(major_to_minor=tuple(range(g.ndim)))
    return (with_layout_constraint(g, row_major),)


gradient_as_stored.defvjp(lambda leaf: (leaf, None), _gradient_as_stored_bwd)


def held_experts(x, weight, up, down):
    """What the experts held give: ``x [S, D]`` tokens, ``weight [S, E]``
    the weight of token s at held expert e (0 where it did not choose
    it), ``up [E, D, F]`` and ``down [E, F, D]``. Returns ``[S, D]`` in
    float32: ``sum_e weight[s, e] * relu(x[s] @ up[e])**2 @ down[e]``,
    every held expert over every token, two batched products. A token's
    weight is one scalar a row of the expert's activation, so it is laid
    on the activation, in float32, the product rounded once to the
    operands' type (a weight rounded first doubles the rounding error of
    the router's gradient), and the second product contracts over
    experts and features at once, summing in float32 as it goes: no
    ``[E, S, D]`` result is made, forward or backward (268 MB a layer at
    8 experts, 16384 tokens, 1024 wide), and the weight's gradient reads
    the activation. The first product is named ``moe_experts_up`` for
    the checkpoint policy. ``up``'s gradient leaves as ``up`` is stored
    (``gradient_as_stored``)."""
    pre = checkpoint_name(
        jnp.einsum("sd,edf->esf", x, gradient_as_stored(up.astype(x.dtype))),
        "moe_experts_up")
    act = (jnp.square(jax.nn.relu(pre.astype(jnp.float32)))
           * weight.astype(jnp.float32).T[:, :, None]).astype(x.dtype)
    return jnp.einsum("esf,efd->sd", act, down.astype(x.dtype),
                      preferred_element_type=jnp.float32)


def sow_counter(module, name: str, value) -> None:
    """One of a model's ``step_counters``, summed over the layers that sow
    it (engine/steps.py carries the sums in the step's metrics)."""
    module.sow("counters", name, jnp.asarray(value, jnp.float32),
               reduce_fn=lambda a, b: a + b,
               init_fn=lambda: jnp.zeros((), jnp.float32))


def held_gated_experts(x, weight, gate, up, down):
    """``held_experts`` for experts of three matrices: ``gate``,
    ``up [E, D, F]`` and ``down [E, F, D]``. Returns ``[S, D]`` in float32:
    ``sum_e weight[s, e] * (silu(x[s] @ gate[e]) * (x[s] @ up[e])) @
    down[e]``, every held expert over every token. A token's weight is
    one scalar a row of the expert's activation, so it is laid on the
    activation and the third product contracts over experts and
    features at once, summing in float32 as it goes: no ``[E, S, D]``
    result is made (537 MB at 8 experts, 8192 tokens, 4096 wide). The
    first two products are named ``moe_experts_gate`` and
    ``moe_experts_up`` as the einsums make them: kept, the backward runs
    neither a second time. The gradients of ``gate`` and ``up`` leave as
    the two are stored (``gradient_as_stored``)."""
    pre = checkpoint_name(
        jnp.einsum("sd,edf->esf", x,
                   gradient_as_stored(gate.astype(x.dtype))),
        "moe_experts_gate")
    lin = checkpoint_name(
        jnp.einsum("sd,edf->esf", x, gradient_as_stored(up.astype(x.dtype))),
        "moe_experts_up")
    act = jax.nn.silu(pre) * lin * weight.T[:, :, None].astype(x.dtype)
    return jnp.einsum("esf,efd->sd", act, down.astype(x.dtype),
                      preferred_element_type=jnp.float32)


# the room for pairs of token and held expert over what uniform routing
# fills of it. Four leaves the densest routing the cells' records show (1.09
# and 1.19 times uniform a layer: 7 126 pairs a step over solar's four
# layers, 33 540 over the hybrid's five) under a quarter of its room, and
# gives a chip that holds a quarter of the experts or more its bound,
# ``min(top_k, held)`` places a token
ROOM_OVER_UNIFORM = 4


def token_places(top_k: int, n_held: int, n_routed: int) -> int:
    """The places a token has in the room for its pairs with the
    ``n_held`` experts held here of ``n_routed``, of which it takes
    ``top_k``: ``ROOM_OVER_UNIFORM`` times the ``top_k * n_held /
    n_routed`` that uniform routing gives it, rounded up, at least one
    and at most the bound ``min(top_k, n_held)``. The room is that a
    token; at ``n_held`` places it is every held expert over every token
    (``ExpertLayer`` says what follows from either)."""
    uniform = -(-ROOM_OVER_UNIFORM * top_k * n_held // n_routed)
    return max(1, min(top_k, n_held, uniform))


class ExpertLayer(nn.Module):
    """Routed experts as a chip holds them, with what today's sparse
    models put around them. Each part is there or not by its argument.

    The router scores all ``n_routed`` published experts in float32
    (``sigmoid`` or ``softmax``), takes the ``top_k`` with the largest
    score plus ``selection_bias`` (a leaf no gradient reaches: it steers
    the choice, not the weights, and the training step moves it against
    each expert's load, ``engine/steps.selection_bias_step``; the choice
    is a mask, every expert at or over the ``top_k``-th largest, so
    experts tied exactly there are all taken), and weighs each chosen
    expert by its score over the sum of the chosen scores, times
    ``scale``. Of those, this chip holds experts ``held[0] .. held[0] + held[1]`` (``held[1]``
    0: all of them) and adds their part: ``relu(l @ up_e)**2 @ down_e``
    on ``l``, the token itself or its ``latent`` projection. What the
    experts held elsewhere would add is theirs to add: on one chip the
    layer runs without its exchange, and the sum over all shares of
    ``held`` (the shared expert counted once) is the whole layer.

    No token is dropped whatever the imbalance. How many places a token
    has in the room for pairs of token and held expert is read from
    shapes alone (``token_places``): what uniform routing gives a token
    here, ``top_k * held / n_routed`` pairs, times ``ROOM_OVER_UNIFORM``,
    rounded up; never under one place nor over the bound ``min(top_k,
    held)``. The room is ``tokens x places`` rows, and which of two forms
    the routed experts' products take is read from the same number:

    - ``places < held`` (8 held of 320, 8 a token: 1 place, a room of
      8192 rows at 8192 tokens where uniform routing fills 1638; 8 held
      of 512, 22 a token: 2 places; 16 held of 64, 4 a token: 4 places,
      the bound): the products run over the pairs
      (``routed_over_pairs``): the tokens' rows gathered expert by expert
      into the room (``hit.T`` read row-major is in expert order, so a
      pair's row is a running count and nothing is sorted by expert),
      grouped products with one group a held expert (ops/grouped.py:
      ``jax.lax.ragged_dot``, which the TPU's compiler makes kernels
      that visit only the row tiles a group fills, so their time follows
      the rows filled and not the room; the gathers, the casts and the
      activation's passes follow the room), a token's rows summed back
      in float32. The room is no bound: routing more than
      ``ROOM_OVER_UNIFORM`` times as dense as uniform passes it, and so
      do experts tied exactly at a token's bar (the choice is a mask, so
      a tie gives the token more than ``top_k`` experts: a router of
      zeros gives it all of them). In a step whose pairs pass the room a
      ``cond`` on their count takes every held expert over every token
      instead, and ``moe_rows_run`` says so. Nothing is clipped. (Every
      held expert over every token was 65 536 rows a layer where 8 of
      320 are held, 163.9 ms of a 372 ms step for what fills 1 400-1 800
      of them: PERF.md, PR 51. A buffer sized at the bound and sorted by
      expert had cost as much as the dense products there, PR 33: the
      room has to be the routing's, not the bound's.)
    - ``places == held`` (as many experts a token by uniform routing as
      are held, or nearly: a chip that holds 2 of 8 of which a token
      takes 2, or all of 16 of which it takes 16): with that much room
      every held expert has a place for every token, so nothing is
      sorted or gathered: each held expert's products run over all the
      tokens, weighed 0 where a token did not choose it
      (``held_experts``), no routing passes the room, and the step's
      time does not follow the routing.

    ``gated`` makes every expert three matrices,
    ``(silu(l @ gate_e) * (l @ up_e)) @ down_e`` (``held_gated_experts``),
    and the shared expert a ``SwiGLU``; the routing, the mask and the
    counters are the same. Either way the token's weight lies on the
    expert's activation and the last product sums over experts and
    features at once, so no result a held expert wide is made; and the
    gradient of a matrix that a first product reads leaves the layer
    ``[E][D][F]`` in memory, as the leaf lies, so that the optimizer
    updates it where it lies (``gradient_as_stored``). With a ``latent``
    the sum over the experts held is what ``latent_up`` reads, and its
    weight gradient reads it again: it carries the checkpoint name
    ``moe_experts_out``, and kept (33.5 MB a layer at 16384 tokens and a
    latent of 1024) the backward does not run the last product a second
    time. Without one the sum joins the shared expert's output, no
    gradient reads it, and it has no name.

    ``shared_d_ff`` adds one expert every token takes. Counters of the
    step, sown under ``counters`` (engine/steps.py carries them):
    ``moe_pairs_here``, ``moe_load_max_over_mean`` over the experts held
    (``1 / n_layers`` of it, so that the layers' sum is their mean),
    ``moe_tokens_unserved``, the tokens none of whose experts is held,
    and ``moe_rows_run``, the rows the routed experts' products ran over
    (``tokens x held``; over the pairs the rows the groups hold, which
    are the pairs: the tiles ``ragged_dot`` rounds a group up to are the
    compiler's; ``tokens x held`` again in a step whose pairs pass the
    room).
    """
    d_model: int
    d_ff: int
    n_routed: int
    top_k: int
    held: Tuple[int, int] = (0, 0)
    latent: int = 0
    shared_d_ff: int = 0
    router: str = "sigmoid"
    selection_bias: bool = False
    scale: float = 1.0
    gated: bool = False             # three matrices an expert, SiLU gate
    n_layers: int = 1               # expert layers in the model (counters)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        s = b * t
        lo, n_held = self.held[0], self.held[1] or self.n_routed
        if not 0 <= lo <= lo + n_held <= self.n_routed:
            raise ValueError(f"held {self.held} lies outside the "
                             f"{self.n_routed} routed experts")
        if self.router not in ("sigmoid", "softmax"):
            raise ValueError(f"router={self.router!r}; expected "
                             "'sigmoid'/'softmax'")
        k = min(self.top_k, self.n_routed)
        xf = x.reshape(s, d)
        xc = xf.astype(self.dtype)
        dense = functools.partial(nn.Dense, use_bias=False, dtype=self.dtype,
                                  kernel_init=_init(0.02))

        with jax.named_scope("moe_route"):
            w_r = self.param("router", _init(0.02), (d, self.n_routed),
                             jnp.float32)
            logits = checkpoint_name(jnp.matmul(
                xf.astype(jnp.float32), w_r,
                precision=jax.lax.Precision.HIGHEST), "moe_router")
            scores = (jax.nn.sigmoid(logits) if self.router == "sigmoid"
                      else jax.nn.softmax(logits, axis=-1))
            choice = scores
            if self.selection_bias:
                bias = self.param("selection_bias", nn.initializers.zeros,
                                  (self.n_routed,), jnp.float32)
                choice = scores + jax.lax.stop_gradient(bias)
            # the k-th largest is the bar: a mask over all the experts
            # and a slice of the held ones, and nothing is gathered
            bar = jax.lax.top_k(jax.lax.stop_gradient(choice), k)[0][:, -1:]
            took = jax.lax.stop_gradient(choice) >= bar            # [S, E]
            chosen_sum = jnp.sum(jnp.where(took, scores, 0.0), axis=1,
                                 keepdims=True)
            if self.selection_bias:
                # tokens each published expert got, under the bias's own
                # path: what the step's rule for it reads (engine/steps.py)
                self.sow("router_load", "selection_bias",
                         jnp.sum(took, axis=0, dtype=jnp.float32),
                         reduce_fn=lambda a, b: a + b,
                         init_fn=lambda: jnp.zeros((self.n_routed,),
                                                   jnp.float32))
            hit = took[:, lo:lo + n_held]
            weight = jnp.where(hit, self.scale * scores[:, lo:lo + n_held]
                               / (chosen_sum + 1e-20), 0.0)
            counts = jnp.sum(hit, axis=0)
            pairs = jnp.sum(counts)
            self._count("moe_pairs_here", pairs)
            self._count("moe_load_max_over_mean",
                        jnp.max(counts) * n_held / jnp.maximum(pairs, 1)
                        / self.n_layers)
            self._count("moe_tokens_unserved",
                        s - jnp.sum(jnp.any(hit, axis=1)))

        with jax.named_scope("moe_shared"):
            tokens = xc
            if self.latent:
                tokens = checkpoint_name(
                    dense(self.latent, name="latent_down")(tokens),
                    "moe_latent")
        width = self.latent or d
        if self.gated:
            gate = self.param("experts_gate", _init(0.02),
                              (n_held, width, self.d_ff), jnp.float32)
        up = self.param("experts_up", _init(0.02),
                        (n_held, width, self.d_ff), jnp.float32)
        down = self.param("experts_down", _init(0.02),
                          (n_held, self.d_ff, width), jnp.float32)

        # the room for pairs from what uniform routing fills, under its
        # bound; the pairs' form wherever that is fewer rows than every
        # held expert over every token
        places = token_places(k, n_held, self.n_routed)
        room, over_pairs = s * places, places < n_held
        said = dict(tokens=s, held=n_held, routed=self.n_routed, top_k=k,
                    expected=s * k * n_held / self.n_routed, rows=room,
                    **({"experts": "gated"} if self.gated else {}))
        text = ("moe/dispatch: %(tokens)d tokens, %(held)d of %(routed)d "
                "experts held, %(top_k)d a token: %(expected).0f pairs a "
                "layer a step at uniform routing, "
                f"{places} place(s) a token, room for %(rows)d, ")
        if over_pairs:
            text += (f"{room / said['expected']:.1f} times that and "
                     "under every held expert over every token "
                     "(%(dense_rows)d rows): the products run over the "
                     "pairs, one group a held expert; every held expert "
                     "over every token only in a step whose pairs pass "
                     "the room")
            said["dense_rows"] = s * n_held
        else:
            text += ("which no routing passes: every held expert over every "
                     "token")
        say_once(logger, "moe/dispatch", said,
                 text + (", three matrices an expert" if self.gated else ""))
        with jax.named_scope("moe_experts"):
            if over_pairs:
                mats = tuple(gradient_as_stored(m.astype(self.dtype))
                             for m in ((gate, up) if self.gated else (up,)))
                mats += (down.astype(self.dtype),)
                fits = pairs <= room
                routed = routed_over_pairs(
                    tokens, weight, hit, counts.astype(jnp.int32), mats,
                    room)
                self._count("moe_rows_run",
                            jnp.where(fits, pairs, s * n_held))
            else:
                self._count("moe_rows_run", s * n_held)
                if self.gated:
                    routed = held_gated_experts(tokens, weight, gate, up,
                                                down)
                else:
                    routed = held_experts(tokens, weight, up, down)

        with jax.named_scope("moe_shared"):
            out = routed.astype(self.dtype)
            if self.latent:
                # `latent_up`'s weight gradient reads the layer's sum:
                # kept by name, the second product is not run again
                out = dense(d, name="latent_up")(
                    checkpoint_name(out, "moe_experts_out"))
            if self.shared_d_ff and self.gated:
                out = out + SwiGLU(d, self.shared_d_ff, self.dtype,
                                   name="shared")(xc)
            elif self.shared_d_ff:
                mid = checkpoint_name(
                    dense(self.shared_d_ff, name="shared_up")(xc),
                    "moe_shared_up")
                out = out + dense(d, name="shared_down")(
                    jnp.square(jax.nn.relu(mid)))
        return out.reshape(b, t, d)

    def _count(self, name, value):
        sow_counter(self, name, value)


@MODELS.register("MoeLM")
def moe_lm(vocab_size: int = 50257, n_layer: int = 12, n_head: int = 12,
           d_model: int = 768, max_len: int = 1024, dropout: float = 0.1,
           num_experts: int = 8, top_k: int = 2, moe_every: int = 2,
           capacity_factor: float = 1.25, aux_loss_weight: float = 0.01,
           bfloat16: bool = False, attn_impl: str = "xla",
           remat: bool = False, mesh=None, **overrides):
    """Decoder-only LM with MoE FFNs every ``moe_every``-th block
    (GShard-style interleaving; ``moe_every=1`` = every block)."""
    from .transformer import TransformerLM

    return TransformerLM(
        vocab_size=vocab_size, n_layer=n_layer, n_head=n_head,
        d_model=d_model, max_len=max_len, dropout=dropout,
        dtype=jnp.bfloat16 if bfloat16 else jnp.float32,
        attn_impl=attn_impl, remat=remat, mesh=mesh,
        moe_experts=num_experts, moe_top_k=top_k, moe_every=moe_every,
        moe_capacity_factor=capacity_factor,
        moe_aux_loss_weight=aux_loss_weight, **overrides,
    )


@MODELS.register("TinyMoeLM")
def tiny_moe_lm(vocab_size: int = 256, n_layer: int = 2, n_head: int = 4,
                d_model: int = 64, max_len: int = 128, dropout: float = 0.0,
                num_experts: int = 4, top_k: int = 2, moe_every: int = 1,
                capacity_factor: float = 2.0, aux_loss_weight: float = 0.01,
                attn_impl: str = "xla", remat: bool = False, mesh=None,
                bfloat16: bool = False):
    """Small MoE config for tests and the multi-chip dry run."""
    return moe_lm(
        vocab_size=vocab_size, n_layer=n_layer, n_head=n_head,
        d_model=d_model, max_len=max_len, dropout=dropout,
        num_experts=num_experts, top_k=top_k, moe_every=moe_every,
        capacity_factor=capacity_factor, aux_loss_weight=aux_loss_weight,
        bfloat16=bfloat16, attn_impl=attn_impl, remat=remat, mesh=mesh,
    )


def expert_block_sizes(d_ff: int, n_routed: int, top_k: int = 0, held=(0, 0),
                       latent: int = 0, shared_d_ff: int = 0,
                       gated: bool = False, dtype: Any = jnp.float32,
                       **_) -> dict:
    """What a block with an ``ExpertLayer`` of these fields (the others
    set no width) tells models/remat_policy.py: every name the layer
    makes with its width in features a token of ``dtype``. The float32
    router logits count twice a 16-bit model's item; the routed experts'
    first products are as wide as the rows they run over a token times
    their ``d_ff``: the places a token has in the room for its pairs
    (``token_places``; ``top_k`` 0: not said, every held expert), and
    where those are fewer than the experts held the pairs' layout has a
    name too, ``moe_pairs``;
    the layer's sum has a name only where ``latent_up`` reads it; a gated
    layer's shared expert is a ``SwiGLU`` and makes its two names."""
    n_held = held[1] or n_routed
    places = (token_places(top_k, n_held, n_routed) if top_k
              else n_held)
    first, item = places * d_ff, jnp.dtype(dtype).itemsize
    widths = {"moe_router": n_routed * 4 // item}
    if places < n_held:
        # ``PairLayout`` and the rows' weights: a key, a flag and a weight
        # a row; a row and a place a held expert, a count, a token
        widths["moe_pairs"] = -(-(places * 9 + n_held * 8 + 4) // item)
    if latent:
        widths.update({"moe_latent": latent, "moe_experts_out": latent})
    if shared_d_ff:
        widths.update({"mlp_gate": shared_d_ff, "mlp_up": shared_d_ff}
                      if gated else {"moe_shared_up": shared_d_ff})
    if gated:
        widths["moe_experts_gate"] = first
    return {**widths, "moe_experts_up": first}



# ---------------------------------------------------------------------------
# The routed experts over the pairs, where a token can take fewer experts
# than are held (``ExpertLayer`` says when)
# ---------------------------------------------------------------------------

class PairLayout(NamedTuple):
    """Where each pair of token and held expert lies in a buffer of
    ``room`` rows laid expert by expert, tokens in their order inside an
    expert. Integers all: no gradient reaches a layout.

    ``key [room]`` is ``expert * S + token`` of the pair a row holds
    (``E * S`` where none does), rising; ``live [room]`` whether a pair
    fills the row; ``pos [S, E]`` a pair's row and ``order [S, E]`` its
    place among its token's pairs (-1: no pair); ``taken [S]`` a token's
    pairs."""
    key: Any
    live: Any
    pos: Any
    order: Any
    taken: Any

    @property
    def token(self):
        return self.key % self.taken.shape[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def lay_pairs(weight, hit, counts, room: int):
    """The layout of the pairs ``hit [S, E]`` marks in ``room`` rows, and
    each row's weight ``[room]`` of ``weight [S, E]`` (0 where no pair
    fills it). ``hit.T`` read row-major is in expert order already, so
    nothing is sorted by expert: the marked entries' flat indices are
    moved to the front by one sort of ``E * S`` integers that carries the
    weights along, and a pair's row is a running count. Pairs past the
    room have no row (the caller takes the other way then)."""
    s, e = hit.shape
    flat = hit.T.reshape(-1)
    key, of_row = jax.lax.sort(
        (jnp.where(flat, jnp.arange(e * s, dtype=jnp.int32), e * s),
         weight.T.reshape(-1)), num_keys=1)
    starts = jnp.cumsum(counts) - counts
    pos = starts[None, :] + jnp.cumsum(hit, axis=0, dtype=jnp.int32) - 1
    order = jnp.where(hit, jnp.cumsum(hit, axis=1, dtype=jnp.int32) - 1, -1)
    live = jnp.arange(room, dtype=jnp.int32) < jnp.sum(counts)
    lay = PairLayout(key[:room], live, pos, order,
                     jnp.sum(hit, axis=1, dtype=jnp.int32))
    return lay, jnp.where(live, of_row[:room], 0.0)


def _lay_pairs_fwd(weight, hit, counts, room):
    lay, of_row = lay_pairs(weight, hit, counts, room)
    return (lay, of_row), lay


def _weights_of_rows_back(lay, g):
    """Each live row's cotangent ``g [room]`` back to its pair's place in
    ``[S, E]``: the keys rise and no two are alike; a row no pair fills
    holds a key past the end."""
    s, e = lay.order.shape
    back = jnp.zeros((e * s,), g.dtype).at[lay.key].set(
        g, mode="drop", indices_are_sorted=True, unique_indices=True)
    return back.reshape(e, s).T


lay_pairs.defvjp(
    _lay_pairs_fwd,
    lambda room, lay, g: (_weights_of_rows_back(lay, g[1]), None, None))


@jax.custom_vjp
def rows_of_tokens(x, lay: PairLayout):
    """``x [S, D]`` -> ``[room, D]``: each pair's row is its token's row
    of ``x``. A row no pair fills holds some token's row, which nothing
    may read: the grouped products do not, and ``tokens_of_rows``, its
    transpose over the rows that pairs fill, does not. Each is the
    other's backward: the room's rows are gathered both ways, and only
    the few pairs a token has past its places are scattered."""
    return x[lay.token]


# the pairs past their tokens' places that one turn behind the places adds:
# a row added costs some 0.7 us at 4096 float32 wide, a pair or padding
# (0.72 ms a turn of 1024 inside solar_open2_l4.seq8k's step, where a layer
# has 150-400 such pairs; PERF.md, PR 51), so a turn is what a layer
# usually has
PAST_PLACES = 256


@jax.custom_vjp
def tokens_of_rows(y, lay: PairLayout):
    """``y [room, D]`` -> ``[S, D]`` in float32: each token's row is the
    float32 sum of its pairs' rows of ``y``, read where a pair is and
    nowhere else. A token's first ``room / S`` pairs are gathered at
    once, every token's: the room's rows. A token can have more (the
    room is what uniform routing fills several times over, not a
    token's bound, and experts tied exactly at its bar are all taken):
    those pairs, a few hundred a layer where the room is far under the
    bound, are counted off in the tokens' order and added to their
    tokens' rows ``PAST_PLACES`` a turn, so that what they cost follows
    their number and not the tokens' (a pass over every token for each
    place the busiest token has was a gather of ``S`` rows a turn, two
    or three turns a layer at one place a token)."""
    s = lay.taken.shape[0]
    places = lay.live.shape[0] // s

    def row(i, order, pos):
        return jnp.sum(jnp.where(order == i, pos, 0), axis=1)

    rows = jnp.concatenate([row(i, lay.order, lay.pos)
                            for i in range(places)])
    # place by place, so that the rows gathered split into places on
    # their major dimension and are summed as they lie
    got = y[rows].reshape(places, s, -1)
    out = jnp.zeros((s, y.shape[1]), jnp.float32)
    for i in range(places):         # one pass over what was gathered
        out = out + jnp.where((i < lay.taken)[:, None],
                              got[i].astype(jnp.float32), 0.0)
    past = jnp.cumsum(jnp.maximum(lay.taken - places, 0))

    def add_past(turn, out):
        # the turn's pairs: whose each is (the first token whose running
        # count passes it; ``s`` behind the last, which the add drops) and
        # which of its token's pairs
        nth = turn * PAST_PLACES + jnp.arange(PAST_PLACES, dtype=jnp.int32)
        token = jnp.sum(past[None, :] <= nth[:, None], axis=1)
        at = jnp.minimum(token, s - 1)
        place = lay.taken[at] - (past[at] - nth)
        got = y[row(place[:, None], lay.order[at], lay.pos[at])]
        return out.at[token].add(got.astype(jnp.float32), mode="drop",
                                 indices_are_sorted=True)

    return jax.lax.fori_loop(0, -(-past[-1] // PAST_PLACES), add_past, out)


rows_of_tokens.defvjp(
    lambda x, lay: (rows_of_tokens(x, lay), (lay, x[:0])),
    lambda kept, g: (tokens_of_rows(g, kept[0]).astype(kept[1].dtype), None))
tokens_of_rows.defvjp(
    lambda y, lay: (tokens_of_rows(y, lay), (lay, y[:0])),
    lambda kept, g: (rows_of_tokens(g, kept[0]).astype(kept[1].dtype), None))


def _activation(pre):
    """An expert's activation of its first products, in float32:
    ``silu(gate) * up`` of two, ``relu(up)**2`` of one."""
    pre = [p.astype(jnp.float32) for p in pre]
    return (jax.nn.silu(pre[0]) * pre[1] if len(pre) == 2
            else jnp.square(jax.nn.relu(pre[0])))


def _laid_on(pre, of_row, live, dtype):
    """The experts' activation with the token's weight on it: jax says
    nothing of what ``ragged_dot`` leaves in the rows no group holds, so
    each first product passes a select on ``live`` before it meets
    arithmetic; the weight is laid on in float32 and the product rounded
    once, to ``dtype``."""
    act = _activation([jnp.where(live[:, None], p, 0) for p in pre])
    return (act * of_row[:, None]).astype(dtype)


def experts_over_pairs(x, weight, hit, counts, *mats, room: int):
    """What ``held_experts`` and ``held_gated_experts`` give, computed
    over the pairs alone: ``x [S, D]``, ``weight [S, E]``, ``hit [S, E]``
    where a token chose a held expert, ``counts [E]`` their sums, and
    ``mats`` the experts' matrices in the compute type, ``(up, down)``
    or ``(gate, up, down)``. Returns ``[S, D]`` in float32. The pairs
    must fit the ``room``.

    The tokens' rows are gathered into ``room`` rows expert by expert
    (scope ``moe_dispatch``), the products are grouped products with one
    group a held expert (ops/grouped.py); the
    token's weight is laid on the activation in float32 and the product
    rounded once to the operands' type; the last product gives a float32
    row a pair, and a token's rows are summed in float32 (scope
    ``moe_combine``)."""
    with jax.named_scope("moe_dispatch"):
        lay, of_row = lay_pairs(weight.astype(jnp.float32), hit, counts, room)
    return _over_pairs(x, lay, of_row, counts, *mats)[0]


def _over_pairs(x, lay, of_row, counts, *mats):
    """(``experts_over_pairs``'s value in a layout given, the first
    products as the grouped products make them, ``[room, F]`` each)."""
    *first, down = mats
    with jax.named_scope("moe_dispatch"):
        rows = rows_of_tokens(x, lay)
    pre = tuple(grouped_matmul(rows, m, counts) for m in first)
    out = grouped_matmul(_laid_on(pre, of_row, lay.live, x.dtype), down,
                         counts, jnp.float32)
    with jax.named_scope("moe_combine"):
        return tokens_of_rows(out, lay), pre


def every_expert_over_every_token(x, weight, *mats):
    """``experts_over_pairs``'s values by every held expert over every
    token, the weight laid on in float32 as there: for the step whose
    pairs do not fit the room."""
    *first, down = mats
    # one product for the first matrices side by side: this way runs in a
    # step in a thousand runs, and what it costs every run is its code
    pre = jnp.einsum("sd,edf->esf", x, jnp.concatenate(first, axis=2))
    act = _activation(jnp.split(pre, len(first), axis=2))
    act = (act * weight.astype(jnp.float32).T[:, :, None]).astype(x.dtype)
    return jnp.einsum("esf,efd->sd", act, down,
                      preferred_element_type=jnp.float32)


def _of_expert(e, weight, *mats):
    """Held expert ``e``'s column of ``weight [S, E]`` and its matrices,
    each with the expert's dimension kept, one long."""
    return (jax.lax.dynamic_slice_in_dim(weight, e, 1, axis=1),
            *(jax.lax.dynamic_slice_in_dim(m, e, 1, axis=0) for m in mats))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def routed_over_pairs(x, weight, hit, counts, mats, room: int):
    """``experts_over_pairs`` where the pairs fit the ``room``, and
    ``every_expert_over_every_token`` in the step where they do not
    (the room is what uniform routing fills several times over, no
    bound; and exact ties at a token's bar give it more than ``top_k``
    experts, so ``tokens x top_k`` is none either): no token is dropped
    either way.
    One ``cond`` on the pairs' count forward and one backward, under a
    backward rule of this function's own, so that neither way costs the
    other anything: differentiated, a ``cond`` hands out what EACH branch
    would keep for its backward, the untaken one's as zeros, and the
    compiler cannot fuse across it (1.8 GB a layer of float32
    activations and masks at 32768 rows of 1536, PR 50). Here the
    forward keeps the pairs' layout (``moe_pairs``) and the first
    products under their checkpoint names and what came in, whichever
    way was taken; the backward remakes the activation on the way it
    takes, and no product. The other way keeps nothing: its backward
    runs its first products again, an expert at a time.

    Both rules' bodies are jitted functions of this module, so that a
    model's expert layers of one shape are traced and lowered once, not
    once a layer and again in each layer's recomputation (the step's
    trace 3.2 -> 7.6 s on the chip's host without, PR 50)."""
    return _forward(x, weight, hit, counts, *mats, room=room)[0]


@functools.partial(jax.jit, static_argnames=("room",))
def _forward(x, weight, hit, counts, *mats, room):
    """(the layer's float32 sum, the pairs' layout, each row's weight,
    the first products: zeros from the step whose pairs pass the room)."""
    with jax.named_scope("moe_dispatch"):
        lay, of_row = lay_pairs(weight.astype(jnp.float32), hit, counts, room)

    def pairs(x, weight, *mats):
        out, pre = _over_pairs(x, lay, of_row, counts, *mats)
        return (out, *pre)

    def every(x, weight, *mats):
        def one(e, out):
            return out + every_expert_over_every_token(
                x, *_of_expert(e, weight, *mats))

        nothing = jnp.zeros((room, mats[0].shape[2]), x.dtype)
        return (jax.lax.fori_loop(0, weight.shape[1], one, jnp.zeros(
            x.shape[:1] + mats[-1].shape[2:], jnp.float32)),
            *(nothing for _ in mats[:-1]))

    out, *pre = jax.lax.cond(jnp.sum(counts) <= room, pairs, every,
                             x, weight, *mats)
    return out, lay, of_row, tuple(pre)


def _routed_over_pairs_fwd(x, weight, hit, counts, mats, room):
    out, lay, of_row, pre = _forward(x, weight, hit, counts, *mats,
                                     room=room)
    lay, of_row = jax.tree.map(
        lambda a: checkpoint_name(a, "moe_pairs"), (lay, of_row))
    names = ("moe_experts_gate", "moe_experts_up")[-len(pre):]
    pre = tuple(checkpoint_name(p, name) for p, name in zip(pre, names))
    return out, (x, weight, counts, mats, lay, of_row, pre)


@functools.partial(jax.jit, static_argnames=("room",))
def _backward(x, weight, counts, lay, of_row, pre, g, *mats, room):
    def pairs(x, weight, pre, g, *mats):
        *first, down = mats
        # the first products and the layout are the forward's; the rows
        # they read and the activation are made again, no product is
        with jax.named_scope("moe_dispatch"):
            rows = rows_of_tokens(x, lay)
        act, to_first = jax.vjp(functools.partial(
            _laid_on, live=lay.live, dtype=x.dtype), pre, of_row)
        with jax.named_scope("moe_combine"):
            # the cotangent as it stands in memory: fused into the gather,
            # what makes it would be made again for every row
            d_out = rows_of_tokens(
                jax.lax.optimization_barrier(g.astype(x.dtype)), lay)
        d_act, d_down = grouped_matmul_gradients(act, down, counts, d_out)
        d_pre, d_of_row = to_first(d_act)
        d_rows, d_first = zip(*(
            grouped_matmul_gradients(rows, m, counts, d)
            for m, d in zip(first, d_pre)))
        with jax.named_scope("moe_dispatch"):
            # the products' cotangents are added over the room, in the
            # rows' type as the dense form adds them, and summed by token
            # once
            dx = tokens_of_rows(sum(d_rows[1:], d_rows[0]), lay)
            d_weight = _weights_of_rows_back(lay, d_of_row)
        return dx.astype(x.dtype), d_weight, (*d_first, d_down)

    def every(x, weight, pre, g, *mats):
        def one(e, sums):
            dx, *rest = sums
            dx_e, *rest_e = jax.vjp(
                every_expert_over_every_token,
                x, *_of_expert(e, weight, *mats))[1](g)
            return (dx + dx_e.astype(jnp.float32), *(
                jax.lax.dynamic_update_index_in_dim(whole, part, e, axis)
                for whole, part, axis in zip(
                    rest, rest_e, (1,) + (0,) * len(mats))))

        dx, d_weight, *d_mats = jax.lax.fori_loop(
            0, weight.shape[1], one,
            (jnp.zeros(x.shape, jnp.float32), jnp.zeros_like(weight),
             *(jnp.zeros_like(m) for m in mats)))
        return dx.astype(x.dtype), d_weight, tuple(d_mats)

    return jax.lax.cond(jnp.sum(counts) <= room, pairs, every,
                        x, weight, pre, g, *mats)


def _routed_over_pairs_bwd(room, kept, g):
    x, weight, counts, mats, lay, of_row, pre = kept
    dx, d_weight, d_mats = _backward(x, weight, counts, lay, of_row, pre, g,
                                     *mats, room=room)
    # what reads the matrices' gradients stays outside the ``cond``: moved
    # into its branches (the compiler does that), the cast to the leaves'
    # float32 and the gradient norm's sums made the optimizer's pass read
    # a float32 gradient it had to be written first, 8 bytes a parameter
    # more (`optimizer_ms_per_step` 30.9 -> 37.5, my chip run, PR 50)
    return dx, d_weight, None, None, jax.lax.optimization_barrier(d_mats)


routed_over_pairs.defvjp(_routed_over_pairs_fwd, _routed_over_pairs_bwd)
