"""The routers' selection biases at the fixed point of the family's own
balancing rule, solved by the benchmark from its own reference before the
program's weights go in.

Weights from a seed route as at the first step of pre-training: the
hidden states' common component sends most tokens to the few experts
whose router columns align with it. A model in training is not in that
state: the auxiliary-loss-free rule (Wang et al. arXiv:2408.15664; the
program's `engine/steps.selection_bias_step`) holds every expert's load
at the mean, and the deployment a cell stands for only works if it does.
`benchmarks/reference/*.matmul_weights` counts a held expert as met by
`moe_top_k / moe_n_routed` of the tokens, so the traffic has to be in
that state too.

The solve walks the reference's forward over the cell's first batch,
layer by layer. At each expert layer it takes the router's scores of the
layer's normed input for all the published experts
(`arch.router_scores`, the function `arch.experts` routes by) and turns
the rule on that `[tokens, n_routed]` matrix alone, `TURNS` times:

    load = tokens for which score + bias is at or over the token's
           `moe_top_k`-th largest (the program's mask)
    bias += rate * sign(mean(load) - load)

the rate from the scores' own spread down to `RATE_LAST`, geometrically;
the same turns for every seed, so the biases are a function of the seed
and of nothing else. The layer's forward then runs WITH the solved bias,
so the next layer's input is the one it will see. The cell's batches are
independent draws of one distribution (benchmarks/data.py), so what
balances the first holds for the others to within sampling noise.

The products are bfloat16 with float32 sums, as the program's
(`common.DOTS["bf16"]`): the solve need not be exact, and the hidden
states it routes are then the program's to rounding; the router's own
product and the scores are float32, as the program has them. Nothing
here imports the program. In the cells `selection_bias_rate` stays 0, so
the solved bias is a constant on both sides: no gradient reaches it, it
is outside weight decay, AdamW leaves it where it is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference.common import DOTS, layer_params

BIAS_LEAF = "selection_bias"
TURNS = 96
RATE_LAST = 1e-5


def bias_paths(shapes) -> list:
    return sorted(p for p in shapes if p.rsplit("/", 1)[-1] == BIAS_LEAF)


def loads(scores, bias, top_k: int):
    """Tokens each expert gets: those whose score plus bias is at or
    over the token's `top_k`-th largest."""
    choice = scores + bias
    bar = jax.lax.top_k(choice, top_k)[0][:, -1:]
    return jnp.sum(choice >= bar, axis=0, dtype=jnp.float32)


@functools.partial(jax.jit, static_argnames=("top_k",))
def solve_bias(scores, bias, top_k: int):
    """`(solved bias, loads under `bias`, loads under the solved one)`
    for `scores [tokens, n_routed]`, float32."""
    mean = scores.shape[0] * top_k / scores.shape[1]
    first = jnp.std(scores)
    shrink = (RATE_LAST / first) ** (1.0 / (TURNS - 1))

    def turn(k, b):
        return b + first * shrink ** k * jnp.sign(
            mean - loads(scores, b, top_k))

    solved = jax.lax.fori_loop(0, TURNS, turn, bias)
    # only differences between biases route: the rule lifts more experts
    # than it lowers, and the common part is taken out again
    solved = solved - jnp.mean(solved)
    return solved, loads(scores, bias, top_k), loads(scores, solved, top_k)


class Balancer:
    """The jitted layer functions of one configuration's solve, built
    once and used for every seed (benchmarks/control.py reseeds)."""

    def __init__(self, arch, a: dict, place_rows=None):
        dot = DOTS["bf16"]
        self.arch, self.a = arch, a
        self.place_rows = place_rows or jnp.asarray

        def balanced_layer(p, x):
            """The layer's output with its bias solved, the solved bias,
            and the busiest expert's load over the mean before and
            after."""
            (key,) = bias_paths(p)
            stream, u = arch.expert_input(a, p, x, dot)
            scores = arch.router_scores(a, p, u, DOTS["f32"])
            solved, before, after = solve_bias(
                scores.reshape(-1, scores.shape[-1]), p[key],
                a["moe_top_k"])
            out = stream + arch.experts(a, {**p, key: solved}, u, dot)
            return out, solved, [jnp.max(load) / jnp.mean(load)
                                 for load in (before, after)]

        self._embed = jax.jit(lambda p, tok: arch.embed(a, p, tok))
        self._layer = jax.jit(lambda p, x: arch.layer(a, p, x, dot))
        self._balanced_layer = jax.jit(balanced_layer)

    def solve(self, params: dict, tokens) -> tuple:
        """`({path: solved bias}, [(layer, busiest before, after)])`, the
        busiest published expert's load over the mean. `params` are the
        seed's leaves with every bias as its rule makes it, `tokens` the
        rows of one batch."""
        arch = self.arch
        solved, report = {}, []
        x = self._embed({k: params[k] for k in arch.EMBED_KEYS},
                        self.place_rows(tokens))
        for name in arch.layer_names(self.a):
            p = layer_params(params, name)
            keys = bias_paths(p)
            if not keys:
                x = self._layer(p, x)
                continue
            x, solved[f"{name}/{keys[0]}"], busiest = \
                self._balanced_layer(p, x)
            report.append((name, busiest))
        return solved, [(name, *map(float, busiest))
                        for name, busiest in report]
