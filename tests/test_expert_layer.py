"""models/moe.ExpertLayer: experts held here, routed over all published,
no token dropped; its counters and its biases' rule through the training
step.

The layer, its initialiser and the training state's run jitted, one
program each, as a training run has them: operation by operation a case
was a compile a primitive. The token-by-token numpy references are as
they were."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import pytorch_distributed_template_tpu.models  # noqa: F401
from pytorch_distributed_template_tpu.config.registry import MODELS
from pytorch_distributed_template_tpu.engine.state import create_train_state
from pytorch_distributed_template_tpu.engine.losses import lm_cross_entropy
from pytorch_distributed_template_tpu.engine.steps import (
    COUNTER_PREFIX, finalize_metrics, make_train_step, selection_bias_step,
)
from pytorch_distributed_template_tpu.models.moe import (
    ExpertLayer, held_experts, held_gated_experts, token_places,
)

D, F, LATENT = 16, 24, 12


def train_state(model, tx, length):
    """`create_train_state` as one jitted program (what the init's forward
    pass computes is dead code there; run eagerly it was most of a case)."""
    return jax.jit(lambda: create_train_state(
        model, tx, np.zeros((1, length), np.int32), seed=0))()


def layer(**kw):
    return ExpertLayer(**{**dict(
        d_model=D, d_ff=F, n_routed=16, top_k=2, held=(4, 2), latent=LATENT,
        shared_d_ff=20, selection_bias=True, scale=2.5), **kw})


def plain(params, x, lo, n_held, top_k, scale, softmax=False):
    """Token by token, expert by expert, in numpy."""
    p = jax.tree.map(np.asarray, params)
    out = np.zeros_like(x)
    for i, tok in enumerate(x):
        logits = tok @ p["router"]
        s = (np.exp(logits) / np.exp(logits).sum() if softmax
             else 1 / (1 + np.exp(-logits)))
        bias = p.get("selection_bias", 0.0)
        chosen = np.argsort(-(s + bias), kind="stable")[:top_k]
        w = scale * s[chosen] / (s[chosen].sum() + 1e-20)
        lat = tok @ p["latent_down"]["kernel"] if "latent_down" in p else tok
        routed = np.zeros(lat.shape)
        for e, w_e in zip(chosen, w):
            if lo <= e < lo + n_held:
                h = np.maximum(lat @ p["experts_up"][e - lo], 0) ** 2
                routed += w_e * (h @ p["experts_down"][e - lo])
        if "latent_up" in p:
            routed = routed @ p["latent_up"]["kernel"]
        if "shared_up" in p:
            routed = routed + (np.maximum(tok @ p["shared_up"]["kernel"], 0)
                               ** 2) @ p["shared_down"]["kernel"]
        out[i] = routed
    return out


def init(module, x, seed=0, bias=0.3):
    params = jax.jit(module.init)(jax.random.key(seed), x)["params"]
    if "selection_bias" in params:     # a bias that changes the choice
        params = dict(params, selection_bias=bias * jax.random.normal(
            jax.random.key(seed + 1), params["selection_bias"].shape))
    return params


@pytest.mark.parametrize("kw", [
    {}, {"held": (0, 0)}, {"latent": 0}, {"shared_d_ff": 0},
    {"selection_bias": False}, {"router": "softmax", "scale": 1.0},
    {"top_k": 5, "held": (9, 7)}, {"held": (4, 8)},
    {"held": (4, 8), "latent": 0}, {"top_k": 5, "held": (9, 5)},
], ids=["share", "all-held", "no-latent", "no-shared", "no-bias", "softmax",
        "held-more-than-chosen", "share-pairs", "no-latent-pairs",
        "held-as-many-as-chosen"])
def test_layer_is_the_plain_sum_over_the_experts_held(kw):
    module = layer(**kw)
    x = jax.random.normal(jax.random.key(2), (3, 20, D))
    params = init(module, x)
    with jax.default_matmul_precision("highest"):
        got, sown = jax.jit(lambda p: module.apply(
            {"params": p}, x, mutable=["counters"]))(params)
    assert float(sown["counters"]["moe_rows_run"]) == rows_run(
        module, 60, sown["counters"])[1]
    lo, n_held = module.held[0], module.held[1] or module.n_routed
    want = plain(params, np.asarray(x).reshape(-1, D), lo, n_held,
                 module.top_k, module.scale, module.router == "softmax")
    np.testing.assert_allclose(np.asarray(got).reshape(-1, D), want,
                               rtol=0, atol=2e-5 * np.abs(want).max())


def skewed(module, x, expert):
    """Parameters under which every token's first choice is `expert`."""
    params = init(module, x)
    return dict(params, selection_bias=jnp.zeros(
        module.n_routed).at[expert].set(10.0))


@pytest.mark.parametrize("expert", [4, 5])
def test_no_token_is_dropped_when_every_token_picks_the_same_expert(expert):
    """60 tokens all choose one held expert (and one other each): 60
    pairs or more where uniform routing gives 15, every one of them at
    one expert. Every token gets that expert's part, in full."""
    module = layer(shared_d_ff=0)
    x = jax.random.normal(jax.random.key(3), (3, 20, D))
    params = skewed(module, x, expert)
    with jax.default_matmul_precision("highest"):
        got, sown = jax.jit(lambda p: module.apply(
            {"params": p}, x, mutable=["counters"]))(params)
    counters = sown["counters"]
    assert float(counters["moe_pairs_here"]) >= 60
    assert float(counters["moe_tokens_unserved"]) == 0
    assert float(counters["moe_load_max_over_mean"]) > 1.5
    want = plain(params, np.asarray(x).reshape(-1, D), 4, 2, 2, 2.5)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, D), want,
                               rtol=0, atol=2e-5 * np.abs(want).max())
    assert np.all(np.abs(want).sum(axis=1) > 0)


def test_held_experts_is_the_sum_expert_by_expert_and_has_its_gradient():
    k = jax.random.split(jax.random.key(4), 5)
    s, e = 40, 3
    x = jax.random.normal(k[0], (s, LATENT))
    hit = jax.random.bernoulli(k[1], 0.4, (s, e))
    weight = jnp.where(hit, jax.random.uniform(k[2], (s, e)), 0.0)
    up = 0.3 * jax.random.normal(k[3], (e, LATENT, F))
    down = 0.3 * jax.random.normal(k[4], (e, F, LATENT))

    def one_by_one(x, w, up, down):
        return sum(w[:, i:i + 1] * (jnp.maximum(x @ up[i], 0) ** 2 @ down[i])
                   for i in range(e))

    def through(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a)))

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(held_experts(x, weight, up, down),
                                   one_by_one(x, weight, up, down), atol=2e-5)
        got = jax.grad(through(held_experts), argnums=range(4))(
            x, weight, up, down)
        want = jax.grad(through(one_by_one), argnums=range(4))(
            x, weight, up, down)
    for name, g, w in zip(("x", "weight", "up", "down"), got, want):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()),
                                   err_msg=name)


def test_the_bias_steers_the_choice_and_gets_no_gradient():
    module = layer(shared_d_ff=0)
    x = jax.random.normal(jax.random.key(5), (2, 16, D))
    params = init(module, x)

    def loss(p):
        return jnp.sum(module.apply({"params": p}, x) ** 2)

    grads = jax.jit(jax.grad(loss))(params)
    assert not np.any(np.asarray(grads["selection_bias"]))
    assert np.any(np.asarray(grads["router"]))      # by the weights
    moved = dict(params, selection_bias=-params["selection_bias"])
    apply = jax.jit(module.apply)
    was = apply({"params": params}, x)
    assert float(jnp.abs(apply({"params": moved}, x) - was).max()) \
        > 0.1 * float(jnp.abs(was).max())


def test_a_share_outside_the_experts_is_refused():
    x = jnp.zeros((1, 4, D))
    with pytest.raises(ValueError, match="lies outside"):
        layer(held=(12, 8)).init(jax.random.key(0), x)
    with pytest.raises(ValueError, match="router="):
        layer(router="top").init(jax.random.key(0), x)


@pytest.mark.parametrize("accum", [1, 2])
def test_the_steps_metrics_carry_the_models_counters(accum):
    """Summed over the expert layers, times the valid count, so that the
    metrics' divide-by-count reads a step's mean; the trainer writes the
    same keys to the flight record at the log flush."""
    model = MODELS.get("TinyNemotronH")(pattern="EME*", moe_held=(2, 4))
    tokens = jax.random.randint(jax.random.key(6), (4, 32), 0, 256)
    tx = optax.adamw(1e-3)
    state = train_state(model, tx, 32)
    step = jax.jit(make_train_step(
        model, tx, lm_cross_entropy, [], input_key="tokens",
        target_key="tokens", grad_accum_steps=accum))
    _, metrics = step(state, {"tokens": tokens,
                              "mask": jnp.ones((4,), bool)})
    names = {k for k in metrics if k.startswith(COUNTER_PREFIX)}
    assert names == {f"{COUNTER_PREFIX}{n}_sum" for n in model.step_counters}
    read = finalize_metrics(jax.tree.map(float, metrics))
    # 128 tokens x 2 of 8 experts a token, 4 held, 2 layers: 128 pairs a
    # layer at uniform routing (each micro-batch counts its own pairs)
    pairs = read[f"{COUNTER_PREFIX}moe_pairs_here"]
    assert 0.5 * 256 < pairs * accum < 1.5 * 256
    assert read[f"{COUNTER_PREFIX}moe_load_max_over_mean"] >= 1.0
    assert read[f"{COUNTER_PREFIX}moe_tokens_unserved"] >= 0


def test_a_model_without_counters_gets_none():
    model = MODELS.get("TinyLlama")()
    tx = optax.adamw(1e-3)
    state = train_state(model, tx, 16)
    step = jax.jit(make_train_step(model, tx, lm_cross_entropy, [],
                                   input_key="tokens", target_key="tokens"))
    _, metrics = step(state, {"tokens": jnp.zeros((2, 16), jnp.int32),
                              "mask": jnp.ones((2,), bool)})
    assert set(metrics) == {"loss_sum", "count"}


def test_the_biases_rule_by_hand():
    """Up by the rate where an expert got fewer tokens than the mean,
    down where more, still where it got the mean; other leaves stay."""
    params = {"a": {"selection_bias": jnp.array([0.5, 0.0, -0.5, 0.1]),
                    "router": jnp.ones((2, 4))}}
    loads = {("a", "selection_bias"): jnp.array([10.0, 2.0, 4.0, 0.0])}
    new = selection_bias_step(params, loads, 0.25)
    np.testing.assert_allclose(new["a"]["selection_bias"],
                               [0.25, 0.25, -0.5, 0.35])
    np.testing.assert_array_equal(new["a"]["router"], params["a"]["router"])


def step_of(model, accum=1):
    tx = optax.adamw(1e-3)
    state = train_state(model, tx, 32)
    return state, jax.jit(make_train_step(
        model, tx, lm_cross_entropy, [], input_key="tokens",
        target_key="tokens", grad_accum_steps=accum))


@pytest.mark.parametrize("accum", [1, 2])
def test_a_step_moves_each_bias_against_its_experts_load(accum):
    """The load is the whole batch's, the micro-batches' summed, over all
    the published experts and not the held ones alone; the loads do not
    ride out with the metrics."""
    model = MODELS.get("TinyNemotronH")(pattern="EME", moe_held=(2, 4),
                                        selection_bias_rate=1e-3)
    tokens = jax.random.randint(jax.random.key(7), (4, 32), 0, 256)
    state, step = step_of(model, accum)
    new, metrics = step(state, {"tokens": tokens,
                                "mask": jnp.ones((4,), bool)})
    assert "router_load" not in metrics
    _, sown = jax.jit(lambda p: model.apply(
        {"params": p}, tokens, train=True, mutable=["router_load"]))(
        state.params)
    for name in ("layers_0", "layers_2"):
        load = np.asarray(sown["router_load"][name]["mixer"]["selection_bias"])
        assert load.shape == (8,) and load.sum() == 128 * 2
        np.testing.assert_allclose(
            new.params[name]["mixer"]["selection_bias"],
            1e-3 * np.sign(load.mean() - load))


def test_without_a_rate_the_biases_stay():
    model = MODELS.get("TinyNemotronH")(pattern="EM")
    state, step = step_of(model)
    new, _ = step(state, {"tokens": jnp.ones((2, 32), jnp.int32),
                          "mask": jnp.ones((2,), bool)})
    assert not np.any(new.params["layers_0"]["mixer"]["selection_bias"])
    assert np.any(new.params["layers_0"]["mixer"]["router"]
                  != state.params["layers_0"]["mixer"]["router"])


# -- gated experts: three matrices an expert, a SwiGLU shared expert ---------


def gated(**kw):
    return layer(**{**dict(gated=True, latent=0, scale=1.0), **kw})


def silu(z):
    return z / (1 + np.exp(-z))


def plain_gated(params, x, lo, n_held, top_k, scale):
    """Token by token, expert by expert, in numpy."""
    p = jax.tree.map(np.asarray, params)
    out = np.zeros_like(x)
    for i, tok in enumerate(x):
        s = 1 / (1 + np.exp(-(tok @ p["router"])))
        chosen = np.argsort(-(s + p.get("selection_bias", 0.0)),
                            kind="stable")[:top_k]
        w = scale * s[chosen] / (s[chosen].sum() + 1e-20)
        for e, w_e in zip(chosen, w):
            if lo <= e < lo + n_held:
                h = silu(tok @ p["experts_gate"][e - lo]) \
                    * (tok @ p["experts_up"][e - lo])
                out[i] += w_e * (h @ p["experts_down"][e - lo])
        if "shared" in p:
            sh = p["shared"]
            out[i] += (silu(tok @ sh["gate_proj"]["kernel"])
                       * (tok @ sh["up_proj"]["kernel"])
                       ) @ sh["down_proj"]["kernel"]
    return out


def rows_run(module, tokens, counters=None):
    """What `moe_rows_run` reads of a layer over `tokens` tokens, (the
    form it takes, the rows): every held expert over every token where a
    token has a place for every expert held (`token_places`), and in the
    step whose pairs pass the room; else the rows the grouped products'
    groups hold, which are the pairs that step (`counters`)."""
    n_held = module.held[1] or module.n_routed
    places = token_places(min(module.top_k, module.n_routed), n_held,
                          module.n_routed)
    if places == n_held:
        return "dense", tokens * n_held
    pairs = None if counters is None else float(counters["moe_pairs_here"])
    if pairs is not None and pairs > tokens * places:
        return "pairs", tokens * n_held
    return "pairs", pairs


# the layer's three cells, then the tiny stacks of the tests, of
# `configs/*_debug.json` and of the cells' rehearsal sizes (which keep the
# forms they had), then this file's layers and a few more: a token's, held,
# routed -> places, the bound
@pytest.mark.parametrize("top_k,held,routed,places,bound", [
    (8, 8, 320, 1, 8), (22, 8, 512, 2, 8), (4, 16, 64, 4, 4),
    (2, 4, 4, 2, 2), (2, 8, 8, 2, 2), (2, 2, 8, 2, 2), (4, 5, 12, 4, 4),
    (4, 4, 16, 4, 4), (4, 4, 12, 4, 4),
    (2, 2, 16, 1, 2), (2, 8, 16, 2, 2), (2, 16, 16, 2, 2),
    (16, 16, 16, 16, 16), (5, 7, 16, 5, 5), (5, 5, 16, 5, 5),
    (4, 4, 32, 2, 4), (1, 1, 1024, 1, 1), (8, 8, 64, 4, 8),
], ids=lambda v: str(v))
def test_a_tokens_places_are_uniform_routings_under_the_bound(
        top_k, held, routed, places, bound):
    """`token_places`: `ROOM_OVER_UNIFORM` (4) times what uniform
    routing gives a token of the experts held, rounded up, at least 1 and
    at most `min(top_k, held)`; the room is that a token, and the pairs'
    form where it is under `held`. solar_open2_l4.seq8k: 1 place, 8192
    rows for 1638 pairs; nemotron3_super_l11.seq8k: 2, 32768 for 5632;
    lfm2_24b_a2b_l5.seq8k: 4, its bound, 32768 for 8192."""
    from pytorch_distributed_template_tpu.models import moe

    assert moe.ROOM_OVER_UNIFORM == 4
    assert bound == min(top_k, held)
    got = token_places(top_k, held, routed)
    assert got == places == max(1, min(bound, -(-4 * top_k * held // routed)))
    # never under what uniform routing fills, and four times it or more
    # wherever the bound does not stop it
    assert 1 <= got <= bound
    assert got >= min(bound, 4 * top_k * held / routed)


@pytest.mark.parametrize("cell,tokens,top_k,held,routed,room,uniform", [
    ("solar_open2_l4.seq8k", 8192, 8, 8, 320, 8192, 1638.4),
    ("nemotron3_super_l11.seq8k", 16384, 22, 8, 512, 32768, 5632),
    ("lfm2_24b_a2b_l5.seq8k", 8192, 4, 16, 64, 32768, 8192),
])
def test_the_three_cells_rooms(cell, tokens, top_k, held, routed, room,
                               uniform, caplog):
    """What `moe/dispatch` says of each cell's layer, from shapes alone:
    nothing runs (`jax.eval_shape`)."""
    import logging

    from pytorch_distributed_template_tpu.observability import trace

    trace._said.clear()
    trace.get_recorder().clear()
    module = ExpertLayer(d_model=16, d_ff=8, n_routed=routed, top_k=top_k,
                         held=(0, held), gated=True)
    with caplog.at_level(logging.INFO):
        jax.eval_shape(lambda: module.init(jax.random.key(0),
                                           jnp.zeros((1, tokens, 16))))
    (said,) = [e["args"] for e in trace.get_recorder().snapshot()
               if e["name"] == "moe/dispatch"]
    assert said == dict(tokens=tokens, held=held, routed=routed, top_k=top_k,
                        expected=uniform, rows=room, experts="gated",
                        dense_rows=tokens * held), cell
    assert room < tokens * held and room / uniform >= 4
    assert (f"{room // tokens} place(s) a token, room for {room}, "
            f"{room / uniform:.1f} times that") in caplog.text


# each case as its shapes make it: two experts held of sixteen, of which a
# token takes two, have one place a token (a room under its bound) and
# eight held two, the pairs' form both; so have all sixteen held, or seven
# of which a token takes five; two held of four, as many held as a token
# takes of sixteen where uniform routing gives it more than a quarter of
# them, or all sixteen taken, are every held expert over every token
@pytest.mark.parametrize("kw,form", [
    ({}, "pairs"), ({"held": (4, 8)}, "pairs"),
    ({"held": (0, 0)}, "pairs"), ({"held": (0, 0), "top_k": 16}, "dense"),
    ({"shared_d_ff": 0}, "pairs"), ({"shared_d_ff": 0, "held": (4, 8)},
                                    "pairs"),
    ({"top_k": 5, "held": (9, 7)}, "pairs"),
    ({"top_k": 5, "held": (9, 5)}, "dense"),
    ({"n_routed": 4, "held": (2, 2)}, "dense"),
], ids=["share-one-place", "share-pairs", "all-held", "all-held-dense",
        "no-shared-one-place", "no-shared-pairs", "held-more-than-chosen",
        "held-as-many-as-chosen", "half-held-dense"])
def test_gated_layer_is_the_plain_sum_over_the_experts_held(kw, form):
    module = gated(**kw)
    x = jax.random.normal(jax.random.key(2), (3, 20, D))
    params = init(module, x)
    assert params["experts_gate"].shape == params["experts_up"].shape
    assert ("shared" in params) == bool(module.shared_d_ff)
    assert "shared_up" not in params and "latent_down" not in params
    with jax.default_matmul_precision("highest"):
        got, sown = jax.jit(lambda p: module.apply(
            {"params": p}, x, mutable=["counters"]))(params)
    assert rows_run(module, 60)[0] == form
    assert float(sown["counters"]["moe_rows_run"]) == rows_run(
        module, 60, sown["counters"])[1]
    lo, n_held = module.held[0], module.held[1] or module.n_routed
    want = plain_gated(params, np.asarray(x).reshape(-1, D), lo, n_held,
                       module.top_k, module.scale)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, D), want,
                               rtol=0, atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("held", [(4, 2), (4, 8)],
                         ids=["one-place", "two-places"])
@pytest.mark.parametrize("expert", [4, 5])
def test_gated_no_token_is_dropped_when_all_pick_one_expert(expert, held):
    """All 60 tokens on one held expert. Eight held, two places a token:
    that group is 60 rows of the pairs' 120 and the other seven share
    what second choices fall here. Two held, one place: the 60 pairs
    fill the room, and with one second choice that falls on the other
    held expert they pass it and the step takes both held experts over
    every token (`rows_run` reads which from the counters)."""
    module = gated(shared_d_ff=0, held=held)
    x = jax.random.normal(jax.random.key(3), (3, 20, D))
    params = skewed(module, x, expert)
    with jax.default_matmul_precision("highest"):
        got, sown = jax.jit(lambda p: module.apply(
            {"params": p}, x, mutable=["counters"]))(params)
    counters = sown["counters"]
    assert float(counters["moe_pairs_here"]) >= 60
    assert float(counters["moe_tokens_unserved"]) == 0
    assert float(counters["moe_rows_run"]) == rows_run(module, 60,
                                                        counters)[1]
    want = plain_gated(params, np.asarray(x).reshape(-1, D), *held, 2, 1.0)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, D), want,
                               rtol=0, atol=2e-5 * np.abs(want).max())
    assert np.all(np.abs(want).sum(axis=1) > 0)


@pytest.mark.parametrize("make", ["gated", "relu-squared-latent"])
@pytest.mark.parametrize("shares,top_k", [(4, 2), (4, 4), (8, 2), (8, 1)],
                         ids=["four-shares-at-the-bound", "four-shares-dense",
                              "eight-shares-one-place",
                              "eight-shares-one-a-token"])
def test_the_shares_of_held_add_up_to_the_uncut_layer(make, shares, top_k):
    """Sixteen experts cut into four shares of four or eight of two, as
    the chips of a host or a slice hold them: each chip's layer adds its
    own experts' part, in whatever form its shapes give it (two places a
    token under four held; every held expert over every token at four a
    token; one place under two held, a room under its bound that the
    seed's skewed routing passes in some shares and not in others), and
    the parts add up to the layer with all sixteen held."""
    make = (gated if make == "gated" else layer)
    kw = dict(top_k=top_k, shared_d_ff=0)
    x = jax.random.normal(jax.random.key(9), (3, 20, D))
    whole = make(held=(0, 0), **kw)
    params = init(whole, x, bias=0.1)
    n = 16 // shares

    def of(module, p):
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda p: module.apply(
                {"params": p}, x, mutable=["counters"]))(p)

    want, sown = of(whole, params)
    total, forms, pairs = 0, set(), 0
    for lo in range(0, 16, n):
        module = make(held=(lo, n), **kw)
        share = {k: (v[lo:lo + n] if k.startswith("experts_") else v)
                 for k, v in params.items()}
        got, counters = of(module, share)
        counters = counters["counters"]
        forms.add((rows_run(module, 60)[0],
                   float(counters["moe_rows_run"]) == 60 * n))
        assert float(counters["moe_rows_run"]) == rows_run(
            module, 60, counters)[1]
        pairs += float(counters["moe_pairs_here"])
        total = total + got
    assert pairs == float(sown["counters"]["moe_pairs_here"]) == 60 * top_k
    assert {f for f, _ in forms} == {
        "dense" if token_places(top_k, n, 16) == n else "pairs"}
    np.testing.assert_allclose(total, want, rtol=0,
                               atol=3e-6 * float(jnp.abs(want).max()))


def test_held_gated_experts_is_the_sum_expert_by_expert_with_its_gradient():
    k = jax.random.split(jax.random.key(4), 6)
    s, e = 40, 3
    x = jax.random.normal(k[0], (s, LATENT))
    hit = jax.random.bernoulli(k[1], 0.4, (s, e))
    weight = jnp.where(hit, jax.random.uniform(k[2], (s, e)), 0.0)
    gate, up = (0.3 * jax.random.normal(key, (e, LATENT, F))
                for key in k[3:5])
    down = 0.3 * jax.random.normal(k[5], (e, F, LATENT))

    def one_by_one(x, w, gate, up, down):
        return sum(w[:, i:i + 1] * ((jax.nn.silu(x @ gate[i]) * (x @ up[i]))
                                    @ down[i]) for i in range(e))

    def through(f):
        return lambda *a: jnp.sum(jnp.sin(f(*a)))

    with jax.default_matmul_precision("highest"):
        got = held_gated_experts(x, weight, gate, up, down)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(
            got, one_by_one(x, weight, gate, up, down), atol=2e-5)
        got = jax.grad(through(held_gated_experts), argnums=range(5))(
            x, weight, gate, up, down)
        want = jax.grad(through(one_by_one), argnums=range(5))(
            x, weight, gate, up, down)
    for name, g, w in zip(("x", "weight", "gate", "up", "down"), got, want):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()),
                                   err_msg=name)


def _products(jaxpr, *operand_shapes):
    """How many `dot_general`s a jaxpr and all it calls hold over
    operands of these two shapes: `[S, D]` by `[E, D, F]` finds the
    `sd,edf->esf` products, `[E, S, F]` by `[E, F, D]` the second ones."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and sorted(
                v.aval.shape for v in eqn.invars) == sorted(operand_shapes):
            found += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _products(sub, *operand_shapes)
    return found


@pytest.mark.parametrize("fn,names", [
    (held_gated_experts, ("moe_experts_gate", "moe_experts_up")),
    (held_experts, ("moe_experts_up",))], ids=["gated", "relu-squared"])
def test_kept_first_products_are_not_run_a_second_time(fn, names):
    """Under `jax.checkpoint` the policy can keep the experts' first
    products by their names: the same output and the same gradient of
    every operand as with nothing kept, and the backward holds the
    products once (the forward's) where nothing kept holds them twice."""
    k = jax.random.split(jax.random.key(7), 6)
    s, e = 40, 3
    x = jax.random.normal(k[0], (s, LATENT))
    hit = jax.random.bernoulli(k[1], 0.4, (s, e))
    weight = jnp.where(hit, jax.random.uniform(k[2], (s, e)), 0.0)
    firsts = [0.3 * jax.random.normal(key, (e, LATENT, F))
              for key in k[3:3 + len(names)]]
    down = 0.3 * jax.random.normal(k[5], (e, F, LATENT))
    operands = (x, weight, *firsts, down)

    def loss(policy):
        inner = jax.checkpoint(fn, policy=policy)
        return lambda *a: jnp.sum(jnp.sin(inner(*a)))

    policies = jax.checkpoint_policies
    keeping = loss(policies.save_only_these_names(*names))
    nothing = loss(policies.nothing_saveable)
    argnums = range(len(operands))
    got = jax.value_and_grad(keeping, argnums=argnums)(*operands)
    want = jax.value_and_grad(nothing, argnums=argnums)(*operands)
    np.testing.assert_array_equal(got[0], want[0])
    for g, w in zip(got[1], want[1]):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-7)

    def products(f):
        return _products(
            jax.make_jaxpr(jax.grad(f, argnums=argnums))(*operands).jaxpr,
            x.shape, firsts[0].shape)

    assert products(keeping) == len(names)
    assert products(nothing) == 2 * len(names)
    # a name short, that product alone is run again
    if len(names) == 2:
        assert products(loss(policies.save_only_these_names(names[0]))) == 3


def _made(jaxpr):
    """The shape of every value a jaxpr and all it calls make."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            yield tuple(getattr(v.aval, "shape", ()))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _made(sub)


@pytest.mark.parametrize("fn,firsts", [
    (held_gated_experts, 2), (held_experts, 1)],
    ids=["gated", "relu-squared"])
def test_no_result_a_held_expert_wide_is_made(fn, firsts):
    """The token's weight lies on the activation and the second product
    sums over experts and features at once: neither the forward nor the
    gradient of any operand makes an `[E, S, D]` value (268 MB a layer
    at the hybrid cell's shapes), which the weight's gradient read when
    the weight was laid on the output."""
    s, e = 40, 3
    operands = (jnp.ones((s, LATENT)), jnp.ones((s, e)),
                *[jnp.ones((e, LATENT, F))] * firsts, jnp.ones((e, F, LATENT)))

    def loss(*a):
        return jnp.sum(jnp.sin(fn(*a)))

    made = set(_made(jax.make_jaxpr(jax.value_and_grad(
        loss, argnums=range(len(operands))))(*operands).jaxpr))
    assert (e, s, F) in made and (s, LATENT) in made
    assert not made & {(e, s, LATENT), (s, e, LATENT), (e, LATENT, s)}


@pytest.mark.parametrize("latent", [LATENT, 0], ids=["latent", "no-latent"])
def test_the_layers_sum_has_a_name_where_a_projection_reads_it(latent):
    """`latent_up`'s weight gradient reads the routed experts' sum over
    the experts held, so the sum is named `moe_experts_out` there: kept
    under `jax.checkpoint`, with the first product not kept, the backward
    holds the second product once, the forward's, where nothing kept
    holds it twice; the output and every gradient are the same either
    way. A layer without `latent` adds the sum to the shared expert's
    output, no gradient reads it, it makes no such name, and its backward
    holds the second product once under any policy. (Two held of four,
    every held expert over every token: the form that has the product
    as one einsum.)"""
    module = layer(latent=latent, n_routed=4, held=(2, 2))
    x = jax.random.normal(jax.random.key(8), (2, 20, D))
    params = init(module, x)
    named = str(jax.make_jaxpr(module.apply)({"params": params}, x)).count(
        "name=moe_experts_out")
    assert named == (1 if latent else 0)
    width, s, e = latent or D, 2 * 20, module.held[1]

    def loss(policy):
        def f(p, x):
            out = jax.checkpoint(
                lambda p, x: module.apply({"params": p}, x),
                policy=policy)(p, x)
            return jnp.sum(jnp.sin(out))
        return jax.value_and_grad(f, argnums=(0, 1))

    def seconds(f):
        return _products(jax.make_jaxpr(f)(params, x).jaxpr,
                         (e, s, F), (e, F, width))

    policies = jax.checkpoint_policies
    keeping = loss(policies.save_only_these_names("moe_experts_out"))
    nothing = loss(policies.nothing_saveable)
    # without a latent nothing reads the sum, and jax drops the second
    # product from the recomputation whatever the policy
    assert seconds(nothing) == (2 if latent else 1)
    assert seconds(keeping) == 1
    got, want = jax.jit(keeping)(params, x), jax.jit(nothing)(params, x)
    np.testing.assert_array_equal(got[0], want[0])
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-7)


def held_experts_weight_on_the_output(x, weight, up, down):
    """`held_experts` as it was until PR 47: the `[E, S, D]` result of
    the second product made, the weight laid on it in float32."""
    act = jnp.square(jax.nn.relu(jnp.einsum("sd,edf->esf", x,
                                            up.astype(x.dtype))))
    out = jnp.einsum("esf,efd->esd", act, down.astype(x.dtype))
    return jnp.sum(weight.astype(jnp.float32).T[:, :, None]
                   * out.astype(jnp.float32), axis=0)


def held_experts_weight_rounded_first(x, weight, up, down):
    """The laying with the weight itself rounded to the operands' type
    before it meets the activation: what `held_experts` must not be."""
    act = jnp.square(jax.nn.relu(jnp.einsum("sd,edf->esf", x,
                                            up.astype(x.dtype))))
    act = act * weight.T[:, :, None].astype(x.dtype)
    return jnp.einsum("esf,efd->sd", act, down.astype(x.dtype),
                      preferred_element_type=jnp.float32)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfloat16_operands_keep_the_precision_the_layer_had(seed):
    """bfloat16 operands, float32 sums, a float32 weight, as the hybrid
    cell runs it: the output and all four gradients lie no further from
    the float32 sum expert by expert than those of the form with the
    weight on the `[E, S, D]` output did (within 3%: the two round in
    different places), because the weight is laid on in float32 and the
    product rounded once. Rounding the weight first adds a tenth to the
    output's rounding error and 40% and more to that of the weight's,
    the router's, gradient (twice it at the cell's widths)."""
    k = jax.random.split(jax.random.key(seed), 5)
    s, e, d, f = 512, 4, 64, 96
    x = jax.random.normal(k[0], (s, d)).astype(jnp.bfloat16)
    hit = jax.random.bernoulli(k[1], 0.25, (s, e))
    weight = jnp.where(hit, jax.random.uniform(k[2], (s, e), minval=0.2), 0.0)
    up = jax.random.normal(k[3], (e, d, f)) / np.sqrt(d)
    down = jax.random.normal(k[4], (e, f, d)) / np.sqrt(f)

    def one_by_one(x, w, up, down):
        x = x.astype(jnp.float32)
        return sum(w[:, i:i + 1] * (jnp.maximum(x @ up[i], 0) ** 2 @ down[i])
                   for i in range(e))

    def all_of(fn):
        def f(x, w, up, down):
            out = fn(x, w, up, down)
            return jnp.sum(jnp.sin(out)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            f, argnums=range(4), has_aux=True))(x, weight, up, down)
        return (out, *grads)

    with jax.default_matmul_precision("highest"):
        want = all_of(one_by_one)

    def gaps(fn):
        return [float(jnp.linalg.norm((g - w).astype(jnp.float32))
                      / jnp.linalg.norm(w.astype(jnp.float32)))
                for g, w in zip(all_of(fn), want)]

    names = ("output", "x", "weight", "up", "down")
    was = gaps(held_experts_weight_on_the_output)
    now = gaps(held_experts)
    for name, a, b in zip(names, now, was):
        assert 1e-3 < b < 1e-2 and a <= 1.03 * b, (name, a, b)
    rounded = gaps(held_experts_weight_rounded_first)
    assert rounded[2] > 1.3 * was[2] and rounded[0] > 1.08 * was[0]


def test_the_relu_squared_layer_has_no_third_matrix():
    x = jax.random.normal(jax.random.key(2), (1, 8, D))
    params = init(layer(), x)
    assert "experts_gate" not in params and "shared" not in params
    assert {"experts_up", "experts_down", "shared_up", "shared_down",
            "latent_down", "latent_up"} <= set(params)


@pytest.mark.parametrize("make,keep,devices", [
    (gated, False, 1), (gated, True, 1), (layer, False, 1), (layer, True, 1),
    (gated, True, 8),
], ids=["gated-nothing-kept", "gated-names-kept", "relu-squared-nothing-kept",
        "relu-squared-names-kept", "gated-names-kept-eight-devices"])
def test_a_gradient_as_stored_is_the_same_gradient(make, keep, devices,
                                                   monkeypatch):
    """`gradient_as_stored` pins the order a first product's weight
    gradient leaves in and no value: jitted under `jax.checkpoint`, in
    bfloat16 over float32 leaves as the cells run it, the layer's output
    and every gradient are bit for bit those of the same einsums without
    the rule, with nothing kept and with the products' names kept, and
    with the tokens' rows spread over the tests' eight devices."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pytorch_distributed_template_tpu.models import moe

    module = make(dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.key(5), (8, 12, D))
    params = init(module, x)
    if devices > 1:
        mesh = Mesh(np.asarray(jax.devices()[:devices]), ("data",))
        x = jax.device_put(x, NamedSharding(mesh, P("data")))
        params = jax.device_put(params, NamedSharding(mesh, P()))
    policies = jax.checkpoint_policies
    policy = (policies.save_only_these_names("moe_experts_gate",
                                             "moe_experts_up")
              if keep else policies.nothing_saveable)

    def value_and_gradients():
        def loss(p, x):
            out = jax.checkpoint(
                lambda p, x: module.apply({"params": p}, x),
                policy=policy)(p, x)
            return jnp.sum(jnp.sin(out.astype(jnp.float32))), out
        f = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
        pinned = str(jax.make_jaxpr(f)(params, x)).count("layout_constraint")
        return pinned, jax.jit(f)(params, x)

    pinned, got = value_and_gradients()
    assert pinned == (2 if module.gated else 1)       # gate and up; up
    monkeypatch.setattr(moe, "gradient_as_stored", lambda leaf: leaf)
    pinned, want = value_and_gradients()
    assert pinned == 0
    assert got[1][0]["experts_up"].dtype == jnp.float32
    jax.tree.map(np.testing.assert_array_equal, got, want)
