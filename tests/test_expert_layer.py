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
    ExpertLayer, held_experts, held_gated_experts,
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
    {"top_k": 5, "held": (9, 7)},
], ids=["share", "all-held", "no-latent", "no-shared", "no-bias", "softmax",
        "held-more-than-chosen"])
def test_layer_is_the_plain_sum_over_the_experts_held(kw):
    module = layer(**kw)
    x = jax.random.normal(jax.random.key(2), (3, 20, D))
    params = init(module, x)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(module.apply)({"params": params}, x)
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


@pytest.mark.parametrize("kw", [
    {}, {"held": (0, 0)}, {"shared_d_ff": 0}, {"top_k": 5, "held": (9, 7)},
], ids=["share", "all-held", "no-shared", "held-more-than-chosen"])
def test_gated_layer_is_the_plain_sum_over_the_experts_held(kw):
    module = gated(**kw)
    x = jax.random.normal(jax.random.key(2), (3, 20, D))
    params = init(module, x)
    assert params["experts_gate"].shape == params["experts_up"].shape
    assert ("shared" in params) == bool(module.shared_d_ff)
    assert "shared_up" not in params and "latent_down" not in params
    with jax.default_matmul_precision("highest"):
        got = jax.jit(module.apply)({"params": params}, x)
    lo, n_held = module.held[0], module.held[1] or module.n_routed
    want = plain_gated(params, np.asarray(x).reshape(-1, D), lo, n_held,
                       module.top_k, module.scale)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, D), want,
                               rtol=0, atol=2e-5 * np.abs(want).max())


@pytest.mark.parametrize("expert", [4, 5])
def test_gated_no_token_is_dropped_when_all_pick_one_expert(expert):
    module = gated(shared_d_ff=0)
    x = jax.random.normal(jax.random.key(3), (3, 20, D))
    params = skewed(module, x, expert)
    with jax.default_matmul_precision("highest"):
        got, sown = jax.jit(lambda p: module.apply(
            {"params": p}, x, mutable=["counters"]))(params)
    counters = sown["counters"]
    assert float(counters["moe_pairs_here"]) >= 60
    assert float(counters["moe_tokens_unserved"]) == 0
    want = plain_gated(params, np.asarray(x).reshape(-1, D), 4, 2, 2, 1.0)
    np.testing.assert_allclose(np.asarray(got).reshape(-1, D), want,
                               rtol=0, atol=2e-5 * np.abs(want).max())
    assert np.all(np.abs(want).sum(axis=1) > 0)


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


def _first_products(jaxpr, x_shape, w_shape):
    """The `sd,edf->esf` products in a jaxpr and all it calls: the
    `dot_general`s of a `[S, D]` by an `[E, D, F]` operand."""
    found = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and sorted(
                v.aval.shape for v in eqn.invars) == sorted(
                [x_shape, w_shape]):
            found += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _first_products(sub, x_shape, w_shape)
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
        return _first_products(
            jax.make_jaxpr(jax.grad(f, argnums=argnums))(*operands).jaxpr,
            x.shape, firsts[0].shape)

    assert products(keeping) == len(names)
    assert products(nothing) == 2 * len(names)
    # a name short, that product alone is run again
    if len(names) == 2:
        assert products(loss(policies.save_only_these_names(names[0]))) == 3


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
