"""models/moe.ExpertLayer where a token has fewer places in the room
for its pairs than experts are held (16 routed, 8 held, 2 a token: two
places, the bound; 32 routed, 4 held, 4 a token: two places of a bound
of four): the routed experts' products over the pairs of token and held
expert (`routed_over_pairs`), their layout, a token with more pairs than
places, the step whose pairs pass the room, the checkpoint names through
the `cond`. The rule that sizes the room and the cases both forms share
are in test_expert_layer.py, whose helpers these use."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_template_tpu.models import moe
from pytorch_distributed_template_tpu.models.moe import (
    every_expert_over_every_token, experts_over_pairs, routed_over_pairs,
)
from pytorch_distributed_template_tpu.ops import grouped

from test_expert_layer import (
    D, F, LATENT, gated, held_experts_weight_on_the_output, init, layer,
    plain_gated, silu,
)




def pairs_layer(**kw):
    return gated(**{**dict(held=(4, 8), shared_d_ff=0), **kw})


def value_and_gradients(module, params, x):
    """The layer's output, its counters, and the gradient of every leaf
    and of the input, jitted as one program."""
    def loss(p, x):
        out, sown = module.apply({"params": p}, x, mutable=["counters"])
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), (out, sown)
    (_, (out, sown)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(params, x)
    return out, sown["counters"], grads


def the_dense_body(monkeypatch):
    """The same layer with every held expert over every token in the
    pairs' place: the same routing, weights and laying."""
    monkeypatch.setattr(
        moe, "routed_over_pairs",
        lambda x, weight, hit, counts, mats, room:
        every_expert_over_every_token(x, weight, *mats))


def the_layers_own_dense_body(monkeypatch):
    """The same layer with `held_experts` or `held_gated_experts` in the
    pairs' place, as a layer with a place for every held expert runs
    them: in float32 the same laying too."""
    monkeypatch.setattr(
        moe, "routed_over_pairs",
        lambda x, weight, hit, counts, mats, room:
        (moe.held_gated_experts if len(mats) == 3 else moe.held_experts)(
            x, weight, *mats))


def one_by_one(module, params, x):
    """(The layer without its shared expert in `jax.numpy`, float32,
    expert by expert, so that it has gradients; which pairs of token and
    held expert its routing makes, a mask as the layer's is.)"""
    p, xf = params, x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    lo, n_held = module.held
    scores = jax.nn.sigmoid(jnp.matmul(xf, p["router"], precision="highest"))
    choice = jax.lax.stop_gradient(scores + p["selection_bias"])
    took = choice >= jax.lax.top_k(choice, module.top_k)[0][:, -1:]
    weight = module.scale * jnp.where(took, scores, 0) / jnp.sum(
        jnp.where(took, scores, 0), axis=1, keepdims=True)
    lat = xf @ p["latent_down"]["kernel"] if module.latent else xf
    out = 0
    for e in range(n_held):
        h = (jax.nn.silu(lat @ p["experts_gate"][e]) * (lat @ p["experts_up"][e])
             if module.gated else
             jnp.square(jax.nn.relu(lat @ p["experts_up"][e])))
        out = out + weight[:, lo + e, None] * (h @ p["experts_down"][e])
    if module.latent:
        out = out @ p["latent_up"]["kernel"]
    return out.reshape(x.shape), took[:, lo:lo + n_held]


def gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_over_the_pairs_the_layer_and_every_gradient_are_the_dense_ones(
        dtype, monkeypatch):
    """Output and the gradient of the router, `gate`, `up`, `down` and
    the input: the pairs' form against the dense body with the same
    routing, and against the float32 sum expert by expert. In float32
    the forms differ by the order of their sums; in bfloat16 each lies as
    near the float32 sum as the other."""
    module = pairs_layer(dtype=dtype)
    x = jax.random.normal(jax.random.key(2), (3, 20, D))
    params = init(module, x)
    with jax.default_matmul_precision("highest"):
        out, counters, grads = value_and_gradients(module, params, x)
        the_dense_body(monkeypatch)
        dense_out, dense_counters, dense_grads = value_and_gradients(
            module, params, x)
        want = jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(jnp.sin(one_by_one(module, p, x)[0])),
            argnums=(0, 1)))(params, x)[1]
        want_out = one_by_one(module, params, x)[0]
    assert float(counters["moe_rows_run"]) == float(
        counters["moe_pairs_here"]) <= 60 * 2
    jax.tree.map(np.testing.assert_array_equal, counters, dense_counters)
    assert not np.any(np.asarray(grads[0]["selection_bias"]))

    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    assert gap(out, dense_out) < tol and gap(out, want_out) < tol
    for name in ("router", "experts_gate", "experts_up", "experts_down"):
        assert gap(grads[0][name], dense_grads[0][name]) < tol, name
        near, dense_near = (gap(g[0][name], want[0][name])
                            for g in (grads, dense_grads))
        assert near < tol and near < 1.5 * dense_near + 1e-6, name
    assert gap(grads[1], dense_grads[1]) < tol
    assert gap(grads[1], want[1]) < tol


def routed(kind, module, params, x):
    """Parameters and input under which the layer's 60 tokens route as
    `kind` says, four held of 32 and four a token, two places a token in
    a room of 120: "as-seeded" some 30 pairs; "a-long-tail" the first six
    tokens on three held experts each, one more than their places;
    "past-the-room" every token on all four held, 240 pairs."""
    if kind == "a-long-tail":
        # a router that reads the first feature alone, for experts 4-6
        x = x.at[..., 0].set(0.0).at[0, :6, 0].set(1.0)
        params = dict(params, router=params["router"].at[0].set(0.0)
                      .at[0, 4:7].set(50.0))
    if kind == "past-the-room":
        params = dict(params, selection_bias=jnp.zeros(32).at[4:8].set(10.0))
    return params, x


@pytest.mark.parametrize("kind", ["as-seeded", "a-long-tail",
                                  "past-the-room"])
@pytest.mark.parametrize("make,latent", [
    (gated, 0), (gated, LATENT), (layer, 0), (layer, LATENT)],
    ids=["gated", "gated-latent", "relu-squared", "relu-squared-latent"])
def test_in_a_room_under_its_bound_the_layer_is_the_dense_bodys(
        make, latent, kind, monkeypatch):
    """A room sized from uniform routing, under `tokens x min(top_k,
    held)`: output, the input's gradient, the router's and every
    matrix's are those of `held_experts` / `held_gated_experts` on the
    same routing and of the float32 sum expert by expert, for experts of
    two and three matrices, with and without a latent: as the seed
    routes; with tokens that hold more pairs than they have places (the
    sum's tail behind the places); and in the step whose pairs pass the
    room, which takes every held expert over every token (`moe_rows_run`
    reads tokens x held) and drops no token."""
    module = make(n_routed=32, held=(4, 4), top_k=4, latent=latent,
                  shared_d_ff=0)
    assert moe.token_places(4, 4, 32) == 2 < min(module.top_k, 4)
    x = jax.random.normal(jax.random.key(12), (3, 20, D))
    params, x = routed(kind, module, init(module, x, bias=0.01), x)
    with jax.default_matmul_precision("highest"):
        out, counters, grads = value_and_gradients(module, params, x)
        the_layers_own_dense_body(monkeypatch)
        dense_out, dense_counters, dense_grads = value_and_gradients(
            module, params, x)
        want_grads = jax.jit(jax.grad(
            lambda p, x: jnp.sum(jnp.sin(one_by_one(module, p, x)[0])),
            argnums=(0, 1)))(params, x)
        want_out, hit = one_by_one(module, params, x)
    pairs, busiest = int(hit.sum()), int(hit.sum(axis=1).max())
    assert float(counters["moe_pairs_here"]) == pairs
    if kind == "past-the-room":
        assert pairs == 240 > 120
        assert float(counters["moe_rows_run"]) == 60 * 4
        assert float(counters["moe_tokens_unserved"]) == 0
    else:
        assert float(counters["moe_rows_run"]) == pairs <= 120
        assert busiest > 2 or kind == "as-seeded"
    jax.tree.map(np.testing.assert_array_equal, counters, dense_counters)
    assert gap(out, dense_out) < 2e-6 and gap(out, want_out) < 2e-6
    leaves = ["router", "experts_up", "experts_down"] + (
        ["experts_gate"] if module.gated else []) + (
        ["latent_down", "latent_up"] if latent else [])
    for name in leaves:
        got, dense, want = (jax.tree.leaves(g[0][name])[0] for g in (
            grads, dense_grads, want_grads))
        assert gap(got, dense) < 2e-6 and gap(got, want) < 2e-6, name
    assert gap(grads[1], dense_grads[1]) < 2e-6
    assert gap(grads[1], want_grads[1]) < 2e-6


def test_a_token_with_no_held_expert_gets_nothing_and_costs_no_row():
    """Biases that send every token to experts held elsewhere but the
    first ten tokens: twenty pairs, fifty tokens unserved, whose rows of
    the output are zeros and whose input gets no gradient."""
    module = pairs_layer()
    x = jax.random.normal(jax.random.key(3), (3, 20, D))
    params = init(module, x)
    # a router that reads the first feature alone, for experts 4 and 5:
    # the ten tokens that have it score them 1.0, every other token scores
    # every expert 0.5, and a bias of 0.3 sends those to experts 0 and 1
    x = x.at[..., 0].set(0.0).at[0, :10, 0].set(1.0)
    params = dict(
        params, router=jnp.zeros((D, 16)).at[0, 4:6].set(50.0),
        selection_bias=jnp.zeros(16).at[jnp.array([0, 1])].set(0.3))
    with jax.default_matmul_precision("highest"):
        out, counters, grads = value_and_gradients(module, params, x)
    assert float(counters["moe_pairs_here"]) == 20
    assert float(counters["moe_tokens_unserved"]) == 50
    assert float(counters["moe_rows_run"]) == 20
    out = np.asarray(out).reshape(60, D)
    assert np.all(out[10:] == 0) and np.all(np.abs(out[:10]).sum(axis=1) > 0)
    want = plain_gated(params, np.asarray(x).reshape(-1, D), 4, 8, 2, 1.0)
    np.testing.assert_allclose(out, want, rtol=0,
                               atol=2e-5 * np.abs(want).max())
    dx = np.asarray(grads[1]).reshape(60, D)
    assert np.all(dx[10:] == 0) and np.all(np.abs(dx[:10]).sum(axis=1) > 0)


@pytest.mark.parametrize("make", [gated, layer], ids=["gated",
                                                      "relu-squared"])
def test_tied_experts_pass_the_room_and_no_token_is_dropped(make,
                                                            monkeypatch):
    """A router of zeros ties all sixteen experts at every token's bar:
    each token takes all eight held, 480 pairs where the room is 120.
    The step takes every held expert over every token (the counter says
    so: 60 x 8 rows), output and every gradient are the dense body's, and
    every token gets all eight experts' parts."""
    module = make(held=(4, 8), shared_d_ff=0, latent=0, scale=1.0,
                  selection_bias=False)
    x = jax.random.normal(jax.random.key(4), (3, 20, D))
    params = init(module, x)
    params = dict(params, router=jnp.zeros_like(params["router"]))
    with jax.default_matmul_precision("highest"):
        out, counters, grads = value_and_gradients(module, params, x)
        the_dense_body(monkeypatch)
        dense = value_and_gradients(module, params, x)
    assert float(counters["moe_pairs_here"]) == 480
    assert float(counters["moe_rows_run"]) == 480
    assert float(counters["moe_tokens_unserved"]) == 0
    np.testing.assert_allclose(out, dense[0], rtol=0, atol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=0, atol=2e-6 * max(float(jnp.abs(b).max()), 1e-30)),
        grads, dense[2])
    if module.gated:
        p = jax.tree.map(np.asarray, params)
        xf = np.asarray(x).reshape(-1, D)
        want = sum((silu(xf @ p["experts_gate"][e]) * (xf @ p["experts_up"][e]))
                   @ p["experts_down"][e] for e in range(8)) / 16
        np.testing.assert_allclose(np.asarray(out).reshape(-1, D), want,
                                   rtol=0, atol=2e-5 * np.abs(want).max())


def a_few_busy_tokens(s, e, every):
    """`hit [s, e]`: every `every`-th token on one expert, token 5 on all
    `e` and token 11 on four."""
    hit = np.zeros((s, e), bool)
    of = np.arange(0, s, every)
    hit[of, of % e] = True
    hit[5] = True                                   # six pairs on one token
    hit[11, :4] = True
    return jnp.asarray(hit)


@pytest.mark.parametrize("places,every,pairs", [(2, 1, 33), (1, 3, 18)],
                         ids=["two-places", "one-place"])
def test_a_token_with_more_pairs_than_places_is_summed_whole(places, every,
                                                             pairs):
    """Pairs that fit the room while one token holds six of them and one
    four, in rooms of two places a token and of one: the pairs past the
    places gathered at once are added behind them, forward and
    backward."""
    k = jax.random.split(jax.random.key(5), 6)
    s, e = 24, 6
    hit = a_few_busy_tokens(s, e, every)
    weight = jnp.where(hit, jax.random.uniform(k[0], (s, e), minval=0.2), 0.0)
    counts = jnp.sum(hit, axis=0).astype(jnp.int32)
    assert int(counts.sum()) == pairs <= s * places
    assert int(jnp.max(jnp.sum(hit, axis=1))) == 6 > places
    x = jax.random.normal(k[1], (s, LATENT))
    mats = (0.3 * jax.random.normal(k[2], (e, LATENT, F)),
            0.3 * jax.random.normal(k[3], (e, LATENT, F)),
            0.3 * jax.random.normal(k[4], (e, F, LATENT)))

    def through(f):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=range(5)))

    with jax.default_matmul_precision("highest"):
        got = through(lambda x, w, *m: routed_over_pairs(
            x, w, hit, counts, m, s * places))(x, weight, *mats)
        want = through(every_expert_over_every_token)(x, weight, *mats)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for name, g, w in zip(("x", "weight", "gate", "up", "down"), *(
            a[1] for a in (got, want))):
        if name == "weight":        # no gradient where no pair is
            w = jnp.where(hit, w, 0.0)
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()),
                                   err_msg=name)


@pytest.mark.parametrize("a_turn", [2, 5, 1024],
                         ids=["two-a-turn", "five-a-turn", "all-in-one-turn"])
@pytest.mark.parametrize("places,every", [(2, 1), (1, 3), (1, 24)],
                         ids=["two-places", "one-place", "one-place-sparse"])
def test_the_sum_of_a_tokens_rows_by_hand(places, every, a_turn,
                                          monkeypatch):
    """`tokens_of_rows` against a loop over the pairs: whole numbers, so
    any order of the float32 sums is exact. The pairs past the tokens'
    places (8, 7 and 7 here) are added `PAST_PLACES` a turn: in one turn,
    in turns that end inside a token's pairs, and in no turn at all
    where no token has more pairs than places."""
    monkeypatch.setattr(moe, "PAST_PLACES", a_turn)
    s, e = 24, 6
    hit = a_few_busy_tokens(s, e, every)
    counts = jnp.sum(hit, axis=0).astype(jnp.int32)
    lay, _ = moe.lay_pairs(jnp.where(hit, 1.0, 0.0), hit, counts, s * places)
    y = (jnp.arange(s * places * 3.0).reshape(s * places, 3) % 17) + 1
    want = np.zeros((s, 3), np.float32)
    for t, ex in zip(*np.nonzero(np.asarray(hit))):
        want[t] += np.asarray(y)[int(lay.pos[t, ex])]
    np.testing.assert_array_equal(jax.jit(moe.tokens_of_rows)(y, lay), want)
    # and with nobody past their places
    few = hit.at[5].set(False).at[11].set(False).at[5, 0].set(True)
    lay, _ = moe.lay_pairs(jnp.where(few, 1.0, 0.0), few, jnp.sum(
        few, axis=0).astype(jnp.int32), s * places)
    want = np.zeros((s, 3), np.float32)
    for t, ex in zip(*np.nonzero(np.asarray(few))):
        want[t] += np.asarray(y)[int(lay.pos[t, ex])]
    np.testing.assert_array_equal(jax.jit(moe.tokens_of_rows)(y, lay), want)


def test_the_pairs_layout_by_hand():
    hit = jnp.asarray([[1, 0, 1], [0, 0, 1], [1, 0, 0], [1, 0, 1]], bool)
    weight = jnp.where(hit, jnp.arange(12.0).reshape(4, 3) + 1, 0.0)
    counts = jnp.sum(hit, axis=0).astype(jnp.int32)
    lay, of_row = moe.lay_pairs(weight, hit, counts, 8)
    # expert 0 holds tokens 0, 2, 3; expert 1 none; expert 2 tokens 0, 1, 3
    np.testing.assert_array_equal(lay.token[:6], [0, 2, 3, 0, 1, 3])
    np.testing.assert_array_equal(lay.live, [1, 1, 1, 1, 1, 1, 0, 0])
    np.testing.assert_array_equal(of_row, [1, 7, 10, 3, 6, 12, 0, 0])
    np.testing.assert_array_equal(lay.taken, [2, 1, 1, 2])
    np.testing.assert_array_equal(
        np.where(hit, lay.pos, -1), [[0, -1, 3], [-1, -1, 4], [1, -1, -1],
                                     [2, -1, 5]])
    np.testing.assert_array_equal(
        lay.order, [[0, -1, 1], [-1, -1, 0], [0, -1, -1], [0, -1, 1]])
    y = jnp.arange(16.0).reshape(8, 2)
    np.testing.assert_array_equal(
        moe.tokens_of_rows(y, lay),
        [y[0] + y[3], y[4], y[1], y[2] + y[5]])
    x = jnp.arange(8.0).reshape(4, 2)
    np.testing.assert_array_equal(moe.rows_of_tokens(x, lay)[:6],
                                  x[jnp.array([0, 2, 3, 0, 1, 3])])
    back = jax.grad(lambda w: jnp.sum(moe.lay_pairs(w, hit, counts, 8)[1]
                                      * jnp.arange(1.0, 9.0)))(weight)
    np.testing.assert_array_equal(back, [[1, 0, 4], [0, 0, 5], [2, 0, 0],
                                         [3, 0, 6]])


@pytest.mark.parametrize("names,again", [
    (("moe_experts_gate", "moe_experts_up"), 0), (("moe_experts_gate",), 1),
    ((), 2)], ids=["both-kept", "gate-kept", "nothing-kept"])
def test_over_the_pairs_kept_first_products_are_not_run_again(names, again):
    """The pairs' first products carry the dense form's checkpoint names
    through the `cond`: under `jax.checkpoint` the backward holds a
    grouped product of `[room, D]` rows by `[8, D, F]` matrices for each
    one not kept, beside the forward's two and the backward's own one of
    those shapes (the cotangent's rows by `down` transposed), and the
    same gradients whatever is kept."""
    module = pairs_layer()
    x = jax.random.normal(jax.random.key(6), (3, 20, D))
    params = init(module, x)

    def loss(policy):
        def f(p, x):
            out = jax.checkpoint(
                lambda p, x: module.apply({"params": p}, x),
                policy=policy)(p, x)
            return jnp.sum(jnp.sin(out))
        return jax.value_and_grad(f, argnums=(0, 1))

    def firsts(jaxpr):
        """`ragged_dot`s over `[room, D]` rows by `[8, D, F]` matrices in
        a jaxpr and all it calls, the untaken branch's aside."""
        found = 0
        for eqn in jaxpr.eqns:
            shapes = [getattr(v.aval, "shape", None) for v in eqn.invars]
            if (eqn.primitive.name.startswith("ragged_dot")
                    and shapes[:2] == [(120, D), (8, D, F)]):
                found += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += firsts(sub)
        return found

    policies = jax.checkpoint_policies
    keeping = loss(policies.save_only_these_names(*names) if names
                   else policies.nothing_saveable)
    assert firsts(jax.make_jaxpr(keeping)(params, x).jaxpr) == 3 + again
    got = jax.jit(keeping)(params, x)
    want = jax.jit(loss(policies.everything_saveable))(params, x)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_over_the_pairs_bfloat16_operands_keep_the_precision(seed):
    """`test_bfloat16_operands_keep_the_precision_the_layer_had`'s bound
    on the pairs' form: bfloat16 operands, float32 sums, the float32
    weight laid on the activation and the product rounded once. Output
    and all four gradients lie no further from the float32 sum expert by
    expert than the dense form with the weight on the `[E, S, D]` output
    did, within 3%, the sum rounded to bfloat16 as the layer rounds it
    (the cotangent that comes back is then one the products can take as
    it is)."""
    k = jax.random.split(jax.random.key(seed), 5)
    s, e, d, f = 512, 4, 64, 96
    x = jax.random.normal(k[0], (s, d)).astype(jnp.bfloat16)
    hit = jax.random.bernoulli(k[1], 0.25, (s, e))
    weight = jnp.where(hit, jax.random.uniform(k[2], (s, e), minval=0.2), 0.0)
    counts = jnp.sum(hit, axis=0).astype(jnp.int32)
    up = jax.random.normal(k[3], (e, d, f)) / np.sqrt(d)
    down = jax.random.normal(k[4], (e, f, d)) / np.sqrt(f)

    def one_by_one(x, w, up, down):
        x = x.astype(jnp.float32)
        return sum(w[:, i:i + 1] * (jnp.maximum(x @ up[i], 0) ** 2 @ down[i])
                   for i in range(e))

    def over_pairs(x, w, up, down):
        return experts_over_pairs(x, w, hit, counts, up.astype(x.dtype),
                                  down.astype(x.dtype), room=2 * s)

    def all_of(fn, as_the_layer=True):
        def f(x, w, up, down):
            out = fn(x, w, up, down)
            if as_the_layer:
                out = out.astype(x.dtype).astype(jnp.float32)
            return jnp.sum(jnp.sin(out)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            f, argnums=range(4), has_aux=True))(x, weight, up, down)
        # the pairs give the weight a gradient where a pair is; the layer
        # drops the rest behind its mask
        return (out, grads[0], jnp.where(hit, grads[1], 0.0), *grads[2:])

    with jax.default_matmul_precision("highest"):
        want = all_of(one_by_one, as_the_layer=False)

    def gaps(fn):
        return [float(jnp.linalg.norm((g - w).astype(jnp.float32))
                      / jnp.linalg.norm(w.astype(jnp.float32)))
                for g, w in zip(all_of(fn), want)]

    was = gaps(held_experts_weight_on_the_output)
    now = gaps(over_pairs)
    for name, a, b in zip(("output", "x", "weight", "up", "down"), now, was):
        assert 1e-3 < b < 1.1e-2 and a <= 1.03 * b, (name, a, b)


@pytest.mark.parametrize("sizes", [[100, 0, 156], [40, 30, 50]],
                         ids=["room-filled", "room-to-spare"])
def test_a_grouped_product_is_the_product_a_group(sizes):
    """ops/grouped.py by hand: the product, the rows' gradient and the
    matrices' gradient are each group's rows against that group's matrix,
    an empty group's matrix gets a gradient of zeros, and the rows behind
    the last group get zeros and give nothing."""
    k = jax.random.split(jax.random.key(7), 3)
    m, d, f = 256, 16, 24
    rows = jax.random.normal(k[0], (m, d))
    weights = jax.random.normal(k[1], (3, d, f)) / np.sqrt(d)
    cot = jax.random.normal(k[2], (m, f))
    ends = np.cumsum(sizes)

    def by_hand(rows, weights):
        out = jnp.zeros((m, f))
        for g, (lo, hi) in enumerate(zip(ends - np.asarray(sizes), ends)):
            out = out.at[lo:hi].set(rows[lo:hi] @ weights[g])
        return out

    def through(product):
        def loss(rows, weights):
            out = product(rows, weights)
            return jnp.sum(out * cot), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(rows, weights)
        return (out, *grads)

    with jax.default_matmul_precision("highest"):
        got = through(lambda r, w: grouped.grouped_matmul(
            r, w, jnp.asarray(sizes, jnp.int32)))
        want = through(by_hand)
    for name, g, w in zip(("product", "rows", "matrices"), got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5, err_msg=name)
    assert not np.any(np.asarray(got[0])[ends[-1]:])
    assert not np.any(np.asarray(got[1])[ends[-1]:])
