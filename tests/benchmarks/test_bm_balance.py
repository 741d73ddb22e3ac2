"""benchmarks/balance.py on the CPU at the rehearsal sizes of the two
expert cells: after the solve every published expert's load on the
solve's batch is at the mean, the solve is a function of the seed, the
solved leaves reach the program's state and the reference's parameters
as the same arrays, a configuration without a selection bias is passed
over, and the reference's experts and the solver route by one function."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import balance, run
from benchmarks.data import batch_rows, make_tokens
from benchmarks.reference import common
from benchmarks.weights import Weights

EXPERT_CELLS = ("nemotron3_super_l11.seq8k", "solar_open2_l4.seq8k")
SEEDS = (2**31 + 5, 7, 2**31 + 99)
BF16, F32 = common.DOTS["bf16"], common.DOTS["f32"]


def rehearsal_of(cell_name):
    """(arch, sizes, rows of a batch, the cell's data block) at the
    rehearsal's sizes."""
    _, cell, config = run.load_cell(cell_name, rehearse=True)
    batch = run.experiment_of(cell, config)["train_loader"]["args"][
        "batch_size"]
    return (run.reference_module(config), config["sizes"], batch,
            cell["data"])


@functools.lru_cache(maxsize=None)
def balancer_of(cell_name):
    """One solver a cell for the whole file: its layer programs are
    traced and compiled once."""
    arch, a, _, _ = rehearsal_of(cell_name)
    return balance.Balancer(arch, a)


def seeded(cell_name, seed):
    arch, a, batch, d = rehearsal_of(cell_name)
    tokens = make_tokens(seed, d["rows"], d["seq_len"], a["vocab_size"],
                         d["skew"])
    weights = Weights(arch.param_shapes(a), arch.init_rules(a), seed)
    return arch, a, weights, batch_rows(tokens, 0, batch)


def loads_by_layer(arch, a, params, tokens) -> dict:
    """Every expert layer's loads over `tokens`, walked here layer by
    layer through the reference's own functions, not through the
    solver's jitted ones."""
    x = arch.embed(a, {k: params[k] for k in arch.EMBED_KEYS},
                   jnp.asarray(tokens))
    out = {}
    for name in arch.layer_names(a):
        p = common.layer_params(params, name)
        if balance.bias_paths(p):
            (key,) = balance.bias_paths(p)
            _, u = arch.expert_input(a, p, x, BF16)
            scores = arch.router_scores(a, p, u, F32)
            out[name] = np.asarray(balance.loads(
                scores.reshape(-1, a["moe_n_routed"]), p[key],
                a["moe_top_k"]))
        x = arch.layer(a, p, x, BF16)
    return out


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell_name", EXPERT_CELLS)
def test_the_solve_puts_every_published_expert_at_the_mean(cell_name, seed):
    """Behind a mixer every token's input is its own, and the rule's
    fixed point is exact to two tokens. `nemotron3_super_l11`'s first
    layer routes raw embeddings: every copy of a token id chooses alike,
    the loads move in lumps, and an eighth of the mean is what the
    rehearsal's 256 tokens over 512 ids leave."""
    arch, a, weights, tokens = seeded(cell_name, seed)
    solved, report = balancer_of(cell_name).solve(weights.make(), tokens)
    assert sorted(solved) == balance.bias_paths(weights.shapes)
    weights.give(solved)
    after = loads_by_layer(arch, a, weights.make(), tokens)
    mean = tokens.size * a["moe_top_k"] / a["moe_n_routed"]
    raw = {"layers_0"} if a["pattern"][0] == "E" else set()
    for name, load in after.items():
        assert load.shape == (a["moe_n_routed"],)
        assert load.sum() >= tokens.size * a["moe_top_k"]
        room = mean / 8 if name in raw else 2
        assert np.max(np.abs(load - mean)) <= room, (name, load)
    assert [name for name, _, _ in report] == list(after)
    for name, was, now in report:
        assert was > 1.15                           # the seed's routing
        assert now == pytest.approx(after[name].max() / mean, abs=0.07)
    for bias in solved.values():
        assert abs(float(jnp.mean(bias))) < 1e-6    # centred
        assert float(jnp.max(jnp.abs(bias))) > 1e-3


@pytest.mark.parametrize("cell_name", EXPERT_CELLS)
def test_the_solve_is_a_function_of_the_seed(cell_name):
    def solved_for(seed):
        arch, a, weights, tokens = seeded(cell_name, seed)
        return balancer_of(cell_name).solve(weights.make(), tokens)[0]

    one, again, other = (solved_for(s) for s in (SEEDS[0], SEEDS[0],
                                                 SEEDS[1]))
    for path in one:
        assert np.array_equal(one[path], again[path]), path     # bit-equal
        assert not np.array_equal(one[path], other[path]), path


@pytest.mark.parametrize("tokens,n,k,common_part", [
    (2048, 64, 6, 0.5), (1024, 40, 4, 2.0)])
def test_the_rule_balances_scores_with_a_common_component(tokens, n, k,
                                                          common_part):
    """What seeded weights do to a router: one vector in every token, so
    the same few experts for all of them."""
    k1, k2, k3 = jax.random.split(jax.random.key(tokens), 3)
    h = jax.random.normal(k1, (tokens, 256)) \
        + common_part * jax.random.normal(k2, (256,))
    h = h / jnp.sqrt(jnp.mean(h * h, axis=-1, keepdims=True))
    scores = jax.nn.sigmoid(h @ (0.02 * jax.random.normal(k3, (256, n))) * 4)
    bias, before, after = balance.solve_bias(scores, jnp.zeros(n), k)
    mean = tokens * k / n
    assert float(before.max()) > 2 * mean
    assert float(jnp.max(jnp.abs(after - mean))) <= 2
    assert np.array_equal(after, balance.loads(scores, bias, k))
    # the load is the program's mask: at or over the k-th largest
    took = (scores + bias) >= jnp.sort(scores + bias, axis=1)[:, -k][:, None]
    assert np.array_equal(after, jnp.sum(took, axis=0))


def test_given_leaves_take_the_place_of_their_rules():
    shapes = {"a/selection_bias": (4,), "a/router": (3, 4), "norm/weight": (3,)}
    rules = [(r"norm/weight$", "ones", 0.0),
             (r"/selection_bias$", "const", 0.0), (r"", "normal", 0.02)]
    plain = Weights(shapes, rules, 2**31 + 3)
    made = plain.make()
    assert not np.any(np.asarray(made["a/selection_bias"]))
    given = Weights(shapes, rules, 2**31 + 3)
    bias = jnp.asarray([0.25, -0.5, 0.125, 0.125], jnp.float32)
    given.give({"a/selection_bias": bias})
    assert np.array_equal(given.leaf("a/selection_bias"), bias)
    assert np.array_equal(given.make()["a/selection_bias"], bias)
    assert np.array_equal(
        jax.jit(given.all)(given.root(), given.given)["a/selection_bias"],
        bias)
    for path in ("a/router", "norm/weight"):    # the others as the seed has
        assert np.array_equal(given.make()[path], made[path])
        assert np.array_equal(given.leaf(path), made[path])
    with pytest.raises(ValueError, match="shape"):
        given.give({"a/selection_bias": jnp.zeros((5,))})
    with pytest.raises(ValueError, match="shape"):
        given.give({"b/selection_bias": jnp.zeros((4,))})
    given.give({})
    assert not np.any(np.asarray(given.leaf("a/selection_bias")))


def bench_of(cell_name, seed, tmp_path, said):
    _, cell, config = run.load_cell(cell_name, rehearse=True)
    return run.Bench(cell, config, seed, tmp_path, True, said.append,
                     said.append)


@pytest.mark.parametrize("cell_name", EXPERT_CELLS)
def test_the_solved_bias_reaches_both_sides_as_one_array(cell_name, tmp_path):
    """The trainer's state, `Weights.leaf`, `Weights.all` and what the
    reference is handed (`weights.make` on the reference's placement)
    hold the same numbers; so do both sides' `update_norms`, which are a
    leaf's change from `weights.leaf`."""
    said = []
    b = bench_of(cell_name, SEEDS[0], tmp_path, said)
    paths = balance.bias_paths(b.weights.shapes)
    assert paths and sorted(b.weights.given) == paths
    everywhere, _ = run.reference_placement(b.devices)
    handed = b.weights.make(everywhere)
    state = b._params()
    for path in paths:
        solved = b.weights.given[path]
        assert float(jnp.max(jnp.abs(solved))) > 1e-3
        assert np.array_equal(b.weights.leaf(path), solved)
        assert np.array_equal(handed[path], solved)
        assert np.array_equal(state[path], solved)
    other = next(p for p in b.weights.shapes if p.endswith("router"))
    assert np.array_equal(state[other], b.weights.leaf(other))
    (line,) = [s for s in said if s.startswith("balance: ")]
    assert all(name.rsplit("/", 2)[0] in line for name in paths)
    assert any("selection biases solved" in s for s in said)
    first = {p: np.asarray(v) for p, v in b.weights.given.items()}
    b.reseed(SEEDS[1])      # benchmarks/control.py: solved again
    assert sorted(b.weights.given) == paths
    for path in paths:
        assert not np.array_equal(b.weights.given[path], first[path])
        assert np.array_equal(b._params()[path], b.weights.given[path])
    assert len([s for s in said if s.startswith("balance: ")]) == 2


def test_a_configuration_without_a_selection_bias_is_passed_over(
        tmp_path, monkeypatch):
    """Not one line of its set-up changes: nothing is solved, nothing
    said, no program of the solve's is built, and every leaf in the
    trainer's state is what its rule makes from the seed."""
    def no_solve(*a, **kw):
        raise AssertionError("a cell without experts built the solver")

    monkeypatch.setattr(balance, "Balancer", no_solve)
    said = []
    b = bench_of("mistral7b_l2.seq4k", SEEDS[0], tmp_path, said)
    assert not balance.bias_paths(b.weights.shapes)
    assert b.weights.given == {}
    assert not [s for s in said if "balance" in s or "biases" in s]
    plain = Weights(b.weights.shapes, run.reference_module(
        b.config).init_rules(b.sizes), SEEDS[0]).make()
    for path, leaf in b._params().items():
        assert np.array_equal(leaf, plain[path]), path


@pytest.mark.parametrize("cell_name", EXPERT_CELLS)
def test_the_experts_and_the_solver_route_by_one_function(cell_name,
                                                          monkeypatch):
    arch, a, weights, tokens = seeded(cell_name, SEEDS[2])
    calls = []
    real = arch.router_scores

    def counted(a_, p, u, dot):
        calls.append(dot)
        return real(a_, p, u, dot)

    monkeypatch.setattr(arch, "router_scores", counted)
    params = weights.make()
    first = next(n for n in arch.layer_names(a)
                 if balance.bias_paths(common.layer_params(params, n)))
    x = jnp.zeros((1, 8, a["d_model"])) + 0.1
    arch.layer(a, common.layer_params(params, first), x, F32)
    assert calls == [F32]       # the reference's experts
    del calls[:]
    balance.Balancer(arch, a).solve(params, tokens)
    # a kind of expert layer, traced once: the solve's scores in float32,
    # then the layer's own forward with the solved bias
    assert calls and calls == [F32, BF16] * (len(calls) // 2)
