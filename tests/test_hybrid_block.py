"""models/hybrid.py: one block and one LM assembled from a family's data.
The six older registered factories build the parameter trees they built when
each family had a stack of its own (paths, shapes, dtypes and, at the
`Tiny*` sizes, values under one key: every checkpoint, `hf_import` and the
benchmark's `Weights.give` find leaves by path); a record no family uses
builds, trains a step and tells the checkpoint policy what its kinds
state; and an `ExpertLayer`'s names and widths are stated once, beside
it. The numbers were read from the parent commit (b84262e), where the
three stacks were models/nemotron_h.py, granite_hybrid.py, solar_open2.py."""
import json
import logging
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import pytorch_distributed_template_tpu.models  # noqa: F401
from pytorch_distributed_template_tpu.config.registry import MODELS
from pytorch_distributed_template_tpu.models import remat_policy as rp
from pytorch_distributed_template_tpu.models.hybrid import (
    Family, HybridLM,
)
from pytorch_distributed_template_tpu.models.moe import expert_block_sizes
from pytorch_distributed_template_tpu.observability import trace

REPO = Path(__file__).resolve().parent.parent

# -- the Tiny* trees, written out: {leaf under the layer: shape}, all float32
D = 64


def _mamba(d_in, g):
    return {
        "mixer/A_log": (4,), "mixer/D": (4,),
        "mixer/conv_bias": (d_in + 2 * g * 16,),
        "mixer/conv_kernel": (4, d_in + 2 * g * 16), "mixer/dt_bias": (4,),
        "mixer/in_proj/kernel": (D, 2 * d_in + 2 * g * 16 + 4),
        "mixer/norm_weight": (d_in,), "mixer/out_proj/kernel": (d_in, D)}


ATTENTION = {"mixer/q_proj/kernel": (D, 64), "mixer/k_proj/kernel": (D, 32),
             "mixer/v_proj/kernel": (D, 32), "mixer/o_proj/kernel": (64, D)}
KDA = {"mixer/A_log": (4,), "mixer/b_proj/kernel": (D, 4),
       "mixer/dt_bias": (64,), "mixer/f_a_proj/kernel": (D, 8),
       "mixer/f_b_proj/kernel": (8, 64), "mixer/g_a_proj/kernel": (D, 8),
       "mixer/g_b_proj/bias": (64,), "mixer/g_b_proj/kernel": (8, 64),
       "mixer/o_norm": (16,), "mixer/o_proj/kernel": (64, D),
       **{f"mixer/{n}_conv": (4, 64) for n in "qkv"},
       **{f"mixer/{n}_proj/kernel": (D, 64) for n in "qkv"}}
LATENT_EXPERTS = {
    "mixer/experts_down": (8, 48, 32), "mixer/experts_up": (8, 32, 48),
    "mixer/latent_down/kernel": (D, 32), "mixer/latent_up/kernel": (32, D),
    "mixer/router": (D, 8), "mixer/selection_bias": (8,),
    "mixer/shared_down/kernel": (96, D), "mixer/shared_up/kernel": (D, 96)}
GATED_EXPERTS = {
    "experts/experts_down": (8, 48, D), "experts/experts_gate": (8, D, 48),
    "experts/experts_up": (8, D, 48), "experts/router": (D, 8),
    "experts/selection_bias": (8,),
    "experts/shared/down_proj/kernel": (48, D),
    "experts/shared/gate_proj/kernel": (D, 48),
    "experts/shared/up_proj/kernel": (D, 48)}
MLP = {"mlp/down_proj/kernel": (96, D), "mlp/gate_proj/kernel": (D, 96),
       "mlp/up_proj/kernel": (D, 96)}
SHORT_CONV = {"mixer/conv_kernel": (3, D), "mixer/in_proj/kernel": (D, 3 * D),
              "mixer/out_proj/kernel": (D, D)}
QK_NORMS = {"mixer/q_layernorm/weight": (16,),
            "mixer/k_layernorm/weight": (16,)}
LFM2_NORMS = {"operator_norm/weight": (D,), "ffn_norm/weight": (D,)}
LFM2_EXPERTS = {k: v for k, v in GATED_EXPERTS.items()
                if "/shared/" not in k}
ONE_NORM = {"norm/weight": (D,)}
TWO_NORMS = {"input_layernorm/weight": (D,),
             "post_attention_layernorm/weight": (D,)}
EMBED = {"embed_tokens/embedding": (256, D)}
HEAD = {"lm_head/kernel": (D, 256)}


def _tree(layers, *outside):
    tree = {"norm/weight": (D,)}
    for table in outside:
        tree.update(table)
    for i, layer in enumerate(layers):
        tree.update({f"layers_{i}/{k}": v for k, v in layer.items()})
    return tree


# name -> (the tree, a few leaves' sums under `jax.random.key(1)`)
TINY = {
    "TinyNemotronH": (
        _tree([{**ONE_NORM, **LATENT_EXPERTS}, {**ONE_NORM, **_mamba(64, 2)},
               {**ONE_NORM, **ATTENTION}], EMBED, HEAD),
        {"embed_tokens/embedding": -4.3325700759887695,
         "layers_0/mixer/experts_up": -1.4490877389907837,
         "layers_0/mixer/router": -0.7688363790512085,
         "layers_1/mixer/dt_bias": -18.317707061767578,
         "layers_1/mixer/A_log": 3.040029525756836,
         "layers_2/mixer/q_proj/kernel": -0.42808231711387634,
         "lm_head/kernel": 1.392583966255188}),
    "TinyGraniteHybrid": (
        _tree([{**TWO_NORMS, **_mamba(64, 1), **MLP},
               {**TWO_NORMS, **ATTENTION, **MLP},
               {**TWO_NORMS, **_mamba(64, 1), **MLP}], EMBED),   # tied head
        {"embed_tokens/embedding": -4.3325700759887695,
         "layers_0/mixer/dt_bias": -25.145458221435547,
         "layers_0/mlp/gate_proj/kernel": -1.6468461751937866,
         "layers_1/mixer/v_proj/kernel": 1.4547951221466064,
         "layers_2/mixer/A_log": 7.665737152099609,
         "layers_2/mixer/in_proj/kernel": 0.552285373210907}),
    "TinySolarOpen2": (
        _tree([{**TWO_NORMS, **ATTENTION, "mixer/g_proj/kernel": (D, 64),
                **GATED_EXPERTS},
               {**TWO_NORMS, **KDA, **GATED_EXPERTS},
               {**TWO_NORMS, **KDA, **GATED_EXPERTS}], EMBED, HEAD),
        {"embed_tokens/embedding": -4.3325700759887695,
         "layers_0/mixer/g_proj/kernel": -1.7046539783477783,
         "layers_0/experts/experts_up": -10.493301391601562,
         "layers_1/mixer/dt_bias": -297.67327880859375,
         "layers_1/mixer/A_log": 8.963991165161133,
         "layers_2/experts/shared/gate_proj/kernel": -2.3518905639648438,
         "layers_2/mixer/k_conv": 0.02845807373523712,
         "lm_head/kernel": 1.392583966255188}),
    # PR 49's family: the leading layer takes the gated MLP, the others
    # experts without a shared one; tied head
    "TinyLfm2Moe": (
        _tree([{**LFM2_NORMS, **SHORT_CONV, **MLP},
               {**LFM2_NORMS, **ATTENTION, **QK_NORMS, **LFM2_EXPERTS},
               {**LFM2_NORMS, **SHORT_CONV, **LFM2_EXPERTS}], EMBED),
        {"embed_tokens/embedding": -4.3325700759887695,
         "layers_0/mixer/conv_kernel": -0.21451514959335327,
         "layers_0/mixer/in_proj/kernel": -0.16797837615013123,
         "layers_0/mlp/up_proj/kernel": -1.6237635612487793,
         "layers_1/mixer/q_proj/kernel": 1.2203103303909302,
         "layers_1/mixer/q_layernorm/weight": 16.0,
         "layers_1/experts/experts_gate": 4.088201522827148,
         "layers_2/experts/router": 0.15178877115249634,
         "layers_2/mixer/out_proj/kernel": -1.4241102933883667}),
}
# name -> (leaves, parameters at the factory's defaults; its cell's file,
# parameters and leaves at the cell's arguments)
FULL = {
    "NemotronH": (98, 16_023_116_160,
                  "nemotron3_super_l11", 700_865_520, 98),
    "GraniteHybrid": (128, 951_991_232,
                      "granite4_h_micro_l10", 772_160_448, 128),
    "SolarOpen2": (96, 22_333_740_864,
                   "solar_open2_l4", 840_875_672, 96),
    # the cell holds five of the forty layers: embedding, final norm, a
    # dense conv layer's 8, an attention expert layer's 13, 3 x 10
    "Lfm2Moe": (428, 23_843_661_440,
                "lfm2_24b_a2b_l5", 788_052_352, 53),
}


def _abstract(model):
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 16), jnp.int32))["params"]
    return {"/".join(k.key for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)}


@pytest.mark.parametrize("name", [*TINY, *FULL])
def test_the_six_factories_build_the_trees_they_built(name):
    if name in TINY:
        want, sums = TINY[name]
        model = MODELS.get(name)()
        got = _abstract(model)
        assert {k: v.shape for k, v in got.items()} == want
        assert {v.dtype for v in got.values()} == {jnp.dtype("float32")}
        tokens = jax.random.randint(jax.random.key(3), (2, 32), 0, 256)
        params = jax.jit(model.init)(jax.random.key(1), tokens)["params"]
        for path, total in sums.items():
            leaf = params
            for key in path.split("/"):
                leaf = leaf[key]
            np.testing.assert_allclose(float(jnp.sum(leaf)), total,
                                       rtol=1e-5, err_msg=path)
        return
    leaves, count, cell, cell_count, cell_leaves = FULL[name]
    arch = json.loads((REPO / "benchmarks" / "configs" / f"{cell}.json"
                       ).read_text())["experiment"]["arch"]
    assert arch["type"] == name
    for model, n, want_leaves in (
            (MODELS.get(name)(), count, leaves),
            (MODELS.get(name)(**arch["args"]), cell_count, cell_leaves)):
        got = _abstract(model)
        assert len(got) == want_leaves
        assert sum(int(np.prod(v.shape)) for v in got.values()) == n
        assert {v.dtype for v in got.values()} == {jnp.dtype("float32")}


@pytest.mark.parametrize("name", ["GraniteHybrid", "SolarOpen2", "Lfm2Moe"])
def test_a_field_the_family_does_not_have_is_refused(name):
    with pytest.raises(TypeError, match="unexpected keyword argument 'n'"):
        MODELS.get(name)(n=1)
    # `Lfm2Moe`: the rotation's base is the record's, no size of a call
    other = {"GraniteHybrid": "kda_chunk", "SolarOpen2": "ssm_chunk",
             "Lfm2Moe": "rope_base"}[name]
    with pytest.raises(TypeError, match=other):
        MODELS.get(name)(**{other: 16})


# -- a record no family uses -------------------------------------------------

def test_the_block_is_assembled_from_a_record(monkeypatch, caplog):
    """Every mixer that is not an expert layer, each followed by a gated
    MLP behind the norm names of a third family, a scaled residual sum and
    a tied head: no registered family is this. It initialises, takes a
    jitted forward and gradient with `remat` on, gives the loss and
    gradient of the same stack with nothing kept, and its `remat/policy`
    line names what its kinds state."""
    family = Family("Nobody's", ("K", "M", "*"), "input_layernorm", "mlp",
                    "post_attention_layernorm", residual_multiplier=0.5,
                    tied_head=True, out_gate=True,
                    step_counters=("kda_beta_mean",))
    model = HybridLM(
        family, vocab_size=256, pattern="KM*K", d_model=64, max_len=128,
        d_ff=96, n_head=4, n_kv_head=2, head_dim=16, ssm_n_head=4,
        ssm_head_dim=16, ssm_n_group=1, ssm_state=16, ssm_conv=4,
        ssm_chunk=16, kda_n_head=4, kda_head_dim=16, kda_conv=4,
        kda_chunk=16, kda_rank=8, remat=True)
    assert model.step_counters == ("kda_beta_mean",)
    kda, ssm, attn = model._block_kinds()
    assert (kda.count, ssm.count, attn.count) == (2, 1, 1)
    mlp = {"mlp_gate": 96, "mlp_up": 96}
    assert kda.widths == {"kda_in_proj": 192, "kda_out_proj": 64, **mlp}
    assert ssm.widths == {"ssm_in_proj": 2 * 64 + 4 + 2 * 16, **mlp}
    assert attn.widths == {"qkv_proj": 128, "attn_gate": 64,
                           "attn_proj": 64, **mlp}
    assert (attn.attn_heads, attn.head_dim) == (4, 16)

    trace._said.clear()
    trace.get_recorder().clear()
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 256)
    with caplog.at_level(logging.INFO):
        params = jax.jit(model.init)(jax.random.key(1), tokens)["params"]
    assert "lm_head" not in params
    assert set(params["layers_3"]) == {
        "input_layernorm", "mixer", "post_attention_layernorm", "mlp"}

    def loss(p):
        logits, _ = model.apply({"params": p}, tokens, train=True,
                                mutable=["counters"])
        return jnp.mean(jax.nn.logsumexp(logits, -1) - logits[..., 0])

    want = jax.jit(jax.value_and_grad(loss))(params)    # no step: nothing
    monkeypatch.setattr(rp, "device_capacity_bytes",
                        lambda mesh=None: 2 << 30)
    with caplog.at_level(logging.INFO), rp.step_holds(1 << 20):
        got = jax.jit(jax.value_and_grad(loss))(params)
    (said,) = [e["args"] for e in trace.get_recorder().snapshot()
               if e["name"] == "remat/policy"]
    assert said["blocks"] == 4
    assert said["names"] == ("attn_out,qkv_proj,attn_gate,attn_proj,"
                             "kda_in_proj,kda_out_proj,ssm_in_proj,"
                             "mlp_gate,mlp_up")
    assert ("model/pattern: KM*K (4 layers, each a mixer and a gated MLP of "
            "96); K: 4 delta-rule heads") in caplog.text
    assert "no rotation, gated output; multipliers: embedding 1, residual " \
        "0.5" in caplog.text
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)


# -- an expert layer's names, stated once, beside it --------------------------

@pytest.mark.parametrize("fields,want", [
    # nemotron3_super_l11's `E` mixer: 8 of 512 held, a 1024-wide latent
    (dict(d_model=4096, d_ff=2688, n_routed=512, top_k=22, held=(0, 8),
          latent=1024, shared_d_ff=5376, router="sigmoid",
          selection_bias=True, scale=5.0, gated=False, n_layers=5,
          dtype=jnp.bfloat16),
     # two places a token in the room for its pairs (`token_places`: four
     # times the 22 x 8 / 512 that uniform routing gives it), and their
     # layout: 9 bytes a place, 8 a held expert, 4 a token
     {"moe_router": 1024, "moe_pairs": 43, "moe_latent": 1024,
      "moe_experts_out": 1024, "moe_shared_up": 5376,
      "moe_experts_up": 2 * 2688}),
    # solar_open2_l4's experts: 8 of 320 held, gated, no latent: one place
    (dict(d_model=4096, d_ff=1280, n_routed=320, top_k=8, held=(0, 8),
          latent=0, shared_d_ff=1280, router="sigmoid", selection_bias=True,
          scale=1.0, gated=True, n_layers=4, dtype=jnp.bfloat16),
     {"moe_router": 640, "moe_pairs": 39, "mlp_gate": 1280, "mlp_up": 1280,
      "moe_experts_gate": 1280, "moe_experts_up": 1280}),
    # lfm2_24b_a2b_l5's: 16 of 64 held, 4 a token: four places, the bound
    (dict(d_model=2048, d_ff=1536, n_routed=64, top_k=4, held=(0, 16),
          selection_bias=True, gated=True, n_layers=4, dtype=jnp.bfloat16),
     {"moe_router": 128, "moe_pairs": 84, "moe_experts_gate": 6144,
      "moe_experts_up": 6144}),
    # a share of 2 of 8, 2 a token: a place for every expert held, no layout
    (dict(d_ff=48, n_routed=8, top_k=2, held=(0, 2), gated=True),
     {"moe_router": 8, "moe_experts_gate": 96, "moe_experts_up": 96}),
    # none said held: all are; float32: the router's logits count once
    (dict(d_ff=48, n_routed=8), {"moe_router": 8, "moe_experts_up": 384}),
], ids=["hybrid-E", "solar-experts", "lfm2-experts", "a-share-of-two",
        "bare"])
def test_an_expert_layers_names_and_widths(fields, want):
    assert expert_block_sizes(**fields) == want


# -- what PR 49's family asked of the attention --------------------------------

def test_attention_without_its_new_option_is_what_it_was():
    """`qk_norm` off (the default): `LlamaAttention` has the four leaves
    it had and gives the output it gave at the parent commit (e5a4fb7,
    the sums read there); on, it gains one weight `[head_dim]` for q and
    one for k, and with both at identity it norms each head before the
    rotation."""
    from pytorch_distributed_template_tpu.models.llama import (
        LlamaAttention, apply_rope, rope_tables,
    )

    x = jax.random.normal(jax.random.key(2), (2, 24, 48), jnp.float32)
    pos = jnp.arange(24, dtype=jnp.int32)
    fields = dict(d_model=48, n_head=4, n_kv_head=2, dtype=jnp.float32,
                  head_dim=8, rope_base=1e6)
    off = LlamaAttention(**fields)
    held = jax.jit(lambda k: off.init(k, x, pos, True))(jax.random.key(1))
    assert set(held["params"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    y = jax.jit(lambda v: off.apply(v, x, pos, True))(held)
    np.testing.assert_allclose(float(jnp.sum(y)), -1.4076359272003174,
                               rtol=1e-5)
    np.testing.assert_allclose(float(jnp.sum(jnp.abs(y))), 12.19357681274414,
                               rtol=1e-5)
    on = LlamaAttention(**fields, qk_norm=True, qk_norm_eps=1e-5)
    shapes = jax.eval_shape(lambda k: on.init(k, x, pos, True),
                            jax.random.key(1))["params"]
    assert set(shapes) == set(held["params"]) | {"q_layernorm", "k_layernorm"}
    assert shapes["q_layernorm"]["weight"].shape == (8,)
    assert shapes["k_layernorm"]["weight"].shape == (8,)
    weights = {**held["params"],
               "q_layernorm": {"weight": jnp.full((8,), 1.5)},
               "k_layernorm": {"weight": jnp.full((8,), 0.5)}}
    got = jax.jit(lambda v: on.apply({"params": v}, x, pos, True))(weights)

    def by_hand(p):
        def normed(z, w):
            return w * z * jax.lax.rsqrt(
                jnp.mean(z * z, axis=-1, keepdims=True) + 1e-5)

        q = normed((x @ p["q_proj"]["kernel"]).reshape(2, 24, 4, 8), 1.5)
        k = normed((x @ p["k_proj"]["kernel"]).reshape(2, 24, 2, 8), 0.5)
        v = (x @ p["v_proj"]["kernel"]).reshape(2, 24, 2, 8)
        cos, sin = rope_tables(pos, 8, 1e6)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        k, v = (jnp.repeat(m, 2, axis=2) for m in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 8 ** 0.5
        scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        return ctx.reshape(2, 24, 32) @ p["o_proj"]["kernel"]

    np.testing.assert_allclose(got, jax.jit(by_hand)(held["params"]),
                               rtol=2e-5, atol=2e-6)
