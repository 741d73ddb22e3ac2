"""The three flash kernels (ops/flash.py) on the CPU in interpret mode:
against the blockwise oracle ``_bwd_3d`` and a plain softmax at a shape
that has interior, diagonal, band-edge and padded tiles at once, in
bfloat16 (what the MXU gets in training) and float32; an interior tile's
body against the masked body, bit for bit; the tile predicates and the
``flash/tiles`` counts against a brute-force mask."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_distributed_template_tpu.observability import trace
from pytorch_distributed_template_tpu.observability.trace import get_recorder
from pytorch_distributed_template_tpu.ops import flash

BH, T, T_VALID = 2, 1024, 1000


def _seen(t, t_valid, causal, window):
    """[t, t] bool: query row sees key column (the kernels' contract)."""
    q = np.arange(t)[:, None]
    k = np.arange(t)[None, :]
    ok = np.ones((t, t), bool)
    if causal:
        ok &= k <= q
    if window > 0:
        ok &= q - k < window
    return ok & (k < t_valid)


@functools.lru_cache(maxsize=None)
def _case(d, dtype, block_q, block_k, window):
    """Kernels, oracle and plain softmax on one draw: name -> (got, want)."""
    q, k, v, g = (
        jax.random.normal(kk, (BH, T, d), jnp.float32).astype(dtype)
        for kk in jax.random.split(jax.random.key(d), 4))
    call = dict(causal=True, block_q=block_q, block_k=block_k,
                t_valid=T_VALID, interpret=True, window=window)
    out, lse = flash._flash_fwd_3d(q, k, v, **call)
    res = (q, k, v, out, lse)
    dq, dk, dv = flash._bwd_pallas_3d(True, block_q, block_k, T_VALID, True,
                                      res, g, window=window)
    o_dq, o_dk, o_dv = flash._bwd_3d(True, block_k, T_VALID, res, g,
                                     window=window)

    seen = jnp.asarray(_seen(T, T_VALID, True, window))

    def plain(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q, k) * d ** -0.5
        s = jnp.where(seen[None], s, flash.NEG_INF)
        return (jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v),
                jax.nn.logsumexp(s, -1))

    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    (p_out, p_lse), vjp = jax.vjp(plain, *f32)
    p_dq, p_dk, p_dv = vjp((g.astype(jnp.float32), jnp.zeros_like(p_lse)))
    return {
        "fwd": [(out, p_out), (lse, p_lse)],
        "dkv": [(dk, o_dk), (dv, o_dv), (dk, p_dk), (dv, p_dv)],
        "dq": [(dq, o_dq), (dq, p_dq)],
    }


# block_q, block_k, window -> the branches of _tile_branches the grid takes
GRID_OF_1024 = {
    "128x256-band384": ((128, 256, 384), {"whole", "masked"}),
    "256x128-band384": ((256, 128, 384), {"whole", "masked"}),
    # the diagonal and the band's edge cross square tiles corner to corner
    "512x512-band512": ((512, 512, 512), {"masked", "lower", "upper"}),
}


@pytest.mark.parametrize("kernel", ["fwd", "dkv", "dq"])
@pytest.mark.parametrize("grid,takes", GRID_OF_1024.values(),
                         ids=GRID_OF_1024.keys())
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("d", [64, 128])
def test_kernels_match_oracle_and_plain_softmax(d, dtype, grid, takes,
                                                kernel):
    """1024 tokens, 1000 valid, a band: the grid holds interior, diagonal,
    band-edge and padded tiles, or triangular tiles computed in strips.
    bfloat16 operands go to the matmuls as they are and the probabilities
    are rounded to them, so the gap to a float32 reckoning of the same
    inputs is rounding, by norm; float32 keeps the oracle tests'
    tolerance element by element."""
    block_q, block_k, window = grid
    geo = (block_q, block_k, True, T_VALID, T, window)
    taken = set()
    for i in range(T // block_q):
        for j in range(T // block_k):
            names = [name for name, pred, _ in flash._tile_branches(
                i, j, geo, flash.STRIPS_OF[kernel]) if pred]
            assert len(names) <= 1
            taken.update(names)
    # the forward computes a triangular tile whole
    assert taken == (takes if kernel != "fwd" else
                     takes - {"lower", "upper"} | {"masked"})
    for got, want in _case(d, jnp.dtype(dtype), *grid)[kernel]:
        got = np.asarray(got.astype(jnp.float32))
        want = np.asarray(want.astype(jnp.float32))
        assert np.isfinite(got).all()
        if dtype == "float32":
            np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
        else:
            gap = np.linalg.norm(got - want) / np.linalg.norm(want)
            assert gap < 1e-2, gap


@pytest.mark.parametrize("d", [64, 128])
def test_padded_keys_get_exact_zero_gradients(d):
    (dk, _), (dv, _) = _case(
        d, jnp.dtype("bfloat16"), 128, 256, 384)["dkv"][:2]
    assert not np.asarray(dk[:, T_VALID:].astype(jnp.float32)).any()
    assert not np.asarray(dv[:, T_VALID:].astype(jnp.float32)).any()


@pytest.mark.parametrize("kernel", ["fwd", "dkv", "dq"])
def test_interior_tiles_equal_the_masked_body_bit_for_bit(kernel,
                                                          monkeypatch):
    """An interior tile's mask is all true, so leaving it out changes no
    bit: float32, every visited tile made an edge tile against the
    kernels as they are."""
    t, t_valid, window, d = 512, 500, 192, 64
    q, k, v, g = (jax.random.normal(kk, (BH, t, d), jnp.float32)
                  for kk in jax.random.split(jax.random.key(3), 4))

    def run():
        call = dict(causal=True, block_q=64, block_k=128, t_valid=t_valid,
                    interpret=True, window=window)
        out, lse = flash._flash_fwd_3d(q, k, v, **call)
        if kernel == "fwd":
            return out, lse
        delta = jnp.sum(g * out, -1, keepdims=True)
        fn = {"dkv": flash._flash_dkv_3d, "dq": flash._flash_dq_3d}[kernel]
        return jax.tree.leaves(fn(q, g, lse[..., None], delta, k, v, **call))

    counts = flash.tile_counts(t, t_valid, 64, 128, True, window)[kernel]
    assert counts["tiles_interior"] > 0 and counts["tiles_edge"] > 0
    as_they_are = run()
    try:
        with monkeypatch.context() as patched:
            patched.setattr(flash, "_tile_is_edge", lambda *a: True)
            flash._grid_walk.cache_clear()  # the walk reads the predicate
            all_masked = run()
    finally:
        flash._grid_walk.cache_clear()
    for a, b in zip(as_they_are, all_masked):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# (t, t_valid, block_q, block_k, causal, window)
GRIDS = {
    "gpt2-large": (1024, 1024, 1024, 1024, True, 0),
    "gpt2-large-parent": (1024, 1024, 512, 1024, True, 0),
    "gpt2-large-256": (1024, 1024, 256, 256, True, 0),
    "mistral-band": (8192, 8192, 1024, 1024, True, 4096),
    "mistral-band-parent": (8192, 8192, 1024, 512, True, 4096),
    "mistral-band-512": (8192, 8192, 512, 512, True, 4096),
    "band-inactive": (4096, 4096, 1024, 1024, True, 4096),
    "band-narrower-than-a-block": (2048, 2048, 512, 512, True, 384),
    "band-the-blocks-do-not-divide": (2048, 2048, 512, 512, True, 768),
    "triangles-and-padding": (2048, 1900, 512, 512, True, 1024),
    "band-and-padding": (1024, 1000, 128, 256, True, 384),
    "band-off-the-blocks": (1024, 1024, 256, 128, True, 200),
    "whole-padded-tiles": (1024, 600, 512, 128, True, 0),
    "vit-padded": (256, 197, 128, 128, False, 0),
    "vit": (256, 256, 128, 128, False, 0),
    "band-without-causality": (512, 512, 128, 64, False, 100),
}


@pytest.mark.parametrize("strips", [None, "rows", "cols"],
                         ids=["whole", "by-rows", "by-cols"])
@pytest.mark.parametrize("grid", GRIDS.values(), ids=GRIDS.keys())
def test_tile_branches_against_a_brute_force_mask(grid, strips):
    """Which tiles are skipped, which keep their mask, which lose it and
    which are computed in strips, tile by tile: nothing that holds a seen
    pair is left out, a tile loses its mask only where every pair of it
    is seen, and at most one branch holds."""
    t, t_valid, bq, bk, causal, window = grid
    seen = _seen(t, t_valid, causal, window)
    geo = (bq, bk, causal, t_valid, t, window)
    for i in range(t // bq):
        for j in range(t // bk):
            tile = seen[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            held = [(pieces, name != "whole") for name, pred, pieces
                    in flash._tile_branches(i, j, geo, strips)
                    if pred is None or pred]
            assert len(held) <= 1, (i, j)
            if not held:
                assert not tile.any(), (i, j)
                continue
            (pieces, masked), = held
            covered = np.zeros_like(tile)
            for rows, cols in pieces:
                assert not covered[rows, cols].any()    # counted once
                covered[rows, cols] = True
                assert (cols.stop - cols.start) % min(bk, 128) == 0
            assert not (tile & ~covered).any(), (i, j)
            assert masked == (not tile[covered].all()), (i, j)
            if t_valid == t:
                assert tile.any(), (i, j)
            edge = flash._tile_is_edge(i, j, *geo)
            assert bool(edge) == (not tile.all()), (i, j)


# computed over useful scores, as the kernels' grids come to them
COMPUTED = {
    "gpt2-large": (1.998, 1.2488), "gpt2-large-parent": (1.998, 1.998),
    "gpt2-large-256": (1.2488, 1.2488), "mistral-band": (1.2499, 1.0624),
    "mistral-band-parent": (1.2499, 1.2499),
    "mistral-band-512": (1.1249, 1.0312), "band-inactive": (1.2497, 1.0622),
}


@pytest.mark.parametrize("grid", GRIDS.values(), ids=GRIDS.keys())
def test_tile_counts_against_a_brute_force_mask(grid, request):
    """Each kernel's own grid (banded or plain) reaches every tile that
    holds a seen pair, and the counts are those of the brute-force mask."""
    t, t_valid, bq, bk, causal, window = grid
    seen = _seen(t, t_valid, causal, window)
    tiles = seen.reshape(t // bq, bq, t // bk, bk).transpose(0, 2, 1, 3)
    holds_seen = tiles.any((2, 3))
    all_seen = tiles.all((2, 3))
    name = request.node.callspec.id
    for kernel, c in flash.tile_counts(*grid).items():
        assert c["tiles_visited"] >= holds_seen.sum(), kernel
        if t_valid == t:
            assert c["tiles_visited"] == holds_seen.sum(), kernel
        assert c["tiles_interior"] == all_seen.sum(), kernel
        assert c["tiles_edge"] == c["tiles_visited"] - all_seen.sum()
        assert c["grid_steps"] >= c["tiles_visited"]
        whole = c["tiles_visited"] * bq * bk / seen[:t_valid].sum()
        assert 1.0 <= c["computed_over_useful"] <= whole + 1e-4
        if kernel == "fwd" or flash._tile_triangles(
                0, 0, bq, bk, causal, t_valid, t, window) == (None, None):
            assert c["computed_over_useful"] == pytest.approx(whole, abs=1e-4)
        if name in COMPUTED:     # (the forward, the backward kernels)
            assert c["computed_over_useful"] == COMPUTED[name][kernel != "fwd"]


@pytest.mark.parametrize("grid,kernel,live", [
    # one diagonal tile a head: the strips alone, the forward's one masked
    # body; no other branch is compiled
    ("gpt2-large", "dkv", ("lower",)), ("gpt2-large", "dq", ("lower",)),
    ("gpt2-large", "fwd", ("masked",)),
    # no padding: the strips of both triangles and the whole tile, never
    # the whole tile with its mask
    ("mistral-band", "dkv", ("lower", "upper", "whole")),
    ("mistral-band", "fwd", ("masked", "whole")),
    ("triangles-and-padding", "dq", ("lower", "upper", "masked", "whole")),
    ("vit", "fwd", ("whole",)),
])
def test_only_branches_a_grid_takes_are_compiled(grid, kernel, live):
    assert flash._grid_walk(kernel, *GRIDS[grid])[-1] == tuple(sorted(live))


def _tiles_said():
    return [e["args"] for e in get_recorder().snapshot()
            if e["name"] == "flash/tiles"]


def test_flash_tiles_line_once_a_call_shape(caplog):
    """One `flash/tiles` INFO line and one zero-length span a process and
    distinct call shape; GPT-2-large's call computes under 1.5 times the
    scores its queries see."""
    trace._said.clear()
    get_recorder().clear()
    shape = jax.ShapeDtypeStruct((8, 1024, 20, 64), jnp.bfloat16)
    shaped = lambda **kw: jax.eval_shape(functools.partial(
        flash.flash_attention, interpret=True, **kw), shape, shape, shape)
    with caplog.at_level("INFO", logger=flash.logger.name):
        shaped(causal=True)
        shaped(causal=True)
        said = _tiles_said()
        assert len(said) == 1
        block_q, block_k = flash.pick_block_sizes(1024, 64)
        assert (said[0]["t"], said[0]["d"], said[0]["window"]) == (1024, 64, 0)
        assert (said[0]["block_q"], said[0]["block_k"]) == (block_q, block_k)
        for kernel in ("fwd", "dkv", "dq"):
            # the backward's strips; the forward computes the tile whole
            assert (said[0][f"{kernel}_computed_over_useful"] < 1.5) == (
                kernel != "fwd")
            assert (said[0][f"{kernel}_tiles_edge"]
                    + said[0][f"{kernel}_tiles_interior"]
                    == said[0][f"{kernel}_tiles_visited"])
        shaped(causal=True, window=256)
        assert len(_tiles_said()) == 2
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("flash/tiles")]
    assert len(lines) == 2 and "computed/useful" in lines[0]
