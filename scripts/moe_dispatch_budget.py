"""Measure the MoE routing-overhead component budget at the bench
rung's shapes (VERDICT r4 next #6: cut the 52% overhead to <=25% or
prove the floor with a measured decomposition).

Five timed programs, all fwd+bwd (the rung measures a train step), all
under the repo's timing rules (in-jit scan chaining, double warm,
host-readback fence of a value that depends on the whole chain):

1. dense_mlp      — the dense arm's MLP at matched active FLOPs
                    ([S, d] @ [d, 3072] @ [3072, d]).
2. experts_only   — the expert einsums on a PREBUILT [E, C, d] input:
                    the irreducible compute, including the
                    capacity_factor padding (E*C = 1.25 * k * S slots
                    vs k*S active) — this gap vs dense_mlp is the
                    capacity tax, paid in MXU flops.
3. routing_only   — router + top-k + capacity assignment (cumsum fill)
                    with a token-sized output, no expert math.
4. dispatch_only  — the gather/scatter data movement with FIXED
                    indices: build expert_in by row-gather, combine by
                    row-gather + weighted sum; its backward is the
                    scatter-add transpose (the suspected hidden cost).
5. moe_full       — the real MoeMlp (dispatch_impl='gather').

Budget identity (approximate): moe_full - dense_mlp ==
(experts_only - dense_mlp) + routing_only + dispatch_only + residual.

Usage: python scripts/moe_dispatch_budget.py [--cf 1.25] [--steps 20]
Prints one JSON line with per-component ms and the decomposition.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cf", type=float, default=1.25)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax import lax

    from pytorch_distributed_template_tpu.models.moe import MoeMlp

    b, t, d, e, k, d_ff = args.batch, args.seq, 768, 8, 2, 1536
    s = b * t
    cap = max(int(-(-k * s * args.cf // e)), 1)
    dtype = jnp.bfloat16
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(b, t, d)), dtype)

    def timed(f, x0, steps=args.steps):
        """fwd+bwd of ``f`` chained inside one jit (the carry feeds
        the next step, so no call can be deduplicated or reordered);
        median of 3 repeats."""
        g = jax.grad(lambda a: jnp.sum(f(a).astype(jnp.float32) ** 2))

        @jax.jit
        def many(c0):
            def body(c, _):
                return c + g(c).astype(c.dtype) * 1e-6, None

            out, _ = lax.scan(body, c0, None, length=steps)
            return out

        y = many(x0)
        float(jnp.sum(y.astype(jnp.float32)))      # compile + warm
        y = many(y)
        float(jnp.sum(y.astype(jnp.float32)))      # second warm
        reps = []
        for _ in range(3):
            t0 = time.perf_counter()
            y = many(y)
            float(jnp.sum(y.astype(jnp.float32)))
            reps.append((time.perf_counter() - t0) / steps)
        return sorted(reps)[1] * 1e3               # median ms/step

    out = {"shapes": {"S": s, "E": e, "C": cap, "d": d, "d_ff": d_ff,
                      "cf": args.cf, "EC_over_kS": round(e * cap / (k * s),
                                                         3)}}

    # 0. null arm: the scan/fence floor every arm pays (the fenced
    # dispatch's round trip amortized over `steps` + the carry update) —
    # subtracted from every component so the decomposition measures
    # the PROGRAMS, not the platform's dispatch overhead
    out["null_ms"] = round(timed(lambda x: x * (1.0 + 1e-9), x), 3)

    # 1. dense arm MLP (matched active flops: d_ff 3072)
    wi_d = jnp.asarray(rng.normal(size=(d, 3072), scale=0.02), dtype)
    wo_d = jnp.asarray(rng.normal(size=(3072, d), scale=0.02), dtype)

    def dense_mlp(x):
        h = jax.nn.gelu(x.reshape(s, d) @ wi_d)
        return (h @ wo_d).reshape(b, t, d)

    out["dense_mlp_ms"] = round(timed(dense_mlp, x), 3)

    # 2. expert einsums on prebuilt [E, C, d] (capacity tax included)
    wi = jnp.asarray(rng.normal(size=(e, d, d_ff), scale=0.02), dtype)
    wo = jnp.asarray(rng.normal(size=(e, d_ff, d), scale=0.02), dtype)
    xe = jnp.asarray(rng.normal(size=(e, cap, d)), dtype)

    def experts_only(xe):
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, wi))
        return jnp.einsum("ecf,efd->ecd", h, wo)

    out["experts_only_ms"] = round(timed(experts_only, xe), 3)

    # 3. routing math only (router + topk + fill cumsum), no experts
    wr = jnp.asarray(rng.normal(size=(d, e), scale=0.02), jnp.float32)

    def routing_only(x):
        xf = x.reshape(s, d)
        logits = xf.astype(jnp.float32) @ wr
        probs = jax.nn.softmax(logits, -1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        fill = jnp.zeros((e,), jnp.int32)
        acc = 0.0
        for slot in range(k):
            oh = jax.nn.one_hot(gate_idx[:, slot], e, dtype=jnp.int32)
            pos = jnp.cumsum(oh, axis=0) - 1 + fill[None, :]
            keep = (pos < cap) & (oh > 0)
            fill = fill + jnp.sum(keep, axis=0, dtype=jnp.int32)
            acc = acc + jnp.sum(gate_vals[:, slot]
                                * keep.any(-1).astype(jnp.float32))
        return (x + (acc * 1e-9).astype(x.dtype))

    out["routing_only_ms"] = round(timed(routing_only, x), 3)

    # 4. dispatch data movement with FIXED indices (bwd = scatter-add;
    # random sources/destinations — duplicates model the real
    # contention of scatter-add rows)
    inv_fix = jnp.asarray(
        rng.integers(0, s, size=e * cap).astype(np.int32))
    dst_fix = jnp.asarray(
        rng.integers(0, e * cap, size=(s, k)).astype(np.int32))
    gates_fix = jnp.asarray(rng.uniform(size=(s, k)), jnp.float32)

    def dispatch_only(x):
        xf = x.reshape(s, d)
        xf_ext = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], 0)
        expert_in = xf_ext[inv_fix[: e * cap]].reshape(e, cap, d)
        out_ext = jnp.concatenate(
            [expert_in.reshape(e * cap, d),
             jnp.zeros((1, d), xf.dtype)], 0)
        y = sum(gates_fix[:, i, None].astype(xf.dtype)
                * out_ext[dst_fix[:, i]] for i in range(k))
        return y.reshape(b, t, d)

    out["dispatch_only_ms"] = round(timed(dispatch_only, x), 3)

    # 5. the real thing (gather dispatch)
    moe = MoeMlp(d_model=d, d_ff=d_ff, num_experts=e, top_k=k,
                 capacity_factor=args.cf, aux_loss_weight=0.0,
                 dtype=dtype, dispatch_impl="gather")
    params = moe.init(jax.random.key(0), x, False)

    def moe_full(x):
        return moe.apply(params, x, False)

    out["moe_full_ms"] = round(timed(moe_full, x), 3)

    null = out["null_ms"]
    real = {kk: max(out[kk] - null, 0.0)
            for kk in ("dense_mlp_ms", "experts_only_ms",
                       "routing_only_ms", "dispatch_only_ms",
                       "moe_full_ms")}
    out["real_ms"] = {kk: round(v, 3) for kk, v in real.items()}
    dense = max(real["dense_mlp_ms"], 1e-6)
    out["decomposition_pct_of_dense"] = {
        "capacity_tax": round(
            100 * (real["experts_only_ms"] - dense) / dense, 1),
        "routing_math": round(
            100 * real["routing_only_ms"] / dense, 1),
        "dispatch_memops": round(
            100 * real["dispatch_only_ms"] / dense, 1),
        "moe_total_overhead": round(
            100 * (real["moe_full_ms"] - dense) / dense, 1),
    }
    dec = out["decomposition_pct_of_dense"]
    out["residual_pct"] = round(
        dec["moe_total_overhead"] - dec["capacity_tax"]
        - dec["routing_math"] - dec["dispatch_memops"], 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
