"""Seeded weights, made on the device by the benchmark (not by the
program's init), so that the program and the plain reference start from
the same numbers and neither takes them from the other.

`shapes` is `{path: shape}` as the reference module of the architecture
gives it; `rules` is its `init_rules`: the first `(regex, kind, std)`
whose regex is found in the path decides the leaf: `normal` with that
standard deviation, `ones`, `zeros`, or `const`, whose third field is
the value (a state-space layer's step bias, a router's selection bias:
leaves that mean something only away from 0 and 1). A leaf's key is the
seed folded with the leaf's rank among the sorted paths, so one leaf can
be made again alone.

A leaf can also be given from outside (`give`): benchmarks/balance.py
solves the routers' selection biases from the seed's other leaves, and
`leaf`, `all` and `make` then return the solved arrays for those paths,
so that the program's state, the reference's parameters and both sides'
`update_norms` start from the same numbers. The rule of such a leaf
stays what the solve starts from.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

KINDS = ("normal", "ones", "zeros", "const")


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std"))
def _leaf(key, shape, kind, std):
    if kind == "normal":
        return std * jax.random.normal(key, shape, jnp.float32)
    value = {"ones": 1.0, "zeros": 0.0, "const": std}[kind]
    return jnp.full(shape, value, jnp.float32)


class Weights:
    def __init__(self, shapes: dict, rules: list, seed: int):
        self.shapes = {k: tuple(v) for k, v in shapes.items()}
        self.seed = int(seed)
        self._rank = {k: i for i, k in enumerate(sorted(shapes))}
        self._rule = {}
        for path in shapes:
            for pattern, kind, std in rules:
                if re.search(pattern, path):
                    if kind not in KINDS:
                        raise ValueError(f"init rule {pattern!r} names the "
                                         f"unknown kind {kind!r}")
                    self._rule[path] = (kind, float(std))
                    break
            else:
                raise ValueError(f"no init rule matches {path!r}")
        self.given = {}

    def give(self, leaves: dict) -> None:
        """`{path: array}` that takes the place of those paths' rules."""
        for path, leaf in leaves.items():
            if self.shapes.get(path) != tuple(leaf.shape):
                raise ValueError(f"the leaf given for {path!r} has shape "
                                 f"{tuple(leaf.shape)}, not "
                                 f"{self.shapes.get(path)}")
        self.given = dict(leaves)

    def _make(self, path, root, given):
        if path in given:
            return given[path]
        kind, std = self._rule[path]
        return _leaf(jax.random.fold_in(root, self._rank[path]),
                     self.shapes[path], kind, std)

    def leaf(self, path: str):
        return self._make(path, self.root(), self.given)

    def root(self):
        # the seed is folded in two 31-bit halves: it may pass 2**31
        key = jax.random.key(self.seed & 0x7FFFFFFF)
        return jax.random.fold_in(key, self.seed >> 31)

    def all(self, root, given) -> dict:
        """Every leaf from `self.root()` and `self.given`. Jit this with
        both as arguments, as `make` does: a seed or a solved leaf traced
        into the program as a constant would compile anew for every
        seed."""
        return {path: self._make(path, root, given) for path in self.shapes}

    def make(self, out_shardings=None) -> dict:
        """Every leaf, in one jitted program that any seed reuses."""
        return jax.jit(self.all, out_shardings=out_shardings)(
            self.root(), self.given)
