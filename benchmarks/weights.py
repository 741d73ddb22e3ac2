"""Seeded weights, made on the device by the benchmark (not by the
program's init), so that the program and the plain reference start from
the same numbers and neither takes them from the other.

`shapes` is `{path: shape}` as the reference module of the architecture
gives it; `rules` is its `init_rules`: the first `(regex, kind, std)`
whose regex is found in the path decides the leaf (`normal`, `ones`,
`zeros`). A leaf's key is the seed folded with the leaf's rank among the
sorted paths, so one leaf can be made again alone.
"""
from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("shape", "kind", "std"))
def _leaf(key, shape, kind, std):
    if kind == "normal":
        return std * jax.random.normal(key, shape, jnp.float32)
    return jnp.full(shape, 1.0 if kind == "ones" else 0.0, jnp.float32)


class Weights:
    def __init__(self, shapes: dict, rules: list, seed: int):
        self.shapes = {k: tuple(v) for k, v in shapes.items()}
        self.seed = int(seed)
        self._rank = {k: i for i, k in enumerate(sorted(shapes))}
        self._rule = {}
        for path in shapes:
            for pattern, kind, std in rules:
                if re.search(pattern, path):
                    self._rule[path] = (kind, float(std))
                    break
            else:
                raise ValueError(f"no init rule matches {path!r}")

    def _make(self, path, root):
        kind, std = self._rule[path]
        return _leaf(jax.random.fold_in(root, self._rank[path]),
                     self.shapes[path], kind, std)

    def leaf(self, path: str):
        return self._make(path, self.root())

    def root(self):
        # the seed is folded in two 31-bit halves: it may pass 2**31
        key = jax.random.key(self.seed & 0x7FFFFFFF)
        return jax.random.fold_in(key, self.seed >> 31)

    def all(self, root) -> dict:
        """Every leaf from `self.root()`. Jit this with the root as an
        argument, as `make` does: a seed traced into the program as a
        constant would compile anew for every seed."""
        return {path: self._make(path, root) for path in self.shapes}

    def make(self, out_shardings=None) -> dict:
        """Every leaf, in one jitted program that any seed reuses."""
        return jax.jit(self.all, out_shardings=out_shardings)(self.root())
