"""Random weights from the seed, made by the benchmark on the device.

The benchmark, not the program, makes the weights, so that the plain
reference can make the same ones again and take nothing from the
program. The tree is the program's parameter layout for the group's
architecture (``bench/arch``: its ``shapes``), which is its input
format, like a checkpoint's.

One jitted call per model makes every leaf, in the type it is served
in. Matrices are normal with std ``min(0.02, 1/sqrt(fan-in))``, where
the fan-in is the second-to-last axis (the vocabulary for the input
embedding); norm scales are 1.
"""
from __future__ import annotations

import functools

import numpy as np

from bench import arch


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = leaf
    return tree


@functools.lru_cache(maxsize=None)
def _maker(spec: tuple, dtype: str):
    import jax
    import jax.numpy as jnp

    def make(key):
        keys = jax.random.split(key, len(spec))
        out = {}
        for (name, shape), k in zip(spec, keys):
            if name.endswith("scale"):
                out[name] = jnp.ones(shape, dtype)
                continue
            std = min(0.02, 1.0 / float(np.sqrt(shape[-2])))
            out[name] = (jax.random.normal(k, shape, jnp.float32)
                         * std).astype(dtype)
        return out
    return jax.jit(make)


def make(m: dict, seed: int, dtype: str = "bfloat16") -> dict:
    """The model's weights as a nested dict of device arrays."""
    import jax
    spec = tuple(sorted(arch.of(m).shapes(m).items()))
    flat = _maker(spec, dtype)(jax.random.PRNGKey(seed))
    return _nest(flat)


def seeds(seed: int) -> list:
    """Eight 31-bit seeds from one ``--seed`` of any size: word 0 the
    probe's weights, 1-3 the members', 4 the traffic, 5 the fleet's
    sampling seed, 6 the correctness sample."""
    words = np.random.SeedSequence(int(seed)).generate_state(8, np.uint32)
    return [int(w) & 0x7FFFFFFF for w in words]
