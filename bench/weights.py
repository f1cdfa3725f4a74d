"""Random weights of a model, made on the device from the seed in one
jitted call, in bf16 as they are served.

Matrices are normal with standard deviation 0.02 and norm scales are
ones, the scales of the program's own initialiser.  The layout (names
and stacked shapes) and which leaves are norms are the family's
(``bench/families``); the harness checks the layout against the
program's before serving.  The reference regenerates the same weights
with the same call instead of taking them from the program."""
from __future__ import annotations

import functools
from types import ModuleType
from typing import Dict

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def _paths(tree, prefix=()):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            yield from _paths(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size up to 2**63."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make(family: ModuleType, d, key: jax.Array) -> Dict:
    out: Dict = {}
    for i, (path, shape) in enumerate(_paths(family.layout(d))):
        if path[-1] in family.NORMS:
            leaf = jnp.ones(shape, jnp.bfloat16)
        else:
            k = jax.random.fold_in(key, i)
            leaf = (jax.random.normal(k, shape, jnp.float32)
                    * INIT_STD).astype(jnp.bfloat16)
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = leaf
    return out


def make_weights(family: ModuleType, d, seed: int, device=None) -> Dict:
    """Every weight of the model of ``family`` and shapes ``d``, on
    ``device`` (default: the first)."""
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.block_until_ready(_make(family, d, key))


def n_params(family: ModuleType, d) -> int:
    total = 0
    for _, shape in _paths(family.layout(d)):
        n = 1
        for s in shape:
            n *= s
        total += n
    return total
