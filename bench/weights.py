"""Random weights of a dense GQA decoder, made on the device from the
seed in one jitted call, in bf16 as they are served.

Matrices are normal with standard deviation 0.02 and norm scales are
ones, the scales of the program's own initialiser.  The layout (names
and stacked shapes) is the served one; the harness checks it against
the program's before serving.  The reference regenerates the same
weights with the same call instead of taking them from the program."""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench.dims import Dims

INIT_STD = 0.02
NORMS = ("ln1", "ln2", "qnorm", "knorm", "final_norm")


def layout(d: Dims) -> Dict:
    """Leaf shapes, stacked over layers where a leaf is per layer."""
    L, D, H, K, dh, F = (d.n_layers, d.d_model, d.n_heads, d.n_kv_heads,
                         d.head_dim, d.d_ff)
    attn = {"wq": (L, D, H, dh), "wk": (L, D, K, dh), "wv": (L, D, K, dh),
            "wo": (L, H, dh, D)}
    if d.qk_norm:
        attn["qnorm"] = (L, dh)
        attn["knorm"] = (L, dh)
    tree = {"embed": (d.vocab, D), "final_norm": (D,),
            "layers": {"ln1": (L, D), "ln2": (L, D), "attn": attn,
                       "mlp": {"w1": (L, D, F), "w3": (L, D, F),
                               "w2": (L, F, D)}}}
    if not d.tie_embeddings:
        tree["unembed"] = (D, d.vocab)
    return tree


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


@functools.partial(jax.jit, static_argnums=(0,))
def _make(d: Dims, key: jax.Array) -> Dict:
    out: Dict = {}
    for i, (path, shape) in enumerate(_paths(layout(d))):
        if path[-1] in NORMS:
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


def make_weights(d: Dims, seed: int, device=None) -> Dict:
    """Every weight of the model, on ``device`` (default: the first)."""
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.block_until_ready(_make(d, key))


def n_params(d: Dims) -> int:
    total = 0
    for _, shape in _paths(layout(d)):
        n = 1
        for s in shape:
            n *= s
        total += n
    return total
