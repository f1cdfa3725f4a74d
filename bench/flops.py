"""Model FLOPs of serving a dense GQA decoder, from its shapes.

A multiply-add counts two.  Matmul FLOPs are ``2 * params`` per token;
attention adds ``4 * n_heads * head_dim`` per key a query attends to
(scores and the weighted sum of values).  A prefilled token needs no
logits (the served path keeps none of them); a decoded token does."""
from __future__ import annotations

from typing import Iterable

from bench.dims import Dims


def layer_matmul_params(d: Dims) -> int:
    attn = d.d_model * d.head_dim * (2 * d.n_heads + 2 * d.n_kv_heads)
    return attn + 3 * d.d_model * d.d_ff


def _attn_flops_per_key(d: Dims) -> int:
    return 4 * d.n_heads * d.head_dim


def prefill_flops(d: Dims, start: int, n: int) -> float:
    """``n`` tokens at positions ``start .. start+n-1``, each attending
    causally to every key up to and including its own position."""
    keys = n * start + n * (n + 1) // 2
    return float(d.n_layers * (2 * layer_matmul_params(d) * n
                               + _attn_flops_per_key(d) * keys))


def decode_flops(d: Dims, keys_per_row: Iterable[int]) -> float:
    """One decode round over live rows; ``keys_per_row`` is how many
    keys each row's new token attends to (its own included)."""
    keys = list(keys_per_row)
    per_row = 2 * (d.n_layers * layer_matmul_params(d)
                   + d.d_model * d.vocab)
    return float(len(keys) * per_row
                 + d.n_layers * _attn_flops_per_key(d) * sum(keys))
