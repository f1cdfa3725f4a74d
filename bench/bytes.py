"""HBM bytes a decode round needs at the least, from the shapes: every
weight read once (the embedding table only for the rows it looks up),
the bf16 K/V of each live row's real context, and the new K/V written.
What the code moves beyond that (a ``max_len`` gather, a whole-pool
copy) is not counted, so the share stays at most 100% after any
correct change."""
from __future__ import annotations

from typing import Iterable

from bench.dims import Dims
from bench.flops import layer_matmul_params

BF16 = 2


def decode_weight_bytes(d: Dims, rows: int) -> int:
    norms = 2 * d.d_model + (2 * d.head_dim if d.qk_norm else 0)
    per_layer = layer_matmul_params(d) + norms
    head = d.d_model * d.vocab + d.d_model          # unembed + final norm
    lookup = rows * d.d_model
    return BF16 * (d.n_layers * per_layer + head + lookup)


def decode_bytes(d: Dims, keys_per_row: Iterable[int]) -> int:
    """``keys_per_row`` counts each row's keys including the new one:
    the new K/V is written once and read once."""
    keys = list(keys_per_row)
    kv = d.kv_bytes_per_token
    return (decode_weight_bytes(d, len(keys)) + kv * sum(keys)
            + kv * len(keys))
