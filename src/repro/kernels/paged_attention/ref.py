"""Pure-jnp oracle: gather pages to contiguous KV, run dense decode."""
from __future__ import annotations

from repro.models.layers import decode_attention


def paged_decode_ref(q, k_pool, v_pool, layer, block_tables, lens, *,
                     window: int = 0):
    """q: (B, H, dh); pools: (L, num_blocks, block, K, dh); layer: the
    layer to read; block_tables: (B, nb); lens: (B,) keys per row, the
    new token's included.  Returns (B, H, dh); a row of length 0 is
    garbage here."""
    B, H, dh = q.shape
    K = k_pool.shape[3]
    k = k_pool[layer][block_tables].reshape(B, -1, K, dh)
    v = v_pool[layer][block_tables].reshape(B, -1, K, dh)
    out = decode_attention(q[:, None], k, v, lens - 1, window=window)
    return out[:, 0]
