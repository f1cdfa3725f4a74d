"""Jit'd public wrappers for paged decode attention.

``paged_attention`` is the single-layer kernel entry (compiled for the
TPU; ``interpret=True`` runs it on any backend).  ``paged_decode_step``
is the batched multi-layer entry the serving layout uses: it
dynamic-updates the new step's K/V into each session's current tail
block of the (L, num_blocks, block, K, dh) pool arrays, then attends
every layer over the block tables — append + attend in one jitted call, no
contiguous copy of parked KV anywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_decode_attention


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pool, v_pool, block_tables, lens,
                    interpret: bool = False):
    """q: (B, H, dh); pools: (num_blocks, block, K, dh);
    block_tables: (B, nb) int32; lens: (B,) int32."""
    return paged_decode_attention(q, k_pool, v_pool, block_tables, lens,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_step(q, k_new, v_new, k_pool, v_pool, block_tables,
                      lens, append_blocks, append_offsets,
                      interpret: bool = False):
    """Batched multi-layer paged decode: append the step's K/V, then
    attend over block tables, for all L layers in one call.

    q: (L, B, H, dh) — per-layer queries for the new token;
    k_new/v_new: (L, B, K, dh) — the new token's per-layer K/V;
    k_pool/v_pool: (L, num_blocks, block, K, dh);
    block_tables: (B, nb) int32; lens: (B,) int32 token counts
    INCLUDING the new token; append_blocks/append_offsets: (B,) int32
    destination of the new token (an out-of-range block id is a drop
    sentinel for idle batch rows).

    Returns (out (L, B, H, dh), k_pool, v_pool) with the pools updated
    in place of the tail blocks only — parked KV never moves.
    """
    kp = k_pool.at[:, append_blocks, append_offsets].set(
        k_new.astype(k_pool.dtype), mode="drop")
    vp = v_pool.at[:, append_blocks, append_offsets].set(
        v_new.astype(v_pool.dtype), mode="drop")
    outs = [paged_decode_attention(q[l], kp[l], vp[l], block_tables,
                                   lens, interpret=interpret)
            for l in range(q.shape[0])]
    return jnp.stack(outs), kp, vp
