"""Jit'd public wrapper for paged decode attention.

``paged_attention`` is the kernel entry (compiled for the TPU;
``interpret=True`` runs it on any backend).  The served decode step
(``models.lm.decode_step_paged``) calls the kernel inside its own jitted
program, with the pool carried through its layer scan.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import paged_decode_attention


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_attention(q, k_pool, v_pool, layer, block_tables, lens,
                    window: int = 0, interpret: bool = False):
    """q: (B, H, dh); pools: (L, num_blocks, block, K, dh); layer: int32
    scalar; block_tables: (B, nb) int32; lens: (B,) int32."""
    return paged_decode_attention(q, k_pool, v_pool, layer, block_tables,
                                  lens, window=window, interpret=interpret)
