"""Paged-attention decode kernel (Pallas TPU).

PagedAttention's pointer-chasing gather is re-thought for TPU: the block
table rides in scalar-prefetch memory (SMEM) so the index_map can stream
exactly the KV pages a sequence owns from HBM into VMEM, page by page,
while the MXU consumes the previous page (automatic double-buffering
from the sequential grid).  No warp-level gather exists on TPU — the
indirection lives entirely in the grid's index_map, which is the
idiomatic TPU equivalent.

Layout: one layer's pool (num_blocks, block, K, dh); query (B, H, dh);
grid (B, max_blocks_per_seq), second dim sequential with online-softmax
state in VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _paged_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, block: int, n_kv: int,
                  groups: int, dh: int, nb: int, scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].reshape(n_kv, groups, dh)            # (K, G, dh)
    k = k_ref[0].transpose(1, 0, 2)                   # (K, block, dh)
    v = v_ref[0]
    # batched over kv heads: (K, G, dh) x (K, block, dh) -> (K, G, block)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32) * scale   # (K, G, block)

    length = lens_ref[b]
    tok = j * block + jax.lax.broadcasted_iota(
        jnp.int32, (n_kv, groups, block), 2)
    mask = tok < length
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask, p, 0.0)                       # (K, G, block)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    m_ref[...] = m_new
    # pv: for each kv head: (G, block) @ (block, dh)
    pv = jax.lax.dot_general(
        p.astype(v.dtype).transpose(0, 1, 2),
        v.transpose(1, 0, 2),
        (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)           # (K, G, dh)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + pv

    @pl.when(j == nb - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(n_kv * groups, dh).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lens, *,
                           interpret: bool = False):
    """q: (B, H, dh); pools: (num_blocks, block, K, dh);
    block_tables: (B, nb) int32; lens: (B,) int32 -> (B, H, dh)."""
    B, H, dh = q.shape
    num_blocks, block, K, _ = k_pool.shape
    G = H // K
    nb = block_tables.shape[1]
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(_paged_kernel, block=block, n_kv=K,
                               groups=G, dh=dh, nb=nb, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, H, dh), lambda b, j, T, L: (b, 0, 0)),
            pl.BlockSpec((1, block, K, dh),
                         lambda b, j, T, L: (T[b, j], 0, 0, 0)),
            pl.BlockSpec((1, block, K, dh),
                         lambda b, j, T, L: (T[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, dh), lambda b, j, T, L: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, G), jnp.float32),
            pltpu.VMEM((K, G), jnp.float32),
            pltpu.VMEM((K, G, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables, lens, q, k_pool, v_pool)
