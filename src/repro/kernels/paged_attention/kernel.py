"""Paged-attention decode kernel (Pallas TPU).

PagedAttention's pointer-chasing gather is re-thought for TPU: the block
tables ride in scalar-prefetch memory (SMEM), and the kernel copies
exactly the KV blocks a row attends from HBM into VMEM with its own
double-buffered DMAs, while the MXU consumes the previous chunk.

The pool is the serving pool itself, all layers of it: (L, num_blocks,
block, K, dh), indexed by a scalar-prefetched layer id, so a caller that
carries the pool through its layer scan hands the kernel the buffer it
owns and no per-layer slice is ever materialised.  One grid step is one
batch row; it walks the row's live blocks in chunks of ``CHUNK_TOKENS``
tokens (one DMA per block), and while the last chunk of a row is on the MXU
the first chunk of the next row with work is already in flight.  Blocks
past a row's length (or before its sliding window) are never copied; a
row of length 0 copies and computes nothing and returns zeros.

Within a chunk every query head meets every key head in one (H, T*K)
score matrix, masked to the head's own KV group: decode attention is
bound by HBM bytes, and the K-fold redundant MXU work costs less than
reshaping K/V per head.  Softmax is online, in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
CHUNK_TOKENS = 256          # tokens of one row a chunk of DMAs moves


def _div_mod(x, n: int):
    """x // n and x % n for a non-negative int32 vector and a static n,
    by shift and mask where n is a power of two."""
    if n & (n - 1) == 0:
        s = n.bit_length() - 1
        return x >> s, x & (n - 1)
    return x // n, x % n


def _paged_kernel(layer_ref, tables_ref, lens_ref, q_ref, k_hbm, v_hbm,
                  o_ref, kbuf, vbuf, sems, state, *, block: int, n_kv: int,
                  groups: int, per: int, nb: int, window: int,
                  scale: float):
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    layer = layer_ref[0]
    rows = block * n_kv                 # buffer rows one block fills

    @pl.when(b == 0)
    def _init():
        state[0] = 0                    # slot of the next chunk
        state[1] = 0                    # 1: its copies are in flight
        # a short last chunk leaves stale rows in the buffer; they get
        # probability 0, which must not meet a NaN left in fresh VMEM
        vbuf[...] = jnp.zeros_like(vbuf)

    def span(r):
        """(first block, live blocks, chunks) of row ``r``."""
        n = lens_ref[r]
        live = (n + block - 1) // block
        first = jnp.maximum(n - window, 0) // block if window else 0
        return first, live, (live - first + per - 1) // per

    def copies(r, c, slot, start: bool):
        """Start (or wait for) the DMAs of chunk ``c`` of row ``r`` into
        ``slot``: one per live block of the chunk, for K and for V."""
        first, live, _ = span(r)
        b0 = first + c * per

        def one(j, _):
            blk = tables_ref[r * nb + b0 + j]
            for hbm, buf, kv in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                cp = pltpu.make_async_copy(
                    hbm.at[layer, blk], buf.at[slot, pl.ds(j * rows, rows)],
                    sems.at[kv, slot])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return 0
        jax.lax.fori_loop(0, jnp.minimum(per, live - b0), one, 0)

    def next_row(r):
        """The first row after ``r`` with something to read, or
        ``n_rows``."""
        def more(x):
            return jnp.logical_and(x < n_rows, span(
                jnp.minimum(x, n_rows - 1))[2] == 0)
        return jax.lax.while_loop(more, lambda x: x + 1, r + 1)

    first, _, n_chunks = span(b)
    length = lens_ref[b]

    @pl.when(n_chunks == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_chunks > 0)
    def _row():
        s0 = state[0]

        @pl.when(state[1] == 0)
        def _():
            copies(b, 0, s0, start=True)

        nxt = next_row(b)
        q = q_ref[0]                                    # (H, dh)
        H, dh = q.shape
        width = per * rows
        head = jax.lax.broadcasted_iota(jnp.int32, (H, width), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (H, width), 1)
        tok_in_chunk, kv_head = _div_mod(col, n_kv)
        own = jnp.logical_and(kv_head * groups <= head,
                              head < kv_head * groups + groups)

        def step(c, carry):
            m, l, acc = carry
            slot = (s0 + c) % 2
            other = 1 - slot

            @pl.when(c + 1 < n_chunks)
            def _():
                copies(b, c + 1, other, start=True)

            @pl.when(jnp.logical_and(c + 1 == n_chunks, nxt < n_rows))
            def _():
                copies(jnp.minimum(nxt, n_rows - 1), 0, other, start=True)

            copies(b, c, slot, start=False)
            tok = (first + c * per) * block + tok_in_chunk
            valid = jnp.logical_and(own, tok < length)
            if window:
                valid = jnp.logical_and(valid, tok >= length - window)
            k = kbuf[slot]                              # (width, dh)
            v = vbuf[slot]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (H, width)
            s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l = alpha * l + p.sum(axis=1, keepdims=True)
            acc = alpha * acc + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # (H, dh)
            return m_new, l, acc

        m, l, acc = jax.lax.fori_loop(
            0, n_chunks, step,
            (jnp.full((H, 1), NEG_INF, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, dh), jnp.float32)))
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        state[0] = (s0 + n_chunks) % 2
        state[1] = (nxt < n_rows).astype(jnp.int32)


def paged_decode_attention(q, k_pool, v_pool, layer, block_tables, lens, *,
                           window: int = 0, interpret: bool = False):
    """One layer's decode attention over the whole paged pool.

    q: (B, H, dh); k_pool/v_pool: (L, num_blocks, block, K, dh); layer:
    int32 scalar, the layer to read; block_tables: (B, nb) int32;
    lens: (B,) int32 keys per row, the new token's included (0 for an
    idle row); ``window`` > 0 attends only the last ``window`` keys.
    Returns (B, H, dh); an idle row's output is zeros."""
    B, H, dh = q.shape
    L, num_blocks, block, K, _ = k_pool.shape
    G = H // K
    nb = block_tables.shape[1]
    per = max(1, CHUNK_TOKENS // block)
    rows = block * K
    # (block, K) -> block*K rows: the pool's minor (K, dh) tiles stay put
    k_flat = k_pool.reshape(L, num_blocks, rows, dh)
    v_flat = v_pool.reshape(L, num_blocks, rows, dh)
    kernel = functools.partial(
        _paged_kernel, block=block, n_kv=K, groups=G, per=per, nb=nb,
        window=int(window), scale=1.0 / math.sqrt(dh))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, dh), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, dh), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, per * rows, dh), k_pool.dtype),
            pltpu.VMEM((2, per * rows, dh), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a row's last chunk prefetches the next row's first: the
            # rows run in order
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_tables.reshape(-1).astype(jnp.int32), lens.astype(jnp.int32),
      q, k_flat, v_flat)
