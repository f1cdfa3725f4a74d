"""Flash-attention prefill kernel (Pallas TPU).

Canonical 3-level grid (batch*heads, q_blocks, kv_blocks) with the kv
dimension sequential ("arbitrary") so the online-softmax state lives in
VMEM scratch between kv steps.  Block shapes are MXU-aligned (q/kv block
multiples of 128 recommended; head_dim 64/128).

HBM->VMEM traffic per program: one (bq, D) q tile + one (bk, D) k tile +
one (bk, D) v tile; the (bq, bk) score tile never leaves VMEM — this is
the IO-awareness the TPU adaptation keeps from FlashAttention, with
systolic-MXU-sized tiles instead of warp-level SRAM staging.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, causal: bool, window: int,
                  scale: float, nk: int):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # kv block (sequential)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                  # (bq, D)
    k = k_ref[0]                  # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv

    @pl.when(j == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False):
    """q,k,v: (BH, S, D) with identical head counts (GQA pre-expanded).
    Returns (BH, Sq, D)."""
    BH, Sq, D = q.shape
    Sk = k.shape[1]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                               window=window, scale=scale, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
