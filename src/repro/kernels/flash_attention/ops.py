"""Jit'd public wrapper for the flash-attention kernel.

Accepts model-layout tensors (B, S, H|K, D), expands GQA, folds heads
into the batch grid dimension, and dispatches to the Pallas kernel
(compiled for the TPU; ``interpret=True`` runs the body on any backend).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.models.layers import expand_kv


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Sq, H, D); k/v: (B, Sk, K, D).  Returns (B, Sq, H, D)."""
    B, Sq, H, D = q.shape
    k = expand_kv(k, H)
    v = expand_kv(v, H)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, -1, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, -1, D)
    o = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
