"""Jit'd public wrapper for the WKV6 kernel (model layout in/out)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6.kernel import wkv6_bht


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, *, chunk: int = 64, interpret: bool = False):
    """r,k,v,w: (B, T, H, dh); u: (H, dh) -> (B, T, H, dh) f32."""
    B, T, H, dh = r.shape

    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, dh)

    uf = jnp.broadcast_to(u[None], (B, H, dh)).reshape(B * H, dh)
    o = wkv6_bht(fold(r), fold(k), fold(v), fold(w), uf, chunk=chunk,
                 interpret=interpret)
    return o.reshape(B, H, T, dh).transpose(0, 2, 1, 3)
