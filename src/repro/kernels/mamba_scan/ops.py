"""Jit'd public wrapper for the mamba selective-scan kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.mamba_scan.kernel import mamba_scan_btd


@functools.partial(jax.jit, static_argnames=("block_d", "chunk",
                                             "interpret"))
def mamba_scan(x, dt, Bc, Cc, A_log, D, *, block_d: int = 256,
               chunk: int = 64, interpret: bool = False):
    """x, dt: (B, T, di); Bc, Cc: (B, T, ds); A_log: (di, ds); D: (di,)."""
    return mamba_scan_btd(x, dt, Bc, Cc, A_log, D, block_d=block_d,
                          chunk=chunk, interpret=interpret)
