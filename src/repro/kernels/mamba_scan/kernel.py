"""Selective-SSM scan kernel (Pallas TPU) — Mamba's recurrence.

Grid (B, d_inner/bd, T/chunk): channels are parallel (each program owns
a (bd, d_state) state tile in VMEM), time chunks are sequential.  The
(bd, d_state) per-channel state never leaves VMEM between chunks; the
discretized dA/dBx products are computed on the VPU per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(x_ref, dt_ref, b_ref, c_ref, alog_ref, d_ref, o_ref,
                  h_ref, *, chunk: int, bd: int, ds: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = -jnp.exp(alog_ref[...].astype(jnp.float32))   # (bd, ds)
    D = d_ref[...].astype(jnp.float32)                # (bd,)

    def step(t, h):
        xt = x_ref[0, t].astype(jnp.float32)          # (bd,)
        dtt = dt_ref[0, t].astype(jnp.float32)        # (bd,)
        bt = b_ref[0, t].astype(jnp.float32)          # (ds,)
        ct = c_ref[0, t].astype(jnp.float32)          # (ds,)
        dA = jnp.exp(dtt[:, None] * A)                # (bd, ds)
        h = dA * h + (dtt * xt)[:, None] * bt[None, :]
        y = (h * ct[None, :]).sum(axis=1) + D * xt
        o_ref[0, t] = y.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])


def mamba_scan_btd(x, dt, Bc, Cc, A_log, D, *, block_d: int = 256,
                   chunk: int = 64, interpret: bool = False):
    """x, dt: (B, T, di); Bc, Cc: (B, T, ds); A_log: (di, ds); D: (di,).
    Returns y: (B, T, di) f32 (without gating)."""
    B, T, di = x.shape
    ds = Bc.shape[-1]
    bd = min(block_d, di)
    c = min(chunk, T)
    assert di % bd == 0 and T % c == 0, (di, bd, T, c)

    kernel = functools.partial(_mamba_kernel, chunk=c, bd=bd, ds=ds)
    return pl.pallas_call(
        kernel,
        grid=(B, di // bd, T // c),
        in_specs=[
            pl.BlockSpec((1, c, bd), lambda b, d, j: (b, j, d)),
            pl.BlockSpec((1, c, bd), lambda b, d, j: (b, j, d)),
            pl.BlockSpec((1, c, ds), lambda b, d, j: (b, j, 0)),
            pl.BlockSpec((1, c, ds), lambda b, d, j: (b, j, 0)),
            pl.BlockSpec((bd, ds), lambda b, d, j: (d, 0)),
            pl.BlockSpec((bd,), lambda b, d, j: (d,)),
        ],
        out_specs=pl.BlockSpec((1, c, bd), lambda b, d, j: (b, j, d)),
        out_shape=jax.ShapeDtypeStruct((B, T, di), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, Bc, Cc, A_log, D)
