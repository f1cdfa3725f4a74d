"""WKV6 recurrence kernel (Pallas TPU) — data-dependent-decay linear
attention (RWKV-6 "Finch").

Grid (B*H, T/chunk): the chunk dimension is sequential with the
(dk, dv) state matrix resident in VMEM scratch between chunks — the
HBM<->VMEM traffic is exactly one (chunk, dh) tile per operand per
step, and the state never spills.  Inside a chunk the recurrence is a
fori loop of rank-1 updates; dh=64 keeps each update a single
(64, 64) VPU tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref, *,
                 chunk: int, dh: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0]                              # (dh,)

    def step(t, state):
        rt = r_ref[0, t].astype(jnp.float32)  # (dh,)
        kt = k_ref[0, t].astype(jnp.float32)
        vt = v_ref[0, t].astype(jnp.float32)
        wt = w_ref[0, t].astype(jnp.float32)
        kv = kt[:, None] * vt[None, :]        # (dk, dv)
        out = ((state + u[:, None] * kv) * rt[:, None]).sum(axis=0)
        o_ref[0, t] = out.astype(o_ref.dtype)
        return wt[:, None] * state + kv

    state = jax.lax.fori_loop(0, chunk, step, state_ref[...])
    state_ref[...] = state


def wkv6_bht(r, k, v, w, u, *, chunk: int = 64, interpret: bool = False):
    """r,k,v,w: (BH, T, dh); u: (BH, dh).  Returns (BH, T, dh) f32."""
    BH, T, dh = r.shape
    c = min(chunk, T)
    assert T % c == 0, (T, c)
    nchunks = T // c

    kernel = functools.partial(_wkv6_kernel, chunk=c, dh=dh)
    return pl.pallas_call(
        kernel,
        grid=(BH, nchunks),
        in_specs=[
            pl.BlockSpec((1, c, dh), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, c, dh), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, c, dh), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, c, dh), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, dh), lambda b, j: (b, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, dh), lambda b, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)
