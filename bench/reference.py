"""Plain reference of a dense GQA decoder (Mistral-Nemo, Qwen3) in
float32 ``jax.numpy``, with ``default_matmul_precision("highest")``.

Per layer, following the published blocks (no biases):

    h = RMSNorm(x) * ln1
    q, k, v = h Wq, h Wk, h Wv            (Qwen3: RMSNorm of q and k per
                                           head, over head_dim)
    q, k = RoPE(q), RoPE(k)               (rotate-half pairing, theta)
    o = softmax(q k^T / sqrt(head_dim), causal) v     (GQA: each KV head
                                                       serves H/K heads)
    x = x + o Wo
    h = RMSNorm(x) * ln2
    x = x + (silu(h W1) * (h W3)) W2

then ``logits = (RMSNorm(x) * final_norm) Wunembed``.  It runs one
sequence at a time, layer by layer over the stacked weights, and
computes logits only at the rows asked for, so that it fits beside the
weights.  It imports nothing of the program under test.

``precision="fp8"`` is the control: the same forward with every weight
matrix and every matmul input rounded to float8 e4m3 with one scale per
tensor, the lower precision a later change might be tempted to serve.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.dims import Dims

F32 = jnp.float32
PAD = 512            # sequences are padded to a multiple of this
Q_BLOCK = 512        # query rows per attention block
_F8_MAX = 448.0      # largest finite float8 e4m3fn


def _fp8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """x: (S, heads, dh); rotate-half pairing of dims i and i + dh/2."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = pos.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, n_kv):
    """Causal GQA attention, one block of queries at a time.
    q: (S, H, dh); k, v: (S, K, dh)."""
    S, H, dh = q.shape
    G = H // n_kv
    qg = q.reshape(S, n_kv, G, dh)
    kpos = jnp.arange(S)
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = qg[s0:s0 + Q_BLOCK]
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k) / math.sqrt(dh)
        qpos = jnp.arange(s0, s0 + qb.shape[0])
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v))
    return jnp.concatenate(outs, 0).reshape(S, H, dh)


@functools.partial(jax.jit, static_argnames=("d", "fp8"))
def _layer(x, layers, i, *, d: Dims, fp8: bool):
    w = jax.tree_util.tree_map(lambda a: a[i].astype(F32), layers)
    mm = _fp8 if fp8 else (lambda a: a)
    pos = jnp.arange(x.shape[0])
    h = _rms(x, w["ln1"], d.norm_eps)
    hq = mm(h)
    q = jnp.einsum("sd,dhx->shx", hq, mm(w["attn"]["wq"]))
    k = jnp.einsum("sd,dkx->skx", hq, mm(w["attn"]["wk"]))
    v = jnp.einsum("sd,dkx->skx", hq, mm(w["attn"]["wv"]))
    if d.qk_norm:
        q = _rms(q, w["attn"]["qnorm"], d.norm_eps)
        k = _rms(k, w["attn"]["knorm"], d.norm_eps)
    q = _rope(q, pos, d.rope_theta)
    k = _rope(k, pos, d.rope_theta)
    o = _attend(q, k, v, d.n_kv_heads)
    x = x + jnp.einsum("shx,hxd->sd", mm(o), mm(w["attn"]["wo"]))
    h = mm(_rms(x, w["ln2"], d.norm_eps))
    g = jax.nn.silu(h @ mm(w["mlp"]["w1"])) * (h @ mm(w["mlp"]["w3"]))
    return x + mm(g) @ mm(w["mlp"]["w2"])


@functools.partial(jax.jit, static_argnames=("d",))
def _embed(tokens, embed, *, d: Dims):
    return embed[tokens].astype(F32)


@functools.partial(jax.jit, static_argnames=("d", "fp8"))
def _head(x, rows, final_norm, unembed, *, d: Dims, fp8: bool):
    mm = _fp8 if fp8 else (lambda a: a)
    h = _rms(x[rows], final_norm.astype(F32), d.norm_eps)
    return mm(h) @ mm(unembed.astype(F32))


def logits_at(weights: Dict, d: Dims, tokens: Sequence[int],
              rows: Sequence[int], *, precision: str = "f32") -> np.ndarray:
    """Logits (len(rows), vocab) of the forward over ``tokens`` at the
    given positions, as float32 numpy."""
    if precision not in ("f32", "fp8"):
        raise ValueError(f"precision {precision!r}")
    fp8 = precision == "fp8"
    n = len(tokens)
    S = -(-n // PAD) * PAD
    # padding sits after every real position, so the causal mask keeps
    # it out of every row that is read
    toks = np.zeros(S, np.int32)
    toks[:n] = np.asarray(tokens, np.int32)
    rows = np.asarray(rows, np.int32)
    if rows.size and (rows.min() < 0 or rows.max() >= n):
        raise ValueError("row outside the sequence")
    unembed = weights["embed"].T if d.tie_embeddings else weights["unembed"]
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(toks), weights["embed"], d=d)
        for i in range(d.n_layers):
            x = _layer(x, weights["layers"], i, d=d, fp8=fp8)
        out = _head(x, jnp.asarray(rows), weights["final_norm"], unembed,
                    d=d, fp8=fp8)
    return np.asarray(out, np.float32)
