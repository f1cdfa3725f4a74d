"""Model-zoo primitive layers (pure jnp; GSPMD-friendly).

Everything here must (a) run on a single CPU device for smoke tests and
(b) lower under 512-way SPMD for the production dry-run.  The Pallas
kernels in ``repro.kernels`` are drop-in single-device replacements for
the hot paths (flash prefill / paged decode / rwkv6 / mamba scan); the
jnp implementations below are simultaneously their reference oracles and
the distributed lowering path.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.models.sharding import ShardingEnv

F32 = jnp.float32
NEG_INF = -1e30


def pin_bf16(x):
    """Force a bf16 tensor's storage rounding to actually happen.

    XLA's excess-precision pass may elide an f32->bf16->f32 convert pair
    inside a fused graph, so the *same* bf16-typed intermediate holds
    different values in differently-fused programs (e.g. the S-token
    prefill graph vs the 1-token decode graph).  Any knife-edge discrete
    decision downstream — the MoE router's top_k above all — then
    diverges between serving paths.  ``lax.reduce_precision`` performs
    the rounding explicitly and is never elided, making residual-stream
    values bit-identical across fusion choices."""
    if x.dtype == jnp.bfloat16:
        return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rms_norm(x, scale, eps: float = 1e-5):
    var = jnp.mean(jnp.square(x.astype(F32)), axis=-1, keepdims=True)
    out = x.astype(F32) * jax.lax.rsqrt(var + eps)
    return pin_bf16((out * scale.astype(F32)).astype(x.dtype))


def group_norm_heads(x, scale, n_heads: int, eps: float = 1e-5):
    """Per-head group norm over the trailing dim split into n_heads groups
    (RWKV's ln_x)."""
    orig = x.shape
    x = x.reshape(orig[:-1] + (n_heads, orig[-1] // n_heads)).astype(F32)
    mean = x.mean(axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    x = x.reshape(orig)
    return (x * scale.astype(F32)).astype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=F32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, D); positions broadcastable to (..., S)."""
    D = x.shape[-1]
    freqs = rope_freqs(D, theta)                      # (D/2,)
    ang = positions.astype(F32)[..., None] * freqs    # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]                  # (..., S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(F32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention — dense reference (small shapes / oracle)
# ---------------------------------------------------------------------------
def expand_kv(k, n_heads: int):
    """(B,S,K,D) -> (B,S,H,D) by repeating each kv head H/K times."""
    K = k.shape[2]
    if K == n_heads:
        return k
    return jnp.repeat(k, n_heads // K, axis=2)


def attention_dense(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, logit_cap: float = 0.0):
    """q: (B,Sq,H,D), k/v: (B,Sk,K,D[v]).  GQA expanded internally."""
    B, Sq, H, D = q.shape
    k = expand_kv(k, H)
    v = expand_kv(v, H)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bshd->bhqs", q, k,
                   preferred_element_type=F32) * scale
    if logit_cap:
        s = logit_cap * jnp.tanh(s / logit_cap)
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= qpos[:, None] - kpos[None, :] < window
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bshd->bqhd", p.astype(v.dtype), v,
                   preferred_element_type=F32)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# attention — chunked online-softmax (memory-safe; the distributed path)
# ---------------------------------------------------------------------------
def _pick_block(S: int, target: int) -> int:
    if S <= target:
        return S
    b = target
    while S % b:
        b -= 1
    return b


def _visible(i, j, qb, kb, q_offset, causal, window) -> bool:
    q_lo = i * qb + q_offset
    q_hi = q_lo + qb - 1
    k_lo, k_hi = j * kb, j * kb + kb - 1
    if causal and k_lo > q_hi:
        return False
    if window and q_lo - k_hi >= window:
        return False
    return True


def chunked_attention(q, k, v, *, causal: bool = True, window: int = 0,
                      q_block: int = 512, kv_block: int = 512,
                      mode: str = "full", q_offset: int = 0,
                      logit_cap: float = 0.0, bwd_safe: bool = False,
                      unroll_pairs: bool = False):
    """Flash-style two-level blocked attention in pure jnp.

    mode="full": every (q_block, kv_block) pair with masking — the
      baseline (compute ~2x for causal).
    mode="tri": only visible block pairs (causal triangle /
      sliding-window band) — the beyond-paper optimized path.
    bwd_safe=True (training): python loop over q blocks with a
      checkpointed inner kv scan, so the backward pass recomputes scores
      instead of saving O(Sq*Sk) residuals.  Inference (prefill) uses the
      flat pair-scan which keeps the HLO small.
    unroll_pairs=True: python-unroll the pair loop — used by the dry-run
      slope compiles so XLA cost analysis sees every block pair (a scan
      body is otherwise counted once regardless of trip count).
    """
    if bwd_safe:
        return _chunked_attention_bwd_safe(
            q, k, v, causal=causal, window=window, q_block=q_block,
            kv_block=kv_block, mode=mode, q_offset=q_offset,
            logit_cap=logit_cap)
    B, Sq, H, D = q.shape
    assert k.shape[2] == H, "expand_kv before chunked_attention"
    Sk = k.shape[1]
    Dv = v.shape[-1]
    qb = _pick_block(Sq, q_block)
    kb = _pick_block(Sk, kv_block)
    nq, nk = Sq // qb, Sk // kb
    scale = 1.0 / math.sqrt(D)

    pairs = []
    for i in range(nq):
        for j in range(nk):
            q_lo = i * qb + q_offset
            q_hi = q_lo + qb - 1
            k_lo, k_hi = j * kb, j * kb + kb - 1
            visible = True
            if causal and k_lo > q_hi:
                visible = False
            if window and q_hi - k_hi >= window + qb - 1 and k_hi < q_lo:
                # entire kv block is left of every q position's window
                if q_lo - k_hi >= window:
                    visible = False
            if mode == "tri" and not visible:
                continue
            pairs.append((i, j))
    ii = jnp.array([p[0] for p in pairs], dtype=jnp.int32)
    jj = jnp.array([p[1] for p in pairs], dtype=jnp.int32)

    m0 = jnp.full((B, H, Sq), NEG_INF, dtype=F32)
    l0 = jnp.zeros((B, H, Sq), dtype=F32)
    a0 = jnp.zeros((B, H, Sq, Dv), dtype=F32)

    def body(carry, idx):
        m, l, acc = carry
        i, j = idx
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)    # (B,qb,H,D)
        kj = lax.dynamic_slice_in_dim(k, j * kb, kb, axis=1)    # (B,kb,H,D)
        vj = lax.dynamic_slice_in_dim(v, j * kb, kb, axis=1)
        s = jnp.einsum("bqhd,bshd->bhqs", qi, kj,
                       preferred_element_type=F32) * scale
        if logit_cap:
            s = logit_cap * jnp.tanh(s / logit_cap)
        qpos = i * qb + jnp.arange(qb) + q_offset
        kpos = j * kb + jnp.arange(kb)
        msk = jnp.ones((qb, kb), dtype=bool)
        if causal:
            msk &= kpos[None, :] <= qpos[:, None]
        if window:
            msk &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(msk[None, None], s, NEG_INF)

        mi = lax.dynamic_slice_in_dim(m, i * qb, qb, axis=2)
        li = lax.dynamic_slice_in_dim(l, i * qb, qb, axis=2)
        ai = lax.dynamic_slice_in_dim(acc, i * qb, qb, axis=2)
        m_new = jnp.maximum(mi, s.max(axis=-1))
        # guard all-masked rows (m_new == NEG_INF) against inf-inf
        alpha = jnp.exp(jnp.minimum(mi - m_new, 0.0))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(msk[None, None], p, 0.0)
        l_new = li * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhqs,bshd->bhqd", p.astype(v.dtype), vj,
                        preferred_element_type=F32)
        a_new = ai * alpha[..., None] + pv
        m = lax.dynamic_update_slice_in_dim(m, m_new, i * qb, axis=2)
        l = lax.dynamic_update_slice_in_dim(l, l_new, i * qb, axis=2)
        acc = lax.dynamic_update_slice_in_dim(acc, a_new, i * qb, axis=2)
        return (m, l, acc), None

    if unroll_pairs:
        carry = (m0, l0, a0)
        for pi, pj in pairs:
            carry, _ = body(carry, (jnp.int32(pi), jnp.int32(pj)))
        m, l, acc = carry
    else:
        (m, l, acc), _ = lax.scan(body, (m0, l0, a0), (ii, jj))
    out = acc / jnp.maximum(l, 1e-30)[..., None]        # (B,H,Sq,Dv)
    out = jnp.moveaxis(out, 1, 2)                       # (B,Sq,H,Dv)
    return out.astype(q.dtype)


def _chunked_attention_bwd_safe(q, k, v, *, causal, window, q_block,
                                kv_block, mode, q_offset, logit_cap):
    """Training attention: O(block) backward residuals.

    Outer python loop over q blocks (static), inner checkpointed scan over
    kv blocks; jax.checkpoint forces score recomputation in the backward
    pass so only the small (m,l,acc) block carries are stored.
    """
    B, Sq, H, D = q.shape
    assert k.shape[2] == H, "expand_kv before chunked_attention"
    Sk = k.shape[1]
    Dv = v.shape[-1]
    qb = _pick_block(Sq, q_block)
    kb = _pick_block(Sk, kv_block)
    nq, nk = Sq // qb, Sk // kb
    scale = 1.0 / math.sqrt(D)

    def qblock(qkv, jj, i):
        q_, k_, v_ = qkv

        def inner(carry, j):
            m, l, acc = carry
            qi = lax.dynamic_slice_in_dim(q_, i * qb, qb, axis=1)
            kj = lax.dynamic_slice_in_dim(k_, j * kb, kb, axis=1)
            vj = lax.dynamic_slice_in_dim(v_, j * kb, kb, axis=1)
            s = jnp.einsum("bqhd,bshd->bhqs", qi, kj,
                           preferred_element_type=F32) * scale
            if logit_cap:
                s = logit_cap * jnp.tanh(s / logit_cap)
            qpos = i * qb + jnp.arange(qb) + q_offset
            kpos = j * kb + jnp.arange(kb)
            msk = jnp.ones((qb, kb), dtype=bool)
            if causal:
                msk &= kpos[None, :] <= qpos[:, None]
            if window:
                msk &= qpos[:, None] - kpos[None, :] < window
            s = jnp.where(msk[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(jnp.minimum(m - m_new, 0.0))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(msk[None, None], p, 0.0)
            l_new = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bhqs,bshd->bhqd", p.astype(v_.dtype), vj,
                            preferred_element_type=F32)
            acc = acc * alpha[..., None] + pv
            return (m_new, l_new, acc), None

        m0 = jnp.full((B, H, qb), NEG_INF, dtype=F32)
        l0 = jnp.zeros((B, H, qb), dtype=F32)
        a0 = jnp.zeros((B, H, qb, Dv), dtype=F32)
        (m, l, acc), _ = lax.scan(jax.checkpoint(inner), (m0, l0, a0), jj)
        return acc / jnp.maximum(l, 1e-30)[..., None]

    outs = []
    for i in range(nq):
        js = [j for j in range(nk)
              if mode != "tri" or _visible(i, j, qb, kb, q_offset, causal,
                                           window)]
        jj = jnp.array(js, dtype=jnp.int32)
        outs.append(jax.checkpoint(qblock, static_argnums=(2,))(
            (q, k, v), jj, i))
    out = jnp.concatenate(outs, axis=2)                 # (B,H,Sq,Dv)
    out = jnp.moveaxis(out, 1, 2)                       # (B,Sq,H,Dv)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0):
    """Single-token decode attention over a (possibly sharded) KV cache.

    q: (B,1,H,D); k_cache/v_cache: (B,S,K,D[v]); pos: scalar or (B,) —
    the position of the *current* token (already written into the cache).
    """
    B, _, H, D = q.shape
    S, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    qr = q.reshape(B, K, G, D)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache,
                   preferred_element_type=F32) * scale
    idx = jnp.arange(S)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    valid = idx[None, :] <= pos_b[:, None]
    if window:
        valid &= idx[None, :] > (pos_b[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=F32)
    return o.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


def paged_kv_gather(k_pool, v_pool, tables):
    """Gather per-row block tables to a contiguous (B, nb*block, K, dh)
    view.  With nb*block equal to the gather-mode cache's max_len this
    produces the same shapes (hence the same XLA program) as dense
    decode over a contiguous cache; positions past each row's length
    hold unrelated block contents, but ``decode_attention`` masks them
    to NEG_INF before any reduction, so their softmax weight underflows
    to exactly 0.0 and the outputs stay bit-identical."""
    B, nb = tables.shape
    blk = k_pool.shape[1]
    k = k_pool[tables].reshape(B, nb * blk, k_pool.shape[2],
                               k_pool.shape[3])
    v = v_pool[tables].reshape(B, nb * blk, v_pool.shape[2],
                               v_pool.shape[3])
    return k, v


def gqa_attention_decode_paged(x, p, cfg, env, k_pool, v_pool, layer,
                               tables, pos, block_ids, offsets, *,
                               kernel: bool = False):
    """One-token decode over pool blocks: the twin of
    ``gqa_attention_decode`` with the contiguous (B, S, K, dh) cache
    replaced by (pool, block-table) pairs.  ``k_pool``/``v_pool`` are
    the whole (L, num_blocks, block, K, dh) pool and ``layer`` the layer
    this call reads and writes.  Writes the new token's K/V in place at
    ``[layer, block_ids, offsets]`` (an out-of-range block id, an idle
    row's sentinel, writes nothing), then attends: with ``kernel`` the
    Pallas block-table kernel reads each row's live blocks in place;
    otherwise the reference gathers every row out to ``max_len`` for
    ``decode_attention``.  Returns (y, k_pool, v_pool)."""
    B = x.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    q = jnp.einsum("bsd,dhx->bshx", x, p["wq"])
    k = jnp.einsum("bsd,dkx->bskx", x, p["wk"])
    v = jnp.einsum("bsd,dkx->bskx", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["qnorm"], cfg.norm_eps)
        k = rms_norm(k, p["knorm"], cfg.norm_eps)
    q = apply_rope(q, pos_b[:, None], cfg.rope_theta)
    k = apply_rope(k, pos_b[:, None], cfg.rope_theta)
    k_pool = k_pool.at[layer, block_ids, offsets].set(
        k[:, 0].astype(k_pool.dtype), mode="drop")
    v_pool = v_pool.at[layer, block_ids, offsets].set(
        v[:, 0].astype(v_pool.dtype), mode="drop")
    if kernel:
        lens = jnp.where(block_ids < k_pool.shape[1], pos_b + 1, 0)
        y = paged_decode_attention(q[:, 0], k_pool, v_pool, layer, tables,
                                   lens, window=cfg.sliding_window)[:, None]
    else:
        kg, vg = paged_kv_gather(k_pool[layer], v_pool[layer], tables)
        y = decode_attention(q, kg, vg, pos_b, window=cfg.sliding_window)
    return jnp.einsum("bshx,hxd->bsd", y, p["wo"]), k_pool, v_pool


# ---------------------------------------------------------------------------
# sqrt(T)-remat sequential scan (mamba / rwkv training)
# ---------------------------------------------------------------------------
def seq_scan(step, carry0, xs, *, chunk: int = 64):
    """lax.scan with two-level sqrt(T) rematerialization.

    Differentiating a length-T scan stores the carry at every step; for
    T=4096 state scans that is tens of GB.  Chunking into sqrt(T)-sized
    checkpointed sub-scans bounds backward residuals to
    O((T/chunk + chunk) * carry).
    """
    S = jax.tree_util.tree_leaves(xs)[0].shape[0]
    if S <= chunk or S % chunk != 0:
        return lax.scan(step, carry0, xs)
    n = S // chunk
    xs_r = jax.tree_util.tree_map(
        lambda a: a.reshape((n, chunk) + a.shape[1:]), xs)

    def outer(c, xc):
        return lax.scan(step, c, xc)

    cT, ys = lax.scan(jax.checkpoint(outer), carry0, xs_r)
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape((S,) + a.shape[2:]), ys)
    return cT, ys


# ---------------------------------------------------------------------------
# GQA attention layer (wq/wk/wv/wo), shared by dense/moe/vlm archs
# ---------------------------------------------------------------------------
def _attn_q_spec(cfg, env: ShardingEnv):
    """Shard q heads over 'model' if divisible; otherwise run attention
    pure-DP with batch over (data x model).  (Sharding head_dim instead
    all-reduces every score tile — measured 403 GB/device/step on
    llama3.2 train_4k; the batch reshard is 16x cheaper.)"""
    if env.heads_shardable(cfg.n_heads):
        return (env.batch_axes, None, "model", None)
    combined = tuple(env.batch_axes) + ("model",)
    return (combined, None, None, None)


def gqa_qkv(x, p, cfg, env: ShardingEnv, positions):
    """Project + rope.  Head-factored weights (d,H,dh)/(d,K,dh) — no
    flat<->grouped reshapes, so GSPMD never hits an involuntary
    resharding.  Returns q (B,S,H,D), k,v (B,S,K,D)."""
    q = jnp.einsum("bsd,dhx->bshx", x, p["wq"])
    k = jnp.einsum("bsd,dkx->bskx", x, p["wk"])
    v = jnp.einsum("bsd,dkx->bskx", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["qnorm"], cfg.norm_eps)
        k = rms_norm(k, p["knorm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = env.cs(q, *_attn_q_spec(cfg, env))
    k = env.cs(k, env.batch_axes, None, None, None)
    v = env.cs(v, env.batch_axes, None, None, None)
    return q, k, v


def gqa_attention_full(x, p, cfg, env, positions, *, causal=True,
                       kv_override=None, attn_mode="full",
                       bwd_safe=False):
    """Full-sequence attention (train / prefill).  Returns (y, k, v)."""
    q, k, v = gqa_qkv(x, p, cfg, env, positions)
    if kv_override is not None:                 # cross-attention
        k, v = kv_override
    kx = env.cs(expand_kv(k, cfg.n_heads), *_attn_q_spec(cfg, env))
    vx = env.cs(expand_kv(v, cfg.n_heads), *_attn_q_spec(cfg, env))
    y = chunked_attention(q, kx, vx, causal=causal,
                          window=cfg.sliding_window, mode=attn_mode,
                          logit_cap=cfg.attn_logit_softcap,
                          bwd_safe=bwd_safe,
                          q_block=env.opts.get("attn_block", 512),
                          kv_block=env.opts.get("attn_block", 512),
                          unroll_pairs=env.opts.get("unroll_pairs", False))
    if env.opts.get("rs_matmul") and env.heads_shardable(cfg.n_heads):
        return rs_out_proj(y, p["wo"], env, "bshx,hxd->bsd"), k, v
    return jnp.einsum("bshx,hxd->bsd", y, p["wo"]), k, v


def gqa_attention_decode(x, p, cfg, env, k_cache, v_cache, pos):
    """One-token decode.  Returns (y, new_k_cache, new_v_cache)."""
    B = x.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))
    q = jnp.einsum("bsd,dhx->bshx", x, p["wq"])
    k = jnp.einsum("bsd,dkx->bskx", x, p["wk"])
    v = jnp.einsum("bsd,dkx->bskx", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["qnorm"], cfg.norm_eps)
        k = rms_norm(k, p["knorm"], cfg.norm_eps)
    q = apply_rope(q, pos_b[:, None], cfg.rope_theta)
    k = apply_rope(k, pos_b[:, None], cfg.rope_theta)
    k_cache = _cache_insert(k_cache, k, pos)
    v_cache = _cache_insert(v_cache, v, pos)
    y = decode_attention(q, k_cache, v_cache, pos_b,
                         window=cfg.sliding_window)
    return jnp.einsum("bshx,hxd->bsd", y, p["wo"]), k_cache, v_cache


def _cache_insert(cache, item, pos):
    """Insert (B,1,...) item into (B,S,...) cache at position(s) ``pos``.

    A scalar position (dry-run / uniform batch) uses a single DUS —
    SPMD-friendly on a sharded seq dim.  Per-batch (B,) positions use a
    vmapped DUS (lowers to scatter; used by the CPU engine).
    """
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        start = (0, pos) + (0,) * (cache.ndim - 2)
        return lax.dynamic_update_slice(cache, item.astype(cache.dtype), start)

    def upd(c, it, p):
        return lax.dynamic_update_slice(c, it.astype(c.dtype),
                                        (p,) + (0,) * (c.ndim - 1))
    return jax.vmap(upd)(cache, item, pos)


# ---------------------------------------------------------------------------
# MLA attention (deepseek-v2)
# ---------------------------------------------------------------------------
def mla_attention_full(x, p, cfg, env, positions, *, attn_mode="full",
                       bwd_safe=False):
    """Training / prefill MLA.  Returns (y, ckv_cache, krope_cache)."""
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    hspec = _attn_q_spec(cfg, env)
    cq = rms_norm(x @ p["wdq"], p["q_ln"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhx->bshx", cq, p["wuq"])
    q = env.cs(q, *hspec)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv_full = x @ p["wdkv"]
    ckv = rms_norm(ckv_full[..., :cfg.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
    k_rope = ckv_full[..., cfg.kv_lora_rank:]
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)          # (B,S,1,rope)

    kv = jnp.einsum("bsr,rhx->bshx", ckv, p["wukv"])
    kv = env.cs(kv, *hspec)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rope_d))], axis=-1)
    k = env.cs(k, *hspec)
    v = env.cs(v, *hspec)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    q_full = env.cs(q_full, *hspec)
    y = chunked_attention(q_full, k, v, causal=True, mode=attn_mode,
                          bwd_safe=bwd_safe,
                          q_block=env.opts.get("attn_block", 512),
                          kv_block=env.opts.get("attn_block", 512),
                          unroll_pairs=env.opts.get("unroll_pairs", False))
    return jnp.einsum("bshv,hvd->bsd", y, p["wo"]), ckv, k_rope[:, :, 0, :]


def mla_attention_decode(x, p, cfg, env, ckv_cache, krope_cache, pos):
    """Absorbed-matrix MLA decode over the compressed latent cache."""
    B = x.shape[0]
    H = cfg.n_heads
    nope, rope_d, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))

    cq = rms_norm(x @ p["wdq"], p["q_ln"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhx->bshx", cq, p["wuq"])       # (B,1,H,*)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, pos_b[:, None], cfg.rope_theta)

    ckv_full = x @ p["wdkv"]                            # (B,1,r+rope)
    ckv_new = rms_norm(ckv_full[..., :r], p["kv_ln"], cfg.norm_eps)
    krope_new = apply_rope(ckv_full[:, :, None, r:], pos_b[:, None],
                           cfg.rope_theta)[:, :, 0, :]
    ckv_cache = _cache_insert(ckv_cache, ckv_new, pos)
    krope_cache = _cache_insert(krope_cache, krope_new, pos)

    wukv = p["wukv"]                                   # (r, H, nope+vd)
    wk_b, wv_b = wukv[..., :nope], wukv[..., nope:]
    q_lat = jnp.einsum("bxhn,rhn->bhr", q_nope, wk_b,
                       preferred_element_type=F32)      # x==1
    scale = 1.0 / math.sqrt(nope + rope_d)
    s = (jnp.einsum("bhr,bsr->bhs", q_lat,
                    ckv_cache.astype(F32)) +
         jnp.einsum("bxhp,bsp->bhs", q_rope.astype(F32),
                    krope_cache.astype(F32))) * scale
    S = ckv_cache.shape[1]
    valid = jnp.arange(S)[None, :] <= pos_b[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", pr, ckv_cache.astype(F32))
    o = jnp.einsum("bhr,rhv->bhv", o_lat, wv_b.astype(F32))
    y = o[:, None].astype(x.dtype)                     # (B,1,H,vd)
    return jnp.einsum("bshv,hvd->bsd", y, p["wo"]), ckv_cache, krope_cache


# ---------------------------------------------------------------------------
# reduce-scatter TP matmul (beyond-paper §Perf lever)
# ---------------------------------------------------------------------------
def rs_out_proj(y, w, env: ShardingEnv, einsum_str: str):
    """Tensor-parallel output projection with an explicit
    psum_scatter("model") onto the SEQUENCE dim, producing the
    sequence-parallel layout directly (half the bytes of the all-reduce
    XLA otherwise emits).  Used when opts['rs_matmul'] is set and the
    contraction dims are 'model'-sharded."""
    bt = env.batch_axes
    S = y.shape[1]
    if (env.tp <= 1 or S % env.tp != 0
            or not env.opts.get("rs_matmul", False)):
        return jnp.einsum(einsum_str, y, w)
    d_out = w.shape[-1]
    y_spec = env.spec(y.shape, [bt, None, "model", None])
    w_spec = env.spec(w.shape, ["model", None, env.fsdp_axis])
    out_spec = env.spec((y.shape[0], S, d_out), [bt, "model", None])
    if w_spec[-1] is not None:          # FSDP'd weight: gather inside
        pass

    def body(yb, wb):
        if wb.shape[-1] != d_out:       # FSDP shard: gather over data
            wb = lax.all_gather(wb, env.fsdp_axis, axis=2, tiled=True)
        part = jnp.einsum(einsum_str, yb, wb)
        return lax.psum_scatter(part, "model", scatter_dimension=1,
                                tiled=True)

    fn = jax.shard_map(body, mesh=env.mesh, in_specs=(y_spec, w_spec),
                       out_specs=out_spec, check_vma=False)
    return fn(y, w)


# ---------------------------------------------------------------------------
# dense FFN (SwiGLU)
# ---------------------------------------------------------------------------
def ffn_swiglu(x, p, env: ShardingEnv):
    h = jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])
    h = env.cs(h, env.batch_axes, None, "model")
    return h @ p["w2"]


# ---------------------------------------------------------------------------
# MoE — dense reference (oracle; small shapes only)
# ---------------------------------------------------------------------------
def moe_router(x2d, router_w, top_k: int):
    logits = (x2d @ router_w).astype(F32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_e


def moe_dense_ref(x2d, p, cfg):
    """Computes every expert then masks — exact oracle for moe_ep."""
    top_p, top_e = moe_router(x2d, p["router"], cfg.top_k)
    h1 = jnp.einsum("td,edf->tef", x2d, p["w1"])
    h3 = jnp.einsum("td,edf->tef", x2d, p["w3"])
    h = jax.nn.silu(h1) * h3
    y_e = jnp.einsum("tef,efd->ted", h, p["w2"])        # (T,E,d)
    T = x2d.shape[0]
    gate = jnp.zeros((T, cfg.n_experts), dtype=F32)
    gate = gate.at[jnp.arange(T)[:, None], top_e].add(top_p)
    y = jnp.einsum("ted,te->td", y_e.astype(F32), gate)
    return y.astype(x2d.dtype)


# ---------------------------------------------------------------------------
# MoE — capacity-buffer dispatch (local math, shared by ep/single-device)
# ---------------------------------------------------------------------------
def _moe_local(x2d, router_w, w1, w3, w2, *, n_experts: int, top_k: int,
               e_start: int, e_local: int, capacity: int):
    """Route local tokens to experts [e_start, e_start+e_local) with a
    static-capacity buffer.  All ops are local (no collectives) so this is
    safe inside shard_map."""
    T, d = x2d.shape
    top_p, top_e = moe_router(x2d, router_w, top_k)     # (T,k)
    flat_e = top_e.reshape(-1)
    flat_p = top_p.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)

    local = (flat_e >= e_start) & (flat_e < e_start + e_local)
    loc_e = jnp.where(local, flat_e - e_start, e_local)  # overflow bucket
    order = jnp.argsort(loc_e, stable=True)
    s_e = loc_e[order]
    s_t = flat_t[order]
    s_p = flat_p[order]
    counts = jnp.bincount(s_e, length=e_local + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(s_e.shape[0]) - starts[s_e]
    keep = (pos < capacity) & (s_e < e_local)
    slot = jnp.where(keep, s_e * capacity + pos, e_local * capacity)

    buf = jnp.zeros((e_local * capacity + 1, d), dtype=x2d.dtype)
    buf = buf.at[slot].set(jnp.where(keep[:, None], x2d[s_t], 0))
    buf = buf[:-1].reshape(e_local, capacity, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w1)) * \
        jnp.einsum("ecd,edf->ecf", buf, w3)
    out = jnp.einsum("ecf,efd->ecd", h, w2)             # (e_local,C,d)

    rows = out.reshape(e_local * capacity, -1)
    gathered = jnp.where(keep[:, None], rows[jnp.minimum(slot, rows.shape[0] - 1)], 0)
    y = jnp.zeros((T, rows.shape[-1]), dtype=F32)
    y = y.at[s_t].add(gathered.astype(F32) * s_p[:, None])
    return y.astype(x2d.dtype)


def moe_ep(x, p, cfg, env: ShardingEnv, capacity_factor: float = 1.25):
    """Expert-parallel MoE via shard_map over the 'model' axis.

    Experts shard over 'model' when divisible (deepseek 160, jamba 16);
    otherwise every shard computes all experts over a d_ff slice
    (mixtral 8 experts over tp=16).  Expert weights are FSDP-sharded over
    'data' on d_model and all-gathered inside the body.
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    if env.mesh is None:
        y2 = moe_dense_ref(x.reshape(-1, d), p, cfg)
        return y2.reshape(B, S, d)

    ep = env.moe_ep(E)
    fullshard = env.opts.get("serve_fullshard") and ep and \
        "data" in env.axis_sizes
    tp_ax, fsdp_ax = env.tp_axis, env.fsdp_axis or "data"
    bt = None if fullshard else env.batch_axes
    x_spec = env.spec(x.shape, [bt, None, None])
    r_spec = env.spec(p["router"].shape,
                      [None if fullshard else env.fsdp_axis, None])
    if fullshard:
        # experts over 'model', d_model over 'data': weights fully
        # sharded 256-way; tokens replicated; partial-d contraction +
        # psum("data") replaces the FSDP weight all-gather entirely.
        w1_spec = env.spec(p["w1"].shape, [tp_ax, "data", None])
        w2_spec = env.spec(p["w2"].shape, [tp_ax, None, "data"])
    elif ep:
        w1_spec = env.spec(p["w1"].shape, [tp_ax, env.fsdp_axis, None])
        w2_spec = env.spec(p["w2"].shape, [tp_ax, None, env.fsdp_axis])
    else:
        w1_spec = env.spec(p["w1"].shape, [None, env.fsdp_axis, tp_ax])
        w2_spec = env.spec(p["w2"].shape, [None, tp_ax, env.fsdp_axis])
    out_spec = x_spec

    e_local = E // env.tp if ep else E
    # tokens per data-shard replica inside the body (use the PRUNED spec:
    # divisibility pruning may have left the batch replicated):
    b_shards = env.axis_size(x_spec[0]) if len(x_spec) else 1
    t_local = (B // max(b_shards, 1)) * S
    capacity = max(4, int(math.ceil(t_local * k / E * capacity_factor)))
    d_local = d // env.axis_sizes.get("data", 1)

    def body_fullshard(xb, rw, w1, w3, w2):
        T = xb.shape[0] * xb.shape[1]
        x2 = xb.reshape(T, d)
        e0 = lax.axis_index(tp_ax) * e_local
        top_p, top_e = moe_router(x2, rw, k)
        flat_e = top_e.reshape(-1)
        flat_p = top_p.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), k)
        local = (flat_e >= e0) & (flat_e < e0 + e_local)
        loc_e = jnp.where(local, flat_e - e0, e_local)
        order = jnp.argsort(loc_e, stable=True)
        s_e, s_t, s_p = loc_e[order], flat_t[order], flat_p[order]
        counts = jnp.bincount(s_e, length=e_local + 1)
        starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        pos = jnp.arange(s_e.shape[0]) - starts[s_e]
        keep = (pos < capacity) & (s_e < e_local)
        slot = jnp.where(keep, s_e * capacity + pos, e_local * capacity)
        # dispatch only the LOCAL d-slice of each token
        didx = lax.axis_index("data") * d_local
        x2l = lax.dynamic_slice_in_dim(x2, didx, d_local, axis=1)
        buf = jnp.zeros((e_local * capacity + 1, d_local), dtype=x2.dtype)
        buf = buf.at[slot].set(jnp.where(keep[:, None], x2l[s_t], 0))
        buf = buf[:-1].reshape(e_local, capacity, d_local)
        # partial-d contraction + psum over 'data' (weights never move)
        h1 = lax.psum(jnp.einsum("ecd,edf->ecf", buf, w1), "data")
        h3 = lax.psum(jnp.einsum("ecd,edf->ecf", buf, w3), "data")
        h = jax.nn.silu(h1) * h3
        out = jnp.einsum("ecf,efd->ecd", h, w2)   # (e_local, C, d_local)
        rows = out.reshape(e_local * capacity, d_local)
        gathered = jnp.where(keep[:, None],
                             rows[jnp.minimum(slot, rows.shape[0] - 1)], 0)
        y2 = jnp.zeros((T, d_local), dtype=F32)
        y2 = y2.at[s_t].add(gathered.astype(F32) * s_p[:, None])
        y2 = lax.psum(y2, tp_ax)                  # combine experts
        y2 = lax.all_gather(y2, "data", axis=1, tiled=True)  # (T, d)
        return y2.astype(xb.dtype).reshape(xb.shape)

    def body(xb, rw, w1, w3, w2):
        T = xb.shape[0] * xb.shape[1]
        x2 = xb.reshape(T, d)
        rw = _maybe_gather(rw, env.fsdp_axis, 0, env, p["router"].shape[0])
        w1 = _maybe_gather(w1, env.fsdp_axis, 1, env, p["w1"].shape[1])
        w3 = _maybe_gather(w3, env.fsdp_axis, 1, env, p["w3"].shape[1])
        w2 = _maybe_gather(w2, env.fsdp_axis, 2, env, p["w2"].shape[2])
        if ep:
            e0 = lax.axis_index(tp_ax) * e_local
        else:
            e0 = 0
        y2 = _moe_local(x2, rw, w1, w3, w2, n_experts=E, top_k=k,
                        e_start=e0, e_local=e_local, capacity=capacity)
        y2 = lax.psum(y2, tp_ax)
        return y2.reshape(xb.shape)

    fn = jax.shard_map(body_fullshard if fullshard else body,
                       mesh=env.mesh,
                       in_specs=(x_spec, r_spec, w1_spec, w1_spec, w2_spec),
                       out_specs=out_spec, check_vma=False)
    return fn(x, p["router"], p["w1"], p["w3"], p["w2"])


def _maybe_gather(w, axis_name, dim, env, full_dim):
    """all_gather a weight block along `axis_name` if it was FSDP-sharded."""
    if axis_name is None or env.axis_sizes.get(axis_name, 1) == 1:
        return w
    if w.shape[dim] == full_dim:    # divisibility pruning left it whole
        return w
    return lax.all_gather(w, axis_name, axis=dim, tiled=True)


def moe_block(x, p, cfg, env: ShardingEnv, impl: str = "ep"):
    """MoE FFN + optional shared experts."""
    B, S, d = x.shape
    if impl == "dense" or env.mesh is None:
        y = moe_dense_ref(x.reshape(-1, d), p, cfg).reshape(B, S, d)
    else:
        y = moe_ep(x, p, cfg, env)
    if cfg.n_shared_experts:
        y = y + ffn_swiglu(x, {"w1": p["ws1"], "w3": p["ws3"],
                               "w2": p["ws2"]}, env)
    return y
