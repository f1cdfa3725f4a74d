"""Decoder-only LM assembly: init, sharding rules, train / prefill / decode.

Families handled here: dense, moe, vlm (patch-prefix), hybrid (jamba
superblocks), ssm (rwkv6).  Encoder-decoder (seamless) lives in
``repro.models.encdec`` and is dispatched via ``repro.models.api``.

Conventions:
  * params are bf16; math accumulates in f32 where it matters.
  * uniform archs scan over stacked layer params; jamba scans over
    superblocks of ``attn_period`` python-unrolled slots.
  * caches: dense/moe/vlm {k,v}: (L,B,Smax,K,dh); MLA {ckv,krope};
    hybrid adds {conv,ssm}; rwkv {wkv,shift_tm,shift_cm}.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.configs.base import ModelConfig, ATTN, MAMBA
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import rwkv as R
from repro.models.sharding import ShardingEnv

F32 = jnp.float32
BF16 = jnp.bfloat16


# ===========================================================================
# init
# ===========================================================================
def _dense(key, shape, scale=0.02):
    return (jax.random.normal(key, shape, dtype=F32) * scale).astype(BF16)


def _keys(key, n):
    return jax.random.split(key, n)


def _init_attn(key, cfg: ModelConfig) -> Dict[str, Any]:
    d, H, K, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = _keys(key, 8)
    if cfg.use_mla:
        nope, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        p = {
            "wdq": _dense(ks[0], (d, cfg.q_lora_rank)),
            "q_ln": jnp.ones((cfg.q_lora_rank,), BF16),
            "wuq": _dense(ks[1], (cfg.q_lora_rank, H, nope + rd)),
            "wdkv": _dense(ks[2], (d, cfg.kv_lora_rank + rd)),
            "kv_ln": jnp.ones((cfg.kv_lora_rank,), BF16),
            "wukv": _dense(ks[3], (cfg.kv_lora_rank, H, nope + vd)),
            "wo": _dense(ks[4], (H, vd, d)),
        }
        return p
    p = {
        "wq": _dense(ks[0], (d, H, dh)),
        "wk": _dense(ks[1], (d, K, dh)),
        "wv": _dense(ks[2], (d, K, dh)),
        "wo": _dense(ks[3], (H, dh, d)),
    }
    if cfg.qk_norm:
        p["qnorm"] = jnp.ones((dh,), BF16)
        p["knorm"] = jnp.ones((dh,), BF16)
    return p


def _init_ffn(key, cfg: ModelConfig, d_ff: Optional[int] = None):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = _keys(key, 3)
    return {"w1": _dense(ks[0], (d, f)), "w3": _dense(ks[1], (d, f)),
            "w2": _dense(ks[2], (f, d))}


def _init_moe(key, cfg: ModelConfig):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = _keys(key, 7)
    p = {
        "router": _dense(ks[0], (d, E)),
        "w1": _dense(ks[1], (E, d, f)),
        "w3": _dense(ks[2], (E, d, f)),
        "w2": _dense(ks[3], (E, f, d)),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        p["ws1"] = _dense(ks[4], (d, fs))
        p["ws3"] = _dense(ks[5], (d, fs))
        p["ws2"] = _dense(ks[6], (fs, d))
    return p


def _init_mamba(key, cfg: ModelConfig):
    d, di, ds = cfg.d_model, cfg.mamba_d_inner, cfg.mamba_d_state
    dtr = cfg.dt_rank
    ks = _keys(key, 6)
    A = jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=F32)[None, :], (di, ds))
    return {
        "in_proj": _dense(ks[0], (d, 2 * di)),
        "conv_w": _dense(ks[1], (di, cfg.mamba_d_conv), 0.2),
        "conv_b": jnp.zeros((di,), BF16),
        "x_proj": _dense(ks[2], (di, dtr + 2 * ds)),
        "dt_w": _dense(ks[3], (dtr, di)),
        "dt_b": jnp.full((di,), -4.6, BF16),
        "A_log": jnp.log(A),
        "D": jnp.ones((di,), BF16),
        "out_proj": _dense(ks[4], (di, d)),
    }


def _init_rwkv(key, cfg: ModelConfig):
    d, H, hs, f = cfg.d_model, cfg.rwkv_n_heads, cfg.rwkv_head_size, cfg.d_ff
    ks = _keys(key, 12)
    dec = -5.0 + 8.0 * (jnp.arange(d, dtype=F32) / max(d - 1, 1)) ** 0.7
    tm = {
        "maa_x": jnp.zeros((d,), BF16), "maa_w": jnp.zeros((d,), BF16),
        "maa_k": jnp.zeros((d,), BF16), "maa_v": jnp.zeros((d,), BF16),
        "maa_r": jnp.zeros((d,), BF16), "maa_g": jnp.zeros((d,), BF16),
        "maa_w1": _dense(ks[0], (d, 5 * R.DDLERP_W), 0.01),
        "maa_w2": _dense(ks[1], (5, R.DDLERP_W, d), 0.01),
        "decay": dec.astype(BF16),
        "decay_w1": _dense(ks[2], (d, R.DECAY_W), 0.01),
        "decay_w2": _dense(ks[3], (R.DECAY_W, d), 0.01),
        "faaaa": _dense(ks[4], (H, hs), 0.5),
        "Wr": _dense(ks[5], (d, d)), "Wk": _dense(ks[6], (d, d)),
        "Wv": _dense(ks[7], (d, d)), "Wg": _dense(ks[8], (d, d)),
        "Wo": _dense(ks[9], (d, d)),
        "ln_x": jnp.ones((d,), BF16),
    }
    cm = {
        "cmix_maa_k": jnp.zeros((d,), BF16),
        "cmix_maa_r": jnp.zeros((d,), BF16),
        "Wck": _dense(ks[10], (d, f)),
        "Wcv": _dense(ks[11], (f, d)),
        "Wcr": _dense(ks[0], (d, d)),
    }
    return {"tm": tm, "cm": cm}


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    if cfg.enc_dec:
        from repro.models import encdec
        return encdec.init_params(cfg, key)
    d = cfg.d_model
    k_emb, k_un, k_layers = _keys(key, 3)
    params: Dict[str, Any] = {
        "embed": _dense(k_emb, (cfg.vocab, d)),
        "final_norm": jnp.ones((d,), BF16),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = _dense(k_un, (d, cfg.vocab))

    if cfg.family == "ssm":
        ls = []
        for i in range(cfg.n_layers):
            kk = jax.random.fold_in(k_layers, i)
            blk = _init_rwkv(kk, cfg)
            blk["ln1"] = jnp.ones((d,), BF16)
            blk["ln2"] = jnp.ones((d,), BF16)
            ls.append(blk)
        params["layers"] = _stack(ls)
        return params

    if cfg.attn_period:   # jamba superblocks
        per = cfg.attn_period
        nsb = cfg.n_layers // per
        sbs = []
        for s in range(nsb):
            kk = jax.random.fold_in(k_layers, s)
            sb: Dict[str, Any] = {}
            sb["attn"] = _init_attn(jax.random.fold_in(kk, 0), cfg)
            sb["attn_ln"] = jnp.ones((d,), BF16)
            mams, moes, ffns = [], [], []
            for slot in range(per):
                kk2 = jax.random.fold_in(kk, 100 + slot)
                gi = s * per + slot
                if cfg.layer_kind(gi) == MAMBA:
                    mams.append(_init_mamba(kk2, cfg))
                if cfg.layer_is_moe(gi):
                    moes.append(_init_moe(jax.random.fold_in(kk2, 1), cfg))
                else:
                    ffns.append(_init_ffn(jax.random.fold_in(kk2, 2), cfg))
            sb["mamba"] = _stack(mams)
            sb["mamba_ln"] = jnp.ones((len(mams), d), BF16)
            sb["moe"] = _stack(moes)
            sb["moe_ln"] = jnp.ones((len(moes), d), BF16)
            sb["ffn"] = _stack(ffns)
            sb["ffn_ln"] = jnp.ones((len(ffns), d), BF16)
            sbs.append(sb)
        params["superblocks"] = _stack(sbs)
        return params

    # uniform decoder (dense / moe / vlm)
    ls = []
    for i in range(cfg.n_layers):
        kk = jax.random.fold_in(k_layers, i)
        blk = {
            "ln1": jnp.ones((d,), BF16),
            "ln2": jnp.ones((d,), BF16),
            "attn": _init_attn(jax.random.fold_in(kk, 0), cfg),
        }
        if cfg.layer_is_moe(i):
            blk["mlp"] = _init_moe(jax.random.fold_in(kk, 1), cfg)
        else:
            blk["mlp"] = _init_ffn(jax.random.fold_in(kk, 1), cfg)
        ls.append(blk)
    params["layers"] = _stack(ls)
    return params


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ===========================================================================
# sharding rules
# ===========================================================================
_COL = {"w1", "w3", "wdq", "wdkv", "in_proj", "x_proj", "dt_w", "ws1",
        "ws3", "Wr", "Wk", "Wv", "Wg", "Wck", "Wcr", "maa_w1", "decay_w1"}
_ROW = {"w2", "out_proj", "ws2", "Wo", "Wcv", "decay_w2"}


def param_rules(cfg: ModelConfig, env: ShardingEnv):
    """rules(path, shape) -> per-dim axis wish list (divisibility-pruned
    later by ShardingEnv.spec)."""
    fsdp, tp = env.fsdp_axis, env.tp_axis

    def rules(path: str, shape):
        name = path.split("/")[-1]
        rank = len(shape)
        if name == "embed":
            base = [tp, None]
        elif name == "unembed":
            base = [None, tp]
        elif name in ("conv_w", "A_log"):
            base = [tp, None]
        elif name in ("conv_b", "D", "dt_b"):
            base = [tp]
        elif name == "faaaa":
            base = [tp, None]
        elif name == "router":
            base = [fsdp, None]
        elif name in ("wq", "wuq", "wukv"):
            # (d|r, H, dh): shard heads over tp if divisible, else head_dim
            if env.heads_shardable(cfg.n_heads):
                base = [fsdp, tp, None]
            else:
                base = [fsdp, None, tp]
        elif name in ("wk", "wv"):
            base = [fsdp, None, None]          # kv heads replicated over tp
        elif name == "wo":
            if env.heads_shardable(cfg.n_heads):
                base = [tp, None, fsdp]
            else:
                base = [None, tp, fsdp]
        elif name in _COL:
            base = [fsdp, tp]
        elif name in _ROW:
            base = [tp, fsdp]
        else:
            base = [None] * min(rank, 2)
        if name in ("w1", "w3", "w2") and rank - _n_stack(path) == 3:
            # MoE expert weights
            ep = env.moe_ep(cfg.n_experts)
            if name == "w2":
                base = [tp, None, fsdp] if ep else [None, tp, fsdp]
            else:
                base = [tp, fsdp, None] if ep else [None, fsdp, tp]
        pad = rank - len(base)
        return [None] * pad + base

    return rules


def _n_stack(path: str) -> int:
    n = 0
    if path.startswith("layers/") or "/layers/" in path:
        n = 1
    if "superblocks" in path:
        parts = path.split("/")
        n = 1 + (1 if parts[-2] in ("mamba", "moe", "ffn") else 0)
    return n


def param_shardings(cfg: ModelConfig, env: ShardingEnv):
    from repro.models.sharding import param_pspecs
    return param_pspecs(abstract_params(cfg), env, param_rules(cfg, env))


# ===========================================================================
# embedding / logits / loss
# ===========================================================================
def embed_tokens(params, tokens, cfg):
    return jnp.take(params["embed"], tokens, axis=0)


def unembed(params, x, cfg):
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
    return jnp.einsum("bsd,dv->bsv", x, w, preferred_element_type=F32)


def chunked_xent(params, x, labels, cfg, env: ShardingEnv):
    """Scan-chunked softmax cross-entropy (labels -100 are masked)."""
    B, S, d = x.shape
    c = L._pick_block(S, env.opts.get("loss_chunk", 512))
    n = S // c
    w = params["embed"].T if cfg.tie_embeddings else params["unembed"]

    def body(carry, i):
        tot, cnt = carry
        xs = lax.dynamic_slice_in_dim(x, i * c, c, axis=1)
        ls = lax.dynamic_slice_in_dim(labels, i * c, c, axis=1)
        logits = jnp.einsum("bsd,dv->bsv", xs, w,
                            preferred_element_type=F32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        safe = jnp.maximum(ls, 0)
        lab = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        valid = (ls >= 0).astype(F32)
        tot = tot + jnp.sum((lse - lab) * valid)
        cnt = cnt + jnp.sum(valid)
        return (tot, cnt), None

    (tot, cnt), _ = lax.scan(jax.checkpoint(body),
                             (jnp.zeros((), F32), jnp.zeros((), F32)),
                             jnp.arange(n))
    return tot / jnp.maximum(cnt, 1.0)


# ===========================================================================
# layer stacks
# ===========================================================================
def layer_scan(body, carry, xs, env: ShardingEnv):
    """lax.scan over stacked layers, or a python unroll when
    env.opts['unroll_layers'] is set.

    The dry-run unrolls: XLA's HLO cost analysis counts a while-loop body
    ONCE regardless of trip count, so scanned models under-report
    flops/bytes/collectives by ~n_layers.  Unrolling restores exact
    accounting (and lets XLA schedule across layer boundaries).
    """
    if env.opts.get("unroll_layers", False):
        L = jax.tree_util.tree_leaves(xs)[0].shape[0]
        ys = []
        for i in range(L):
            sl = jax.tree_util.tree_map(lambda a: a[i], xs)
            carry, y = body(carry, sl)
            ys.append(y)
        if ys and ys[0] is not None:
            ys_out = jax.tree_util.tree_map(lambda *z: jnp.stack(z), *ys)
        else:
            ys_out = None
        return carry, ys_out
    return lax.scan(body, carry, xs)


def _res_cs(x, env, sp: bool):
    # pin the residual stream's bf16 rounding so the prefill/full and
    # decode graphs see bit-identical layer inputs (see L.pin_bf16)
    return env.cs(L.pin_bf16(x), env.batch_axes,
                  "model" if sp else None, None)


def _maybe_remat(fn, env):
    if not env.opts.get("remat", False):
        return fn
    policy = None
    if env.opts.get("remat_policy") == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
    return jax.checkpoint(fn, policy=policy)


def _uniform_block(x, lp, cfg, env, positions, *, collect_kv=False):
    opts = env.opts
    sp = opts.get("sp", True)
    bwd_safe = not collect_kv            # train path recomputes attn in bwd
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        y, c1, c2 = L.mla_attention_full(h, lp["attn"], cfg, env, positions,
                                         attn_mode=opts.get("attn_mode", "full"),
                                         bwd_safe=bwd_safe)
    else:
        y, c1, c2 = L.gqa_attention_full(h, lp["attn"], cfg, env, positions,
                                         attn_mode=opts.get("attn_mode", "full"),
                                         bwd_safe=bwd_safe)
    # constrain the contraction OUTPUT (not just the residual) so XLA can
    # lower the tensor-parallel all-reduce as a reduce-scatter into the
    # sequence-parallel layout (half the ICI bytes)
    y = _res_cs(y, env, sp)
    x = _res_cs(x + y, env, sp)
    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if "router" in lp["mlp"]:
        y = L.moe_block(h, lp["mlp"], cfg, env,
                        impl=opts.get("moe_impl", "ep"))
    else:
        y = L.ffn_swiglu(h, lp["mlp"], env)
    y = _res_cs(y, env, sp)
    x = _res_cs(x + y, env, sp)
    if collect_kv:
        c1 = env.cs(c1, env.batch_axes, "model", *([None] * (c1.ndim - 2)))
        c2 = env.cs(c2, env.batch_axes, "model", *([None] * (c2.ndim - 2)))
        return x, (c1, c2)
    return x, None


def _run_uniform(params, x, cfg, env, positions, *, collect_kv=False):
    def body(x, lp):
        return _uniform_block(x, lp, cfg, env, positions,
                              collect_kv=collect_kv)
    x, kv = layer_scan(_maybe_remat(body, env), x, params["layers"], env)
    return x, kv


def _uniform_decode_block(x, lp, kc, vc, cfg, env, pos):
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.use_mla:
        y, kc, vc = L.mla_attention_decode(h, lp["attn"], cfg, env, kc, vc, pos)
    else:
        y, kc, vc = L.gqa_attention_decode(h, lp["attn"], cfg, env, kc, vc, pos)
    # pin the sublayer output AND the residual add, mirroring
    # _uniform_block's _res_cs(y) / _res_cs(x + y) pair exactly, so
    # decode and prefill round the stream identically (L.pin_bf16)
    x = L.pin_bf16(x + L.pin_bf16(y))
    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if "router" in lp["mlp"]:
        y = L.moe_block(h, lp["mlp"], cfg, env,
                        impl=env.opts.get("moe_impl", "ep"))
    else:
        y = L.ffn_swiglu(h, lp["mlp"], env)
    return L.pin_bf16(x + L.pin_bf16(y)), kc, vc


def _uniform_decode_block_paged(x, lp, kp, vp, layer, tables, pos,
                                block_ids, offsets, cfg, env, kernel):
    """Twin of ``_uniform_decode_block`` attending over pool blocks
    instead of a contiguous per-slot cache; identical residual-stream
    pinning so both paths round the stream bit-identically."""
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    y, kp, vp = L.gqa_attention_decode_paged(h, lp["attn"], cfg, env, kp,
                                             vp, layer, tables, pos,
                                             block_ids, offsets,
                                             kernel=kernel)
    x = L.pin_bf16(x + L.pin_bf16(y))
    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    if "router" in lp["mlp"]:
        y = L.moe_block(h, lp["mlp"], cfg, env,
                        impl=env.opts.get("moe_impl", "ep"))
    else:
        y = L.ffn_swiglu(h, lp["mlp"], env)
    return L.pin_bf16(x + L.pin_bf16(y)), kp, vp


# --- jamba superblocks -----------------------------------------------------
def _jamba_superblock(x, sb, cfg, env, positions, *, states=None,
                      collect=False, pos=None):
    """One superblock (attn_period slots).  states: dict of per-superblock
    decode states or None (train).  Returns (x, new_states_or_caches)."""
    per = cfg.attn_period
    opts = env.opts
    sp = opts.get("sp", True) and states is None
    mi = ji = fi = 0
    out_states: Dict[str, list] = {"conv": [], "ssm": []}
    kv_out = None
    for slot in range(per):
        kind = ATTN if slot == per // 2 else MAMBA
        if kind == ATTN:
            h = L.rms_norm(x, sb["attn_ln"], cfg.norm_eps)
            if states is None:
                y, k, v = L.gqa_attention_full(
                    h, sb["attn"], cfg, env, positions,
                    attn_mode=opts.get("attn_mode", "full"),
                    bwd_safe=not collect)
                if collect:
                    k = env.cs(k, env.batch_axes, "model", None, None)
                    v = env.cs(v, env.batch_axes, "model", None, None)
                    kv_out = (k, v)
            else:
                y, kc, vc = L.gqa_attention_decode(
                    h, sb["attn"], cfg, env, states["k"], states["v"], pos)
                kv_out = (kc, vc)
            x = _res_cs(x + y, env, sp)
        else:
            lp = jax.tree_util.tree_map(lambda a: a[mi], sb["mamba"])
            h = L.rms_norm(x, sb["mamba_ln"][mi], cfg.norm_eps)
            if states is None and not collect:
                y = M.mamba_layer(h, lp, cfg, env)
            elif states is None and collect:
                y, conv_s, ssm_s = M.mamba_layer(h, lp, cfg, env,
                                                 return_state=True)
                out_states["conv"].append(conv_s)
                out_states["ssm"].append(ssm_s)
            else:
                y, conv_s, ssm_s = M.mamba_layer(
                    h, lp, cfg, env, conv_state=states["conv"][mi],
                    ssm_state=states["ssm"][mi], return_state=True)
                out_states["conv"].append(conv_s)
                out_states["ssm"].append(ssm_s)
            x = _res_cs(x + y, env, sp)
            mi += 1
        # ffn slot
        if cfg.layer_is_moe(slot):
            lp = jax.tree_util.tree_map(lambda a: a[ji], sb["moe"])
            h = L.rms_norm(x, sb["moe_ln"][ji], cfg.norm_eps)
            y = L.moe_block(h, lp, cfg, env, impl=opts.get("moe_impl", "ep"))
            ji += 1
        else:
            lp = jax.tree_util.tree_map(lambda a: a[fi], sb["ffn"])
            h = L.rms_norm(x, sb["ffn_ln"][fi], cfg.norm_eps)
            y = L.ffn_swiglu(h, lp, env)
            fi += 1
        x = _res_cs(x + y, env, sp)
    new_states = None
    if out_states["conv"]:
        new_states = {"conv": jnp.stack(out_states["conv"]),
                      "ssm": jnp.stack(out_states["ssm"])}
    return x, kv_out, new_states


def _run_jamba(params, x, cfg, env, positions, *, collect=False):
    def body(x, sb):
        x, kv, st = _jamba_superblock(x, sb, cfg, env, positions,
                                      collect=collect)
        return x, (kv, st) if collect else None
    x, ys = layer_scan(_maybe_remat(body, env), x, params["superblocks"], env)
    return x, ys


# --- rwkv ------------------------------------------------------------------
def _run_rwkv(params, x, cfg, env, *, collect=False):
    def body(x, lp):
        h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
        if collect:
            y, s_tm, wkv = R.rwkv6_time_mix(h, lp["tm"], cfg, env,
                                            return_state=True)
        else:
            y = R.rwkv6_time_mix(h, lp["tm"], cfg, env)
        x = x + y
        h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if collect:
            y, s_cm = R.rwkv6_channel_mix(h, lp["cm"], cfg, env,
                                          return_state=True)
        else:
            y = R.rwkv6_channel_mix(h, lp["cm"], cfg, env)
        x = x + y
        x = _res_cs(x, env, env.opts.get("sp", True))
        return x, (wkv, s_tm, s_cm) if collect else None
    x, ys = layer_scan(_maybe_remat(body, env), x, params["layers"], env)
    return x, ys


def _rwkv_decode_block(x, lp, st, cfg, env):
    wkv, s_tm, s_cm = st
    h = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
    y, s_tm2, wkv2 = R.rwkv6_time_mix(h, lp["tm"], cfg, env,
                                      shift_state=s_tm, wkv_state=wkv,
                                      return_state=True)
    x = x + y
    h = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
    y, s_cm2 = R.rwkv6_channel_mix(h, lp["cm"], cfg, env,
                                   shift_state=s_cm, return_state=True)
    x = x + y
    return x, (wkv2, s_tm2, s_cm2)


# ===========================================================================
# public entry points
# ===========================================================================
def _assemble_inputs(params, batch, cfg):
    """Returns (x, labels, positions)."""
    if cfg.family == "vlm":
        patches = batch["patches"].astype(BF16)
        tok_emb = embed_tokens(params, batch["tokens"], cfg)
        x = jnp.concatenate([patches, tok_emb], axis=1)
        labels = None
        if "labels" in batch:
            Bt, P = patches.shape[0], patches.shape[1]
            labels = jnp.concatenate(
                [jnp.full((Bt, P), -100, jnp.int32), batch["labels"]],
                axis=1)
    else:
        x = embed_tokens(params, batch["tokens"], cfg)
        labels = batch.get("labels")
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    return x, labels, positions


def forward_train(params, batch, cfg: ModelConfig, env: ShardingEnv):
    """Full causal forward; returns scalar mean xent loss."""
    if cfg.enc_dec:
        from repro.models import encdec
        return encdec.forward_train(params, batch, cfg, env)
    x, labels, positions = _assemble_inputs(params, batch, cfg)
    x = _res_cs(x, env, env.opts.get("sp", True))
    if cfg.family == "ssm":
        x, _ = _run_rwkv(params, x, cfg, env)
    elif cfg.attn_period:
        x, _ = _run_jamba(params, x, cfg, env, positions)
    else:
        x, _ = _run_uniform(params, x, cfg, env, positions)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return chunked_xent(params, x, labels, cfg, env)


def forward_logits(params, batch, cfg: ModelConfig, env: ShardingEnv):
    """Forward returning full logits (small shapes / tests)."""
    if cfg.enc_dec:
        from repro.models import encdec
        return encdec.forward_logits(params, batch, cfg, env)
    x, _, positions = _assemble_inputs(params, batch, cfg)
    if cfg.family == "ssm":
        x, _ = _run_rwkv(params, x, cfg, env)
    elif cfg.attn_period:
        x, _ = _run_jamba(params, x, cfg, env, positions)
    else:
        x, _ = _run_uniform(params, x, cfg, env, positions)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params, x, cfg)


# --- caches ----------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=BF16, src_len: Optional[int] = None) -> Dict[str, Any]:
    if cfg.enc_dec:
        from repro.models import encdec
        return encdec.init_cache(cfg, batch, max_len, dtype,
                                 src_len=src_len or max_len)
    d = cfg.d_model
    if cfg.family == "ssm":
        H, hs = cfg.rwkv_n_heads, cfg.rwkv_head_size
        Ln = cfg.n_layers
        return {"wkv": jnp.zeros((Ln, batch, H, hs, hs), F32),
                "shift_tm": jnp.zeros((Ln, batch, d), dtype),
                "shift_cm": jnp.zeros((Ln, batch, d), dtype)}
    if cfg.attn_period:
        nsb = cfg.n_layers // cfg.attn_period
        nm = cfg.attn_period - 1
        K, dh = cfg.n_kv_heads, cfg.head_dim
        di, ds = cfg.mamba_d_inner, cfg.mamba_d_state
        return {
            "k": jnp.zeros((nsb, batch, max_len, K, dh), dtype),
            "v": jnp.zeros((nsb, batch, max_len, K, dh), dtype),
            "conv": jnp.zeros((nsb, nm, batch, cfg.mamba_d_conv - 1, di), dtype),
            "ssm": jnp.zeros((nsb, nm, batch, di, ds), F32),
        }
    Ln = cfg.n_layers
    if cfg.use_mla:
        return {"ckv": jnp.zeros((Ln, batch, max_len, cfg.kv_lora_rank), dtype),
                "krope": jnp.zeros((Ln, batch, max_len, cfg.qk_rope_head_dim),
                                   dtype)}
    K, dh = cfg.n_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((Ln, batch, max_len, K, dh), dtype),
            "v": jnp.zeros((Ln, batch, max_len, K, dh), dtype)}


def abstract_cache(cfg, batch, max_len, dtype=BF16, src_len=None):
    return jax.eval_shape(
        lambda: init_cache(cfg, batch, max_len, dtype, src_len=src_len))


def cache_pspecs(cfg: ModelConfig, env: ShardingEnv, batch: int,
                 max_len: int, src_len: Optional[int] = None):
    """Sharding for the serving cache: batch over data axes, seq over
    'model' (flash-decoding layout); rwkv/mamba states shard their inner
    dim over 'model'."""
    ab = abstract_cache(cfg, batch, max_len, src_len=src_len)
    bt = env.batch_axes
    if env.opts.get("serve_fullshard"):
        # decode mode for >100B archs: batch replicated, sequence sharded
        # over (model x data) -> weights stay fully sharded, no gathers
        bt = None
        seq = ("model", "data")
    elif env.opts.get("cache_2d"):
        # serve layout: KV sequence sharded over BOTH axes (batch stays
        # on 'data'); decode reads it back identically
        seq = ("model", "data")
    else:
        seq = "model"

    def spec_of(path, leaf):
        name = path[-1]
        dims = leaf.shape
        if name in ("k", "v", "ckv", "krope", "cross_k", "cross_v"):
            if len(dims) == 4:
                want = [None, bt, seq, None]
            else:
                want = [None, bt, seq, None, None]
            return env.named(dims, want)
        if name == "wkv":
            return env.named(dims, [None, bt, "model", None, None])
        if name in ("shift_tm", "shift_cm"):
            return env.named(dims, [None, bt, None])
        if name == "conv":
            return env.named(dims, [None, None, bt, None, "model"])
        if name == "ssm":
            return env.named(dims, [None, None, bt, "model", None])
        return env.named(dims, [None] * len(dims))

    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: spec_of([getattr(k, "key", getattr(k, "idx", k))
                                  for k in kp], leaf), ab)


# --- prefill ---------------------------------------------------------------
def prefill(params, batch, cfg: ModelConfig, env: ShardingEnv,
            max_len: Optional[int] = None):
    """Full-sequence prefill.  Returns (last_logits, cache)."""
    if cfg.enc_dec:
        from repro.models import encdec
        return encdec.prefill(params, batch, cfg, env, max_len)
    x, _, positions = _assemble_inputs(params, batch, cfg)
    S = x.shape[1]
    max_len = max_len or S

    if cfg.family == "ssm":
        x, ys = _run_rwkv(params, x, cfg, env, collect=True)
        wkv, s_tm, s_cm = ys
        cache = {"wkv": wkv, "shift_tm": s_tm, "shift_cm": s_cm}
    elif cfg.attn_period:
        x, ys = _run_jamba(params, x, cfg, env, positions, collect=True)
        (k, v), st = ys
        cache = {"k": _pad_seq(k, max_len, 2), "v": _pad_seq(v, max_len, 2),
                 "conv": st["conv"], "ssm": st["ssm"]}
    else:
        x, kv = _run_uniform(params, x, cfg, env, positions, collect_kv=True)
        c1, c2 = kv
        if cfg.use_mla:
            cache = {"ckv": _pad_seq(c1, max_len, 2),
                     "krope": _pad_seq(c2, max_len, 2)}
        else:
            cache = {"k": _pad_seq(c1, max_len, 2),
                     "v": _pad_seq(c2, max_len, 2)}
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = unembed(params, x[:, -1:, :], cfg)
    return last, cache


def _pad_seq(x, max_len, axis):
    if x.shape[axis] == max_len:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, max_len - x.shape[axis])
    return jnp.pad(x, pad)


# --- decode ----------------------------------------------------------------
def decode_step(params, tokens, cache, pos, cfg: ModelConfig,
                env: ShardingEnv):
    """One decode step.  tokens: (B,1) int32; pos: scalar or (B,) position
    of the new token.  Returns (logits (B,1,V), new_cache)."""
    if cfg.enc_dec:
        from repro.models import encdec
        return encdec.decode_step(params, tokens, cache, pos, cfg, env)
    x = embed_tokens(params, tokens, cfg)

    if cfg.family == "ssm":
        def body(x, xs):
            lp, wkv, s_tm, s_cm = xs
            x, st = _rwkv_decode_block(x, lp, (wkv, s_tm, s_cm), cfg, env)
            return x, st
        x, ys = layer_scan(body, x, (params["layers"], cache["wkv"],
                                     cache["shift_tm"], cache["shift_cm"]), env)
        new_cache = {"wkv": ys[0], "shift_tm": ys[1], "shift_cm": ys[2]}
    elif cfg.attn_period:
        def body(x, xs):
            sb, kc, vc, conv, ssm = xs
            x, kv, st = _jamba_superblock(
                x, sb, cfg, env, None,
                states={"k": kc, "v": vc, "conv": conv, "ssm": ssm}, pos=pos)
            return x, (kv[0], kv[1], st["conv"], st["ssm"])
        x, ys = layer_scan(body, x, (params["superblocks"], cache["k"],
                                     cache["v"], cache["conv"], cache["ssm"]), env)
        new_cache = {"k": ys[0], "v": ys[1], "conv": ys[2], "ssm": ys[3]}
    else:
        def body(x, xs):
            lp, c1, c2 = xs
            x, c1, c2 = _uniform_decode_block(x, lp, c1, c2, cfg, env, pos)
            return x, (c1, c2)
        if cfg.use_mla:
            x, ys = layer_scan(body, x, (params["layers"], cache["ckv"],
                                         cache["krope"]), env)
            new_cache = {"ckv": ys[0], "krope": ys[1]}
        else:
            x, ys = layer_scan(body, x, (params["layers"], cache["k"],
                                         cache["v"]), env)
            new_cache = {"k": ys[0], "v": ys[1]}

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x, cfg)
    return logits, new_cache


def decode_step_paged(params, tokens, k_pool, v_pool, tables, pos,
                      block_ids, offsets, cfg: ModelConfig,
                      env: ShardingEnv, *, kernel: bool = False):
    """Paged twin of ``decode_step``: the contiguous ``cache`` dict is
    replaced by the serving pool's block arrays plus per-row block
    tables, so parked/resident KV never moves — decode attends over it
    in place.

    tokens: (B, 1) int32; k_pool/v_pool: (L, num_blocks, block, K, dh);
    tables: (B, max_blocks) int32 (rows padded with any in-range id —
    padded positions are masked); pos: (B,) position of the new token;
    block_ids/offsets: (B,) append destination of the new token's K/V
    (idle rows pass num_blocks as an out-of-range drop sentinel).
    The pools ride in the layer scan's carry, so each layer writes its
    token in place and a caller that donates them gets them back in the
    same buffers; ``kernel`` attends with the Pallas block-table kernel
    (TPU), else with the gather reference (see
    ``layers.gqa_attention_decode_paged``).  Covers the decoder-only GQA
    families the serving engine admits (dense / moe / vlm).  Returns
    (logits (B,1,V), k_pool, v_pool)."""
    assert not (cfg.enc_dec or cfg.use_mla or cfg.family == "ssm"
                or cfg.attn_period), \
        "paged decode covers the uniform GQA-cache families"
    x = embed_tokens(params, tokens, cfg)

    def body(carry, xs):
        x, kp, vp = carry
        lp, layer = xs
        x, kp, vp = _uniform_decode_block_paged(
            x, lp, kp, vp, layer, tables, pos, block_ids, offsets, cfg, env,
            kernel)
        return (x, kp, vp), None

    layers = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    (x, k_pool, v_pool), _ = layer_scan(
        body, (x, k_pool, v_pool), (params["layers"], layers), env)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(params, x, cfg), k_pool, v_pool
