"""Pallas kernel validation: shape/dtype sweeps vs ref.py oracles
(interpret=True executes the kernel bodies on CPU)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# --- flash attention ----------------------------------------------------------
@pytest.mark.parametrize("B,Sq,Sk,H,K,D", [
    (2, 256, 256, 4, 2, 64),
    (1, 128, 256, 4, 4, 64),
    (2, 128, 128, 8, 2, 128),
    (1, 384, 384, 6, 3, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0),
                                           (True, 64)])
def test_flash_attention(B, Sq, Sk, H, K, D, dtype, causal, window):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    if not causal and Sq != Sk:
        pytest.skip("cross shapes covered by causal sweep")
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, Sq, H, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Sk, K, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Sk, K, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                ref.astype(jnp.float32))))
    assert err < TOL[dtype], err


# --- paged decode attention ------------------------------------------------------
def _paged_case(B, H, K, dh, block, nblocks, nb, dtype, L=2, seed=0):
    """Random (L, nblocks, block, K, dh) pools, a query per row and
    disjoint block tables."""
    key = jax.random.PRNGKey(seed)
    rng = np.random.RandomState(seed)
    q = jax.random.normal(key, (B, H, dh), dtype)
    kp = jax.random.normal(jax.random.fold_in(key, 1),
                           (L, nblocks, block, K, dh), dtype)
    vp = jax.random.normal(jax.random.fold_in(key, 2),
                           (L, nblocks, block, K, dh), dtype)
    tables = rng.permutation(nblocks)[:B * nb].reshape(B, nb)
    return q, kp, vp, jnp.asarray(tables, jnp.int32)


def _paged_err(out, ref, lens):
    """Largest error over the rows with keys; a row of length 0 must
    come back as zeros."""
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    live = np.asarray(lens) > 0
    assert not out[~live].any()
    return float(np.abs(out[live] - ref[live]).max())


@pytest.mark.parametrize("B,H,K,dh,block,nblocks,nb", [
    (2, 4, 2, 64, 16, 32, 4),
    (3, 8, 8, 128, 32, 64, 3),
    (1, 8, 4, 64, 8, 16, 5),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention(B, H, K, dh, block, nblocks, nb, dtype):
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.paged_attention.ref import paged_decode_ref
    q, kp, vp, tables = _paged_case(B, H, K, dh, block, nblocks, nb, dtype)
    lens = jnp.asarray(np.random.RandomState(1).randint(
        1, nb * block + 1, size=B), jnp.int32)
    out = paged_attention(q, kp, vp, 1, tables, lens, interpret=True)
    ref = paged_decode_ref(q, kp, vp, 1, tables, lens)
    assert _paged_err(out, ref, lens) < TOL[dtype]


# lengths at and around block edges, an idle row and a full row
RAGGED = [0, 1, 15, 16, 17, 6 * 16]


@pytest.fixture
def two_block_chunks(monkeypatch):
    """The kernel reads 2 blocks a chunk, so the rows above take one
    chunk, several and a short last one; returns the kernel, unjitted
    (a jitted wrapper would keep its trace at the default chunk)."""
    from repro.kernels.paged_attention import kernel
    monkeypatch.setattr(kernel, "CHUNK_TOKENS", 32)
    return functools.partial(kernel.paged_decode_attention, interpret=True)


@pytest.mark.parametrize("G", [3, 4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_ragged_rows_every_group_size(G, dtype,
                                                      two_block_chunks):
    from repro.kernels.paged_attention.ref import paged_decode_ref
    K, nb = 2, 6
    q, kp, vp, tables = _paged_case(len(RAGGED), G * K, K, 64, 16, 40, nb,
                                    dtype, L=3, seed=G)
    lens = jnp.asarray(RAGGED, jnp.int32)
    for layer in range(3):
        out = two_block_chunks(q, kp, vp, layer, tables, lens)
        ref = paged_decode_ref(q, kp, vp, layer, tables, lens)
        assert _paged_err(out, ref, lens) < TOL[dtype], layer


@pytest.mark.parametrize("window", [1, 20, 40])
def test_paged_attention_sliding_window(window, two_block_chunks):
    """Only the last ``window`` keys count; blocks wholly before the
    window are skipped."""
    from repro.kernels.paged_attention.ref import paged_decode_ref
    q, kp, vp, tables = _paged_case(len(RAGGED), 8, 2, 64, 16, 40, 6,
                                    jnp.float32)
    lens = jnp.asarray(RAGGED, jnp.int32)
    out = two_block_chunks(q, kp, vp, 0, tables, lens, window=window)
    ref = paged_decode_ref(q, kp, vp, 0, tables, lens, window=window)
    assert _paged_err(out, ref, lens) < TOL[jnp.float32]


def test_paged_attention_reads_no_block_past_a_row(two_block_chunks):
    """Blocks outside a row's live span are never read: poisoning them
    with NaN leaves every output finite and unchanged."""
    q, kp, vp, tables = _paged_case(len(RAGGED), 8, 2, 64, 16, 40, 6,
                                    jnp.float32)
    lens = jnp.asarray(RAGGED, jnp.int32)
    out = two_block_chunks(q, kp, vp, 0, tables, lens)
    t = np.asarray(tables)
    dead = [t[r, -(-n // 16):] for r, n in enumerate(RAGGED)]
    dead = np.concatenate(dead + [np.setdiff1d(np.arange(40), t)])
    kp = kp.at[:, dead].set(jnp.nan)
    vp = vp.at[:, dead].set(jnp.nan)
    poisoned = two_block_chunks(q, kp, vp, 0, tables, lens)
    assert np.array_equal(np.asarray(out), np.asarray(poisoned))


# --- rwkv6 -------------------------------------------------------------------------
@pytest.mark.parametrize("B,T,H,dh,chunk", [
    (2, 128, 2, 16, 32), (1, 64, 4, 64, 64), (2, 96, 2, 32, 32),
])
def test_wkv6(B, T, H, dh, chunk):
    from repro.kernels.rwkv6.ops import wkv6
    from repro.kernels.rwkv6.ref import wkv6_ref
    key = jax.random.PRNGKey(0)
    r = jax.random.normal(key, (B, T, H, dh)) * 0.5
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, H, dh)) * 0.5
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, H, dh))
    w = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 3),
                                         (B, T, H, dh))) * 0.5 + 0.45
    u = jax.random.normal(jax.random.fold_in(key, 4), (H, dh)) * 0.3
    out = wkv6(r, k, v, w, u, chunk=chunk, interpret=True)
    ref = wkv6_ref(r, k, v, w, u)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-3


# --- mamba scan -----------------------------------------------------------------------
@pytest.mark.parametrize("B,T,di,ds,bd,chunk", [
    (2, 64, 32, 8, 32, 32), (1, 128, 64, 16, 32, 64), (2, 96, 48, 8, 16, 32),
])
def test_mamba_scan(B, T, di, ds, bd, chunk):
    from repro.kernels.mamba_scan.ops import mamba_scan
    from repro.kernels.mamba_scan.ref import mamba_scan_ref
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, T, di))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 5),
                                           (B, T, di))) * 0.1
    Bc = jax.random.normal(jax.random.fold_in(key, 6), (B, T, ds))
    Cc = jax.random.normal(jax.random.fold_in(key, 7), (B, T, ds))
    A_log = jnp.log(jnp.broadcast_to(
        jnp.arange(1, ds + 1, dtype=jnp.float32)[None], (di, ds)))
    D = jnp.ones((di,), jnp.float32)
    out = mamba_scan(x, dt, Bc, Cc, A_log, D, block_d=bd, chunk=chunk,
                     interpret=True)
    ref = mamba_scan_ref(x, dt, Bc, Cc, A_log, D)
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-4


# --- kernels vs model layers (integration) ---------------------------------------------
def test_flash_matches_model_chunked_attention():
    """The Pallas kernel, the chunked-jnp distributed path, and the dense
    oracle all agree."""
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.models.layers import attention_dense, chunked_attention, \
        expand_kv
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (2, 256, 4, 64))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 256, 2, 64))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 256, 2, 64))
    a = flash_attention(q, k, v, causal=True, interpret=True)
    b = chunked_attention(q, expand_kv(k, 4), expand_kv(v, 4), causal=True)
    c = chunked_attention(q, expand_kv(k, 4), expand_kv(v, 4), causal=True,
                          mode="tri")
    d = chunked_attention(q, expand_kv(k, 4), expand_kv(v, 4), causal=True,
                          bwd_safe=True)
    e = attention_dense(q, k, v, causal=True)
    for name, x in [("pallas", a), ("chunked", b), ("tri", c),
                    ("bwd_safe", d)]:
        err = float(jnp.max(jnp.abs(x - e)))
        assert err < 2e-5, (name, err)
