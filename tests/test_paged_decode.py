"""True paged decode: serial-vs-paged token identity per architecture
family, block-lifecycle property tests, the multi-layer fused
append+attend kernel entry, and runtime-level paged-vs-gather
byte-identity with zero park/resume device copies.

The gather path (``Engine(paged=False)``) is the reference oracle: both
modes share prefill and policy arithmetic, and the masked paged
attention is constructed to be bit-identical, so token ids must match
exactly — not approximately."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, load_all, llava_next_34b, \
    mixtral_8x22b
from repro.kernels.paged_attention import ops
from repro.kernels.paged_attention.ref import paged_decode_ref
from repro.models import lm
from repro.serving.engine import Engine
from repro.serving.kvcache import PagedKVPool
from repro.serving.runtime import AgentRequest, ServingRuntime

load_all()
CFG = get_config("micro")
PARAMS = lm.init_params(CFG, jax.random.PRNGKey(0))


# --- serial-vs-paged token identity, per decoder-only family ---------------
def _identity_roundtrip(cfg, params, prompt, n_first=5, n_rest=3):
    """Paged engine with a park/resume in the middle must emit the same
    token ids as an uninterrupted gather-mode decode."""
    eg = Engine(cfg, params, n_slots=2, max_len=64, pool_blocks=16,
                paged=False)
    sg = eg.start_session("x", prompt, cached_hit=False)
    ref = eg.decode({sg: int(prompt[-1])}, n_steps=n_first + n_rest)[sg]

    ep = Engine(cfg, params, n_slots=2, max_len=64, pool_blocks=16,
                paged=True)
    sp = ep.start_session("x", prompt, cached_hit=False)
    first = ep.decode({sp: int(prompt[-1])}, n_steps=n_first)[sp]
    assert ep.park_session("x")
    ctx = np.concatenate([prompt, np.asarray(first, np.int32)])
    sp2 = ep.start_session("x", ctx, cached_hit=True)
    rest = ep.decode({sp2: int(ctx[-1])}, n_steps=n_rest)[sp2]
    assert first + rest == ref
    # the whole paged round-trip moved zero park/resume device bytes
    assert ep.park_copy_bytes == 0 and ep.resume_copy_bytes == 0
    assert ep.pool.audit_blocks() == []


def test_token_identity_dense():
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, CFG.vocab, size=24).astype(np.int32)
    _identity_roundtrip(CFG, PARAMS, prompt)


def test_token_identity_moe_sliding_window():
    cfg = mixtral_8x22b.tiny()
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.RandomState(1)
    prompt = rng.randint(1, cfg.vocab, size=21).astype(np.int32)
    _identity_roundtrip(cfg, params, prompt, n_first=4, n_rest=2)


def test_token_identity_vlm():
    cfg = llava_next_34b.tiny()
    params = lm.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.RandomState(2)
    prompt = rng.randint(1, cfg.vocab, size=19).astype(np.int32)
    _identity_roundtrip(cfg, params, prompt, n_first=4, n_rest=2)


# --- multi-layer fused append+attend entry ---------------------------------
def test_paged_decode_step_matches_ref():
    """ops.paged_decode_step (append the step's K/V, attend all layers)
    must match a manual per-layer scatter + paged_decode_ref."""
    L, B, H, K, dh, NB, blk = 3, 4, 4, 2, 8, 12, 4
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 6)
    q = jax.random.normal(ks[0], (L, B, H, dh), jnp.float32)
    k_new = jax.random.normal(ks[1], (L, B, K, dh), jnp.float32)
    v_new = jax.random.normal(ks[2], (L, B, K, dh), jnp.float32)
    k_pool = jax.random.normal(ks[3], (L, NB, blk, K, dh), jnp.float32)
    v_pool = jax.random.normal(ks[4], (L, NB, blk, K, dh), jnp.float32)
    tables = jnp.asarray([[0, 1, 2], [3, 4, 5], [6, 7, 8], [9, 10, 11]],
                         jnp.int32)
    # lens INCLUDE the just-appended token; row 2 is idle (drop sentinel)
    lens = jnp.asarray([7, 3, 1, 12], jnp.int32)
    ablk = jnp.asarray([1, 3, NB, 11], jnp.int32)     # NB = drop sentinel
    aoff = jnp.asarray([2, 2, 0, 3], jnp.int32)

    out, kp, vp = ops.paged_decode_step(q, k_new, v_new, k_pool, v_pool,
                                        tables, lens, ablk, aoff,
                                        interpret=True)
    kp_ref, vp_ref = k_pool, v_pool
    for b in (0, 1, 3):                                # row 2 dropped
        kp_ref = kp_ref.at[:, ablk[b], aoff[b]].set(k_new[:, b])
        vp_ref = vp_ref.at[:, ablk[b], aoff[b]].set(v_new[:, b])
    assert jnp.array_equal(kp, kp_ref) and jnp.array_equal(vp, vp_ref)
    for l in range(L):
        ref = paged_decode_ref(q[l], kp_ref[l], vp_ref[l], tables, lens)
        active = np.asarray(jnp.abs(out[l] - ref).max(axis=(1, 2)))
        for b in (0, 1, 3):
            assert active[b] < 1e-5, f"layer {l} row {b}"


# --- block-lifecycle property test -----------------------------------------
def test_random_lifecycle_interleavings_keep_pool_clean():
    """Random alloc/extend/append/park/resume/import/free interleavings
    under the engine's discipline (bounded residents, bounded session
    length) never break block conservation or exhaust the headroom."""
    L, blk, Kh, dh = 2, 4, 1, 4
    n_slots, max_nb = 3, 4
    max_len = max_nb * blk
    nominal = 6
    pool = PagedKVPool(L, nominal, blk, Kh, dh,
                       headroom_blocks=n_slots * max_nb)
    rng = np.random.RandomState(0)
    resident, parked = [], []
    next_sid = [0]

    def kv(n):
        a = jnp.asarray(rng.randn(L, n, Kh, dh), jnp.bfloat16)
        return a, a

    def check(tag):
        errs = pool.audit_blocks()
        assert errs == [], f"{tag}: {errs}"
        held = sum(len(t) for t in pool.tables.values())
        assert len(pool.free) + held == pool.total_blocks, tag
        assert pool.used_blocks() <= pool.num_blocks, tag

    for step in range(300):
        op = rng.choice(["alloc", "extend", "append", "park", "resume",
                         "import", "free"])
        if op == "alloc" and len(resident) < n_slots:
            sid = f"s{next_sid[0]}"
            next_sid[0] += 1
            pool.alloc(sid)
            resident.append(sid)
        elif op == "extend" and resident:
            sid = resident[rng.randint(len(resident))]
            room = max_len - pool.lens[sid]
            if room:
                k, v = kv(rng.randint(1, room + 1))
                pool.extend(sid, k, v, bucket=blk * 2)
        elif op == "append" and resident:
            sid = resident[rng.randint(len(resident))]
            if pool.lens[sid] < max_len:
                pool.ensure_tail_room(sid)
                pool.append_token(sid)
        elif op == "park" and resident:
            sid = resident[rng.randint(len(resident))]
            if pool.lens[sid] and pool.park_resident(sid):
                resident.remove(sid)
                parked.append(sid)
        elif op == "resume" and parked and len(resident) < n_slots:
            sid = parked[rng.randint(len(parked))]
            pool.mark_resident(sid)
            parked.remove(sid)
            resident.append(sid)
        elif op == "import":                # work-steal migration lands
            sid = f"m{next_sid[0]}"
            next_sid[0] += 1
            n = rng.randint(1, nominal * blk + 1)
            k, v = kv(n)
            if pool.park(sid, k, v, n):
                parked.append(sid)
        elif op == "free" and (resident or parked):
            pop = resident if (resident and
                               (not parked or rng.rand() < 0.5)) \
                else parked
            sid = pop[rng.randint(len(pop))]
            pool.free_session(sid)
            pop.remove(sid)
        check(f"step {step} op {op}")

    for sid in list(pool.tables):
        pool.free_session(sid)
    check("drain")
    assert len(pool.free) == pool.total_blocks


def test_failed_repark_keeps_existing_blocks():
    """Satellite regression: a re-park that does not fit must leave the
    session's previously parked KV intact (the old code freed first and
    lost it)."""
    pool = PagedKVPool(1, num_blocks=3, block_size=4, n_kv_heads=1,
                       head_dim=4)
    k = jnp.ones((1, 8, 1, 4), jnp.bfloat16)
    assert pool.park("a", k, k, 8)           # 2 blocks
    big = jnp.ones((1, 24, 1, 4), jnp.bfloat16)
    assert not pool.park("a", big, big, 24)  # net demand 6-2 > 3-2
    assert pool.has("a") and pool.lens["a"] == 8
    assert pool.audit_blocks() == []


def test_extend_rejects_bucket_splitting_blocks():
    pool = PagedKVPool(1, num_blocks=4, block_size=16, n_kv_heads=1,
                       head_dim=4)
    pool.alloc("s")
    k = jnp.ones((1, 8, 1, 4), jnp.bfloat16)
    with pytest.raises(AssertionError, match="bucket"):
        pool.extend("s", k, k, bucket=24)    # 24 % 16 != 0
    pool.extend("s", k, k, bucket=32)        # lcm quantum: fine


# --- runtime-level byte-identity + zero-copy accounting --------------------
def _mk_requests(n, n_steps=3, seed=0):
    tools = ["code_execution", "web_api", "file_operations"]
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        steps = [(list(map(int, rng.randint(1, CFG.vocab, size=8))), 4,
                  tools[s % 3], float(rng.uniform(0.05, 0.5)))
                 for s in range(n_steps)]
        reqs.append(AgentRequest(f"s{i}", f"t{i % 3}", steps))
    return reqs


def test_runtime_paged_vs_gather_summary_identical():
    """Paged and gather runtimes make bit-identical scheduling decisions
    AND emit bit-identical tokens, so the whole summary repr matches;
    only the device-copy accounting differs (paged park/resume: 0)."""
    outs, stats = [], []
    for paged in (True, False):
        rt = ServingRuntime(CFG, PARAMS, seed=0, n_workers=2, n_slots=2,
                            max_len=256, pool_blocks=96, paged=paged)
        for r in _mk_requests(5):
            rt.submit(r)
        rt.run()
        rt.check_conservation()
        outs.append(repr(rt.summarize()))
        stats.append(rt.stats())
    assert outs[0] == outs[1]
    p, g = stats
    assert p["park_copy_bytes"] == 0 and p["resume_copy_bytes"] == 0
    assert g["park_copy_bytes"] > 0 and g["resume_copy_bytes"] > 0
    assert p["regen_tokens"] == g["regen_tokens"]
