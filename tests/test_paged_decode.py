"""True paged decode: serial-vs-paged token identity per architecture
family, block-lifecycle property tests, the block-table kernel path of
the paged decode step against its gather reference, the donated pool,
and runtime-level paged-vs-gather byte-identity with zero park/resume
device copies.

The gather path (``Engine(paged=False)``) is the reference oracle: both
modes share prefill and policy arithmetic, and the masked paged
attention is constructed to be bit-identical, so token ids must match
exactly — not approximately."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, load_all, llava_next_34b, \
    mixtral_8x22b
from repro.kernels.paged_attention.kernel import paged_decode_attention
from repro.models import layers, lm
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


# --- the paged decode step: kernel path against the gather reference ------
def _step_case(cfg, seed=0, B=4, NB=24, nb=5):
    """Random pools and a round of ``B`` rows: lengths around block edges
    and an idle row (drop sentinel), each row's next token appended at
    its position."""
    blk = 4
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    shape = (cfg.n_layers, NB, blk, cfg.n_kv_heads, cfg.head_dim)
    k_pool = jax.random.normal(ks[0], shape, jnp.float32).astype(jnp.bfloat16)
    v_pool = jax.random.normal(ks[1], shape, jnp.float32).astype(jnp.bfloat16)
    rng = np.random.RandomState(seed)
    tables = rng.permutation(NB)[:B * nb].reshape(B, nb).astype(np.int32)
    pos = np.array([6, 3, 0, nb * blk - 1], np.int32)[:B]     # keys - 1
    ablk = tables[np.arange(B), pos // blk]
    ablk[2] = NB                                              # idle row
    aoff = pos % blk
    tok = rng.randint(1, cfg.vocab, size=(B, 1)).astype(np.int32)
    return (tok, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(pos),
            jnp.asarray(ablk), jnp.asarray(aoff.astype(np.int32)))


@pytest.mark.parametrize("family", ["dense", "moe_sliding_window"])
def test_paged_decode_step_matches_ref(family, monkeypatch):
    """``lm.decode_step_paged`` with the block-table kernel (in interpret
    mode) leaves the same pools as the gather reference and gives the
    same logits for every live row."""
    if family == "dense":
        cfg, params = CFG, PARAMS
    else:
        cfg = mixtral_8x22b.tiny()
        params = lm.init_params(cfg, jax.random.PRNGKey(1))
    args = _step_case(cfg)
    env = Engine(cfg, params, n_slots=1, max_len=16).env
    ref = jax.jit(functools.partial(lm.decode_step_paged, cfg=cfg, env=env))(
        params, *args)
    monkeypatch.setattr(layers, "paged_decode_attention", functools.partial(
        paged_decode_attention, interpret=True))
    out = jax.jit(functools.partial(lm.decode_step_paged, cfg=cfg, env=env,
                                    kernel=True))(params, *args)
    live = [0, 1, 3]
    blocks, offs = args[5][np.array(live)], args[6][np.array(live)]
    for got, want, old in ((out[1], ref[1], args[1]),
                           (out[2], ref[2], args[2])):
        # layer 0 writes the same bits; a deeper layer's token follows
        # the attention above it, so it matches within rounding
        assert jnp.array_equal(got[0], want[0])
        new_got = got[:, blocks, offs].astype(jnp.float32)
        new_want = want[:, blocks, offs].astype(jnp.float32)
        assert float(jnp.abs(new_got - new_want).max()) < 0.05
        # nothing else moved: the idle row wrote nowhere
        assert jnp.array_equal(got.at[:, blocks, offs].set(0),
                               old.at[:, blocks, offs].set(0))
    gap = jnp.abs(out[0][np.array(live)].astype(jnp.float32)
                  - ref[0][np.array(live)].astype(jnp.float32)).max()
    assert float(gap) < 0.05, float(gap)


def test_decode_round_donates_the_pool():
    """A decode round writes its tokens into the pool it was given: the
    arrays the pool held before the round are deleted, and the engine
    holds the round's outputs."""
    eng = Engine(CFG, PARAMS, n_slots=2, max_len=64, pool_blocks=16)
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, CFG.vocab, size=20).astype(np.int32)
    slot = eng.start_session("x", prompt[:-1], cached_hit=False)
    k_old, v_old = eng.pool.k_pool, eng.pool.v_pool
    eng.decode({slot: int(prompt[-1])})
    assert k_old.is_deleted() and v_old.is_deleted()
    assert not eng.pool.k_pool.is_deleted()
    assert eng.pool.audit_blocks() == []


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
