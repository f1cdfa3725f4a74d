"""Single-worker serving engine: continuous batching over decode slots +
paged KV living in pool blocks from admit to finish.

The engine executes REAL forward passes (jitted prefill / batched decode)
against a model from the zoo.  In the default **paged** mode a session's
KV lands in `PagedKVPool` blocks at admit (prefill scatters straight
into blocks), the batched decode step attends over per-slot block tables
and writes each new token's K/V in place into the tail block of the
pool it is donated, and park/resume/preempt are pure metadata flips —
zero device copies.  On a TPU the step attends with the Pallas
block-table kernel, which reads only each row's live blocks; elsewhere
with the gather reference (``layers.gqa_attention_decode_paged``).  A
decode slot is just a batch-row binding, so co-residency is bounded by
pool memory, not slot-cache memory.

``Engine(paged=False)`` keeps the original gather path as the reference
oracle: contiguous per-slot caches, park/resume as real pool<->slot
copies.  Both modes share the same prefill, the same policy-visible
capacity arithmetic, and (by construction of the masked attention) emit
bit-identical token ids — `tests/test_paged_decode.py` gates this per
architecture family.

``Engine(tracer=Tracer(clock=...))`` records the engine's own spans on
track ``engine`` (``engine.prefill`` and ``engine.decode``, each split
into the host's preparation, the jitted dispatch, the bookkeeping and,
for decode, the wait on the device), stamped by the tracer's clock and
entered under the same names as ``jax.profiler.TraceAnnotation``s, so a
profiler trace holds them on the device's clock.  Each span's
``compiles`` meta counts the backend compiles inside it.  Without a
tracer the engine records nothing (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import lm
from repro.models.sharding import ShardingEnv
from repro.obs.tracer import ROOT, Tracer
from repro.serving.kvcache import PagedKVPool


# jitted prefill specializes on sequence length: bucket lengths so a
# trace-driven workload compiles O(max_len / bucket) programs, not one
# per distinct prompt length.  Engines pad to lcm(bucket, block_size)
# so a compile bucket never splits a KV block (PagedKVPool.extend
# asserts this invariant).
_PREFILL_BUCKET = 32

# one jitted (decode, prefill, paged-decode) triple per (config,
# sharding-options, platform) — engines of the same model share compiled
# code instead of each instance re-tracing through its own bound-method
# closures (a multi-engine runtime otherwise pays the full compile set
# per engine)
_JIT_CACHE: Dict[tuple, tuple] = {}


def _jitted_fns(cfg: ModelConfig, env: ShardingEnv, platform: str):
    """The engine's jitted steps for a device of ``platform``.  On a TPU
    the paged decode attends with the Pallas block-table kernel; on any
    other platform with the gather reference.  Both donate the pool."""
    if env.mesh is not None:
        key = None          # meshes aren't value-hashable: no sharing
    else:
        key = (cfg, tuple(sorted(env.opts.items())), platform)
    try:
        fns = _JIT_CACHE.get(key) if key is not None else None
    except TypeError:       # unhashable opt value: no sharing
        key, fns = None, None
    if fns is None:
        kernel = platform == "tpu"

        def decode_fn(params, tokens, cache, positions):
            return lm.decode_step(params, tokens, cache, positions, cfg,
                                  env)

        def prefill_fn(params, tokens, pad_to):
            batch = {"tokens": tokens}
            if cfg.family == "vlm":
                # text-only serving of a VLM: zero-length patch stream
                # (patches are pre-projected d_model embeddings
                # concatenated before the tokens, so an empty one is
                # exact, not an approximation)
                batch["patches"] = jnp.zeros(
                    (tokens.shape[0], 0, cfg.d_model), jnp.bfloat16)
            return lm.prefill(params, batch, cfg, env, max_len=pad_to)

        def paged_decode_fn(params, tokens, k_pool, v_pool, tables,
                            positions, block_ids, offsets):
            return lm.decode_step_paged(params, tokens, k_pool, v_pool,
                                        tables, positions, block_ids,
                                        offsets, cfg, env, kernel=kernel)

        fns = (jax.jit(decode_fn),
               jax.jit(prefill_fn, static_argnames=("pad_to",)),
               # the round writes its tokens into the pool it is given
               jax.jit(paged_decode_fn, donate_argnums=(2, 3)))
        if key is not None:
            _JIT_CACHE[key] = fns
    return fns


class _Compiles:
    """Backend compiles in this process.  JAX's event listeners are
    process-wide and cannot be removed, so the count is too: a span
    reads its difference across the span."""
    n = 0
    _installed = False

    @classmethod
    def install(cls) -> None:
        if not cls._installed:
            jax.monitoring.register_event_duration_secs_listener(cls._on)
            cls._installed = True

    @classmethod
    def _on(cls, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            cls.n += 1


_NO_SPAN = contextlib.nullcontext()


class _NoSpans:
    """What an engine without a tracer records: nothing."""

    def __call__(self, name: str, **meta):
        return _NO_SPAN

    def note(self, **meta) -> None:
        pass


class _Spans:
    """The engine's spans on its tracer's clock, each also entered as a
    profiler annotation of the same name; a span opened inside another
    is its child."""

    def __init__(self, tracer: Tracer):
        _Compiles.install()
        self.tracer = tracer
        self.open = ROOT

    @contextlib.contextmanager
    def __call__(self, name: str, **meta):
        parent, compiles = self.open, _Compiles.n
        with jax.profiler.TraceAnnotation(name), \
                self.tracer.span("engine", name, parent=parent,
                                 **meta) as sid:
            self.open = sid
            try:
                yield
            finally:
                self.open = parent
                self.tracer.note(sid, compiles=_Compiles.n - compiles)

    def note(self, **meta) -> None:
        """Attach meta to the innermost open span."""
        self.tracer.note(self.open, **meta)


def serving_env() -> ShardingEnv:
    """The single-device sharding environment engines serve under."""
    return ShardingEnv(None, opts={"remat": False, "sp": False,
                                   "moe_impl": "dense"})


@dataclasses.dataclass
class SlotState:
    session_id: Optional[str] = None
    length: int = 0                 # tokens currently cached for the slot


class Engine:
    """Decode slots + prefill + park/resume for one worker."""

    def __init__(self, cfg: ModelConfig, params, *, n_slots: int = 4,
                 max_len: int = 512, pool_blocks: int = 64,
                 block_size: int = 16, env: Optional[ShardingEnv] = None,
                 paged: bool = True, device: Optional[jax.Device] = None,
                 tracer: Optional[Tracer] = None):
        assert not cfg.enc_dec and cfg.family in ("dense", "moe", "vlm"), \
            "engine demo supports decoder-only KV families"
        assert not cfg.use_mla, \
            "engine KV paths assume the GQA (k, v) cache layout"
        self.cfg = cfg
        # the engine owns one device: its params replica, its pool and
        # every input it feeds the jitted steps live there (a no-op copy
        # when ``params`` already sit on it)
        self.device = device if device is not None else jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        self.env = env or serving_env()
        self.n_slots = n_slots
        self.max_len = max_len
        self.paged = paged
        self.slots = [SlotState() for _ in range(n_slots)]
        if paged:
            assert max_len % block_size == 0, \
                "paged decode needs max_len to be a whole number of blocks"
            self.max_nb = max_len // block_size
            # resident headroom: every slot can hold a max_len session in
            # blocks without ever competing with the parked population,
            # so policy-visible capacity stays identical to gather mode
            headroom = n_slots * self.max_nb
            self.cache = None
        else:
            self.max_nb = 0
            headroom = 0
            self.cache = jax.device_put(lm.init_cache(cfg, n_slots, max_len),
                                        self.device)
        self.pool = PagedKVPool(cfg.n_layers, pool_blocks, block_size,
                                cfg.n_kv_heads, cfg.head_dim,
                                headroom_blocks=headroom,
                                device=self.device)
        # prefill compile quantum: a whole number of blocks AND of the
        # base bucket, so a bucket boundary never splits a tail block
        self._prefill_quantum = (_PREFILL_BUCKET * block_size
                                 // math.gcd(_PREFILL_BUCKET, block_size))
        # stats
        self.prefill_tokens = 0
        self.regen_tokens = 0
        self.decode_steps = 0
        # device-copy accounting for the park/resume/migration paths
        # (paged mode: park/resume are metadata-only and stay 0)
        self.park_copy_bytes = 0
        self.resume_copy_bytes = 0
        self.migration_copy_bytes = 0
        # prefill->decode handoff transport (disaggregated pools):
        # counted separately from migration so the A/B stays legible
        self.handoff_copy_bytes = 0
        # wall-clock spans: the caller injects the tracer and its clock
        self.tracer = tracer
        self._spans = _Spans(tracer) if tracer is not None else _NoSpans()

        (self._jit_decode, self._jit_prefill,
         self._jit_paged_decode) = _jitted_fns(self.cfg, self.env,
                                               self.device.platform)
        # how decode attends: the paged kernel on a TPU, else XLA
        self.attn = ("kernel" if paged and self.device.platform == "tpu"
                     else "xla")

    def _put(self, x) -> jnp.ndarray:
        return jax.device_put(x, self.device)

    # -- slot management -----------------------------------------------------
    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s.session_id is None:
                return i
        return None

    def used_slots(self) -> int:
        """Occupied decode slots (ground truth for load reporting and
        the runtime's conservation checks)."""
        return sum(1 for s in self.slots if s.session_id is not None)

    def _write_slot(self, slot: int, k, v, length: int) -> None:
        """k/v: (L, S, K, dh) -> into the batched decode cache."""
        pad = self.max_len - k.shape[1]
        if pad > 0:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        self.cache["k"] = self.cache["k"].at[:, slot].set(k)
        self.cache["v"] = self.cache["v"].at[:, slot].set(v)
        self.slots[slot].length = length

    def _prefill_into(self, tokens: np.ndarray, land: Callable):
        """Prefill ``tokens`` and hand their K/V, each (L, n, K, dh), to
        ``land(k, v)``; returns what ``land`` returns.

        Token length is padded up to the compile quantum — lcm(32-token
        bucket, block size) — so the jitted prefill compiles O(max_len /
        quantum) programs and a bucket boundary never splits a KV
        block.  Padding is exact under the causal mask: positions < n
        attend to the same key set either way, so their KV is
        bit-identical."""
        n = len(tokens)
        pad_to = min(self.max_len, -(-n // self._prefill_quantum)
                     * self._prefill_quantum)
        pad_to = max(pad_to, n)
        self._spans.note(n=n, pad_to=pad_to)
        with self._spans("engine.prefill.prep"):
            padded = np.zeros(pad_to, np.int32)
            padded[:n] = tokens
            ids = self._put(padded[None])
        with self._spans("engine.prefill.dispatch"):
            _, cache = self._jit_prefill(self.params, ids, pad_to=pad_to)
        with self._spans("engine.prefill.kv_write"):
            return land(cache["k"][:, 0, :n], cache["v"][:, 0, :n])

    # -- public API ------------------------------------------------------------
    def start_session(self, sid: str, tokens: np.ndarray,
                      cached_hit: bool) -> Optional[int]:
        """Admit a session: resume parked KV if present (prefill only the
        delta) else full prefill.  Returns the slot id, or ``None`` when
        every slot is occupied — the caller (the serving runtime) queues
        the session instead of crashing."""
        slot = self.free_slot()
        if slot is None:
            return None
        tokens = np.asarray(tokens, np.int32)
        with self._spans("engine.prefill", sid=sid, n=0, pad_to=0):
            if self.paged:
                self._admit_paged(sid, tokens, cached_hit)
                self.slots[slot] = SlotState(sid, len(tokens))
            else:
                self._admit_gather(slot, sid, tokens, cached_hit)
                self.slots[slot].session_id = sid
        return slot

    def _admit_paged(self, sid: str, tokens: np.ndarray,
                     cached_hit: bool) -> None:
        """Land the session's KV in pool blocks.  A cached hit is a pure
        metadata flip (parked -> resident) plus a delta prefill scattered
        straight into blocks; a miss allocates at admit and prefills the
        full context into blocks.  No gather, no slot copy — resume-copy
        bytes stay 0."""
        pool = self.pool

        def land(k, v):
            pool.extend(sid, k, v, bucket=self._prefill_quantum)

        if cached_hit and pool.has(sid):
            n = pool.lens[sid]
            pool.mark_resident(sid)
            delta = tokens[n:]
            if len(delta):
                self._prefill_into(delta, land)
                self.prefill_tokens += len(delta)
        else:
            pool.alloc(sid)
            self._prefill_into(tokens, land)
            self.prefill_tokens += len(tokens)
            self.regen_tokens += len(tokens)

    def _admit_gather(self, slot: int, sid: str, tokens: np.ndarray,
                      cached_hit: bool) -> None:
        """Reference path: gather parked blocks into the contiguous
        per-slot cache (an O(context-bytes) resume copy)."""
        resumed = self.pool.resume(sid) if cached_hit else None
        if resumed is not None:
            k, v, n = resumed
            self.resume_copy_bytes += self.pool.session_bytes(sid)
            delta = tokens[n:]
            self.pool.free_session(sid)
            if len(delta):
                self._prefill_into(delta, lambda dk, dv: self._write_slot(
                    slot, jnp.concatenate([k, dk], axis=1),
                    jnp.concatenate([v, dv], axis=1), len(tokens)))
                self.prefill_tokens += len(delta)
            else:
                self._write_slot(slot, k, v, len(tokens))
        else:
            self._prefill_into(tokens, lambda k, v: self._write_slot(
                slot, k, v, len(tokens)))
            self.prefill_tokens += len(tokens)
            self.regen_tokens += len(tokens)

    def decode(self, slot_tokens: Dict[int, int], n_steps: int = 1,
               greedy: bool = True) -> Dict[int, List[int]]:
        """Run `n_steps` batched decode steps for the given slots.
        slot_tokens: {slot: next input token id}.  Returns generated ids
        per slot."""
        if self.tracer is None:
            return self._decode(slot_tokens, n_steps)
        # keys the first step's new tokens attend: each row's context
        # with its new token; and the blocks that holds
        lens = [self.slots[s].length + 1 for s in slot_tokens]
        block = self.pool.block
        with self._spans("engine.decode", rows=len(lens), keys=sum(lens),
                         kv_blocks=sum(-(-n // block) for n in lens),
                         attn=self.attn):
            return self._decode(slot_tokens, n_steps)

    def _decode(self, slot_tokens: Dict[int, int],
                n_steps: int) -> Dict[int, List[int]]:
        if self.paged:
            return self._decode_paged(slot_tokens, n_steps)
        out: Dict[int, List[int]] = {s: [] for s in slot_tokens}
        cur = dict(slot_tokens)
        for _ in range(n_steps):
            tok = np.zeros((self.n_slots, 1), np.int32)
            pos = np.zeros((self.n_slots,), np.int32)
            for s, t in cur.items():
                tok[s, 0] = t
                pos[s] = self.slots[s].length
            logits, self.cache = self._jit_decode(
                self.params, self._put(tok), self.cache, self._put(pos))
            nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1))
            for s in cur:
                self.slots[s].length += 1
                out[s].append(int(nxt[s]))
                cur[s] = int(nxt[s])
            self.decode_steps += 1
        return out

    def _decode_paged(self, slot_tokens: Dict[int, int],
                      n_steps: int) -> Dict[int, List[int]]:
        """Batched decode attending directly over pool block tables.
        Each step appends the new K/V into the tail block on device;
        idle batch rows carry an out-of-range append sentinel so they
        write nowhere."""
        out: Dict[int, List[int]] = {s: [] for s in slot_tokens}
        cur = dict(slot_tokens)
        for _ in range(n_steps):
            logits = self.paged_step_logits(cur)
            with self._spans("engine.decode.sync"):
                nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1))
            for s in cur:
                out[s].append(int(nxt[s]))
                cur[s] = int(nxt[s])
        return out

    def paged_step_logits(self, slot_tokens: Dict[int, int]) -> jnp.ndarray:
        """One batched paged decode step: feed ``{slot: token id}``,
        append each row's K/V into its tail block, and return the
        (n_slots, 1, vocab) logits (idle rows are garbage)."""
        pool = self.pool
        with self._spans("engine.decode.prep"):
            tok = np.zeros((self.n_slots, 1), np.int32)
            pos = np.zeros((self.n_slots,), np.int32)
            tables = np.zeros((self.n_slots, self.max_nb), np.int32)
            ablk = np.full((self.n_slots,), pool.total_blocks, np.int32)
            aoff = np.zeros((self.n_slots,), np.int32)
            for s, t in slot_tokens.items():
                sid = self.slots[s].session_id
                pool.ensure_tail_room(sid)
                tok[s, 0] = t
                pos[s] = self.slots[s].length
                tbl = pool.tables[sid]
                tables[s, :len(tbl)] = tbl
                ablk[s], aoff[s] = pool.tail_slot(sid)
            tok, tables, pos, ablk, aoff = (
                self._put(a) for a in (tok, tables, pos, ablk, aoff))
        with self._spans("engine.decode.dispatch"):
            logits, pool.k_pool, pool.v_pool = self._jit_paged_decode(
                self.params, tok, pool.k_pool, pool.v_pool, tables, pos,
                ablk, aoff)
        with self._spans("engine.decode.book"):
            for s in slot_tokens:
                pool.append_token(self.slots[s].session_id)
                self.slots[s].length += 1
            self.decode_steps += 1
        return logits

    def park_session(self, sid: str) -> bool:
        """Session pauses for a tool call.  Paged mode: metadata-only —
        the blocks already live in the pool, parking just flips the
        session from resident to parked accounting (on False the slot
        keeps its binding so ``release_session`` still frees the
        blocks).  Gather mode: copy the slot KV into pool blocks."""
        slot = next((i for i, s in enumerate(self.slots)
                     if s.session_id == sid), None)
        if slot is None:
            return False
        if self.paged:
            if not self.pool.park_resident(sid):
                return False
            self.slots[slot] = SlotState()
            return True
        n = self.slots[slot].length
        k = self.cache["k"][:, slot]
        v = self.cache["v"][:, slot]
        ok = self.pool.park(sid, k, v, n)
        if ok:
            self.park_copy_bytes += self.pool.session_bytes(sid)
        self.slots[slot] = SlotState()
        return ok

    def release_session(self, sid: str) -> bool:
        """Free a session's slot WITHOUT parking its KV (task finished:
        nothing will resume).  In paged mode this returns the resident
        blocks to the free list — still metadata-only."""
        slot = next((i for i, s in enumerate(self.slots)
                     if s.session_id == sid), None)
        if slot is None:
            return False
        if self.paged and sid in self.pool.resident:
            self.pool.free_session(sid)
        self.slots[slot] = SlotState()
        return True

    # -- KV export/import (cross-engine migration + prefetch copies) --------
    def export_kv(self, sid: str) -> Optional[Tuple[jnp.ndarray,
                                                    jnp.ndarray, int]]:
        """Gather a parked session's KV to contiguous (L, n, K, dh)
        WITHOUT freeing its blocks — the transport half of a pool-to-pool
        copy (work-steal migration, speculative prefetch).  Only the
        owned blocks are copied."""
        return self.pool.resume(sid)

    def import_kv(self, sid: str, k: jnp.ndarray, v: jnp.ndarray,
                  n_tokens: int) -> bool:
        """Land an exported KV prefix into this engine's pool.  Returns
        False when the pool has no room (caller evicts and retries, or
        abandons the copy).  The KV may come from another device: it
        lands on this engine's own first."""
        ok = self.pool.park(sid, self._put(k), self._put(v), n_tokens)
        if ok:
            self.migration_copy_bytes += self.pool.session_bytes(sid)
        return ok

    # -- disaggregated prefill/decode handoff (serving/disagg.py) -----------
    def stage_prefill(self, sid: str, tokens: np.ndarray,
                      start: int) -> bool:
        """Prefill-role engines: compute KV for ``tokens[start:]``
        standalone (the causal mask makes a delta prefill independent of
        where the parked prefix lives — same jitted fn, same inputs,
        bit-identical KV) and stage it in this pool as a PARKED session
        awaiting handoff.  ``start == 0`` is a miss: the full context is
        regenerated here.  Returns False when the staging pool cannot
        fit — the PrefillScheduler gates admission on ``can_fit`` so
        this only trips under races it then defers."""
        delta = np.asarray(tokens[start:], np.int32)
        with self._spans("engine.prefill", sid=sid, n=0, pad_to=0):
            parked = self._prefill_into(delta, lambda dk, dv: self.pool.park(
                sid, dk, dv, len(delta)))
        if not parked:
            return False
        self.prefill_tokens += len(delta)
        if start == 0:
            self.regen_tokens += len(delta)
        return True

    def import_handoff(self, sid: str, k: jnp.ndarray, v: jnp.ndarray,
                       n_tokens: int, *, append: bool) -> bool:
        """Decode-role engines: land handed-off prefill KV.  ``append``
        (cache hit) extends the parked prefix in place; otherwise (miss)
        the full context parks fresh.  Returns False when the parked
        population would overflow nominal capacity — the runtime evicts
        and retries, or cancels the handoff."""
        k, v = self._put(k), self._put(v)
        if append:
            ok = self.pool.extend_parked(sid, k, v, n_tokens)
        else:
            ok = self.pool.park(sid, k, v, n_tokens)
        if ok:
            self.handoff_copy_bytes += int(n_tokens) * \
                (self.pool.bytes_per_block // self.pool.block)
        return ok

    def evict_session(self, sid: str) -> None:
        """Policy eviction of parked blocks.  A resident session's
        blocks are pinned by its slot (mirroring gather mode, where a
        resumed session holds no pool blocks at all): no-op until the
        slot releases them."""
        if self.paged and sid in self.pool.resident:
            return
        self.pool.free_session(sid)

    def fail(self) -> List[str]:
        """Engine crash: every decode slot and every parked session is
        lost at once.  Clears the slot table and the block tables (the
        device arrays stay allocated — new sessions overwrite them, and
        an empty slot/table means no decode or resume can read stale
        KV).  Returns the session ids whose state was held here, sorted,
        so the runtime can cancel their in-flight attempts."""
        lost = {s.session_id for s in self.slots
                if s.session_id is not None}
        lost.update(self.pool.tables)
        self.slots = [SlotState() for _ in range(self.n_slots)]
        for sid in list(self.pool.tables):
            self.pool.free_session(sid)
        return sorted(lost)

    def has_cache(self, sid: str) -> bool:
        return self.pool.has(sid)

    def pool_used_fraction(self) -> float:
        return self.pool.used_blocks() / self.pool.num_blocks
