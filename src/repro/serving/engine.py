"""Continuous-batching serving engine: the per-ES "DEdgeAI worker".

One engine wraps one model replica and serves admitted requests with one
of two KV memory models:

**Dense slot pool** (fallback, any arch family).  A FIXED pool of
``kv_slots`` per-request caches, each ``max_len`` deep.  Each ``step()``
runs one batch-1 prefill per joining request, then ONE batched decode
round (a jitted ``vmap`` over the per-slot caches).  Capacity is
``kv_slots`` concurrent requests, full stop — a 32-token request holds a
``max_len``-deep cache hostage for its whole lifetime.

**Paged page pool** (all-attention configs; auto-detected).  KV memory is
a single shared pool of fixed-size pages per layer (vLLM-style), and each
request holds only ``ceil((prompt + max_new_tokens) / page_size)`` pages
named by a per-request block table (see repro.serving.paged_kv).
Admission is gated on *free pages*, not free slots, so many short
requests can be in flight at once — up to ``max_lanes`` — inside the same
KV budget that gave the dense pool ``kv_slots``.  Prefill is CHUNKED:
each step advances every still-prefilling lane by one ``prefill_chunk``-
token chunk and then runs one decode round across the lanes that have
finished prefilling — a long prompt no longer blocks the decode batch,
it interleaves with it.  Worst-case pages are reserved at admission
(generation length is deterministic), so admitted requests never
deadlock waiting for memory.

**Overlapped stepping** (the fleet fast path).  ``step()`` is split into
a non-blocking :meth:`dispatch` — admit, enqueue this round's prefill
chunks + decode round on the device, and return WITHOUT any host sync —
and a :meth:`collect` that blocks on the round's results and finalizes
requests.  A cluster driver dispatches ALL engines before collecting ANY
(see ``EdgeCluster.step`` / :func:`serve_batch`), so E engines' decode
rounds execute concurrently on device instead of serializing E host
round-trips.  ``step() == dispatch(); collect()`` exactly: control flow
(admission order, slot/lane reuse, finish decisions) is resolved at
dispatch time from token COUNTS only, so tokens and terminal statuses
are bit-identical between serial and overlapped stepping.

**Shared compiled steps** (``repro.serving.compiled``).  The jitted
prefill/decode callables are fetched from a module-level cache keyed on
(config, shapes, mesh), so same-config engines in a fleet share one
executable instead of re-jitting per replica, and decode-round states
are donated (in-place pool update, no per-round copy).

**Sharded big-model engines.**  Pass ``mesh=`` (e.g.
``launch.mesh.make_smoke_mesh()`` on CPU CI or ``make_production_mesh()``
on real devices) and the engine places params via
``launch.sharding.param_shardings`` (a no-op for params the builders
already generated there) and builds its KV pool / recurrent states
directly into their ``state_pspecs`` placement on that mesh, running
every step inside the corresponding
:class:`~repro.launch.sharding.ShardingContext` — this is how
``starcoder2-7b`` on four chips and the ``mixtral_8x22b`` /
``dbrx_132b`` scale configs serve.

Per-request latency is MEASURED, not modelled: the Request lifecycle
timestamps (queue / prefill / decode) decompose the serving-side terms of
the paper's Eqn (2) exactly.  The edge-level scheduler (``repro.cluster``)
decides WHICH engine serves a request; the engine reports its backlog via
``pending_tokens`` / ``pending_seconds`` (the q_b signal of Eqn 3).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.cluster.request import Request
from repro.faults.policy import AVAILABILITY, Health
from repro.launch import sharding as shlib
from repro.serving import compiled
from repro.serving.paged_kv import (BlockTable, PagePool, PrefixCache, cdiv,
                                    paged_supported)
from repro.workload.capability import EngineCapability, cold_token_seconds
from repro.workload.queueing import EDFQueue


@dataclasses.dataclass
class RequestResult:
    """Batch-level result of the blocking :meth:`ServeEngine.generate`.

    For B == 1 the three phases decompose the request's wall time
    exactly.  For a batch they are aggregates — worst per-request queue
    wait (slot contention when B > kv_slots), summed prefill compute,
    and the shared decode span; per-request timestamps are available
    through the ``admit()``/``step()`` API instead."""

    tokens: list
    prefill_s: float
    decode_s: float
    queue_s: float

    @property
    def total_s(self) -> float:
        return self.prefill_s + self.decode_s + self.queue_s


@dataclasses.dataclass
class _Lane:
    """One in-flight request in the paged engine."""

    req: Request
    table: BlockTable
    prompt_len: int
    chunk_pos: int = 0       # next prompt position to prefill
    length: int = 0          # KV positions written so far
    last_tok: Optional[np.ndarray] = None

    @property
    def decoding(self) -> bool:
        return self.chunk_pos >= self.prompt_len


@dataclasses.dataclass
class _Pending:
    """Device work enqueued by :meth:`ServeEngine.dispatch`, awaiting its
    :meth:`~ServeEngine.collect`.

    ``prefill`` holds ``(req, tok_device, pos, finished)`` — the deferred
    first-token of each prompt that completed prefilling this step
    (``pos`` is the token's index in ``req.tokens``); ``decode`` holds
    the round's stacked device tokens plus ``(slot/lane, req, pos,
    finished)`` per active participant.  Finish decisions are structural
    (token counts), so they are resolved at dispatch time; only VALUES
    and timestamps wait for the sync."""

    prefill: List[Tuple[Request, jax.Array, int, bool]] = \
        dataclasses.field(default_factory=list)
    decode: Optional[Tuple[jax.Array,
                           List[Tuple[int, Request, int, bool]]]] = None


class ServeEngine:
    """Continuous-batching engine for one model replica."""

    def __init__(self, cfg, params, *, max_len: int = 256,
                 kv_slots: int = 4, sample: bool = False,
                 temperature: float = 1.0,
                 clock: Callable[[], float] = time.monotonic,
                 paged: Optional[bool] = None, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 max_lanes: Optional[int] = None,
                 prefill_chunk: int = 64,
                 prefix_cache: Optional[bool] = None,
                 arch_id: Optional[str] = None,
                 mesh=None):
        self.cfg = cfg
        self.max_len = max_len
        self.kv_slots = kv_slots
        self.sample = sample
        self.arch_id = arch_id or cfg.name
        self._clock = clock
        # sharded serving: place params on the mesh and run every step
        # inside its ShardingContext (trace-time constraint annotations)
        self.mesh = mesh
        self._ctx = shlib.ShardingContext(mesh) if mesh is not None else None
        if mesh is not None:
            params = jax.device_put(params,
                                    shlib.param_shardings(mesh, params))
        self.params = params
        # priority/EDF ordering; exact FIFO for requests without QoS
        self._queue = EDFQueue()
        self._zero_tok = np.zeros(
            (1, cfg.num_codebooks) if cfg.num_codebooks else (1,), np.int32)
        self._rng = jax.random.key(0)
        self._ewma_tok_s = 0.0         # measured seconds per decode round
        self._next_rid = 0
        self.peak_inflight = 0
        # overlapped stepping: uncollected device work from dispatch()
        self._pending: Optional[_Pending] = None
        self._round_t0 = 0.0           # decode-round enqueue time
        # fault-tolerance state (repro.faults)
        self.health = Health.HEALTHY
        self.fail_reason: Optional[str] = None
        self._stall_until = 0.0        # DEGRADED: frozen until this clock
        self._slow_every = 1           # DEGRADED: serve 1 step out of k
        self._step_seq = 0
        # prefix-cache accounting (0 forever on dense / cache-off engines)
        self.prefill_tokens_saved = 0
        self.prefix_hits = 0
        self.prefix_lookups = 0
        self.cow_forks = 0

        self.paged = paged_supported(cfg) if paged is None else bool(paged)
        if self.paged:
            self.page_size = page_size
            self.prefill_chunk = prefill_chunk
            if num_pages is None:
                # same KV token budget the dense pool would hold, plus the
                # reserved null page — the win is sharing, not more memory
                num_pages = 1 + kv_slots * cdiv(max_len, page_size)
            self.num_pages = num_pages
            self.max_lanes = max_lanes or 2 * kv_slots
            # fixed jit-stable block-table width: a max_len request plus
            # null padding for chunked-prefill overshoot writes
            self._row_width = (cdiv(max_len, page_size)
                               + cdiv(prefill_chunk, page_size) + 1)
            self._pool = PagePool(num_pages, page_size)
            self._lanes: List[Optional[_Lane]] = [None] * self.max_lanes
            self._paged_states = None   # built lazily on first admission
            self._paged_prefill = compiled.paged_prefill_step(
                cfg, num_pages, page_size, mesh=mesh)
            self._paged_decode = compiled.paged_decode_step(
                cfg, num_pages, page_size, sample, temperature, mesh=mesh)
            # automatic prefix caching (on by default for paged engines):
            # completed prompt blocks stay resident, refcount-shared with
            # later prompts that hash to the same token-block chain
            if prefix_cache is None or prefix_cache:
                self._prefix: Optional[PrefixCache] = PrefixCache(self._pool)
                self._page_copy = compiled.page_copy_step(
                    cfg, num_pages, page_size, mesh=mesh)
            else:
                self._prefix = None
                self._page_copy = None
        else:
            self._prefix = None
            self._prefill = compiled.prefill_step(cfg, max_len, mesh=mesh)
            self._slots: List[Optional[Request]] = [None] * kv_slots
            self._last_tok: List[Optional[np.ndarray]] = [None] * kv_slots
            self._pool_states = None   # (slots, ...) stacked per-slot caches
            self._pool_decode = compiled.pool_decode_step(
                cfg, kv_slots, sample, temperature, mesh=mesh)
            self._insert = compiled.pool_insert()

    def _sharded(self):
        """Context manager activating this engine's mesh rules (no-op for
        unsharded engines)."""
        if self._ctx is None:
            return contextlib.nullcontext()
        return shlib.use(self._ctx)

    # ------------------------------------------------------------------
    # continuous-batching core
    # ------------------------------------------------------------------
    def admit(self, req: Request) -> None:
        """Enqueue a request; it joins the decode batch when capacity
        (a dense slot, or a lane + enough free pages) opens up."""
        if self.health is Health.DOWN:
            raise RuntimeError(
                f"engine {getattr(self, 'engine_id', '?')} "
                f"({self.arch_id}) is DOWN"
                f"{f' ({self.fail_reason})' if self.fail_reason else ''}; "
                f"cannot admit request {req.rid}")
        req.t_enqueue = self._clock()
        req.engine_id = getattr(self, "engine_id", None)
        self._queue.append(req)

    def step(self) -> List[Request]:
        """One scheduling iteration; returns requests finished this step.

        Exactly ``dispatch()`` followed by ``collect()`` — the serial
        reference the overlapped cluster path is parity-tested against."""
        if not self.dispatch():
            return []
        return self.collect()

    def dispatch(self) -> bool:
        """Enqueue one step's device work WITHOUT a host sync.

        Runs admission, this round's prefill chunks, and the decode
        round, leaving the round's tokens as uncommitted device arrays;
        :meth:`collect` blocks on them and finalizes requests.  Returns
        False when the engine is gated off this step (DOWN, stalled, or
        slowdown-skipped) and no collect is pending.

        A DOWN engine is inert.  A DEGRADED engine is either stalled
        (frozen until ``_stall_until``, then self-healing — a transient
        straggler) or slowed (serving one step out of ``_slow_every``
        until an explicit :meth:`recover`)."""
        if self._pending is not None:
            raise RuntimeError(
                "dispatch() with an uncollected step in flight; call "
                "collect() first")
        if self.health is Health.DOWN:
            return False
        if self.health is Health.DEGRADED:
            now = self._clock()
            if now < self._stall_until:
                return False
            if self._stall_until and self._slow_every <= 1:
                self.recover()          # stall window elapsed
            else:
                self._stall_until = 0.0
                self._step_seq += 1
                if self._step_seq % self._slow_every:
                    return False
        if not self._queue and not self._inflight_requests():
            return False               # idle: nothing to enqueue
        self._pending = (self._dispatch_paged() if self.paged
                         else self._dispatch_dense())
        return True

    def collect(self) -> List[Request]:
        """Sync the dispatched round and finalize requests.

        One host round-trip per engine per step: the round's stacked
        decode tokens (plus any deferred prefill first-tokens, already
        computed by then).  Returns the requests that finished this step,
        prefill-completions first — the same order serial ``step()``
        produced.  A no-op (empty list) when nothing was dispatched."""
        if self._pending is None:
            return []
        pend, self._pending = self._pending, None
        finished: List[Request] = []

        # decode sync first: one blocking transfer for the whole round.
        # _note_round windows from the DISPATCH-time enqueue (t0) to
        # results-ready here, so the EWMA tok/s times only this engine's
        # device wait — not the other engines' host loops that ran
        # between its dispatch and its collect.
        tok_np = None
        if pend.decode is not None:
            tok_all, entries = pend.decode
            tok_np = np.asarray(tok_all)           # blocks until ready
            self._note_round(self._round_t0, len(entries))

        # deferred prefill first-tokens (ready by now: they were enqueued
        # before the decode round)
        for req, tok_dev, pos, fin in pend.prefill:
            req.tokens[pos] = np.asarray(tok_dev)
            req.t_prefill_end = self._clock()
            if fin:
                req.finish(req.t_prefill_end)
                finished.append(req)

        if pend.decode is not None:
            now = self._clock()
            for i, req, pos, fin in entries:
                tk = tok_np[i] if not self.paged else tok_np[i:i + 1]
                req.tokens[pos] = tk
                if fin:
                    req.finish(now)
                    finished.append(req)
                elif self.paged:
                    self._lanes[i].last_tok = tk
                else:
                    self._last_tok[i] = tk
        return finished

    @property
    def pending_collect(self) -> bool:
        """True between a dispatch() and its collect()."""
        return self._pending is not None

    def _dispatch_dense(self) -> _Pending:
        pend = _Pending()
        # admission: every joining request's prefill is ENQUEUED here but
        # its first-token sync is deferred to collect() — K admissions
        # cost one deferred round-trip, not K blocking ones
        free = [i for i, r in enumerate(self._slots) if r is None]
        while free and self._queue:
            req = self._queue.popleft()
            i = free.pop(0)
            req.t_prefill_start = self._clock()
            batch = {"tokens": req.prompt}
            if req.patches is not None:
                batch["patches"] = req.patches
            with self._sharded():
                logits, st = self._prefill(self.params, batch)
                tok = self._pick(logits)           # device; sync deferred
            pos = len(req.tokens)
            req.tokens.append(tok)
            if len(req.tokens) >= req.max_new_tokens:
                pend.prefill.append((req, tok, pos, True))
                free.insert(0, i)
                continue
            pend.prefill.append((req, tok, pos, False))
            self._ensure_pool(st)
            with self._sharded():
                self._pool_states = self._insert(self._pool_states, st,
                                                 jnp.int32(i))
            self._slots[i] = req
            self._last_tok[i] = tok
        self._note_inflight(sum(r is not None for r in self._slots))

        active = [i for i, r in enumerate(self._slots) if r is not None]
        if active:
            toks = jnp.stack([jnp.asarray(t if t is not None
                                          else self._zero_tok, jnp.int32)
                              for t in self._last_tok])
            keys = jax.random.split(self._next_key(), self.kv_slots)
            self._round_t0 = self._clock()
            with self._sharded():
                tok_all, self._pool_states = self._pool_decode(
                    self.params, toks[..., None], self._pool_states, keys)
            entries = []
            for i in active:
                req = self._slots[i]
                pos = len(req.tokens)
                req.tokens.append(tok_all[i])      # device slice, lazy
                self._last_tok[i] = tok_all[i]
                fin = len(req.tokens) >= req.max_new_tokens
                entries.append((i, req, pos, fin))
                if fin:
                    self._slots[i] = None
            pend.decode = (tok_all, entries)
        return pend

    # ------------------------------------------------------------------
    # paged step: page-gated admission, chunked prefill, decode round
    # ------------------------------------------------------------------
    def _dispatch_paged(self) -> _Pending:
        pend = _Pending()
        # 1. admission — head-of-line, gated on free pages (worst case
        # reserved up front) and a free lane.  The queue drains in
        # priority/EDF order (exact FIFO without QoS classes); no
        # skipping past the ordered head.
        free = [i for i, ln in enumerate(self._lanes) if ln is None]
        while free and self._queue:
            req = self._queue[0]
            plen = self._prompt_len(req)
            total = plen + req.max_new_tokens
            need = self._pool.pages_needed(total)
            if need > self._row_width - 1 - cdiv(self.prefill_chunk,
                                                 self.page_size):
                raise ValueError(
                    f"request needs {need} pages > per-request capacity "
                    f"(max_len={self.max_len})")
            # prefix match: reuse every cached page whose token-block
            # chain equals this prompt's.  The match is capped at
            # plen - 1 so at least one position is always prefilled
            # (the last-chunk logits produce the first token).
            m = None
            if self._prefix is not None:
                m = self._prefix.match(req.prompt, max_tokens=plen - 1)
                self.prefix_lookups += 1
            shared = m.pages if m is not None else []
            # retain matched pages FIRST so eviction below can never free
            # them, then make room for the private remainder by evicting
            # LRU cached leaves if needed
            if m is not None:
                self._prefix.acquire(m)
            need_new = need - len(shared)
            ok = (self._pool.num_free >= need_new
                  or (self._prefix is not None
                      and self._prefix.ensure_free(need_new)))
            if not ok:
                if m is not None:
                    self._prefix.release_match(m)
                break
            self._queue.popleft()
            i = free.pop(0)
            table = BlockTable(self._pool, total, shared=shared)
            matched = len(shared) * self.page_size
            if m is not None and m.cow_page is not None:
                # copy-on-write fork: the lane diverges mid-block, so it
                # gets a device-side copy of the partially-matching
                # cached page and re-prefills only from the divergence
                self._ensure_paged_states()
                dst = table.pages[len(shared)]
                with self._sharded():
                    self._paged_states = self._page_copy(
                        self._paged_states, jnp.int32(m.cow_page),
                        jnp.int32(dst))
                self._pool.release([m.cow_page])   # fork done: drop src
                matched += m.cow_tokens
                self.cow_forks += 1
            if matched:
                self.prefix_hits += 1
                self.prefill_tokens_saved += matched
                req.prefix_tokens = matched
            self._lanes[i] = _Lane(req=req, table=table, prompt_len=plen,
                                   chunk_pos=matched, length=matched)
        self._note_inflight(sum(ln is not None for ln in self._lanes))

        # 2. one prefill chunk per still-prefilling lane (device enqueue
        # only; last-chunk first-tokens sync in collect())
        self._ensure_paged_states()
        C = self.prefill_chunk
        for i, lane in enumerate(self._lanes):
            if lane is None or lane.decoding:
                continue
            req = lane.req
            if req.t_prefill_start is None:    # first chunk (chunk_pos may
                req.t_prefill_start = self._clock()  # start past 0 on a hit)
            c0 = lane.chunk_pos
            chunk = np.asarray(req.prompt[..., c0:c0 + C])
            pad = C - chunk.shape[-1]
            if pad:
                widths = [(0, 0)] * (chunk.ndim - 1) + [(0, pad)]
                chunk = np.pad(chunk, widths)
            row = jnp.asarray(lane.table.row(self._row_width), jnp.int32)
            with self._sharded():
                logits, self._paged_states = self._paged_prefill(
                    self.params,
                    {"tokens": jnp.asarray(chunk, jnp.int32),
                     "start": jnp.asarray(c0, jnp.int32),
                     "block_table": row},
                    self._paged_states)
            lane.chunk_pos = c0 + C
            lane.length = min(lane.chunk_pos, lane.prompt_len)
            if lane.decoding:                      # last chunk of prompt
                last = lane.prompt_len - 1 - c0
                with self._sharded():
                    tok = self._pick(logits[0, last][None])
                pos = len(req.tokens)
                req.tokens.append(tok)
                lane.last_tok = tok
                fin = len(req.tokens) >= req.max_new_tokens
                pend.prefill.append((req, tok, pos, fin))
                # the prompt's KV is complete: index every full prompt
                # block so later prompts with the same chain reuse it
                # (insert BEFORE any lane release so cached pages carry
                # their reference when the lane lets go)
                if self._prefix is not None:
                    self._prefix.insert(req.prompt, lane.table.pages)
                if fin:
                    self._free_lane(i)

        # 3. one decode round across the lanes that finished prefilling;
        # idle/prefilling lanes ride along masked (null table, length 0)
        active = [i for i, ln in enumerate(self._lanes)
                  if ln is not None and ln.decoding]
        if active:
            L, W = self.max_lanes, self._row_width
            rows = [self._zero_tok] * L
            tables = np.zeros((L, W), np.int32)
            lengths = np.zeros((L,), np.int32)
            for i in active:
                lane = self._lanes[i]
                rows[i] = lane.last_tok        # may be a device array
                tables[i] = lane.table.row(W)
                lengths[i] = lane.length
            toks = jnp.stack([jnp.asarray(r, jnp.int32) for r in rows])
            if self.cfg.num_codebooks:
                tok_in = jnp.transpose(toks, (0, 2, 1))  # (L,1,K)->(L,K,1)
            else:
                tok_in = toks                      # (L,1)
            self._round_t0 = self._clock()
            with self._sharded():
                _, tok_all, self._paged_states = self._paged_decode(
                    self.params,
                    {"tokens": tok_in,
                     "block_tables": jnp.asarray(tables, jnp.int32),
                     "lengths": jnp.asarray(lengths, jnp.int32)},
                    self._paged_states, self._next_key())
            entries = []
            for i in active:
                lane = self._lanes[i]
                req = lane.req
                tk = tok_all[i:i + 1]              # device slice, lazy
                pos = len(req.tokens)
                req.tokens.append(tk)
                lane.last_tok = tk
                lane.length += 1                   # decode wrote one KV
                fin = len(req.tokens) >= req.max_new_tokens
                entries.append((i, req, pos, fin))
                if fin:
                    self._free_lane(i)
            pend.decode = (tok_all, entries)
        return pend

    def run_to_completion(self, max_steps: int = 1_000_000) -> List[Request]:
        """Step until queue and slots drain; returns finished requests."""
        done = []
        for _ in range(max_steps):
            if not self.has_work:
                break
            done += self.step()
        return done

    def reset(self) -> None:
        """Drop queued/in-flight work and measurement state.

        Device pool contents need no zeroing — every KV position is
        written before it is read — but the rate EWMA and the request-id
        counter must restart or a reused engine reports the previous
        run's backlog estimate and non-monotonic request ids.  Paged
        engines release every lane and prefix-cache reference through the
        refcount path and ASSERT the pool returns to all-free — a reset
        is the one moment the refcount books must balance exactly, so a
        leak here is a bug, not a condition to paper over."""
        self._queue.clear()
        self._ewma_tok_s = 0.0
        self._next_rid = 0
        self.peak_inflight = 0
        self._pending = None
        self.health = Health.HEALTHY
        self.fail_reason = None
        self._stall_until = 0.0
        self._slow_every = 1
        self._step_seq = 0
        self.prefill_tokens_saved = 0
        self.prefix_hits = 0
        self.prefix_lookups = 0
        self.cow_forks = 0
        if self.paged:
            for i, lane in enumerate(self._lanes):
                if lane is not None:
                    lane.table.release()
            self._lanes = [None] * self.max_lanes
            if self._prefix is not None:
                self._prefix.clear()
            if self._pool.num_free != self.num_pages - 1:
                raise RuntimeError(
                    f"page pool leak on reset: {self._pool.num_free} free "
                    f"of {self.num_pages - 1} allocatable after releasing "
                    f"all lanes and the prefix cache")
            self._pool.reset()
        else:
            self._slots = [None] * self.kv_slots
            self._last_tok = [None] * self.kv_slots

    # ------------------------------------------------------------------
    # fault tolerance: health transitions (repro.faults)
    # ------------------------------------------------------------------
    def fail(self, reason: str = "injected crash") -> List[Request]:
        """Hard crash: mark DOWN, drain queued + in-flight requests and
        reclaim every KV page / dense slot they held.

        Returns the orphaned requests (queued first, then in-flight) so
        the cluster can re-offload them; their per-attempt state is NOT
        reset here — recovery policy belongs to the caller.  An
        uncollected dispatch is dropped: requests that finished inside it
        are orphaned too (their un-synced tokens are discarded on
        retry)."""
        orphans: List[Request] = list(self._queue)
        self._queue.clear()
        if self._pending is not None:
            pend, self._pending = self._pending, None
            orphans += [req for req, _, _, fin in pend.prefill if fin]
            if pend.decode is not None:
                orphans += [req for _, req, _, fin in pend.decode[1] if fin]
        if self.paged:
            for i, lane in enumerate(self._lanes):
                if lane is not None:
                    orphans.append(lane.req)
                    self._free_lane(i)
        else:
            for i, req in enumerate(self._slots):
                if req is not None:
                    orphans.append(req)
                    self._slots[i] = None
                    self._last_tok[i] = None
        self.health = Health.DOWN
        self.fail_reason = str(reason)
        return orphans

    def recover(self) -> None:
        """Return to HEALTHY after a crash, stall, or slowdown window."""
        self.health = Health.HEALTHY
        self.fail_reason = None
        self._stall_until = 0.0
        self._slow_every = 1

    def degrade(self, *, stall_s: float = 0.0, slow_every: int = 1,
                reason: str = "injected degradation") -> None:
        """Soft fault: freeze for ``stall_s`` seconds (transient
        straggler, self-healing) and/or serve only one step out of
        ``slow_every`` (sustained slowdown, until :meth:`recover`)."""
        if self.health is Health.DOWN:
            raise RuntimeError("cannot degrade a DOWN engine; recover it "
                               "first")
        self.health = Health.DEGRADED
        self.fail_reason = str(reason)
        if stall_s > 0:
            self._stall_until = self._clock() + stall_s
        self._slow_every = max(int(slow_every), 1)

    @property
    def available(self) -> bool:
        """Placement-eligible (DEGRADED still serves, DOWN does not)."""
        return self.health is not Health.DOWN

    @property
    def availability(self) -> float:
        """Observation feature: 1 healthy, 0.5 degraded, 0 down."""
        return AVAILABILITY[self.health]

    @property
    def kv_leak(self) -> int:
        """Outstanding KV reservations (page references, or busy dense
        slots), net of the prefix cache's deliberate residency.

        The prefix cache holds exactly ONE pool reference per entry, so
        ``total_refs - cache.size`` counts every reference owed to live
        lanes.  0 whenever the engine is idle — the crash-recovery
        invariant the chaos tests assert, now refcount-exact: a crash
        mid-prefill on a SHARED prefix must drop only the crashed lane's
        references, leaving cached pages resident and every refcount
        right."""
        if self.paged:
            held = self._prefix.size if self._prefix is not None else 0
            return self._pool.total_refs - held
        return sum(r is not None for r in self._slots)

    def shed(self, pred) -> List[Request]:
        """Remove queued (not yet running) requests matching ``pred`` —
        the cluster watchdog's shedding hook."""
        return self._queue.drain(pred)

    # ------------------------------------------------------------------
    # backlog signals (the scheduler's q_b / Eqn-3 observation)
    # ------------------------------------------------------------------
    def _inflight_requests(self) -> List[Request]:
        if self.paged:
            return [ln.req for ln in self._lanes if ln is not None]
        return [r for r in self._slots if r is not None]

    @property
    def has_work(self) -> bool:
        return (bool(self._queue) or bool(self._inflight_requests())
                or self._pending is not None)

    @property
    def pending_tokens(self) -> int:
        """Tokens still to generate across queued + in-flight requests."""
        n = sum(r.max_new_tokens for r in self._queue)
        n += sum(r.max_new_tokens - len(r.tokens)
                 for r in self._inflight_requests())
        return n

    @property
    def pending_seconds(self) -> float:
        """Measured backlog estimate: pending tokens x EWMA token time."""
        return self.pending_tokens * self._ewma_tok_s

    # ------------------------------------------------------------------
    # prefix-cache signals (the scheduler's affinity feature)
    # ------------------------------------------------------------------
    def expected_prefix_tokens(self, req: Request) -> int:
        """Prompt tokens this engine could skip for ``req`` RIGHT NOW — a
        pure peek against the prefix index (no reference taken, no LRU
        bump).  0 for dense / cache-off engines.  This is the per-engine
        observation feature the prefix-affinity scheduler routes on: the
        paper's thesis is to send work where it finishes fastest, and a
        matched prefix is compute already done."""
        if not self.paged or self._prefix is None:
            return 0
        m = self._prefix.match(req.prompt,
                               max_tokens=self._prompt_len(req) - 1)
        return m.tokens

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of admissions that reused at least one cached page."""
        if not self.prefix_lookups:
            return 0.0
        return self.prefix_hits / self.prefix_lookups

    @property
    def prefix_cached_pages(self) -> int:
        return self._prefix.size if (self.paged and self._prefix is not None
                                     ) else 0

    @property
    def prefix_evictions(self) -> int:
        return (self._prefix.evictions
                if (self.paged and self._prefix is not None) else 0)

    @property
    def est_token_seconds(self) -> float:
        """Seconds per decode token: measured EWMA once the engine has run
        a round, else a FLOPs-based cold prior (the paper's rho_n / f_b)."""
        if self._ewma_tok_s > 0:
            return self._ewma_tok_s
        return cold_token_seconds(self.cfg)

    @property
    def capability(self) -> EngineCapability:
        """Snapshot of this engine as an edge-server capability descriptor:
        its live f_b' (measured tok/s) and per-step cost rho_n."""
        active = self.cfg.active_param_count()
        return EngineCapability(
            arch=self.arch_id,
            model_name=self.cfg.name,
            num_layers=self.cfg.num_layers,
            d_model=self.cfg.d_model,
            active_params=active,
            rho_gcycles=2.0 * active / 1e9,
            tok_s=1.0 / self.est_token_seconds,
            measured=self._ewma_tok_s > 0,
            paged=self.paged,
            prefix_hit_rate=self.prefix_hit_rate,
            prefix_cached_tokens=self.prefix_cached_pages * (
                self.page_size if self.paged else 0))

    # ------------------------------------------------------------------
    # blocking compatibility API
    # ------------------------------------------------------------------
    def generate(self, prompts: jnp.ndarray, num_tokens: int,
                 rng: Optional[jax.Array] = None,
                 patches: Optional[jnp.ndarray] = None) -> RequestResult:
        """prompts (B, S) [or (B, K, S) audio] -> (B,)-stacked tokens per
        generated step, plus measured timing (admit all, drain)."""
        if rng is not None:
            self._rng = rng
        reqs = []
        for b in range(prompts.shape[0]):
            reqs.append(Request(
                rid=self._next_rid, prompt=prompts[b:b + 1],
                max_new_tokens=max(num_tokens, 1),
                patches=None if patches is None else patches[b:b + 1]))
            self._next_rid += 1
            self.admit(reqs[-1])
        self.run_to_completion()
        toks = [np.concatenate([r.tokens[s] for r in reqs], axis=0)
                for s in range(max(num_tokens, 1))]
        t_dec0 = max(r.t_prefill_end for r in reqs)
        t_end = max(r.t_finish for r in reqs)
        return RequestResult(
            tokens=toks,
            prefill_s=sum(r.prefill_s for r in reqs),
            decode_s=max(t_end - t_dec0, 0.0),
            queue_s=max(max(r.queue_s for r in reqs), 0.0))

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------
    def _next_key(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def _pick(self, logits):
        if self.sample:
            return jax.random.categorical(self._next_key(), logits, axis=-1
                                          ).astype(jnp.int32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    @staticmethod
    def _prompt_len(req: Request) -> int:
        return int(req.prompt.shape[-1])

    def _note_round(self, t0: float, active: int) -> None:
        # a round advances every active lane one token, so the per-token
        # drain rate is round time / active lanes.  t0 is stamped at
        # DISPATCH (device enqueue) and the sync lands in collect(), so
        # this windows exactly one engine's enqueue-to-ready device wait;
        # a whole-cluster-step window would absorb the other engines'
        # compute under overlapped stepping and corrupt the capability
        # descriptor's f_b' (and with it the deadline-aware scheduler's
        # affinity features).
        dt = (self._clock() - t0) / active
        self._ewma_tok_s = (0.7 * self._ewma_tok_s + 0.3 * dt
                            if self._ewma_tok_s else dt)

    def _note_inflight(self, n: int) -> None:
        self.peak_inflight = max(self.peak_inflight, n)

    def _free_lane(self, i: int) -> None:
        self._lanes[i].table.release()
        self._lanes[i] = None

    def _ensure_paged_states(self) -> None:
        if self._paged_states is None:
            from repro.models.transformer import init_paged_states
            self._paged_states = self._zeros(init_paged_states(
                self.cfg, self.num_pages, self.page_size,
                make=jax.ShapeDtypeStruct))

    def _ensure_pool(self, st):
        """Lazily build the slot pool from the structure of the first
        prefill's cache (covers every arch family: attention ring
        buffers, quantised caches, recurrent states)."""
        if self._pool_states is not None:
            return
        slots = self.kv_slots
        self._pool_states = self._zeros(jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct((slots,) + leaf.shape,
                                              leaf.dtype), st))

    def _zeros(self, abstract):
        """Zero KV / recurrent state for a ShapeDtypeStruct tree, built
        directly into its mesh placement (divisibility-guarded per leaf
        by ``state_shardings``) — never whole on one device first."""
        if self.mesh is None:
            return jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), abstract)
        return compiled.state_zeros(abstract, self.mesh)()


def serve_batch(engines: List[ServeEngine], assignments: List[int],
                prompts: List[jnp.ndarray], num_tokens: int
                ) -> List[RequestResult]:
    """Route each prompt to its assigned engine, serve them concurrently
    (continuous batching within each engine), return per-request results.

    Overlapped stepping: every busy engine's round is DISPATCHED before
    any engine's results are collected, so E engines' decode rounds run
    concurrently on device instead of paying E serial host syncs."""
    reqs = []
    for i, pr in enumerate(prompts):
        # prompts arrive unbatched — (S,) text or (K, S) audio — and gain
        # the leading batch dim here (matching the original serve_batch)
        req = Request(rid=i, prompt=pr[None],
                      max_new_tokens=max(num_tokens, 1))
        reqs.append(req)
        engines[assignments[i]].admit(req)
    while any(e.has_work for e in engines):
        busy = [e for e in engines if e.has_work]
        for e in busy:          # an idle engine's step is not free: it
            e.dispatch()        # still pays host-side bookkeeping
        for e in busy:
            e.collect()
    return [RequestResult(tokens=r.tokens, prefill_s=r.prefill_s,
                          decode_s=r.decode_s, queue_s=r.queue_s)
            for r in reqs]
