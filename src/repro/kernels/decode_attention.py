"""Pallas TPU flash-decode: one query token against a deep KV cache.

The decode_32k / long_500k serving shapes are memory-bound: the whole
point of the kernel is to stream the (B, KV, S, hd) cache through VMEM
exactly once with online softmax, instead of materialising (B, H, S)
score tensors in HBM.

Two cache layouts share the same online-softmax inner loop:

``flash_decode``
    Dense per-sequence ring buffers (B, KV, S, hd).  Grid (B*KV, S/bk),
    kv blocks innermost, running (m, l, acc) in VMEM scratch like the
    prefill kernel.  All G = H/KV query heads of one KV group are
    processed together as a (G, hd) tile (G is tiny: 1-16), so the MXU
    sees a (G, hd) x (hd, bk) matmul per block.  ``length`` masks
    ring-buffer slots that are not yet populated; fully-invalid trailing
    blocks are skipped with @pl.when.

``paged_flash_decode``
    vLLM-style shared page pool (num_pages, KV, page_size, hd): every
    sequence owns a list of pages named by a per-sequence block-index
    table (B, pages_per_seq), so cache memory is pooled across requests
    instead of statically partitioned into per-slot rings.  The grid is
    one step per lane: the lane's query heads for all its KV heads form
    one (KV, G, hd) tile, and a loop inside the step attends the lane's
    live pages a block of several pages at a time.  The pool stays in
    HBM; the kernel gathers a block's pages itself, one DMA per page (all
    KV heads of a page are contiguous), into one of two VMEM buffers, and
    starts the next block's copies -- the lane's next block, or the next
    non-empty lane's first -- before it computes on the current one.  The
    block tables and lengths are scalar-prefetched to SMEM, so only pages
    below ceil(length / page_size) are ever copied or read from the
    table: a lane of length 1 costs one page, and the loop runs only over
    live blocks.  ``length`` masks the tail of the last live page.  With
    a ``window`` a lane's loop starts at the page holding key position
    length - window: pages wholly before it are never copied, and the
    keys before it in that page are masked.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, bk: int, nk: int, G: int):
    kj = pl.program_id(1)
    k_start = kj * bk
    length = len_ref[0]

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (G, hd)
        k = k_ref[0].astype(jnp.float32)              # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (G, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (G, bk), 1)
        s = jnp.where(kpos < length, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * corr
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_decode(q, k_cache, v_cache, length, *, bk: int = 512,
                 interpret: bool = False) -> jnp.ndarray:
    """q (B, H, hd); caches (B, KV, S, hd); length () or (B,) valid tokens.

    Returns (B, H, hd).
    """
    B, H, hd = q.shape
    KV, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    bk = min(bk, S)
    assert S % bk == 0
    nk = S // bk
    scale = 1.0 / math.sqrt(hd)

    qf = q.reshape(B, KV, G, hd).reshape(B * KV, G, hd)
    kf = k_cache.reshape(B * KV, S, hd)
    vf = v_cache.reshape(B * KV, S, hd)
    lengths = jnp.broadcast_to(jnp.asarray(length, jnp.int32), (B,))
    lengths = jnp.repeat(lengths, KV)                  # (B*KV,)

    kernel = functools.partial(_decode_kernel, scale=scale, bk=bk, nk=nk,
                               G=G)
    out = pl.pallas_call(
        kernel,
        grid=(B * KV, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda bh, kj: (bh,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, G, hd), lambda bh, kj: (bh, 0, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, kj: (bh, kj, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, kj: (bh, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda bh, kj: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        interpret=interpret,
    )(lengths, qf, kf, vf)
    return out.reshape(B, H, hd)


# Tokens of context per block of the paged kernel: enough pages per block
# to amortise its fixed cost, few enough that a lane's partial last block
# (an idle lane's only one) wastes little compute.  Timed on a v5e over
# 128-1,024 tokens at both served shapes (PERF.md, PR 14).
BLOCK_TOKENS = 512


def _pages_per_block(page_size: int, pages_per_seq: int) -> int:
    return max(1, min(pages_per_seq, BLOCK_TOKENS // page_size))


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sems, slot_ref, m_scr, l_scr, acc_scr,
                         *, scale: float, ps: int, ppb: int, npages: int,
                         lanes: int, window):
    b = pl.program_id(0)
    KV, hd = k_buf.shape[2], k_buf.shape[4]
    T = ppb * ps

    def length_of(lane):
        return jnp.minimum(len_ref[lane], npages * ps)

    def first_page(lane):
        """The lane's first page in its window (0 without a window)."""
        return jnp.maximum(length_of(lane) - window, 0) // ps

    def block_copies(lane, blk, slot, wait):
        """Start (or wait for) the copies of block ``blk`` of ``lane``
        into buffer ``slot``: one DMA per live page, all KV heads.  A full
        block issues its copies in straight-line code and waits once for
        the whole buffer; the lane's last, partial block loops over its
        live pages (a branch per page costs about what its DMA does)."""
        live = (length_of(lane) + ps - 1) // ps
        base = blk * ppb
        if window is not None:
            base = base + first_page(lane)
        n = jnp.minimum(live - base, ppb)
        pools = ((k_hbm, k_buf, sems.at[0, slot]),
                 (v_hbm, v_buf, sems.at[1, slot]))

        def page(i, carry):
            phys = tbl_ref[lane * npages + base + i]
            for pool, buf, sem in pools:
                copy = pltpu.make_async_copy(pool.at[phys], buf.at[slot, i],
                                             sem)
                if wait:
                    copy.wait()
                else:
                    copy.start()
            return carry

        @pl.when(n == ppb)
        def _full():
            if wait:              # a semaphore counts bytes: one wait
                for pool, buf, sem in pools:
                    pltpu.make_async_copy(pool.at[pl.ds(0, ppb)],
                                          buf.at[slot], sem).wait()
            else:
                for i in range(ppb):
                    page(i, 0)

        @pl.when(n < ppb)
        def _partial():
            jax.lax.fori_loop(0, n, page, 0)

    def next_live_lane(after):
        found = lanes
        for i in reversed(range(lanes)):
            found = jnp.where((i > after) & (len_ref[i] > 0), i, found)
        return found

    @pl.when(b == 0)
    def _prime():
        slot_ref[0] = 0
        lane0 = next_live_lane(-1)

        @pl.when(lane0 < lanes)
        def _start():
            block_copies(lane0, 0, 0, wait=False)

    length = length_of(b)
    if window is None:
        p0 = 0
        nblk = (length + T - 1) // T
    else:
        p0 = first_page(b)
        nblk = ((length + ps - 1) // ps - p0 + ppb - 1) // ppb
    after = next_live_lane(b)
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def block(j, carry):
        slot = slot_ref[0]
        more = j + 1 < nblk
        nb = jnp.where(more, b, after)

        @pl.when(nb < lanes)
        def _prefetch():          # next block of this lane, or the next lane
            block_copies(nb, jnp.where(more, j + 1, 0), 1 - slot,
                         wait=False)
            slot_ref[0] = 1 - slot

        block_copies(b, j, slot, wait=True)
        k_start = j * T if window is None else (p0 + j * ppb) * ps
        q = q_ref[0]                                       # (KV, G, hd)
        k = jnp.stack([k_buf[slot, :, h].reshape(T, hd)
                       for h in range(KV)])                # (KV, T, hd)
        v = jnp.stack([v_buf[slot, :, h].reshape(T, hd)
                       for h in range(KV)]).astype(jnp.float32)
        # slots past the last live page hold whatever an earlier block
        # left there: mask the scores, and zero V so 0 * stale stays 0
        vpos = k_start + jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
        v = jnp.where(vpos < length, v, 0.0)
        # products of two bf16 values are exact in f32: QK^T in the
        # pool's dtype equals it in f32, without the f32 passes
        dt = jnp.promote_types(q.dtype, k.dtype)
        s = jax.lax.dot_general(
            q.astype(dt), k.astype(dt), (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale    # (KV, G, T)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        live = kpos < length
        if window is not None:
            live &= kpos >= length - window
        s = jnp.where(live, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=2, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=2, keepdims=True)
        acc_scr[...] = (acc_scr[...] * corr
                        + jax.lax.dot_general(
                            p, v, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new
        return carry

    jax.lax.fori_loop(0, nblk, block, 0)
    o_ref[0] = (acc_scr[...]
                / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_flash_decode(q, k_pages, v_pages, block_tables, lengths, *,
                       interpret: bool = False,
                       pages_per_block: int | None = None,
                       window: int | None = None) -> jnp.ndarray:
    """Flash decode over a shared KV page pool.

    q            (B, H, hd) one query token per sequence.
    k/v_pages    (num_pages, KV, page_size, hd) the shared pool.
    block_tables (B, pages_per_seq) int32: logical page j of sequence b
                 lives in physical page ``block_tables[b, j]``.  Entries
                 past the mapped range may hold any value (never read;
                 clamped to a valid index all the same).
    lengths      () or (B,) valid tokens per sequence.
    pages_per_block  pages per block; derived from the page size and the
                 row width when None (tests force small blocks).
    window       None: attend every key below the length; else only the
                 last ``window`` keys (positions length - window on).

    Returns (B, H, hd).  A sequence with length 0 returns zeros.
    """
    B, H, hd = q.shape
    P, KV, ps, _ = k_pages.shape
    G = H // KV
    npages = block_tables.shape[1]
    ppb = min(pages_per_block or _pages_per_block(ps, npages), P)
    scale = 1.0 / math.sqrt(hd)

    qf = q.reshape(B, KV, G, hd)
    tbl = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, P - 1)
    lens = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))

    kernel = functools.partial(_paged_decode_kernel, scale=scale, ps=ps,
                               ppb=ppb, npages=npages, lanes=B,
                               window=window)
    lane_block = pl.BlockSpec((1, KV, G, hd),
                              lambda b, tbl, lens: (b, 0, 0, 0))
    buf = pltpu.VMEM((2, ppb, KV, ps, hd), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,        # block tables + lengths land in SMEM
        grid=(B,),                    # a lane's blocks: a loop inside
        in_specs=[
            lane_block,
            pl.BlockSpec(memory_space=pl.ANY),     # pools stay in HBM;
            pl.BlockSpec(memory_space=pl.ANY),     # pages are DMA'd by hand
        ],
        out_specs=lane_block,
        scratch_shapes=[
            buf, buf,
            pltpu.SemaphoreType.DMA((2, 2)),       # (K/V, buffer slot)
            pltpu.SMEM((1,), jnp.int32),           # slot of current block
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, 1), jnp.float32),
            pltpu.VMEM((KV, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        # the prefetch chain crosses lanes: they run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tbl.reshape(-1), lens, qf, k_pages, v_pages)
    return out.reshape(B, H, hd)
