"""GQA attention: full-causal, sliding-window, and single-token decode.

Three execution paths share one set of weights:

  * ``attend_train``    — full sequence, causal (optionally windowed).
    Uses a memory-bounded chunked online-softmax formulation (pure jnp,
    lax.scan over query chunks) so 32k-token prefill never materialises an
    S x S score matrix.  The Pallas flash kernel (repro.kernels) is the TPU
    hot path; this is its reference/lowering twin.
  * ``prefill``         — attend_train + emit a KV cache.
  * ``decode``          — one token against a cache (full-length or
    ring-buffer windowed).

Cache layout: {"k": (B, KV, S, hd), "v": (B, KV, S, hd), "pos": ()}.
``pos`` = number of tokens already written.  Windowed caches are ring
buffers of size W written at ``pos % W``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.launch import sharding
from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_attention(key, cfg, dtype) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, cfg.num_heads * hd, dtype),
        "wk": dense_init(ks[1], d, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], d, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.num_heads * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), dtype)
    return p


def _project_qkv(p, cfg, x, positions, blk):
    """x (B,S,d) -> q (B,S,H,hd), k/v (B,S,KV,hd), with the block's
    RoPE applied (YaRN-scaled on ``blk.yarn`` positions)."""
    B, S, _ = x.shape
    hd = cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    q = sharding.act(q, "batch", "seq", "heads", None)
    k = sharding.act(k, "batch", "seq", "kv_heads", None)
    v = sharding.act(v, "batch", "seq", "kv_heads", None)
    if blk.use_rope:
        yarn = cfg.yarn if blk.yarn else None
        q = apply_rope(q, positions, cfg.rope_theta, yarn)
        k = apply_rope(k, positions, cfg.rope_theta, yarn)
    return q, k, v


# ---------------------------------------------------------------------------
# chunked causal attention (online softmax, pure jnp)
# ---------------------------------------------------------------------------


def _pick_chunk(S: int, target: int = 1024) -> int:
    c = min(S, target)
    while S % c:
        c //= 2
    return max(c, 1)


def chunked_causal_attention(q, k, v, *, window: Optional[int] = None,
                             q_offset: int = 0,
                             q_chunk: int = 1024, kv_chunk: int = 1024):
    """q (B,Sq,H,hd); k,v (B,Skv,KV,hd) -> (B,Sq,H,hd).

    Causal within absolute positions: query i (at q_offset+i) attends keys
    j <= q_offset+i and, if windowed, j > q_offset+i - window.
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    q_chunk = _pick_chunk(Sq, q_chunk)
    kv_chunk = _pick_chunk(Skv, kv_chunk)
    nq, nk = Sq // q_chunk, Skv // kv_chunk
    scale = 1.0 / math.sqrt(hd)

    # keep streams in model dtype; accumulate in f32 inside each block
    qc = q.reshape(B, nq, q_chunk, KV, G, hd)
    kc = k.reshape(B, nk, kv_chunk, KV, hd)
    vc = v.reshape(B, nk, kv_chunk, KV, hd)
    # scan over q chunks; inner scan over kv chunks with online softmax.
    q_starts = jnp.arange(nq) * q_chunk + q_offset
    kv_starts = jnp.arange(nk) * kv_chunk

    def q_step(_, inp):
        qi, qstart = inp                       # (B,Cq,KV,G,hd), ()
        qpos = qstart + jnp.arange(q_chunk)    # (Cq,)

        @functools.partial(
            jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
        def kv_step(carry, kv_inp):
            # Checkpointed: the (Cq x Ckv) score/prob blocks are recomputed
            # in the backward pass instead of being saved per scan iteration
            # — the jnp twin of the flash-attention recompute trick.
            m, l, acc = carry
            ki, vi, kstart = kv_inp
            kpos = kstart + jnp.arange(kv_chunk)
            s = jnp.einsum("bqkgd,bckd->bqkgc", qi, ki,
                           preferred_element_type=jnp.float32) * scale
            mask = kpos[None, :] <= qpos[:, None]          # (Cq,Ckv)
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p_ = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p_.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqkgc,bckd->bqkgd", p_.astype(vi.dtype), vi,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, q_chunk, KV, G), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, q_chunk, KV, G), jnp.float32)
        a0 = jnp.zeros((B, q_chunk, KV, G, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (kc.swapaxes(0, 1), vc.swapaxes(0, 1),
                                       kv_starts))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return None, out

    _, out = jax.lax.scan(q_step, None, (qc.swapaxes(0, 1), q_starts))
    out = out.swapaxes(0, 1).reshape(B, Sq, H, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# block-level entry points
# ---------------------------------------------------------------------------


def attend_train(p, cfg, blk, x, positions) -> jnp.ndarray:
    """Full-sequence causal attention (train / prefill forward)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions, blk)
    out = chunked_causal_attention(q, k, v, window=blk.window,
                                   q_chunk=cfg.attn_chunk,
                                   kv_chunk=cfg.attn_chunk)
    out = sharding.act(out, "batch", "seq", "heads", None)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"]


def _cache_dtype(cfg):
    if cfg.kv_cache_dtype == "int8":
        return jnp.int8
    return jnp.dtype(cfg.dtype)


def _quantize_kv(x):
    """(..., S, hd) -> (int8 values, f32 scales (..., S, 1)).

    Symmetric per-(head, position) scaling: one scale per cache slot."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def init_kv_cache(cfg, blk, batch: int, max_len: int, make=jnp.zeros,
                  dtype=None):
    """Empty cache.  ``make`` can be jax.ShapeDtypeStruct for dry-runs."""
    dtype = dtype or _cache_dtype(cfg)
    W = blk.window or max_len
    W = min(W, max_len)
    kv = cfg.num_kv_heads
    cache = {
        "k": make((batch, kv, W, cfg.head_dim), dtype),
        "v": make((batch, kv, W, cfg.head_dim), dtype),
        "pos": make((), jnp.int32),
    }
    if cfg.kv_cache_dtype == "int8":
        cache["k_scale"] = make((batch, kv, W, 1), jnp.float32)
        cache["v_scale"] = make((batch, kv, W, 1), jnp.float32)
    return cache


def prefill(p, cfg, blk, x, positions, max_len: Optional[int] = None
            ) -> Tuple[jnp.ndarray, dict]:
    """Forward over the prompt; returns (out, cache).

    ``max_len`` sizes the emitted cache (>= S) so decode steps have room;
    windowed blocks emit a ring buffer of size min(window, max_len) with
    position p stored at slot p % W (matching :func:`decode`).
    """
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, positions, blk)
    out = chunked_causal_attention(q, k, v, window=blk.window,
                                   q_chunk=cfg.attn_chunk,
                                   kv_chunk=cfg.attn_chunk)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim) @ p["wo"]
    kT = k.swapaxes(1, 2)   # (B,KV,S,hd)
    vT = v.swapaxes(1, 2)
    max_len = max(max_len or S, S if blk.window is None else 0)
    W = min(blk.window, max_len) if blk.window is not None else max_len
    if W >= S:
        # position p < S <= W lands at ring slot p % W == p: right-pad.
        pad = W - S
        kr = jnp.pad(kT, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vr = jnp.pad(vT, ((0, 0), (0, 0), (0, pad), (0, 0)))
    else:
        # keep the last W positions, placed at their ring slots p % W
        last_pos = jnp.arange(S - W, S)
        slots = last_pos % W
        order = jnp.argsort(slots)
        kr = jnp.take(kT[:, :, S - W:], order, axis=2)
        vr = jnp.take(vT[:, :, S - W:], order, axis=2)
    cache = {"pos": jnp.asarray(S, jnp.int32)}
    if cfg.kv_cache_dtype == "int8":
        kq, ks = _quantize_kv(kr)
        vq, vs = _quantize_kv(vr)
        cache["k"] = sharding.act(kq, "batch", "kv_heads", "kv_seq", None)
        cache["v"] = sharding.act(vq, "batch", "kv_heads", "kv_seq", None)
        cache["k_scale"] = sharding.act(ks, "batch", "kv_heads", "kv_seq",
                                        None)
        cache["v_scale"] = sharding.act(vs, "batch", "kv_heads", "kv_seq",
                                        None)
    else:
        cache["k"] = sharding.act(kr, "batch", "kv_heads", "kv_seq", None)
        cache["v"] = sharding.act(vr, "batch", "kv_heads", "kv_seq", None)
    return out, cache


# ---------------------------------------------------------------------------
# paged KV cache (vLLM-style shared page pool)
# ---------------------------------------------------------------------------
#
# Layout: one pool per layer, {"k_pages": (P, KV, ps, hd), "v_pages": ...};
# page 0 is the allocator's reserved null page (never handed to a live
# sequence), so clamped/unmapped block-table entries and masked write lanes
# land there harmlessly.  The block table (pages_per_seq ids per sequence)
# is SHARED across layers: every layer writes the same logical positions,
# so one allocation describes all of them.
#
# The paged entry points take a STACK of layer pools (L, P, KV, ps, hd) and
# the index of their layer in it: a scanned model carries its stacked pools
# through the layer scan, so each layer writes its tokens' KV into the stack
# in place and reads its pages from it, and no layer's pool is ever sliced
# out or written back.  An unscanned layer passes its pool as a stack of one
# at index 0.


def init_paged_kv_cache(cfg, blk, num_pages: int, page_size: int,
                        make=jnp.zeros):
    """Empty per-layer page pool.  ``make`` may be jax.ShapeDtypeStruct.

    A windowed layer keeps every page of its lanes, as a full one does;
    its reads start at the window (``paged_flash_decode(window=)``)."""
    if cfg.kv_cache_dtype == "int8":
        raise ValueError(
            "paged KV serving supports model-dtype caches only "
            f"(kv_cache_dtype={cfg.kv_cache_dtype})")
    dtype = _cache_dtype(cfg)
    kv = cfg.num_kv_heads
    return {"k_pages": make((num_pages, kv, page_size, cfg.head_dim), dtype),
            "v_pages": make((num_pages, kv, page_size, cfg.head_dim), dtype)}


def _page_of(block_table, pos, page_size):
    """Physical page ids for logical positions; overshoot clamps onto the
    table's trailing null-padded entries (see ServeEngine row padding)."""
    idx = jnp.clip(pos // page_size, 0, block_table.shape[-1] - 1)
    return jnp.take_along_axis(block_table, idx, axis=-1)


def _rows(page, slot, kv: int):
    """Index arrays naming, for each of n positions (page[i], slot[i]),
    the row of hd values that holds each of its ``kv`` heads: (n, KV)
    after broadcasting.  The pools are written and read a row at a time:
    a window across the heads axis makes XLA lay the whole pool out
    afresh with the heads innermost, a copy of every pool at each step."""
    return page[:, None], jnp.arange(kv)[None, :], slot[:, None]


def _scatter_pages(pages, layer, vals, block_table, start):
    """Write ``vals`` (n, KV, hd) at positions start..start+n-1 of one
    sequence into layer ``layer`` of the stacked pool (L, P, KV, ps, hd);
    block_table (pages_per_seq,)."""
    ps = pages.shape[3]
    pos = start + jnp.arange(vals.shape[0])
    rows = _rows(_page_of(block_table, pos, ps), pos % ps, pages.shape[2])
    return pages.at[(layer,) + rows].set(vals.astype(pages.dtype))


def _gather_pages(pages, layer, block_table, max_ctx: int):
    """Dense (max_ctx, KV, hd) view of one sequence's pages in layer
    ``layer`` of the stacked pool (garbage past the written length --
    callers mask by position)."""
    ps = pages.shape[3]
    pos = jnp.arange(max_ctx)
    rows = _rows(_page_of(block_table, pos, ps), pos % ps, pages.shape[2])
    return pages[(layer,) + rows]


def paged_prefill_chunk(p, cfg, blk, x, cache, block_table, start, layer
                        ) -> Tuple[jnp.ndarray, dict]:
    """One prompt chunk through paged attention at layer ``layer`` of the
    stacked pools in ``cache``.  x (1, C, d) holds tokens
    at absolute positions start..start+C-1 (tail may be padding — pad
    positions are only ever read causally by pad queries, and decode
    overwrites their page slots before reading them).

    Writes the chunk's KV into the pool, then attends the chunk's queries
    against the full gathered context with absolute causal masking (and
    the block's window) — so a long prompt admits as a sequence of these
    calls interleaved with decode rounds instead of one blocking batch-1
    prefill.
    """
    B, C, _ = x.shape
    positions = start + jnp.arange(C)[None]                    # (1, C)
    q, k, v = _project_qkv(p, cfg, x, positions, blk)
    k_pages = _scatter_pages(cache["k_pages"], layer, k[0], block_table,
                             start)
    v_pages = _scatter_pages(cache["v_pages"], layer, v[0], block_table,
                             start)
    max_ctx = block_table.shape[-1] * k_pages.shape[3]
    kd = _gather_pages(k_pages, layer, block_table, max_ctx)[None]
    vd = _gather_pages(v_pages, layer, block_table, max_ctx)[None]
    out = chunked_causal_attention(q, kd, vd, window=blk.window,
                                   q_offset=start, q_chunk=cfg.attn_chunk,
                                   kv_chunk=cfg.attn_chunk)
    out = out.reshape(B, C, cfg.num_heads * cfg.head_dim) @ p["wo"]
    return out, {"k_pages": k_pages, "v_pages": v_pages}


def layer_view(k_pages, v_pages, block_tables, layer):
    """What the paged kernel reads for layer ``layer`` of stacked pools
    (L, P, KV, ps, hd): both pools as one pool of L * P pages (a reshape
    of the leading axes, which moves no data), and the block tables
    offset to that layer's pages in it."""
    L, P = k_pages.shape[:2]
    return (k_pages.reshape(L * P, *k_pages.shape[2:]),
            v_pages.reshape(L * P, *v_pages.shape[2:]),
            block_tables + layer * P)


def paged_attention(q, k_pages, v_pages, block_tables, lengths,
                    window: Optional[int] = None) -> jnp.ndarray:
    """The paged flash-decode kernel call (``kernels.ops``); ``window``
    keeps each lane's last ``window`` keys.

    Under an active ShardingContext it runs inside ``shard_map``: GSPMD
    cannot partition a Mosaic kernel.  Each shard sees the page pool as
    ``state_pspecs`` places it and its own KV heads and lanes
    (``sharding.paged_decode_pspecs``)."""
    from repro.kernels import ops as kernel_ops

    kernel = kernel_ops.paged_flash_decode
    if window is not None:
        kernel = functools.partial(kernel, window=window)
    ctx = sharding.current()
    if ctx is None:
        return kernel(q, k_pages, v_pages, block_tables, lengths)
    pool, heads, rows, lens = sharding.paged_decode_pspecs(
        ctx, q.shape[0], k_pages.shape)
    return jax.shard_map(
        kernel, mesh=ctx.mesh,
        in_specs=(heads, pool, pool, rows, lens), out_specs=heads,
        check_vma=False)(q, k_pages, v_pages, block_tables, lengths)


def paged_decode(p, cfg, blk, x, cache, block_tables, lengths, layer
                 ) -> Tuple[jnp.ndarray, dict]:
    """One-token decode over the shared page pool, at layer ``layer`` of
    the stacked pools in ``cache``.

    x (B, 1, d); lengths (B,) tokens already written per lane.  Writes the
    new token's KV at position lengths[b] of each lane's block table, then
    attends lengths[b]+1 tokens (the last ``blk.window`` of them on a
    windowed layer) via the paged flash-decode kernel (TPU) or its XLA
    gather twin (CPU), through :func:`layer_view`.  Inactive lanes pass
    a null block table (all page 0) and length 0 — their writes and reads
    hit the reserved null page and their outputs are discarded by the
    engine.
    """
    B = x.shape[0]
    hd = cfg.head_dim
    q, k, v = _project_qkv(p, cfg, x, lengths[:, None], blk)
    KV, ps = cache["k_pages"].shape[2:4]
    page = _page_of(block_tables, lengths[:, None], ps)[:, 0]  # (B,)
    rows = (layer,) + _rows(page, lengths % ps, KV)
    dtype = cache["k_pages"].dtype
    k_pages = cache["k_pages"].at[rows].set(k[:, 0].astype(dtype))
    v_pages = cache["v_pages"].at[rows].set(v[:, 0].astype(dtype))
    windowed = {} if blk.window is None else {"window": blk.window}
    out = paged_attention(q[:, 0],
                          *layer_view(k_pages, v_pages, block_tables, layer),
                          lengths + 1, **windowed)
    out = out.reshape(B, 1, cfg.num_heads * hd).astype(x.dtype)
    out = out @ p["wo"]
    return out, {"k_pages": k_pages, "v_pages": v_pages}


def decode(p, cfg, blk, x, cache) -> Tuple[jnp.ndarray, dict]:
    """One-token decode.  x (B,1,d); cache holds ``pos`` tokens already."""
    B = x.shape[0]
    hd = cfg.head_dim
    pos = cache["pos"]                                   # scalar int32
    q, k, v = _project_qkv(p, cfg, x, jnp.full((B, 1), pos), blk)
    W = cache["k"].shape[2]
    slot = pos % W
    quant = cfg.kv_cache_dtype == "int8"

    k_new = k.swapaxes(1, 2)
    v_new = v.swapaxes(1, 2)
    new_cache = {"pos": pos + 1}
    if quant:
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        upd = jax.lax.dynamic_update_slice_in_dim
        k_cache = upd(cache["k"], kq, slot, axis=2)
        v_cache = upd(cache["v"], vq, slot, axis=2)
        k_scale = upd(cache["k_scale"], ks, slot, axis=2)
        v_scale = upd(cache["v_scale"], vs, slot, axis=2)
        new_cache.update(k_scale=k_scale, v_scale=v_scale)
        k_read = _dequantize_kv(k_cache, k_scale, jnp.float32)
        v_read = _dequantize_kv(v_cache, v_scale, jnp.float32)
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k_new, slot, axis=2)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v_new, slot, axis=2)
        k_read = k_cache.astype(jnp.float32)
        v_read = v_cache.astype(jnp.float32)
    k_cache = sharding.act(k_cache, "batch", "kv_heads", "kv_seq", None)
    v_cache = sharding.act(v_cache, "batch", "kv_heads", "kv_seq", None)
    new_cache.update(k=k_cache, v=v_cache)

    KV, G = cfg.num_kv_heads, cfg.num_heads // cfg.num_kv_heads
    qh = q.reshape(B, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qh, k_read)
    s *= 1.0 / math.sqrt(hd)
    # ring-buffer validity: slot j is populated iff j <= pos or the buffer
    # has wrapped (pos >= W); window semantics are implied by ring size.
    valid = (jnp.arange(W) <= pos) | (pos >= W)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", w, v_read)
    out = out.reshape(B, 1, cfg.num_heads * hd).astype(x.dtype)
    out = out @ p["wo"]
    return out, new_cache
