"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.diffusion import DiffusionSchedule, reverse_step

NEG_INF = -1e30


def attention_ref(q, k, v, *, causal: bool = True,
                  window: Optional[int] = None) -> jnp.ndarray:
    """Dense masked attention.  q (B,H,Sq,hd); k,v (B,KV,Skv,hd)."""
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    G = H // KV
    kk = jnp.repeat(k, G, axis=1)
    vv = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) / jnp.sqrt(1.0 * hd)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", w, vv.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_ref(q, k_cache, v_cache, length,
               window: Optional[int] = None) -> jnp.ndarray:
    """q (B,H,hd); caches (B,KV,S,hd); length () or (B,); ``window``
    keeps only the last ``window`` keys below the length."""
    B, H, hd = q.shape
    KV, S = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qh = q.reshape(B, KV, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qh, k_cache.astype(jnp.float32))
    s = s / jnp.sqrt(1.0 * hd)
    pos = jnp.arange(S)[None, :]
    lens = jnp.broadcast_to(jnp.asarray(length), (B,))[:, None]
    valid = pos < lens
    if window is not None:
        valid &= pos >= lens - window
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", w, v_cache.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)


def paged_decode_ref(q, k_pages, v_pages, block_tables, lengths,
                     window: Optional[int] = None) -> jnp.ndarray:
    """Paged decode oracle: gather pages dense, then :func:`decode_ref`.

    q (B,H,hd); k/v_pages (P,KV,ps,hd); block_tables (B,npages) int32;
    lengths () or (B,).  Also the XLA-compiled serving path off-TPU.
    """
    B = q.shape[0]
    P, KV, ps, hd = k_pages.shape
    npages = block_tables.shape[1]
    tbl = jnp.clip(jnp.asarray(block_tables, jnp.int32), 0, P - 1)
    k = k_pages[tbl]                              # (B, npages, KV, ps, hd)
    v = v_pages[tbl]
    k = k.transpose(0, 2, 1, 3, 4).reshape(B, KV, npages * ps, hd)
    v = v.transpose(0, 2, 1, 3, 4).reshape(B, KV, npages * ps, hd)
    return decode_ref(q, k, v, lengths, window)


def ladn_denoise_ref(x_I, s, noise, temb_w1, w1x, w1s, b1, w2, b2, w3, b3,
                     sched: DiffusionSchedule,
                     paper_variance: bool = True) -> jnp.ndarray:
    """Unfused reverse chain on the padded weight layout.

    Matches ladn_denoise_fused bit-for-bit op order (f32 throughout).
    noise (T, I, A): noise[:, step] is used at step = I - i.
    """
    I = sched.num_steps  # noqa: E741
    x = x_I.astype(jnp.float32)
    sf = s.astype(jnp.float32)
    s_contrib = sf @ w1s + b1
    for step in range(I):
        i = I - step
        h = jax.nn.relu(x @ w1x + s_contrib + temb_w1[step][None, :])
        h = jax.nn.relu(h @ w2 + b2[None, :])
        eps = h @ w3 + b3[None, :]
        x = reverse_step(sched, eps, x, i, noise[:, step].astype(jnp.float32),
                         paper_variance=paper_variance)
    return x
