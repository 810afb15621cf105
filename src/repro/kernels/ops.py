"""Jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True on CPU (the validation environment) and
False on TPU — the kernels are written for the TPU target (BlockSpec VMEM
tiling) and validated in interpret mode against repro.kernels.ref.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.diffusion import DiffusionSchedule
from repro.core.networks import TIME_EMBED_DIM, timestep_embed
from repro.kernels import ref as _ref
from repro.kernels.decode_attention import flash_decode as _flash_decode
from repro.kernels.decode_attention import (paged_flash_decode
                                            as _paged_flash_decode)
from repro.kernels.flash_attention import flash_attention as _flash_attn
from repro.kernels.ladn_denoise import ladn_denoise_fused

LANE = 128


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, bq: int = 512,
                    bk: int = 512,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    if interpret is None:
        interpret = _default_interpret()
    return _flash_attn(q, k, v, causal=causal, window=window, bq=bq, bk=bk,
                       interpret=interpret)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def flash_decode(q, k_cache, v_cache, length, *, bk: int = 512,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    if interpret is None:
        interpret = _default_interpret()
    return _flash_decode(q, k_cache, v_cache, length, bk=bk,
                         interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def paged_flash_decode(q, k_pages, v_pages, block_tables, lengths, *,
                       interpret: Optional[bool] = None,
                       window: Optional[int] = None) -> jnp.ndarray:
    """Paged flash decode (see kernels.decode_attention.paged_flash_decode).

    ``interpret=None`` picks the serving-sensible path per backend: the
    compiled Pallas kernel on TPU, and the XLA-compiled jnp gather oracle
    elsewhere (the Pallas *interpreter* is orders of magnitude slower than
    XLA and would dominate the engine's decode hot loop on CPU).  Pass
    ``interpret=True`` explicitly to exercise the kernel itself off-TPU
    (the validation tests do).
    """
    if interpret is None:
        if _default_interpret():
            return _ref.paged_decode_ref(q, k_pages, v_pages, block_tables,
                                         lengths, window)
        interpret = False
    return _paged_flash_decode(q, k_pages, v_pages, block_tables, lengths,
                               interpret=interpret, window=window)


# ---------------------------------------------------------------------------
# fused LADN chain: padding + weight-layout adapter over the kernel
# ---------------------------------------------------------------------------


def _pad_to(x, n, axis):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def pack_ladn_weights(theta, state_dim: int, action_dim: int,
                      hidden: int) -> Tuple:
    """Split/pad the (A+TE+S -> H -> H -> A) MLP into the kernel layout.

    LADN input order (networks.apply_ladn): [x | time-embed | state].
    Feature dims pad to the 128-lane width.
    """
    A, TE, S = action_dim, TIME_EMBED_DIM, state_dim
    H = hidden
    w1 = theta[0]["w"]                           # (A+TE+S, H)
    w1x = w1[:A]
    w1t = w1[A:A + TE]
    w1s = w1[A + TE:]
    b1 = theta[0]["b"]
    w2, b2 = theta[1]["w"], theta[1]["b"]
    w3, b3 = theta[2]["w"], theta[2]["b"]
    Ap, Sp, Hp = LANE, LANE, LANE
    return (
        _pad_to(_pad_to(w1x, Ap, 0), Hp, 1),
        w1t,                                      # (TE, H) used host-side
        _pad_to(_pad_to(w1s, Sp, 0), Hp, 1),
        _pad_to(b1, Hp, 0),
        _pad_to(_pad_to(w2, Hp, 0), Hp, 1),
        _pad_to(b2, Hp, 0),
        _pad_to(_pad_to(w3, Hp, 0), Ap, 1),
        _pad_to(b3, Ap, 0),
    )


@functools.partial(jax.jit,
                   static_argnames=("num_steps", "paper_variance", "bt",
                                    "interpret", "state_dim", "action_dim",
                                    "hidden"))
def ladn_denoise(theta, x_I, s, key, *, num_steps: int = 5,
                 beta_min: float = 0.1, beta_max: float = 10.0,
                 paper_variance: bool = True, bt: int = 128,
                 state_dim: int, action_dim: int, hidden: int = 20,
                 interpret: Optional[bool] = None) -> Tuple[jnp.ndarray,
                                                            jnp.ndarray]:
    """Fused reverse chain for a batch of tasks.

    theta: LADN params (list of {"w","b"}); x_I (T, A); s (T, S).
    Returns (x_0 (T, A), probs (T, A)).
    """
    if interpret is None:
        interpret = _default_interpret()
    from repro.core.diffusion import make_schedule_np
    # numpy schedule: constants must be concrete at trace time so the
    # kernel can fold them into immediates
    sched = make_schedule_np(num_steps, beta_min, beta_max)
    T, A = x_I.shape
    S = s.shape[1]

    (w1x, w1t, w1s, b1, w2, b2, w3, b3) = pack_ladn_weights(
        theta, S, A, hidden)
    # per-step time contribution (I, H): computed once, tiny
    steps_i = jnp.arange(num_steps, 0, -1)        # I..1
    temb = timestep_embed(steps_i)                # (I, TE)
    temb_w1 = _pad_to(temb @ w1t, LANE, 1)        # (I, Hp)

    noise = jax.random.normal(key, (T, num_steps, A))
    Tp = ((T + bt - 1) // bt) * bt
    x_p = _pad_to(_pad_to(x_I.astype(jnp.float32), LANE, 1), Tp, 0)
    s_p = _pad_to(_pad_to(s.astype(jnp.float32), LANE, 1), Tp, 0)
    n_p = _pad_to(_pad_to(noise, LANE, 2), Tp, 0)

    x0 = ladn_denoise_fused(x_p, s_p, n_p, temb_w1, w1x, w1s, b1, w2, b2,
                            w3, b3, sched, paper_variance=paper_variance,
                            bt=bt, interpret=interpret)
    x0 = x0[:T, :A]
    return x0, jax.nn.softmax(x0, axis=-1)
