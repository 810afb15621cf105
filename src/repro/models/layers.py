"""Shared neural-net layers: norms, rotary/sinusoidal positions, MLPs.

Everything is functional: ``init_*`` returns a param pytree, ``apply_*``
consumes it.  Params are plain nested dicts of jnp arrays so they stack
cleanly for scan-over-layers and shard via PartitionSpec rules keyed on
path names (see repro/launch/sharding.py).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(key, in_dim: int, out_dim: int, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale
            ).astype(dtype)


def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: dict, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# positions
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies, shape (head_dim // 2,)."""
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_freqs(head_dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies, shape (head_dim // 2,), as HF's
    ``_compute_yarn_parameters`` computes them: the interpolated
    frequencies (divided by ``factor``) below the correction range, the
    original ones above it, and a linear ramp between."""
    dim = head_dim
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    extra, inter = 1.0 / pos, 1.0 / (yarn.factor * pos)

    def corr_dim(rot):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp                 # 1: original, 0: interpolated
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10_000.0, yarn=None) -> jnp.ndarray:
    """Rotary embedding; with ``yarn`` (a ``YarnConfig``), YaRN's
    frequencies and cos/sin scaled by its ``attention_factor``.

    x: (..., seq, heads, head_dim); positions: (..., seq) or (seq,).
    """
    head_dim = x.shape[-1]
    freqs = (rope_freqs(head_dim, theta) if yarn is None
             else jnp.asarray(yarn_freqs(head_dim, theta, yarn)))  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    # broadcast over the heads axis
    angles = angles[..., :, None, :]                        # (..., S, 1, hd/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if yarn is not None:
        sin, cos = sin * yarn.attention_factor, cos * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions: jnp.ndarray, d_model: int) -> jnp.ndarray:
    """Classic transformer sinusoidal table; positions (...,) -> (..., d)."""
    half = d_model // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# feed-forward
# ---------------------------------------------------------------------------


def init_mlp(key, d_model: int, d_ff: int, gated: bool, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d_model, d_ff, dtype),
         "w_down": dense_init(ks[1], d_ff, d_model, dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def apply_mlp(p: dict, x: jnp.ndarray, *, dp_spec=None) -> jnp.ndarray:
    """Dense MLP.  Gated -> SwiGLU; non-gated -> squared-ReLU (Nemotron/
    StarCoder2 style approximated with gelu for smoothness)."""
    up = x @ p["w_up"]
    if dp_spec is not None:
        up = jax.lax.with_sharding_constraint(up, dp_spec)
    if "w_gate" in p:
        h = jax.nn.silu(x @ p["w_gate"]) * up
    else:
        h = jax.nn.gelu(up)
    return h @ p["w_down"]
