"""Plain float32 reference of the dense GQA decoder the program serves.

Written from the configuration file alone; it imports nothing of the
program.  The weights are made here from the engine's seed exactly as
the program draws them (per layer, the same splits of the same key,
normals scaled by 1/sqrt(fan_in) and rounded to the served dtype), then
held in float32.  The forward pass follows the program's equations:

    x = E[tokens]
    per layer:  h = rmsnorm(x);  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
                rotary on q, k (split halves, theta = rope_theta)
                x += softmax_causal(q k^T / sqrt(hd)) v Wo   (GQA groups)
                h = rmsnorm(x);  x += MLP(h)
                MLP: silu(h Wg) * (h Wu) Wd  (silu, gated)
                     gelu_tanh(h Wu) Wd      (gelu_pytorch_tanh)
    logits = rmsnorm(x) E^T (tied) or rmsnorm(x) W_head

Every product runs at ``Precision.HIGHEST``.  It runs layer by layer,
one sequence at a time, so that it fits beside nothing on one chip.

With ``quant=True`` the same pass is the control: every matrix product
takes its operands rounded to float8 e4m3 (per-row scales for
activations, per-column for weights), the precision below the served
bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                 # largest finite float8_e4m3fn


def _eps(c: dict) -> float:
    if c["norm_type"] != "rmsnorm":
        raise ValueError(f"no reference for norm_type {c['norm_type']!r}")
    return float(c.get("rms_norm_eps", c.get("norm_epsilon")))


def _gated(c: dict) -> bool:
    if c.get("use_bias", False):
        raise ValueError("no reference for biases on every linear layer")
    act = c["hidden_act"]
    if act not in ("silu", "gelu_pytorch_tanh"):
        raise ValueError(f"no reference for hidden_act {act!r}")
    return act == "silu"


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


def _dense(key, fan_in: int, fan_out: int, dtype):
    w = jax.random.normal(key, (fan_in, fan_out), jnp.float32)
    return (w * (1.0 / math.sqrt(fan_in))).astype(dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(1,))
def _layer_params(key, shape: Tuple) -> Dict[str, jax.Array]:
    d, H, KV, hd, f, gated, bias, dtype = shape
    ks = jax.random.split(key, 3)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[1], 3)
    p = {"wq": _dense(ka[0], d, H * hd, dtype),
         "wk": _dense(ka[1], d, KV * hd, dtype),
         "wv": _dense(ka[2], d, KV * hd, dtype),
         "wo": _dense(ka[3], H * hd, d, dtype),
         "w_up": _dense(km[0], d, f, dtype),
         "w_down": _dense(km[1], f, d, dtype),
         "norm1": jnp.ones((d,), jnp.float32),
         "norm2": jnp.ones((d,), jnp.float32)}
    if gated:
        p["w_gate"] = _dense(km[2], d, f, dtype)
    if bias:
        p["bq"] = jnp.zeros((H * hd,), jnp.float32)
        p["bk"] = jnp.zeros((KV * hd,), jnp.float32)
        p["bv"] = jnp.zeros((KV * hd,), jnp.float32)
    return p


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _out_params(keys, d: int, V: int, tied_dtype: Tuple):
    tied, dtype = tied_dtype
    p = {"embed": _dense(keys[-1], V, d, dtype),
         "final_norm": jnp.ones((d,), jnp.float32)}
    if not tied:
        p["head"] = _dense(keys[-3], d, V, dtype)
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    s = s / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant: bool):
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x (T, heads, hd) at positions 0..T-1, split-halves rotary."""
    T, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnums=(2,))
def _layer(p, x, dims: Tuple):
    """One decoder layer over one sequence x (T, d)."""
    H, KV, hd, eps, theta, quant = dims
    T = x.shape[0]
    h = _rms(x, p["norm1"], eps)
    q, k, v = (_mm(h, p["wq"], quant), _mm(h, p["wk"], quant),
               _mm(h, p["wv"], quant))
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = _rope(q.reshape(T, H, hd), theta)
    k = _rope(k.reshape(T, KV, hd), theta)
    v = v.reshape(T, KV, hd)
    if quant:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    G = H // KV
    qg = q.reshape(T, KV, G, hd)
    s = jnp.einsum("qkgd,ckd->kgqc", qg, k, precision=HIGHEST)
    s = s / math.sqrt(hd)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    if quant:
        w = _q8(w, -1)
    o = jnp.einsum("kgqc,ckd->qkgd", w, v, precision=HIGHEST)
    x = x + _mm(o.reshape(T, H * hd), p["wo"], quant)
    h = _rms(x, p["norm2"], eps)
    up = _mm(h, p["w_up"], quant)
    if "w_gate" in p:
        a = jax.nn.silu(_mm(h, p["w_gate"], quant)) * up
    else:
        a = _gelu_tanh(up)
    return x + _mm(a, p["w_down"], quant)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head_rows(p, x, start, rows: int, dims: Tuple):
    """Logits of ``rows`` positions of x (T, d) from ``start`` on."""
    eps, quant = dims
    xr = jax.lax.dynamic_slice_in_dim(x, start, rows, axis=0)
    xr = _rms(xr, p["final_norm"], eps)
    w = p["head"] if "head" in p else p["embed"].T
    return _mm(xr, w, quant)


@jax.jit
def _embed(p, tokens):
    return p["embed"][tokens]


@jax.jit
def _gap_of(ref_logits, picked):
    """Per row: how far the picked token's reference logit lies below the
    reference's best."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, picked[:, None], axis=-1)[:, 0]
    return best - got


@jax.jit
def _control_gap(ref_logits, control_logits):
    return _gap_of(ref_logits,
                   jnp.argmax(control_logits, axis=-1).astype(jnp.int32))


def _dims(c: dict):
    return (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"])


def _forward(c: dict, seed: int, toks: List[np.ndarray], modes):
    """Final hidden states of each padded token row, per mode (False:
    the reference, True: the float8 control), and the output weights."""
    L, d, H, KV, hd = _dims(c)
    dtype = jnp.dtype(c["torch_dtype"])
    shape = (d, H, KV, hd, c["intermediate_size"], _gated(c),
             bool(c.get("qkv_bias", False)), dtype)
    eps, theta = _eps(c), float(c["rope_theta"])
    keys = jax.random.split(jax.random.key(seed), L + 4)
    out = _out_params(keys, d, c["vocab_size"],
                      (bool(c["tie_word_embeddings"]), dtype))
    xs = {}
    for m in modes:
        emb = dict(out, embed=_q8(out["embed"], -1)) if m else out
        xs[m] = [_embed(emb, jnp.asarray(t)) for t in toks]
    for i in range(L):
        p = _layer_params(keys[i], shape)
        for m in modes:
            xs[m] = [_layer(p, x, (H, KV, hd, eps, theta, m)) for x in xs[m]]
        del p
    return out, xs


def logits(c: dict, seed: int, tokens: np.ndarray, pad_to: int, start: int,
           rows: int) -> np.ndarray:
    """Reference logits (rows, V) at positions start..start+rows-1 of one
    token sequence."""
    t = np.zeros(pad_to, np.int32)
    t[:tokens.size] = tokens
    out, xs = _forward(c, seed, [t], (False,))
    return np.asarray(_head_rows(out, xs[False][0], jnp.int32(start), rows,
                                 (_eps(c), False)))


def served_gaps(c: dict, seed: int, seqs: Sequence[Tuple[np.ndarray,
                                                          np.ndarray]],
                pad_to: int, rows: int, control: bool = False
                ) -> Dict[str, List[np.ndarray]]:
    """Reference gaps of served tokens, for one engine's weights.

    ``seqs``: (prompt (S,), served tokens (n,)) pairs, served greedily by
    the engine seeded ``seed``.  The reference runs once over prompt +
    served tokens (the last needs no input) padded to ``pad_to``; row
    ``S - 1 + j`` predicts served token ``j``.  Returns ``"served"``: per
    sequence, the gap of every served token, and with ``control`` also
    ``"control"``: the gap, under the reference, of the token the
    float8 control puts first at each of those positions.
    """
    eps = _eps(c)
    toks, starts = [], []
    for prompt, served in seqs:
        if len(served) > rows or len(prompt) + len(served) - 1 > pad_to:
            raise ValueError("sequence longer than the reference's pad")
        t = np.zeros(pad_to, np.int32)
        full = np.concatenate([prompt, served[:-1]])
        t[:full.size] = full
        toks.append(t)
        starts.append(len(prompt) - 1)
    out, xs = _forward(c, seed, toks, (False, True) if control else (False,))
    res: Dict[str, List[np.ndarray]] = {"served": []}
    if control:
        res["control"] = []
    for j, (_, served) in enumerate(seqs):
        n = len(served)
        start = min(starts[j], pad_to - rows)
        off = starts[j] - start
        target = np.zeros(rows, np.int32)
        target[off:off + n] = served
        ref = _head_rows(out, xs[False][j], jnp.int32(start), rows,
                         (eps, False))
        gap = np.asarray(_gap_of(ref, jnp.asarray(target)))
        res["served"].append(gap[off:off + n])
        if control:
            ctl = _head_rows(out, xs[True][j], jnp.int32(start), rows,
                             (eps, True))
            gap = np.asarray(_control_gap(ref, ctl))
            res["control"].append(gap[off:off + n])
    return res
